"""Device-side observability (mxnet_tpu.profiling): executable
accounting, HBM pre-flight, measured-cost calibration, op timelines.

The contracts under test:
  - InstrumentedJit is strictly transparent: same results, ONE compile
    per signature, raw-jit fallback on anything unusual, full bypass
    under MXNET_PROFILING=0.
  - After a warmup, deviceStats holds a record for every exec-cache
    entry (the acceptance join), and steady state adds nothing.
  - preflight_bind warns (structured report attached) over a fake cap,
    raises under MXNET_PROFILING_HBM_STRICT=1 BEFORE any trace, and
    attributes the footprint to the right parameters.
  - CalibrationStore folds repeats by EWMA and survives a process
    restart (fresh store, same path); calibrated_cost prefers measured
    evidence over the analytic byte model.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import exec_cache, profiling
from mxnet_tpu.passes import cost_model
from mxnet_tpu.profiling import timeline as _timeline

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _fresh_profiling(tmp_path, monkeypatch):
    """Isolate every test: empty record table, empty preflight slot,
    empty timeline, and a per-test calibration cache file."""
    monkeypatch.setenv("MXNET_CALIBRATION_CACHE",
                       str(tmp_path / "calibration.json"))
    profiling.reset_device_stats()
    from mxnet_tpu.profiling import preflight as _pf

    _pf.reset_preflight()
    _timeline.reset_timeline()
    yield
    profiling.reset_device_stats()
    _pf.reset_preflight()
    _timeline.reset_timeline()


# ---------------------------------------------------------------------
# InstrumentedJit
# ---------------------------------------------------------------------
def test_instrument_records_and_matches_raw_jit():
    def f(x):
        return x * 2.0 + 1.0

    wrapped = profiling.instrument(jax.jit(f), digest="t01",
                                   kind="unit")
    x = jnp.arange(6.0)
    np.testing.assert_allclose(np.asarray(wrapped(x)),
                               np.asarray(f(x)))
    recs = profiling.device_stats()["executables"]
    assert "t01:unit" in recs
    rec = recs["t01:unit"]
    assert rec["executables"] == 1
    assert rec["compile_s"] > 0
    assert rec["hbm_bytes"] > 0


def test_instrument_one_record_per_signature_merge():
    wrapped = profiling.instrument(jax.jit(lambda x: x + 1),
                                   digest="t02", kind="unit")
    wrapped(jnp.zeros((4,)))
    wrapped(jnp.zeros((4,)))            # same signature: no new compile
    assert profiling.device_stats()["executables"]["t02:unit"][
        "executables"] == 1
    wrapped(jnp.zeros((8,)))            # new signature: merges in
    rec = profiling.device_stats()["executables"]["t02:unit"]
    assert rec["executables"] == 2
    # byte fields keep the LARGEST signature's footprint
    assert rec["arg_bytes"] >= 8 * 4


def test_instrument_falsy_digest_returns_fn_unchanged():
    fn = jax.jit(lambda x: x)
    assert profiling.instrument(fn, digest=None, kind="k") is fn
    assert profiling.instrument(fn, digest="", kind="k") is fn


def test_instrument_disabled_bypasses(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING", "0")
    wrapped = profiling.instrument(jax.jit(lambda x: x - 1),
                                   digest="t03", kind="unit")
    wrapped(jnp.ones((3,)))
    assert profiling.device_stats() == {}


def test_instrument_tracer_args_fall_back():
    inner = profiling.instrument(jax.jit(lambda x: x * 3),
                                 digest="t04", kind="unit")

    @jax.jit
    def outer(x):
        return inner(x) + 1  # x is a Tracer here

    np.testing.assert_allclose(np.asarray(outer(jnp.ones((2,)))), 4.0)
    # the nested call dispatched through the raw jit: no record
    assert "t04:unit" not in profiling.device_stats().get(
        "executables", {})


def test_instrument_lower_compile_path_records():
    wrapped = profiling.instrument(jax.jit(lambda x: x.sum()),
                                   digest="t05", kind="aot")
    compiled = wrapped.lower(jnp.zeros((5,))).compile()
    assert float(compiled(jnp.ones((5,)))) == 5.0
    rec = profiling.device_stats()["executables"]["t05:aot"]
    assert rec["executables"] == 1
    assert rec["compile_s"] > 0


def test_instrument_sig_cap(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING_MAX_SIGS", "1")
    wrapped = profiling.instrument(jax.jit(lambda x: x + 1),
                                   digest="t06", kind="unit")
    wrapped(jnp.zeros((2,)))
    out = wrapped(jnp.zeros((3,)))      # over cap: raw-jit fallback
    assert out.shape == (3,)
    assert profiling.device_stats()["executables"]["t06:unit"][
        "executables"] == 1


# ---------------------------------------------------------------------
# executor wiring: deviceStats <-> exec_cache join
# ---------------------------------------------------------------------
def _toy_net():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    return mx.sym.FullyConnected(h, num_hidden=4, name="fc2")


def test_bind_records_cover_exec_cache_entries():
    exec_cache.clear()
    exec_cache.reset_stats()
    net = _toy_net()
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=(2, 16))
    exe.forward(is_train=False,
                data=mx.nd.array(np.zeros((2, 16), "float32")))
    exe.outputs[0].asnumpy()

    digests = exec_cache.entry_digests()
    assert digests
    recs = profiling.device_stats()["executables"]
    for d in digests:
        assert any(r["digest"] == d for r in recs.values()), \
            f"exec-cache entry {d} has no deviceStats record"
    # the record carries the canonical digest of the optimized graph
    assert all(r["canonical"] for r in recs.values())

    # steady state: more forwards, no new records, no new traces
    traces0 = exec_cache.cache_stats()["traces"]
    n0 = len(recs)
    for _ in range(3):
        exe.forward(is_train=False,
                    data=mx.nd.array(np.zeros((2, 16), "float32")))
        exe.outputs[0].asnumpy()
    assert exec_cache.cache_stats()["traces"] == traces0
    assert len(profiling.device_stats()["executables"]) == n0


def test_records_for_filters():
    profiling.instrument(jax.jit(lambda x: x), digest="aaa",
                         kind="k1")(jnp.zeros((2,)))
    profiling.instrument(jax.jit(lambda x: x), digest="bbb",
                         kind="k2")(jnp.zeros((2,)))
    assert len(profiling.device_stats()["executables"]) == 2
    from mxnet_tpu.profiling import records_for

    assert [r["digest"] for r in records_for(digest="aaa")] == ["aaa"]
    assert [r["kind"] for r in records_for(kind="k2")] == ["k2"]


# ---------------------------------------------------------------------
# HBM pre-flight
# ---------------------------------------------------------------------
def test_preflight_report_fields():
    net = _toy_net()
    report = profiling.preflight_bind(
        net,
        {"data": ((2, 16), "float32"),
         "fc1_weight": ((8, 16), "float32"),
         "fc1_bias": ((8,), "float32"),
         "fc2_weight": ((4, 8), "float32"),
         "fc2_bias": ((4,), "float32")},
        {"fc1_weight": "write", "fc1_bias": "write",
         "fc2_weight": "write", "fc2_bias": "write",
         "data": "null"},
        data_names=("data",))
    assert report["fits"] is True          # no cap on CPU
    assert report["cap_bytes"] is None
    assert report["training"] is True
    w = 4  # float32
    assert report["grad_bytes"] == (8 * 16 + 8 + 4 * 8 + 4) * w
    assert report["opt_bytes"] == report["grad_bytes"] * 2  # default
    assert report["activation_bytes"] > 0
    # attribution: largest non-data parameter first, data excluded
    assert report["top_params"][0][0] == "fc1_weight"
    assert all(n != "data" for n, _ in report["top_params"])
    assert profiling.last_preflight() == report


def test_preflight_warns_over_cap_with_report(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING_DEVICE_MEM_BYTES", "100")
    net = _toy_net()
    with pytest.warns(profiling.HBMPreflightWarning) as caught:
        net.simple_bind(mx.cpu(), grad_req="null", data=(2, 16))
    report = caught[0].message.report
    assert report["fits"] is False
    assert report["cap_bytes"] == 100
    assert report["total_bytes"] > 100


def test_preflight_strict_raises_before_any_trace(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING_DEVICE_MEM_BYTES", "100")
    monkeypatch.setenv("MXNET_PROFILING_HBM_STRICT", "1")
    exec_cache.clear()
    exec_cache.reset_stats()
    with pytest.raises(profiling.HBMPreflightError) as exc:
        _toy_net().simple_bind(mx.cpu(), grad_req="null",
                               data=(2, 16))
    assert exc.value.report["total_bytes"] > 100
    # the raise happened in pre-flight: ZERO programs were traced
    assert exec_cache.cache_stats()["traces"] == 0
    assert exec_cache.entry_digests() == []


def test_preflight_disabled_with_profiling_off(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING", "0")
    monkeypatch.setenv("MXNET_PROFILING_DEVICE_MEM_BYTES", "100")
    monkeypatch.setenv("MXNET_PROFILING_HBM_STRICT", "1")
    exe = _toy_net().simple_bind(mx.cpu(), grad_req="null",
                                 data=(2, 16))  # must not raise
    assert exe is not None


def test_preflight_sharded_divides_param_bytes():
    class FakePlan:
        axis_sizes = {"tp": 4}

        def spec_for(self, name, ndim):
            return ("tp", None)[:ndim]

        def batch_axes(self):
            return ()

    rep = profiling.preflight_bind(
        None, {"w": ((8, 8), "float32")}, {"w": "null"},
        plan=FakePlan())
    assert rep["param_bytes"] == 8 * 8 * 4 // 4


# ---------------------------------------------------------------------
# CalibrationStore + calibrated_cost
# ---------------------------------------------------------------------
def test_calibration_store_ewma_and_restart(tmp_path):
    path = str(tmp_path / "c.json")
    store = profiling.CalibrationStore(path)
    store.record("dig", "cpu", "forward", 0.01)
    rec = store.record("dig", "cpu", "forward", 0.02)
    assert rec["samples"] == 2
    assert rec["seconds"] == pytest.approx(0.7 * 0.01 + 0.3 * 0.02)

    # restart: a fresh store on the same path sees the folded record
    again = profiling.CalibrationStore(path)
    assert again.measured_seconds("dig", "cpu", "forward") == \
        pytest.approx(rec["seconds"])
    assert again.measured_seconds("dig", "cpu", "missing") is None


def test_calibration_store_drops_garbage(tmp_path):
    store = profiling.CalibrationStore(str(tmp_path / "c.json"))
    assert store.record("", "cpu", "forward", 0.5) is None
    assert store.record("d", "cpu", "forward", 0.0) is None
    assert store.record("d", "cpu", "forward", -1.0) is None
    assert store.records() == {}


def test_calibrated_cost_prefers_measured():
    net = _toy_net()
    digest = net.canonical_signature()
    shapes = {"data": (2, 16)}
    before = cost_model.calibrated_cost(net, shapes, platform="cpu")
    assert before["source"] == "analytic"
    assert before["est_s"] == before["analytic_s"] > 0
    assert before["measured_s"] is None

    profiling.calibration_store().record(digest, "cpu", "forward",
                                         0.0123)
    after = cost_model.calibrated_cost(net, shapes, platform="cpu")
    assert after["source"] == "measured"
    assert after["est_s"] == pytest.approx(0.0123)
    assert after["analytic_s"] == before["analytic_s"]
    assert after["digest"] == digest


def test_tuner_upgrades_analytic_record_from_calibration(tmp_path):
    from mxnet_tpu.passes.tuner import Autotuner

    net = _toy_net()
    shapes = {"data": (2, 16)}
    tuner = Autotuner(cache_path=str(tmp_path / "tuning.json"))
    first = tuner.choose(net, shapes, platform="tpu")
    assert first["source"] == "analytic"

    profiling.calibration_store().record(
        net.canonical_signature(), "tpu", "forward", 0.0004)
    upgraded = tuner.choose(net, shapes, platform="tpu")
    assert upgraded["source"] == "calibrated"
    assert upgraded["measured_forward_s"] == pytest.approx(0.0004)
    # 0.4 ms step -> k=4 fills the 2 ms fused-dispatch window
    assert upgraded["multistep_k"] == 4


def test_serving_warmup_harvests_calibration():
    from mxnet_tpu import serving

    data = mx.sym.Variable("data")
    net = mx.sym.Embedding(data, input_dim=50, output_dim=8,
                           name="embed")
    net = mx.sym.mean(net, axis=1)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc")
    shapes, _, _ = net.infer_shape(data=(1, 8))
    rs = np.random.RandomState(0)
    params = {n: mx.nd.array(rs.normal(0, 0.1, s).astype("float32"))
              for n, s in zip(net.list_arguments(), shapes)
              if n != "data"}
    exec_cache.clear()
    exec_cache.reset_stats()
    registry = serving.ModelRegistry()
    registry.load("cal", net.tojson(), params,
                  input_specs={"data": ("L",)},
                  input_dtypes={"data": "int32"},
                  batch_buckets=(1, 2), length_buckets=(8,))

    kinds = {r["kind"] for r in
             profiling.calibration_store().records().values()}
    assert "forward" in kinds            # the largest bucket's record
    assert "forward[2x8]" in kinds
    cc = cost_model.calibrated_cost(net, {"data": (2, 8)})
    assert cc["source"] == "measured"

    # acceptance: deviceStats count matches the exec-cache entry count
    recs = profiling.device_stats()["executables"]
    assert len(recs) == len(exec_cache.entry_digests())


# ---------------------------------------------------------------------
# op-level timelines
# ---------------------------------------------------------------------
_HLO = """HloModule jit_toy, is_scheduled=true, entry_computation_layout={()->f32[4]}

%fused_computation.1 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %inner.9 = f32[4]{0} negate(%param_0.1), metadata={op_name="jit(toy)/hidden/neg"}
}

%region_0.1 (reduce.1: f32[], reduce.2: f32[]) -> f32[] {
  %reduce.1 = f32[] parameter(0)
  %reduce.2 = f32[] parameter(1)
  ROOT %add.5 = f32[] add(%reduce.1, %reduce.2)
}

%body.3 (arg.1: f32[4]) -> f32[4] {
  %arg.1 = f32[4]{0} parameter(0)
  ROOT %fusion.4 = f32[4]{0} fusion(%arg.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(toy)/l1/mlp/while/body/mul"}
}

%cond.3 (arg.2: f32[4]) -> pred[] {
  %arg.2 = f32[4]{0} parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main.9 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.1 = f32[4]{0} copy(%x.1)
  %fusion.1 = f32[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(toy)/jit(main)/l0/kv_write/scatter" source_file="m.py"}
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(toy)/transpose(jvp(conv0))/conv_general_dilated;jit(toy)/bn0/add"}
  %fusion.2.remat_uncompressed = f32[4]{0} copy(%fusion.2)
  %while.1 = f32[4]{0} while(%fusion.2.remat_uncompressed), condition=%cond.3, body=%body.3, metadata={op_name="jit(toy)/l1/mlp/while"}
  %reduce.9 = f32[] reduce(%while.1, %fusion.2), dimensions={0}, to_apply=%region_0.1, metadata={op_name="jit(toy)/vmap(jit(_helper))/reduce_sum"}
  %constant.7 = f32[] constant(0)
  %copy.2 = f32[4]{0} copy(%while.1)
  ROOT %tuple.3 = (f32[], f32[4]{0}) tuple(%reduce.9, %copy.2)
}
"""


def test_scope_path_strips_jit_and_autodiff_wrappers():
    path = _timeline.scope_path
    assert path("jit(run_graph)/fc1_fwd/dot_general") == "fc1_fwd"
    assert path("jit(step)/jit(main)/transpose(jvp(conv0))/"
                "conv_general_dilated") == "conv0"
    assert path("jit(decode_p8)/l3/attn/bhd,bthd->bht/dot_general") \
        == "l3/attn/bhd,bthd->bht"
    # a jit under a vmap is a function's name, not a scope; `vmap()`
    # wraps nothing
    assert path("jit(f)/sample/vmap()/vmap(jit(_fold_in))/mul") \
        == "sample"
    assert path("jit(f)/add") is None      # no scope encloses it
    assert path("params['embed']") is None
    assert _timeline.module_name(_HLO) == "jit_toy"
    assert _timeline.module_name("ENTRY %main () -> f32[] {") is None


def test_scope_map_of_hand_written_hlo():
    """Every instruction a device can report gets its scope: its own
    metadata (wrappers unwrapped, the first of ';'-joined paths), else
    the first user's, else the producer's, else `unscoped`."""
    m = _timeline.parse_scope_map(_HLO)
    assert m["fusion.1"] == "l0/kv_write"
    assert m["fusion.2"] == "conv0"
    # a layout copy without metadata: the scope of its first user
    assert m["copy.1"] == "l0/kv_write"
    assert m["fusion.2.remat_uncompressed"] == "l1/mlp"
    # ... a parameter too (its own op_name holds no scope)
    assert m["x.1"] == "l0/kv_write"
    # its only user is the unnamed root tuple: the scope of what
    # produces its operand, not of the tuple's other operands
    assert m["copy.2"] == "l1/mlp"
    # a helper's name under vmap(jit()) is no scope: the producer's
    assert m["reduce.9"] == "l1/mlp"
    assert m["tuple.3"] == "l1/mlp"
    # neither user nor operand: unscoped
    assert m["constant.7"] == "unscoped"
    # control flow under the entry is reported by the device, fused
    # bodies and reducers are not
    assert m["fusion.4"] == "l1/mlp/while/body"
    assert "lt.1" in m
    assert "inner.9" not in m and "add.5" not in m


def test_scope_map_outlives_the_executable():
    def toy(x):
        with jax.named_scope("l0"):
            with jax.named_scope("attn"):
                return jnp.tanh(x) * 2.0

    wrapped = profiling.instrument(jax.jit(toy), digest="t-scope",
                                   kind="unit")
    wrapped(jnp.arange(8.0))
    rec = profiling.records_for(digest="t-scope")[0]
    assert rec["module"] == "jit_toy" and rec["scope_text_s"] > 0
    del wrapped
    jax.clear_caches()
    m = profiling.scope_map("jit_toy")
    assert m and set(m.values()) <= {"l0/attn", "unscoped"}
    assert "l0/attn" in m.values()
    assert profiling.device_stats()["totals"]["scope_parse_s"] > 0
    assert profiling.scope_map("jit_never_built") is None


def _capture(ops, modules=None, name="/device:TPU:0"):
    """One device of a capture in the plain-list form read_xplane
    returns; `ops` [(instr, t0_us, dur_us)], one launch of `jit_g`
    covering them all unless `modules` says otherwise."""
    ops = [(n, n, t0 * 1e-6, (t0 + d) * 1e-6) for n, t0, d in ops]
    if modules is None:
        modules = [("jit_g(7)", "jit_g(7)", 0.0, 1.0)]
    return {"name": name, "ops": ops, "modules": modules}


_MAPS = {"jit_g": {"f1": "conv0", "f2": "conv0", "f3": "relu0"}}


def test_aggregate_and_ingest_device_events():
    raw = {"devices": [
        _capture([("f1", 10, 5.0), ("f2", 20, 3.0)]),
        _capture([("f3", 10, 2.0)], name="/device:TPU:1")], "host": []}
    events = _timeline.device_slices(raw, _MAPS.get)
    assert [e["pid"] for e in events] == [1001, 1001, 1002]
    assert events[0]["args"] == {"module": "jit_g", "scope": "conv0"}
    events += [
        {"ph": "M", "name": "process_name", "pid": 1001},   # metadata
        {"ph": "X", "name": "no_dur", "pid": 1001},         # no dur
    ]
    _timeline.ingest_device_events(events)
    stats = _timeline.timeline_stats()
    assert stats["ops"]["conv0"] == pytest.approx({
        "count": 2, "total_us": 8.0, "max_us": 5.0, "mean_us": 4.0})
    assert stats["ops"]["relu0"]["total_us"] == pytest.approx(2.0)
    assert stats["totals"]["events"] == 3
    assert stats["totals"]["captures"] == 1
    assert stats["totals"]["devices"] == 2
    # a second capture accumulates
    _timeline.ingest_device_events(events[:1])
    assert _timeline.timeline_stats()["ops"]["conv0"]["count"] == 3


def test_device_slices_attribute_by_the_covering_launch():
    """Instruction names repeat across programs: the launch that
    covers an operation in time decides whose scope map reads it; an
    operation the map does not place is unscoped, one outside every
    launch keeps its own name."""
    raw = {"devices": [_capture(
        [("f1", 10, 5.0), ("f1", 110, 5.0), ("f9", 120, 1.0),
         ("f1", 900, 1.0)],
        modules=[("jit_g(7)", "jit_g(7)", 0.0, 100e-6),
                 ("jit_h(8)", "jit_h(8)", 100e-6, 200e-6)])],
        "host": []}
    maps = {"jit_g": {"f1": "conv0"}, "jit_h": {"f1": "l0/attn"}}
    ev = _timeline.device_slices(raw, maps.get, base_us=1000.0)
    assert [e["args"].get("scope") for e in ev] == [
        "conv0", "l0/attn", "unscoped", None]
    assert ev[0]["ts"] == pytest.approx(1010.0)   # onto the host clock
    agg = _timeline.aggregate_device_events(ev)
    assert set(agg) == {"conv0", "l0/attn", "unscoped", "f1"}


def test_timeline_topk(monkeypatch):
    monkeypatch.setenv("MXNET_PROFILING_TOPK", "2")
    raw = {"devices": [_capture([(f"op{d}", 10 * d, float(d))
                                 for d in (1, 2, 3, 4)])], "host": []}
    maps = {"jit_g": {f"op{d}": f"node{d}" for d in (1, 2, 3, 4)}}
    _timeline.ingest_device_events(
        _timeline.device_slices(raw, maps.get))
    stats = _timeline.timeline_stats()
    assert list(stats["ops"]) == ["node4", "node3"]  # by total_us
    assert stats["totals"]["distinct_ops"] == 4
    assert stats["totals"]["shown"] == 2


def test_dump_profile_embeds_timeline_of_same_capture(tmp_path,
                                                      monkeypatch):
    """The deviceTimelineStats view embedded in a dump must reflect
    the device capture written in the SAME file (events are ingested
    before the view snapshot), through the scope maps the record table
    keeps. A CPU capture has no device plane, so the capture comes in
    at the plain-list seam."""
    from mxnet_tpu import profiler

    def fc(x):
        with jax.named_scope("fc_fwd"):
            return jnp.tanh(x)

    profiling.instrument(jax.jit(fc), digest="t-dump", kind="unit")(
        jnp.arange(4.0))
    instr = next(k for k, v in profiling.scope_map("jit_fc").items()
                 if v == "fc_fwd")
    raw = {"devices": [_capture(
        [(instr, 1.0, 7.0)],
        modules=[("jit_fc(3)", "jit_fc(3)", 0.0, 1.0)])], "host": []}
    monkeypatch.setattr(_timeline, "read_xplane", lambda d: raw)

    old = dict(profiler._state)
    profiler.profiler_set_config(filename=str(tmp_path / "prof.json"))
    profiler._state["ever_ran"] = True
    try:
        fn = profiler.dump_profile(device_trace_dir=str(tmp_path))
    finally:
        profiler._state.update(old)
    with open(fn) as f:
        dump = json.load(f)
    assert dump["deviceTimelineStats"]["ops"]["fc_fwd"]["total_us"] \
        == pytest.approx(7.0)
    # the raw device slice itself rides along in its device's lane
    assert any(e.get("pid") == 1001 and e["args"]["scope"] == "fc_fwd"
               for e in dump["traceEvents"])


# ---------------------------------------------------------------------
# named_scope attribution through the executor
# ---------------------------------------------------------------------
def test_executor_stamps_node_names_into_hlo():
    """run_graph wraps each op in jax.named_scope(node_name), so the
    compiled program's metadata carries our node names — the hook
    timeline attribution keys on."""
    exec_cache.clear()
    net = _toy_net()
    exe = net.simple_bind(mx.cpu(), grad_req="null", data=(2, 16))
    exe.forward(is_train=False,
                data=mx.nd.array(np.zeros((2, 16), "float32")))
    exe.outputs[0].asnumpy()
    # the forward dispatched through the InstrumentedJit wrapper,
    # which holds the captured Compiled — read its HLO text
    fwd = exe._compiled.jit_fwd(False)
    assert isinstance(fwd, profiling.InstrumentedJit)
    captured = [c for c in fwd._compiled.values()
                if hasattr(c, "as_text")]
    assert captured, "forward was not AOT-captured"
    assert "fc1" in captured[0].as_text()


# ---------------------------------------------------------------------
# decoding stats: prefill latency histogram
# ---------------------------------------------------------------------
def test_prefill_latency_histogram_buckets():
    from mxnet_tpu.decoding import stats as dstats
    from mxnet_tpu.telemetry import registry as treg

    st = dstats.DecodeStats(key="t:1")
    st.note_prefill(16, 0.004)          # 4 ms -> the "5" bucket
    text = treg.REGISTRY.prometheus_text()
    assert "mxnet_tpu_decode_prefill_latency_ms_bucket" in text
    assert "mxnet_tpu_decode_prefill_latency_ms_count" in text
