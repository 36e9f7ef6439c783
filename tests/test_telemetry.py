"""mxnet_tpu.telemetry: metrics registry (instruments, views,
Prometheus rendering), span ring + correlation ids, the serving
submit->enqueue->batch_flush->execute->reply trace, the HTTP exporter
(/metrics /statusz /healthz), dump_profile key-shape compatibility,
and the crash flight recorder."""
import json
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import serving, telemetry
from mxnet_tpu.telemetry import registry as treg
from mxnet_tpu.telemetry import trace as ttrace


@pytest.fixture(autouse=True)
def _fresh():
    ttrace.clear()
    serving.stats._registry.clear()
    yield
    telemetry.stop_exporter()


def _params_for(net, **input_shapes):
    shapes, _, _ = net.infer_shape(**input_shapes)
    rs = np.random.RandomState(7)
    return {
        n: mx.nd.array(rs.uniform(-1, 1, s).astype("float32"))
        for n, s in zip(net.list_arguments(), shapes)
        if n not in input_shapes
    }


def _fixed_net():
    data = mx.sym.Variable("data")
    return mx.sym.FullyConnected(data, num_hidden=4, name="fc")


# ----------------------------------------------------------- registry
def test_counter_gauge_labels():
    reg = treg.MetricsRegistry()
    c = reg.counter("reqs_total", "requests")
    c.inc()
    c.inc(2, model="a")
    c.inc(model="a")
    assert c.value() == 1          # label sets are independent cells
    assert c.value(model="a") == 3
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    assert g.value() == 7
    g2 = reg.gauge("live_depth")
    g2.set_fn(lambda: 42)
    assert g2.value() == 42
    # same name returns the same instrument; kind mismatch raises
    assert reg.counter("reqs_total") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs_total")


def test_histogram_buckets_and_render():
    reg = treg.MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()[()]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(555.5)
    assert snap["counts"] == [1, 1, 1, 1]  # one per bucket + overflow
    text = reg.prometheus_text()
    assert "# TYPE lat_ms histogram" in text
    # cumulative bucket counts, then +Inf == count
    assert 'lat_ms_bucket{le="1.0"} 1' in text
    assert 'lat_ms_bucket{le="10.0"} 2' in text
    assert 'lat_ms_bucket{le="100.0"} 3' in text
    assert 'lat_ms_bucket{le="+Inf"} 4' in text
    assert "lat_ms_count 4" in text


def test_views_legacy_order_and_omit_empty():
    reg = treg.MetricsRegistry()
    reg.register_view("graphPassStats", lambda: {"runs": 1})
    reg.register_view("execCacheStats", lambda: {"hits": 2})
    reg.register_view("servingStats", lambda: {}, omit_empty=True)
    reg.register_view("customStats", lambda: {"x": 3})
    reg.register_view("broken", lambda: 1 / 0)
    items = reg.view_items()
    keys = [k for k, _ in items]
    # historical dump order first, non-legacy after, raising skipped,
    # empty omit_empty views dropped
    assert keys == ["execCacheStats", "graphPassStats", "customStats"]
    assert dict(items)["execCacheStats"] == {"hits": 2}


def test_view_prometheus_flattening():
    reg = treg.MetricsRegistry()
    reg.register_view(
        "graphPassStats",
        lambda: {"folds": 3, "enabled": True, "skip_me": None,
                 "pass_time_us": {"dce": 12}},
        prom_prefix="graph_passes")
    reg.register_view(
        "servingStats",
        lambda: {"m:1": {"qps": 2.5, "p99_ms": 8.0}},
        prom_prefix="serving", label_name="model")
    text = reg.prometheus_text()
    assert "mxnet_tpu_graph_passes_folds 3" in text
    assert "mxnet_tpu_graph_passes_enabled 1" in text   # bool -> int
    assert 'mxnet_tpu_graph_passes_pass_time_us{key="dce"} 12' in text
    assert 'mxnet_tpu_serving_qps{model="m:1"} 2.5' in text
    assert "skip_me" not in text


def test_all_five_silos_registered():
    # importing the silos registers their views into the default
    # registry; the profiler's stat functions are thin reads over them
    from mxnet_tpu import profiler

    profiler.exec_cache_stats()
    profiler.serving_stats()
    profiler.input_pipeline_stats()
    profiler.graph_pass_stats()
    for key in treg.MetricsRegistry.LEGACY_ORDER:
        assert telemetry.has_view(key), key
    # thin read == direct silo snapshot (same function, same counters)
    from mxnet_tpu.exec_cache import cache_stats

    assert profiler.exec_cache_stats() == cache_stats()


# --------------------------------------------------------- span ring
def test_span_ring_record_and_evict():
    ttrace.set_capacity(4)
    try:
        for i in range(6):
            ttrace.record_span(f"s{i}", None, 0.0, 1.0)
        names = [s.name for s in telemetry.recent_spans()]
        assert names == ["s2", "s3", "s4", "s5"]
        st = telemetry.trace_stats()
        assert st["recorded"] == 6
        assert st["retained"] == 4
        assert st["evicted"] == 2
    finally:
        ttrace.set_capacity(ttrace._env_capacity())


def test_span_zero_capacity_disables():
    ttrace.set_capacity(0)
    try:
        with telemetry.span("nothing"):
            pass
        ttrace.record_span("direct", None, 0.0, 1.0)
        assert telemetry.recent_spans() == []
        assert telemetry.trace_stats()["recorded"] == 0
    finally:
        ttrace.set_capacity(ttrace._env_capacity())


def test_span_context_manager_error_attr():
    with pytest.raises(ValueError):
        with telemetry.span("boom", trace_id="t-1", extra=7):
            raise ValueError("x")
    (s,) = telemetry.spans_for_trace("t-1")
    assert s.attrs["error"] == "ValueError"
    assert s.attrs["extra"] == 7
    assert s.duration_us >= 0


def test_trace_id_unique_and_batch_coverage():
    a, b = ttrace.new_trace_id(), ttrace.new_trace_id()
    assert a != b
    ttrace.record_span("batch", None, 0.0, 1.0, {"trace_ids": (a, b)})
    ttrace.record_span("own", a, 1.0, 2.0)
    assert {s.name for s in telemetry.spans_for_trace(a)} == \
        {"batch", "own"}
    assert {s.name for s in telemetry.spans_for_trace(b)} == {"batch"}


def test_span_summary_aggregates():
    ttrace.record_span("step", None, 0.0, 0.001)
    ttrace.record_span("step", None, 0.0, 0.002)
    summ = telemetry.span_summary()
    assert summ["step"]["count"] == 2
    assert summ["step"]["total_us"] == pytest.approx(3000.0, rel=0.01)


# ------------------------------------------- serving correlation path
def test_serving_request_correlated_end_to_end():
    """One submitted request must be reconstructable across >= 4 spans
    through its Future's trace id: submit, enqueue, batch_flush,
    execute, reply."""
    net = _fixed_net()
    server = serving.ModelServer(max_wait_us=1000, queue_cap=64)
    try:
        server.load("tm", net.tojson(), _params_for(net, data=(1, 8)),
                    input_specs={"data": (8,)})
        fut = server.submit("tm", {"data": np.ones((8,), np.float32)})
        fut.result(timeout=60)
        tid = fut.trace_id
        assert tid
        spans = telemetry.spans_for_trace(tid)
        names = {s.name for s in spans}
        assert {"serving.submit", "serving.enqueue",
                "serving.batch_flush", "serving.execute",
                "serving.reply"} <= names
        assert len(spans) >= 4
        # request chronology: submit begins before the reply ends
        by = {s.name: s for s in spans}
        assert by["serving.submit"].t0 <= by["serving.reply"].t1
        # batch-level spans carry the id via trace_ids, not directly
        assert tid in by["serving.execute"].attrs["trace_ids"]
    finally:
        server.stop()


def test_serving_latency_histogram_observed():
    net = _fixed_net()
    server = serving.ModelServer(max_wait_us=1000, queue_cap=64)
    try:
        server.load("lm", net.tojson(), _params_for(net, data=(1, 8)),
                    input_specs={"data": (8,)})
        before = telemetry.histogram(
            "mxnet_tpu_serving_request_latency_ms").snapshot()
        n_before = sum(c["count"] for c in before.values())
        for _ in range(3):
            server.predict("lm", {"data": np.ones((8,), np.float32)},
                           timeout=60)
        after = telemetry.histogram(
            "mxnet_tpu_serving_request_latency_ms").snapshot()
        n_after = sum(c["count"] for c in after.values())
        assert n_after - n_before == 3
    finally:
        server.stop()


def test_fit_records_step_spans():
    d = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, num_hidden=4, name="fc"),
        name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(
        rs.rand(32, 6).astype("float32"),
        rs.randint(0, 2, (32,)).astype("float32"), batch_size=8)
    mod = mx.mod.Module(net, context=[mx.cpu()])
    mod.fit(it, num_epoch=1, optimizer_params=(("learning_rate", 0.1),))
    names = {s.name for s in telemetry.recent_spans()}
    assert {"fit.data_wait", "fit.dispatch", "fit.metric_drain"} <= \
        names
    # step spans are correlated per (epoch, batch)
    step0 = telemetry.spans_for_trace("fit-e0-b0")
    assert {"fit.data_wait", "fit.dispatch"} <= \
        {s.name for s in step0}


def test_fit_dispatch_is_partitioned_by_leaf_spans():
    """fit.dispatch = fit.stage + fit.launch + fit.metric +
    fit.window_wait: children of the dispatch, in order, without
    overlap, summing to it; the wait carries its scalar fetch's own
    time."""
    d = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(d, num_hidden=64, name="fc"),
        name="softmax")
    rs = np.random.RandomState(0)
    it = mx.io.NDArrayIter(
        rs.rand(256, 512).astype("float32"),
        rs.randint(0, 2, (256,)).astype("float32"), batch_size=32)
    mod = mx.mod.Module(net, context=[mx.cpu()])
    mod.fit(it, num_epoch=2, optimizer_params=(("learning_rate", 0.1),))
    spans = telemetry.recent_spans()
    leaves = ("fit.stage", "fit.launch", "fit.metric", "fit.window_wait")
    dispatches = [s for s in spans if s.name == "fit.dispatch"]
    assert len(dispatches) == 16 and all(s.parent is None
                                         for s in dispatches)
    waited, uncovered = 0, []
    for disp in dispatches[2:]:          # the first two compile
        kids = sorted((s for s in spans if s.name in leaves
                       and s.t0 >= disp.t0 and s.t1 <= disp.t1),
                      key=lambda s: s.t0)
        assert {k.parent for k in kids} == {"fit.dispatch"}
        names = [k.name for k in kids]
        assert names[:3] == ["fit.stage", "fit.launch", "fit.metric"]
        assert names[3:] in ([], ["fit.window_wait"])
        waited += len(names) == 4
        for a, b in zip(kids, kids[1:]):
            assert a.t1 <= b.t0
        uncovered.append((disp.t1 - disp.t0)
                         - sum(k.t1 - k.t0 for k in kids))
    # the leaves sum to the dispatch: what they leave out is the spans'
    # own bookkeeping, some tens of microseconds. On a loaded host the
    # thread can lose the CPU between two leaves for milliseconds, in
    # one dispatch or another but not in most: the MEDIAN dispatch is
    # held to the slack, every dispatch to order and no overlap (above)
    lengths = sorted(d.t1 - d.t0 for d in dispatches[2:])
    assert sorted(uncovered)[len(uncovered) // 2] < max(
        100e-6, 0.05 * lengths[len(lengths) // 2]), uncovered
    assert waited, "no dispatch had to wait for the window"
    for w in (s for s in spans if s.name == "fit.window_wait"):
        assert 0 <= w.attrs["fetch_us"] <= w.duration_us
    # the epoch-end drain waits under its own parent
    assert any(s.name == "fit.window_wait"
               and s.parent == "fit.metric_drain" for s in spans)
    # the iterator's end is a data wait too, marked as such
    ends = [s for s in spans if s.name == "fit.data_wait"
            and s.attrs and s.attrs.get("error") == "StopIteration"]
    assert len(ends) == 2


def test_span_parent_is_the_enclosing_span_on_the_same_thread():
    import threading

    with telemetry.span("outer.a", trace_id="t"):
        with telemetry.span("inner.b") as sp:
            sp.note(k=1)
            sp.note(j=2)
        ttrace.record_span("after.fact", "t", 0.0, 1.0)
        other = threading.Thread(
            target=lambda: ttrace.record_span("other.thread", "t", 0.0,
                                              1.0))
        other.start()
        other.join(10)
    ttrace.record_span("top.level", "t", 0.0, 1.0)
    ttrace.record_span("explicit.parent", "t", 0.0, 1.0,
                       parent="outer.a")
    got = {s.name: s for s in telemetry.recent_spans()}
    assert got["inner.b"].parent == "outer.a"
    assert got["inner.b"].attrs == {"k": 1, "j": 2}
    assert got["after.fact"].parent == "outer.a"
    assert got["other.thread"].parent is None
    assert got["outer.a"].parent is None
    assert got["top.level"].parent is None
    assert got["explicit.parent"].parent == "outer.a"
    assert got["inner.b"].to_dict()["parent"] == "outer.a"
    assert "parent" not in got["outer.a"].to_dict()
    # an exception unwinds the stack
    with pytest.raises(ValueError):
        with telemetry.span("outer.raises"):
            raise ValueError("x")
    ttrace.record_span("after.raise", "t", 0.0, 1.0)
    assert telemetry.recent_spans()[-1].parent is None


def test_span_shows_up_in_a_profiler_capture_under_its_own_name(
        tmp_path):
    """A span opened while a jax.profiler capture runs sits in the
    capture's host plane, on the profiler's clock, under its own name:
    its length there agrees with the ring's."""
    import jax
    from mxnet_tpu.profiling import timeline

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("decoding.step", live=1):
            with telemetry.span("engine.fetch"):
                sum(range(20000))
    finally:
        jax.profiler.stop_trace()
    raw = timeline.read_xplane(str(tmp_path))
    host = {n: (t0, t1) for n, t0, t1 in raw["host"]}
    assert {"decoding.step", "engine.fetch"} <= set(host)
    ring = {s.name: s for s in telemetry.recent_spans()}
    for name in ("decoding.step", "engine.fetch"):
        in_capture = host[name][1] - host[name][0]
        in_ring = ring[name].t1 - ring[name].t0
        assert abs(in_capture - in_ring) < 200e-6
    # the child lies inside its parent on the capture's clock too
    assert host["decoding.step"][0] <= host["engine.fetch"][0]
    assert host["engine.fetch"][1] <= host["decoding.step"][1]
    # the runtime's own annotations are not the program's spans
    assert not any("::" in n or n.startswith("$") for n in host)


# ------------------------------------------------------ HTTP exporter
def test_exporter_endpoints_agree_with_process_state():
    net = _fixed_net()
    server = serving.ModelServer(max_wait_us=1000, queue_cap=64)
    exp = telemetry.start_exporter(port=0)
    try:
        server.load("em", net.tojson(), _params_for(net, data=(1, 8)),
                    input_specs={"data": (8,)})
        server.predict("em", {"data": np.ones((8,), np.float32)},
                       timeout=60)
        base = f"http://127.0.0.1:{exp.port}"
        assert telemetry.exporter_port() == exp.port

        assert urllib.request.urlopen(
            base + "/healthz", timeout=10).read() == b"ok\n"

        sz = json.loads(urllib.request.urlopen(
            base + "/statusz", timeout=10).read())
        for key in ("execCacheStats", "hostSyncStats",
                    "inputPipelineStats", "graphPassStats",
                    "servingStats"):
            assert key in sz, key
        assert sz["pid"] == telemetry.statusz()["pid"]
        assert sz["servingStats"]["em:1"]["completed"] >= 1
        assert sz["telemetry"]["spans"]["recorded"] > 0

        text = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        _assert_valid_prometheus(text)
        assert "mxnet_tpu_exec_cache_hits" in text
        assert 'mxnet_tpu_serving_completed{model="em:1"}' in text
        assert "mxnet_tpu_serving_request_latency_ms_bucket" in text

        try:
            urllib.request.urlopen(base + "/nope", timeout=10)
            raise AssertionError("unknown path must 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        server.stop()


def _assert_valid_prometheus(text):
    """Minimal exposition-format validation: every non-comment line is
    `name{labels} value` with a float-parseable value."""
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if not line or line.startswith("#"):
            continue
        body, _, value = line.rpartition(" ")
        assert body, line
        float(value)  # raises on malformed samples
        name = body.split("{", 1)[0]
        assert name and all(
            (c.isalnum() and c.isascii()) or c in "_:" for c in name
        ), line


def test_exporter_idempotent_and_conflicting_port():
    exp = telemetry.start_exporter(port=0)
    assert telemetry.start_exporter(port=0) is exp
    assert telemetry.start_exporter() is exp
    with pytest.raises(RuntimeError):
        telemetry.start_exporter(port=65000)
    telemetry.stop_exporter()
    assert telemetry.exporter_port() is None


def test_maybe_start_exporter_env(monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY_PORT", raising=False)
    assert telemetry.maybe_start_exporter() is None
    monkeypatch.setenv("MXNET_TELEMETRY_PORT", "0")
    exp = telemetry.maybe_start_exporter()
    assert exp is not None and exp.port > 0
    monkeypatch.setenv("MXNET_TELEMETRY_PORT", "not-a-port")
    telemetry.stop_exporter()
    assert telemetry.maybe_start_exporter() is None  # never raises


# ------------------------------------- dump_profile byte-compat shape
def test_dump_profile_embeds_live_views(tmp_path):
    """The profiler dump must carry the SAME key shapes the silos
    expose directly — the registry views are the silo snapshot
    functions, not copies."""
    from mxnet_tpu import profiler
    from mxnet_tpu.data.stats import input_pipeline_stats
    from mxnet_tpu.exec_cache import cache_stats
    from mxnet_tpu.passes.manager import graph_pass_stats

    fn = str(tmp_path / "p.json")
    profiler.profiler_set_config(filename=fn)
    profiler.profiler_set_state("run")
    net = _fixed_net()
    ex = net.simple_bind(ctx=mx.cpu(), data=(2, 8))
    ex.forward(data=np.ones((2, 8), np.float32))
    profiler.profiler_set_state("stop")
    with open(fn) as f:
        trace = json.load(f)
    assert set(trace["execCacheStats"]) == set(cache_stats())
    assert set(trace["hostSyncStats"]) == \
        set(profiler.host_sync_stats())
    assert set(trace["inputPipelineStats"]) == \
        set(input_pipeline_stats())
    assert set(trace["graphPassStats"]) == set(graph_pass_stats())
    # historical conditional shape: no servingStats key while nothing
    # is served (omit_empty), and legacy keys keep their dump order
    assert "servingStats" not in trace
    legacy_present = [k for k in trace
                      if k in treg.MetricsRegistry.LEGACY_ORDER]
    assert legacy_present == ["execCacheStats", "hostSyncStats",
                              "inputPipelineStats", "graphPassStats"]


def test_dump_profile_includes_serving_when_active(tmp_path):
    from mxnet_tpu import profiler

    net = _fixed_net()
    server = serving.ModelServer(max_wait_us=1000, queue_cap=64)
    try:
        server.load("dm", net.tojson(), _params_for(net, data=(1, 8)),
                    input_specs={"data": (8,)})
        server.predict("dm", {"data": np.ones((8,), np.float32)},
                       timeout=60)
        fn = str(tmp_path / "p.json")
        profiler.profiler_set_config(filename=fn)
        profiler.profiler_set_state("run")
        profiler.profiler_set_state("stop")
        with open(fn) as f:
            trace = json.load(f)
        assert trace["servingStats"]["dm:1"]["completed"] >= 1
    finally:
        server.stop()


# ----------------------------------------------------- flight recorder
def test_flight_record_on_fault_injector(tmp_path, monkeypatch):
    from mxnet_tpu.fault import FaultInjector

    monkeypatch.setenv("MXNET_TELEMETRY_FLIGHT_DIR", str(tmp_path))
    ttrace.record_span("pre-crash-step", "fit-e0-b3", 0.0, 0.001)
    inj = FaultInjector(spec="step:2")
    inj.note_step()
    with pytest.raises(RuntimeError):
        inj.note_step()
    dumps = list(tmp_path.glob("flight-*.json"))
    assert len(dumps) == 1
    rec = json.loads(dumps[0].read_text())
    assert rec["reason"] == "fault_injector:step:2"
    assert any(s["name"] == "pre-crash-step" for s in rec["spans"])
    for key in ("execCacheStats", "hostSyncStats"):
        assert key in rec["stats"]
    assert not list(tmp_path.glob("*.tmp.*"))  # atomic write


def test_flight_record_epoch_trip(tmp_path, monkeypatch):
    from mxnet_tpu.fault import FaultInjector

    monkeypatch.setenv("MXNET_TELEMETRY_FLIGHT_DIR", str(tmp_path))
    inj = FaultInjector(spec="epoch:1")
    inj.maybe_fail(0)  # no trip, no dump
    assert not list(tmp_path.glob("flight-*.json"))
    with pytest.raises(RuntimeError):
        inj.maybe_fail(1)
    assert len(list(tmp_path.glob("flight-*.json"))) == 1


def test_flight_disabled_is_noop(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_TELEMETRY_FLIGHT_DIR", raising=False)
    assert telemetry.maybe_dump("nothing") is None
    # explicit path works without the env var
    p = str(tmp_path / "explicit.json")
    out = telemetry.dump_flight_record("manual", path=p)
    assert out == p
    rec = json.loads(open(p).read())
    assert rec["reason"] == "manual"


def test_excepthook_dumps_on_unhandled(tmp_path):
    """A crashing process with MXNET_TELEMETRY_FLIGHT_DIR set leaves a
    flight record behind (sys.excepthook chain)."""
    import os
    import subprocess
    import sys

    code = (
        "import mxnet_tpu.telemetry as t\n"
        "t.record_span('doomed', 'tid-1', 0.0, 0.001)\n"
        "raise RuntimeError('simulated crash')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TELEMETRY_FLIGHT_DIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env,
        capture_output=True, text=True, timeout=180)
    assert proc.returncode != 0
    assert "simulated crash" in proc.stderr  # chained to default hook
    dumps = list(tmp_path.glob("flight-*.json"))
    assert len(dumps) == 1
    rec = json.loads(dumps[0].read_text())
    assert rec["reason"] == "unhandled_exception"
    assert rec["exception"]["type"] == "RuntimeError"
    assert any(s["name"] == "doomed" for s in rec["spans"])
