"""Runtime gate for the device-side observability tier (profiling).

Asserts the PR's acceptance contract end to end, in-process on the CPU
backend:

  1. COVERAGE — after a serving warmup every exec-cache entry carries a
     deviceStats record (the digest join), each with nonzero compile
     seconds and a nonzero HBM footprint.
  2. ZERO STEADY-STATE COST — serving traffic after warmup adds no
     exec-cache traces and no new deviceStats records: the
     instrumentation layer never causes a retrace or a recompile.
  3. CALIBRATION — warmup harvested a measured forward time, so
     cost_model.calibrated_cost() returns source="measured" for the
     served graph and falls back to source="analytic" for a graph the
     store has never seen.
  4. PRE-FLIGHT — a fake 100-byte device cap turns the bind-time HBM
     estimate into a structured warning (report attached), and
     MXNET_PROFILING_HBM_STRICT=1 turns it into a raise BEFORE any
     trace happens.
  5. DECODE GRID — a decode-engine warmup lands one record per grid
     program, and a steady-state step adds zero traces.
"""
import os
import sys
import tempfile
import warnings

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the gate must not read or pollute the developer's calibration cache
os.environ["MXNET_CALIBRATION_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="mx_prof_gate_"), "calibration.json")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import exec_cache, profiling, serving  # noqa: E402
from mxnet_tpu.passes import cost_model  # noqa: E402

FAILURES = []


def check(name, ok, detail=""):
    tag = "ok" if ok else "FAIL"
    print(f"[{tag}] {name}" + (f" — {detail}" if detail else ""))
    if not ok:
        FAILURES.append(name)


def build_net():
    data = mx.sym.Variable("data")
    net = mx.sym.Embedding(data, input_dim=200, output_dim=16,
                           name="embed")
    net = mx.sym.mean(net, axis=1)
    return mx.sym.FullyConnected(net, num_hidden=8, name="fc")


def serving_gate():
    net = build_net()
    shapes, _, _ = net.infer_shape(data=(1, 16))
    rs = np.random.RandomState(0)
    params = {n: mx.nd.array(rs.normal(0, 0.1, s).astype("float32"))
              for n, s in zip(net.list_arguments(), shapes)
              if n != "data"}

    profiling.reset_device_stats()
    exec_cache.clear()
    exec_cache.reset_stats()
    server = serving.ModelServer(max_batch=4, max_wait_us=1000)
    server.load("gate", net.tojson(), params,
                input_specs={"data": ("L",)},
                input_dtypes={"data": "int32"},
                batch_buckets=(1, 4), length_buckets=(8, 16))

    snap = profiling.device_stats()
    recs = snap.get("executables", {})
    digests = exec_cache.entry_digests()
    check("warmup produced exec-cache entries", len(digests) > 0,
          f"{len(digests)} entries")
    covered = [d for d in digests
               if any(r["digest"] == d for r in recs.values())]
    check("deviceStats covers every exec-cache entry",
          len(covered) == len(digests),
          f"{len(covered)}/{len(digests)} covered, "
          f"{len(recs)} records")
    check("every record carries compile seconds",
          all(r["compile_s"] > 0 for r in recs.values()))
    check("every record carries an HBM footprint",
          all(r["hbm_bytes"] > 0 for r in recs.values()))
    check("every record carries the canonical digest",
          all(r["canonical"] for r in recs.values()))

    # ---- steady state: traffic must not grow the ledger
    traces0 = exec_cache.cache_stats()["traces"]
    n_records0 = len(recs)
    rs = np.random.RandomState(1)
    for _ in range(24):
        ids = rs.randint(0, 200, size=(int(rs.choice((5, 12))),)) \
            .astype("int32")
        server.predict("gate", {"data": ids})
    traces_added = exec_cache.cache_stats()["traces"] - traces0
    records_added = len(profiling.device_stats()
                        .get("executables", {})) - n_records0
    check("zero steady-state retraces under instrumentation",
          traces_added == 0, f"{traces_added} traces added")
    check("zero steady-state deviceStats growth", records_added == 0,
          f"{records_added} records added")
    server.stop()

    # ---- calibration: measured for the served graph, analytic else
    cc = cost_model.calibrated_cost(net, {"data": (4, 16)})
    check("calibrated_cost is measured-backed after warmup",
          cc["source"] == "measured", f"source={cc['source']}")
    check("measured estimate is positive", (cc["est_s"] or 0) > 0)

    other = mx.sym.FullyConnected(mx.sym.Variable("data"),
                                  num_hidden=3, name="never_served")
    cc2 = cost_model.calibrated_cost(other, {"data": (2, 7)})
    check("unseen graph falls back to the analytic model",
          cc2["source"] == "analytic", f"source={cc2['source']}")


def preflight_gate():
    net = build_net()
    old = os.environ.get("MXNET_PROFILING_DEVICE_MEM_BYTES")
    os.environ["MXNET_PROFILING_DEVICE_MEM_BYTES"] = "100"
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            exe = net.simple_bind(mx.cpu(), grad_req="null",
                                  data=(2, 8))
            exe.forward(is_train=False,
                        data=mx.nd.array(np.zeros((2, 8), "int32")))
        hits = [w for w in caught
                if issubclass(w.category,
                              profiling.HBMPreflightWarning)]
        check("over-cap bind emits HBMPreflightWarning",
              len(hits) == 1, f"{len(hits)} warnings")
        report = getattr(hits[0].message, "report", None) if hits \
            else None
        check("warning carries the structured report",
              bool(report) and not report["fits"]
              and report["total_bytes"] > report["cap_bytes"])

        os.environ["MXNET_PROFILING_HBM_STRICT"] = "1"
        try:
            traces0 = exec_cache.cache_stats()["traces"]
            raised = False
            try:
                net.simple_bind(mx.cpu(), grad_req="null",
                                data=(4, 8))
            except profiling.HBMPreflightError:
                raised = True
            check("strict mode raises HBMPreflightError", raised)
            check("strict raise happens before any trace",
                  exec_cache.cache_stats()["traces"] == traces0)
        finally:
            del os.environ["MXNET_PROFILING_HBM_STRICT"]
    finally:
        if old is None:
            del os.environ["MXNET_PROFILING_DEVICE_MEM_BYTES"]
        else:
            os.environ["MXNET_PROFILING_DEVICE_MEM_BYTES"] = old


def decode_gate():
    from mxnet_tpu import decoding as dec

    cfg = dec.DecoderConfig(vocab=64, d_model=32, n_layers=1,
                            n_heads=2, d_ff=64, max_len=64)
    params = dec.init_decoder_params(cfg, seed=0)
    engine = dec.DecodeEngine(params, cfg, max_batch=2, page_size=8,
                              num_pages=16, page_buckets=(2, 4))
    profiling.reset_device_stats()
    engine.warmup()
    recs = profiling.device_stats().get("executables", {})
    kinds = sorted(r["kind"] for r in recs.values())
    grid = sorted(["copy_page", "decode@2", "decode@4",
                   "prefill@16", "prefill@32"])
    check("decode warmup records the full program grid",
          kinds == grid, f"kinds={kinds}")
    floor = engine.traces()
    engine.step(np.zeros((2,), np.int32),
                np.zeros((2, 2), np.int32),
                np.zeros((2,), np.int32),
                np.zeros((2,), bool))
    check("steady-state decode step adds zero traces",
          engine.traces() == floor,
          f"{engine.traces() - floor} traces added")


def main():
    serving_gate()
    preflight_gate()
    decode_gate()
    if FAILURES:
        print(f"profiling gate: {len(FAILURES)} failure(s): "
              + ", ".join(FAILURES))
        return 1
    print("profiling gate: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
