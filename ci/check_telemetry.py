#!/usr/bin/env python
"""CI gate: the telemetry tier's four load-bearing promises, runtime-
checked on the CPU backend.

  1. Correlation: EVERY request submitted to a live serving.Server is
     reconstructable across its full span path (submit -> enqueue ->
     batch_flush -> execute -> reply) from the Future's trace id.
  2. Endpoints: /metrics parses as Prometheus text exposition and
     /statusz as JSON, and both agree with the in-process snapshots
     (same registry, not a copy).
  3. Overhead: always-on tracing costs <= 3% of step time on the bench
     net (A/B: MXNET_TELEMETRY_SPANS default vs 0 in one process).
  4. Flight recorder: a FaultInjector trip leaves a readable flight
     record (spans + all subsystem stats) on disk.
"""
import json
import os
import statistics
import sys
import tempfile
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import serving, telemetry  # noqa: E402
from mxnet_tpu.telemetry import trace as ttrace  # noqa: E402

N_REQUESTS = 32
OVERHEAD_TOL = 1.03          # <= 3% per ISSUE / docs/observability.md
OVERHEAD_EPS_US = 50.0       # absolute floor: damp sub-µs CI jitter


def _fail(msg):
    print(f"check_telemetry: FAIL — {msg}")
    sys.exit(1)


def _params_for(net, **input_shapes):
    shapes, _, _ = net.infer_shape(**input_shapes)
    rs = np.random.RandomState(7)
    return {
        n: mx.nd.array(rs.uniform(-1, 1, s).astype("float32"))
        for n, s in zip(net.list_arguments(), shapes)
        if n not in input_shapes
    }


def check_correlation_and_endpoints():
    """Gates 1 + 2 on one live server under a small burst."""
    net = mx.sym.FullyConnected(
        mx.sym.Variable("data"), num_hidden=4, name="fc")
    server = serving.ModelServer(max_wait_us=1000, queue_cap=256)
    exporter = telemetry.start_exporter(port=0)
    try:
        server.load("gate", net.tojson(),
                    _params_for(net, data=(1, 8)),
                    input_specs={"data": (8,)})
        rs = np.random.RandomState(0)
        futs = [server.submit(
            "gate", {"data": rs.rand(8).astype("float32")})
            for _ in range(N_REQUESTS)]
        for f in futs:
            f.result(timeout=120)

        # -- gate 1: every request's full path is reconstructable
        required = {"serving.submit", "serving.enqueue",
                    "serving.batch_flush", "serving.execute",
                    "serving.reply"}
        for f in futs:
            if not getattr(f, "trace_id", None):
                _fail("submitted Future carries no trace_id")
            names = {s.name for s in
                     telemetry.spans_for_trace(f.trace_id)}
            if not required <= names:
                _fail(f"trace {f.trace_id} missing spans: "
                      f"{sorted(required - names)}")
        print(f"check_telemetry: correlation OK — {N_REQUESTS} "
              f"requests x {len(required)} spans each")

        # -- gate 2: endpoints parse and agree with process state
        base = f"http://127.0.0.1:{exporter.port}"
        text = urllib.request.urlopen(
            base + "/metrics", timeout=10).read().decode()
        samples = {}
        for line in text.strip().split("\n"):
            if not line or line.startswith("#"):
                continue
            body, _, value = line.rpartition(" ")
            if not body:
                _fail(f"malformed metrics line: {line!r}")
            try:
                samples[body] = float(value)
            except ValueError:
                _fail(f"non-numeric sample value: {line!r}")
        if not samples:
            _fail("/metrics rendered no samples")

        sz = json.loads(urllib.request.urlopen(
            base + "/statusz", timeout=10).read())
        for key in ("execCacheStats", "servingStats", "hostSyncStats",
                    "inputPipelineStats", "graphPassStats"):
            if key not in sz:
                _fail(f"/statusz missing subsystem key {key!r}")

        # agreement: the endpoint serves the live registry, so the
        # serving counters must match the in-process snapshot exactly
        # (the server is idle now — no concurrent mutation)
        local = serving.stats.serving_stats()["gate:1"]
        remote = sz["servingStats"]["gate:1"]
        for field in ("submitted", "completed", "batches"):
            if remote[field] != local[field]:
                _fail(f"/statusz servingStats.{field} = "
                      f"{remote[field]} but in-process snapshot says "
                      f"{local[field]}")
        if remote["completed"] < N_REQUESTS:
            _fail(f"completed {remote['completed']} < {N_REQUESTS}")
        prom_key = 'mxnet_tpu_serving_completed{model="gate:1"}'
        if prom_key not in samples:
            _fail(f"/metrics missing {prom_key}")
        if samples[prom_key] != local["completed"]:
            _fail(f"/metrics {prom_key} = {samples[prom_key]} vs "
                  f"in-process {local['completed']}")
        print(f"check_telemetry: endpoints OK — "
              f"{len(samples)} prometheus samples, statusz agrees")
    finally:
        server.stop()
        telemetry.stop_exporter()


def check_overhead():
    """Gate 3: same-process A/B of the bench net's step time with span
    recording on (default capacity) vs off (capacity 0)."""
    import time

    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=128, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=8, name="fc2")
    net = mx.sym.SoftmaxOutput(h, name="softmax")

    batch, steps, reps = 32, 20, 5
    rs = np.random.RandomState(0)
    x = rs.rand(batch * steps, 16).astype("float32")
    y = rs.randint(0, 8, (batch * steps,)).astype("float32")

    mod = mx.mod.Module(net, context=[mx.cpu()])

    def epoch_time():
        it = mx.io.NDArrayIter(x, y, batch_size=batch)
        t0 = time.perf_counter()
        mod.fit(it, num_epoch=1,
                optimizer_params=(("learning_rate", 0.1),))
        return time.perf_counter() - t0

    epoch_time()  # warmup: compile everything before either arm
    # interleave the arms (off, on, off, on, ...) so machine-load
    # drift between measurements hits both equally — sequential arms
    # mis-attribute any slow patch to whichever ran inside it
    times = {"disabled": [], "enabled": []}
    for _ in range(reps):
        for label, cap in (("disabled", 0), ("enabled", 2048)):
            ttrace.set_capacity(cap)
            times[label].append(epoch_time())
    ttrace.set_capacity(ttrace._env_capacity())
    arms = {label: statistics.median(v) for label, v in times.items()}

    per_step_on = arms["enabled"] / steps * 1e6
    per_step_off = arms["disabled"] / steps * 1e6
    bound = per_step_off * OVERHEAD_TOL + OVERHEAD_EPS_US
    print(f"check_telemetry: overhead — step {per_step_off:.1f}us "
          f"(tracing off) vs {per_step_on:.1f}us (on), "
          f"bound {bound:.1f}us")
    if per_step_on > bound:
        _fail(f"tracing overhead {per_step_on:.1f}us/step exceeds "
              f"{OVERHEAD_TOL:.0%} of {per_step_off:.1f}us/step")
    print("check_telemetry: overhead OK (<= 3% + jitter floor)")


def check_flight_recorder():
    """Gate 4: a FaultInjector trip leaves a complete flight record."""
    from mxnet_tpu.fault import FaultInjector

    with tempfile.TemporaryDirectory() as d:
        old = os.environ.get("MXNET_TELEMETRY_FLIGHT_DIR")
        os.environ["MXNET_TELEMETRY_FLIGHT_DIR"] = d
        try:
            ttrace.record_span("gate-step", "fit-e0-b0", 0.0, 1e-3)
            inj = FaultInjector(spec="step:1")
            try:
                inj.note_step()
            except RuntimeError:
                pass
            else:
                _fail("FaultInjector('step:1') did not trip")
        finally:
            if old is None:
                del os.environ["MXNET_TELEMETRY_FLIGHT_DIR"]
            else:
                os.environ["MXNET_TELEMETRY_FLIGHT_DIR"] = old
        dumps = [f for f in os.listdir(d)
                 if f.startswith("flight-") and f.endswith(".json")]
        if len(dumps) != 1:
            _fail(f"expected exactly one flight record, found {dumps}")
        with open(os.path.join(d, dumps[0])) as f:
            rec = json.load(f)
        if not rec["reason"].startswith("fault_injector:"):
            _fail(f"wrong flight reason {rec['reason']!r}")
        if not any(s["name"] == "gate-step" for s in rec["spans"]):
            _fail("flight record lost the pre-crash span")
        for key in ("execCacheStats", "hostSyncStats",
                    "inputPipelineStats", "graphPassStats"):
            if key not in rec["stats"]:
                _fail(f"flight record stats missing {key!r}")
    print("check_flight_recorder: flight record OK")


def main():
    check_correlation_and_endpoints()
    check_overhead()
    check_flight_recorder()
    print("check_telemetry: PASS")


if __name__ == "__main__":
    main()
