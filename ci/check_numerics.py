"""Numerics-tier runtime gates (ci/check_numerics.sh drives this;
tier-1 safe: CPU backend, tiny model, < 1 min).

Three gates over a live run with a seeded numerics fault:

  (i)   DETECTION within one drain interval: a NaN injected into one
        gradient tensor on-device at step N (the fault.py
        'nan:step:N:param' mode) must surface as a `nonfinite`
        anomaly at exactly step N, recorded in the run event log
        BEFORE any later step's row — the sentinel saw it at the
        first drain after the trip, not epochs later;
  (ii)  ATTRIBUTION: the anomaly's eager replay names the first op
        whose output is non-finite — the op consuming the poisoned
        parameter — and the crash flight record is durable, parseable
        JSON carrying the anomaly + culprit + recent sentinel rows;
  (iii) SYNC BUDGET: ci/check_no_perstep_sync.py re-run with
        MXNET_NUMERICS=1 still passes — run health rides the existing
        dispatch and drains in one fetch per interval, so the
        steady-state host-sync budget is unchanged.
"""
import json
import os
import subprocess
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

INJECT_STEP = 4
INTERVAL = 4

_workdir = tempfile.mkdtemp(prefix="numerics_gate_")
os.environ["MXNET_TPU_FAULT_INJECT"] = \
    f"nan:step:{INJECT_STEP}:fc1_weight"
os.environ["MXNET_TELEMETRY_FLIGHT_DIR"] = \
    os.path.join(_workdir, "flight")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.numerics import NumericsMonitor, read_events  # noqa: E402


def _mlp():
    d = mx.sym.Variable("data")
    f1 = mx.sym.FullyConnected(d, name="fc1", num_hidden=16)
    a1 = mx.sym.Activation(f1, name="relu1", act_type="relu")
    f2 = mx.sym.FullyConnected(a1, name="fc2", num_hidden=4)
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def _iter():
    rs = np.random.RandomState(0)
    X = rs.uniform(-1, 1, (256, 8)).astype(np.float32)
    Y = rs.randint(0, 4, (256,)).astype(np.float32)
    return mx.io.NDArrayIter(X, Y, batch_size=32)


def gate_detection_and_attribution():
    log = os.path.join(_workdir, "runlog.jsonl")
    mon = NumericsMonitor(interval=INTERVAL, run_log=log)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    mod.fit(_iter(), num_epoch=1, numerics=mon, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1})

    # (i) detection at the injected step, within one drain interval
    bad = [a for a in mon.anomalies if a.kind == "nonfinite"]
    assert bad, "injected NaN never detected"
    assert bad[0].step == INJECT_STEP, (
        f"first nonfinite anomaly at step {bad[0].step}, "
        f"injected at {INJECT_STEP}")
    events = read_events(log)
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start", kinds[:1]
    anom_at = kinds.index("anomaly")
    # the interval drain is non-blocking (completed rows only), so the
    # poisoned row surfaces at latest one interval after the trip
    late = [i for i, e in enumerate(events)
            if e["event"] == "step"
            and e["step"] > INJECT_STEP + INTERVAL]
    assert not late or anom_at < min(late), (
        "anomaly logged only after rows a full interval past the trip "
        "— detection missed the first drain that held the bad row")

    # (ii) attribution names the op fed by the poisoned parameter
    anom_ev = events[anom_at]
    assert anom_ev.get("first_bad_op") == "fc1_output", anom_ev
    flight_dir = os.environ["MXNET_TELEMETRY_FLIGHT_DIR"]
    recs = sorted(os.listdir(flight_dir)) if os.path.isdir(flight_dir) \
        else []
    assert recs, "no crash flight record written on the numerics trip"
    with open(os.path.join(flight_dir, recs[0])) as f:
        rec = json.load(f)
    assert rec["reason"] == "numerics:nonfinite", rec["reason"]
    nm = rec["extra"]["numerics"]
    assert nm["first_bad_op"] == "fc1_output", nm
    assert nm["anomaly"]["kind"] == "nonfinite", nm
    assert nm["recent_rows"], "flight record carries no sentinel rows"
    print(f"numerics detection OK: nonfinite at step {bad[0].step} "
          f"(injected {INJECT_STEP}, interval {INTERVAL}), "
          f"first bad op {anom_ev['first_bad_op']}, "
          f"flight record {recs[0]}")


def gate_sync_budget():
    env = dict(os.environ)
    env.pop("MXNET_TPU_FAULT_INJECT", None)
    env["MXNET_NUMERICS"] = "1"
    env["MXNET_NUMERICS_INTERVAL"] = "30"
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "check_no_perstep_sync.py")
    proc = subprocess.run([sys.executable, script], env=env,
                          capture_output=True, text=True)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, (
        "per-step sync gate fails with MXNET_NUMERICS=1 — the "
        "sentinel drain broke the host-sync budget")
    print("numerics sync budget OK: check_no_perstep_sync passes "
          "with MXNET_NUMERICS=1")


if __name__ == "__main__":
    gate_detection_and_attribution()
    gate_sync_budget()
    print("numerics gates passed")
