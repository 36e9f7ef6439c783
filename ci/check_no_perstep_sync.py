#!/usr/bin/env python
"""CI gate: the steady-state training loop must not sync per step.

Sibling of check_no_perstep_jit.py, but a RUNTIME gate: trains a small
MLP through the real `fit` loop (30 steps/epoch, 2 epochs, Speedometer
logging every 10 batches) and reads profiler hostSyncStats. With
device-resident metrics + dispatch-ahead stepping the steady-state
epoch performs blocking fetches only at log intervals and the epoch-end
drain — NOT once per step. The gate then flips MXNET_DEVICE_METRICS=0
and checks per-step fetches come back, proving the counter (and hence
the assertion) is live, not vacuous.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import profiler  # noqa: E402

BATCH = 4
STEPS = 30          # per epoch
FREQUENT = 10       # Speedometer interval
# per steady-state epoch: fetches at nbatch=10,20 (the nbatch=0 call
# only arms the rate meter) + the epoch-end metric drain
INTERVALS = STEPS // FREQUENT - 1 + 1
SLACK = 1


def _mlp():
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=32, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=5, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _train_two_epochs():
    """fit 2 epochs; return hostSyncStats deltas over the SECOND epoch
    (the first contains compile + warmup fetches)."""
    rng = np.random.RandomState(7)
    x = rng.rand(BATCH * STEPS, 20).astype(np.float32)
    y = rng.randint(0, 5, size=(BATCH * STEPS,)).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=BATCH, shuffle=False)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu()])
    snaps = []

    def epoch_cb(epoch, sym, arg, aux):
        snaps.append(profiler.host_sync_stats())

    profiler.reset_host_sync_stats()
    mod.fit(it, num_epoch=2,
            batch_end_callback=mx.callback.Speedometer(BATCH, FREQUENT),
            epoch_end_callback=epoch_cb,
            optimizer_params=(("learning_rate", 0.05),))
    assert mod._fused_step is not None, \
        "gate invalid: Module did not take the fused train-step path"
    first, second = snaps
    delta = {k: second[k] - first[k]
             for k in ("blocking_fetches", "metric_fetches")}
    delta["steps_in_flight_peak"] = second["steps_in_flight_peak"]
    return delta


def main():
    failures = []

    steady = _train_two_epochs()
    allowed = INTERVALS + SLACK
    if steady["blocking_fetches"] > allowed:
        failures.append(
            f"steady-state epoch performed "
            f"{steady['blocking_fetches']} blocking fetches over "
            f"{STEPS} steps (allowed: {allowed} = log intervals + "
            f"epoch drain + {SLACK} slack) — a per-step sync crept "
            f"back into the fit loop")
    k = mx.utils.getenv("MXNET_DISPATCH_AHEAD")
    if steady["steps_in_flight_peak"] > max(k, 0):
        failures.append(
            f"dispatch window held {steady['steps_in_flight_peak']} "
            f"steps in flight, above MXNET_DISPATCH_AHEAD={k}")

    # sensitivity check: with device metrics off, the host update()
    # path must make the per-step fetches visible again — otherwise
    # the counters are dead and the assertion above proves nothing
    os.environ["MXNET_DEVICE_METRICS"] = "0"
    try:
        legacy = _train_two_epochs()
    finally:
        del os.environ["MXNET_DEVICE_METRICS"]
    if legacy["blocking_fetches"] < STEPS:
        failures.append(
            f"counter sensitivity check failed: host-metric run shows "
            f"only {legacy['blocking_fetches']} blocking fetches for "
            f"{STEPS} steps — sync accounting is broken")

    if failures:
        for msg in failures:
            print(f"check_no_perstep_sync: {msg}", file=sys.stderr)
        return 1
    print(
        f"check_no_perstep_sync: OK — steady-state epoch: "
        f"{steady['blocking_fetches']} blocking fetches / {STEPS} "
        f"steps (host-metric control: {legacy['blocking_fetches']}), "
        f"peak {steady['steps_in_flight_peak']} steps in flight")
    return 0


if __name__ == "__main__":
    sys.exit(main())
