#!/usr/bin/env python
"""CI gate: the steady-state training step must never wait on input.

Runtime sibling of check_no_perstep_sync.py for the DATA side: that
gate proved the fit loop doesn't block on the device; this one proves
it doesn't block on the host input path either. Three sub-checks:

1. zero-stall — a real `fit` over the mxnet_tpu.data pipeline (sharded
   loader + device prefetch) must report inputPipelineStats.stall_count
   == 0 over the steady-state (second) epoch: every batch the step
   consumed was already device-resident.
2. sensitivity — the same run with MXNET_DATA_DEVICE_PREFETCH=0
   (synchronous host->device staging) must report stalls for EVERY
   steady-state batch; otherwise the stall counter is dead and check 1
   proves nothing.
3. resume replay — a run killed mid-epoch by FaultInjector("step:N")
   and auto-resumed must consume a bit-identical sequence of remaining
   batches (same seed, same shard): killed-run stream + resumed-run
   stream == uninterrupted reference stream, byte for byte.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import data as mxdata  # noqa: E402
from mxnet_tpu import fault  # noqa: E402

BATCH = 32
STEPS = 30          # batches per epoch (shard of one host)
FEATURES = 64
EPOCHS = 2
SEED = 11
KILL_STEP = int(STEPS * 1.5)   # mid-way through epoch 2


def _mlp():
    # big enough that per-step compute dominates staging cost — the
    # regime the prefetch tier exists for (on a toy model the consumer
    # is pure Python overhead and rate-matches the stager, so "stall"
    # degenerates to a scheduler coin flip)
    d = mx.sym.Variable("data")
    h = mx.sym.FullyConnected(d, num_hidden=512, name="fc1")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=512, name="fc2")
    h = mx.sym.Activation(h, act_type="relu")
    h = mx.sym.FullyConnected(h, num_hidden=5, name="fc3")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _arrays():
    rng = np.random.RandomState(7)
    x = rng.rand(BATCH * STEPS, FEATURES).astype(np.float32)
    y = rng.randint(0, 5, size=(BATCH * STEPS,)).astype(np.float32)
    return x, y


def _pipeline(x, y):
    return mxdata.make_pipeline(
        x, BATCH, label=y, seed=SEED, shard_id=0, num_shards=1)


class _RecordingIter(object):
    """Transparent wrapper hashing every batch the fit loop consumes —
    the observable the resume-replay check compares byte-for-byte."""

    def __init__(self, inner, log):
        self._inner = inner
        self._log = log

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        batch = self._inner.next()
        self._log.append(batch.data[0].asnumpy().tobytes())
        return batch

    def reset(self):
        self._inner.reset()

    def set_epoch(self, epoch):
        self._inner.set_epoch(epoch)

    def state_dict(self):
        return self._inner.state_dict()

    def load_state_dict(self, state):
        self._inner.load_state_dict(state)


def _train(epochs=EPOCHS):
    """fit over the full pipeline; return inputPipelineStats deltas over
    the SECOND epoch (the first holds compile + pipeline-fill warmup)."""
    from mxnet_tpu import profiler

    x, y = _arrays()
    it = _pipeline(x, y)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu()])
    snaps = []

    def epoch_cb(epoch, sym, arg, aux):
        snaps.append(profiler.input_pipeline_stats())

    mxdata.reset_input_pipeline_stats()
    try:
        mod.fit(it, num_epoch=epochs,
                epoch_end_callback=epoch_cb,
                optimizer_params=(("learning_rate", 0.05),))
    finally:
        it.close()
    first, second = snaps[0], snaps[1]
    return {k: second[k] - first[k]
            for k in ("batches", "stall_count", "host_batches")}


def _check_stalls(failures):
    steady = _train()
    if steady["batches"] != STEPS:
        failures.append(
            f"gate invalid: steady-state epoch served "
            f"{steady['batches']} batches, expected {STEPS}")
    if steady["stall_count"] != 0:
        failures.append(
            f"steady-state epoch stalled on input "
            f"{steady['stall_count']}x over {STEPS} steps — the device "
            f"prefetch is not keeping batches resident ahead of fit")

    # sensitivity: prefetch off => synchronous staging => every batch
    # is by definition a stall. If the counter doesn't light up here,
    # the zero above is the silence of a dead counter.
    os.environ["MXNET_DATA_DEVICE_PREFETCH"] = "0"
    try:
        sync = _train()
    finally:
        del os.environ["MXNET_DATA_DEVICE_PREFETCH"]
    if sync["stall_count"] < STEPS:
        failures.append(
            f"counter sensitivity check failed: synchronous run shows "
            f"only {sync['stall_count']} stalls for {STEPS} steps — "
            f"stall accounting is broken")
    return steady, sync


def _fit_recorded(prefix, log, injector):
    x, y = _arrays()
    it = _RecordingIter(_pipeline(x, y), log)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu()])
    try:
        fault.fit_auto_resume(
            mod, it, prefix, num_epoch=EPOCHS,
            fault_injector=injector,
            optimizer_params=(("learning_rate", 0.05),))
    finally:
        it._inner.close()


def _check_resume(failures, workdir):
    prefix = os.path.join(workdir, "job")
    killed = []
    try:
        _fit_recorded(prefix, killed,
                      fault.FaultInjector(f"step:{KILL_STEP}"))
        failures.append("gate invalid: injected fault never fired")
        return
    except RuntimeError as exc:
        if "fault-injection" not in str(exc):
            raise
    if len(killed) != KILL_STEP:
        failures.append(
            f"gate invalid: killed run consumed {len(killed)} batches, "
            f"expected {KILL_STEP}")

    resumed = []
    _fit_recorded(prefix, resumed, fault.FaultInjector(""))

    reference = []
    _fit_recorded(os.path.join(workdir, "ref"), reference,
                  fault.FaultInjector(""))

    if killed + resumed != reference:
        for i, (a, b) in enumerate(zip(killed + resumed, reference)):
            if a != b:
                failures.append(
                    f"mid-epoch resume diverged at batch {i} "
                    f"(killed {len(killed)} + resumed {len(resumed)} "
                    f"vs reference {len(reference)}) — the replayed "
                    f"stream is not bit-identical")
                return
        failures.append(
            f"mid-epoch resume stream length mismatch: "
            f"{len(killed)} + {len(resumed)} != {len(reference)}")
    return len(resumed)


def main():
    import tempfile

    failures = []
    steady, sync = _check_stalls(failures)
    with tempfile.TemporaryDirectory() as workdir:
        remaining = _check_resume(failures, workdir)

    if failures:
        for msg in failures:
            print(f"check_input_stall: {msg}", file=sys.stderr)
        return 1
    print(
        f"check_input_stall: OK — steady-state epoch: "
        f"{steady['stall_count']} stalls / {steady['batches']} steps "
        f"(sync control: {sync['stall_count']}); mid-epoch kill at "
        f"step {KILL_STEP} resumed bit-identically "
        f"({remaining} replayed batches)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
