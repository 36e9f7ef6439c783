"""Sharding-tier runtime gates (ci/check_sharding.sh drives this;
tier-1 safe: CPU backend with 8 virtual devices, tiny model, < 1 min).

Four gates over live plan-driven training:

  (i)   EXACT parity: the same training run unsharded, under a
        dp-only plan {'data': 8}, and under the combined
        {'data': 2, 'fsdp': 2, 'tp': 2} plan ends with final
        parameters `np.array_equal` — bitwise — across all three.
        The model/data are dyadic rationals (power-of-two lr and
        batch, no-bias FC, plain SGD) so every float32 intermediate
        is exact and reduction order cannot alias a real divergence;
  (ii)  fsdp storage: per-device parameter bytes under the combined
        plan are <= 1/2 the replicated footprint (tp x fsdp = 1/4
        here, asserted at the issue's 1/2 bound);
  (iii) ZERO steady-state retraces: after one warmup epoch, further
        epochs add no executor-cache traces, no graph replays beyond
        the compiled path, and no new sharded-jit builds;
  (iv)  pre-trace rejection: an explicit override whose axis size
        does not divide the dim fails Module.bind with the parameter
        and axis NAMED, before anything traces.
"""
import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import exec_cache  # noqa: E402
from mxnet_tpu.sharding import (ShardingPlan,  # noqa: E402
                                device_param_bytes, lower_stats)


def _sym():
    data = mx.symbol.Variable("data")
    fc = mx.symbol.FullyConnected(data, name="out_head", num_hidden=8,
                                  no_bias=True)
    return mx.symbol.LinearRegressionOutput(fc, name="lro")


def _data():
    rng = np.random.RandomState(0)
    X = rng.randint(-1, 2, size=(8, 4)).astype(np.float32) / 2.0
    Y = rng.randint(-1, 2, size=(8, 8)).astype(np.float32) / 2.0
    return mx.io.NDArrayIter(X, Y, batch_size=8, label_name="lro_label")


def _module(plan):
    it = _data()
    mod = mx.mod.Module(_sym(), data_names=("data",),
                        label_names=("lro_label",), sharding=plan)
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    w0 = np.random.RandomState(7).randint(
        -1, 2, size=(8, 4)).astype(np.float32) / 2.0
    mod.init_params(arg_params={"out_head_weight": mx.nd.array(w0)},
                    aux_params={}, force_init=True)
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.5})
    return mod, it


def _epoch(mod, it):
    it.reset()
    for batch in it:
        mod.forward_backward(batch)
        mod.update()


def _train(plan, n_epochs=3):
    mod, it = _module(plan)
    for _ in range(n_epochs):
        _epoch(mod, it)
    params, _ = mod.get_params()
    return mod, {k: v.asnumpy() for k, v in params.items()}


def gate_parity_and_storage():
    _, base = _train(None)
    _, dp = _train(ShardingPlan({"data": 8}))
    mod, full = _train(ShardingPlan({"data": 2, "fsdp": 2, "tp": 2}))
    for name, ref in sorted(base.items()):
        for tag, run in (("dp", dp), ("dp*tp*fsdp", full)):
            assert np.array_equal(ref, run[name]), (
                f"{name} diverged under {tag}: "
                f"max|diff|={np.abs(ref - run[name]).max()}")
    fs = mod._fused_step
    assert fs is not None and fs._mesh is not None, \
        "combined plan did not build the fused mesh step"
    per_dev = device_param_bytes(fs.params)
    repl = sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in fs.params.values())
    assert per_dev * 2 <= repl, (
        f"fsdp did not shard storage: {per_dev} per-device vs "
        f"{repl} replicated")
    print(f"parity OK ({len(base)} params bitwise-equal across "
          f"3 configs); fsdp storage {per_dev}B/device vs "
          f"{repl}B replicated")


def gate_zero_retrace():
    mod, it = _module(ShardingPlan({"data": 2, "fsdp": 2, "tp": 2}))
    _epoch(mod, it)  # warmup: trace + AOT compile
    c0, l0 = exec_cache.cache_stats(), lower_stats()
    for _ in range(4):
        _epoch(mod, it)
    c1, l1 = exec_cache.cache_stats(), lower_stats()
    for key in ("traces", "jit_builds"):
        assert c1[key] == c0[key], (
            f"steady-state exec-cache {key} grew: "
            f"{c0[key]} -> {c1[key]}")
    assert c1["graph_replays"] == c0["graph_replays"], (
        "steady-state graph replays (uncompiled dispatch): "
        f"{c0['graph_replays']} -> {c1['graph_replays']}")
    assert l1["jit_builds"] == l0["jit_builds"], (
        f"steady-state sharded-jit builds grew: "
        f"{l0['jit_builds']} -> {l1['jit_builds']}")
    print(f"zero-retrace OK (4 steady epochs: traces {c1['traces']}, "
          f"sharded jit builds {l1['jit_builds']}, both flat)")


def gate_pretrace_rejection():
    from mxnet_tpu.analysis import GraphVerifyError

    plan = ShardingPlan({"data": 2, "tp": 2},
                        overrides={"out_head_weight": P_bad()})
    mod = mx.mod.Module(_sym(), data_names=("data",),
                        label_names=("lro_label",), sharding=plan)
    t0 = exec_cache.cache_stats()["traces"]
    try:
        mod.bind(data_shapes=[("data", (8, 5))],  # 5 % 2 != 0
                 label_shapes=[("lro_label", (8, 8))])
    except GraphVerifyError as exc:
        msg = str(exc)
        assert "out_head_weight" in msg and "tp" in msg and "5" in msg, \
            f"rejection must name parameter/axis/sizes: {msg}"
    else:
        raise AssertionError("bad explicit plan was not rejected")
    assert exec_cache.cache_stats()["traces"] == t0, \
        "rejection happened after a trace, not before"
    print("pre-trace rejection OK (named parameter, axis, sizes; "
          "zero traces)")


def P_bad():
    from jax.sharding import PartitionSpec

    return PartitionSpec(None, "tp")


def main():
    import jax

    assert len(jax.devices()) >= 8, (
        "shard gate needs XLA_FLAGS=--xla_force_host_platform_"
        f"device_count=8 (got {len(jax.devices())} devices)")
    gate_parity_and_storage()
    gate_zero_retrace()
    gate_pretrace_rejection()
    print("shard gates OK")


if __name__ == "__main__":
    main()
