"""The benchmark's ResNet configuration, gated at tiny scale on CPU:
the path the ResNet cells of perfbench/ measure (Module +
KVStore('tpu') fused step + cast_compute(bfloat16) + NHWC +
space-to-depth stem) must train with finite loss and updating
parameters — so a cell's run can't be broken by a config-interaction
regression the per-feature tests miss.
"""
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.models import get_resnet


def _flagship_module(batch, classes=5):
    """The ResNet cells' configuration at tiny scale (resnet-18,
    64px, NHWC, s2d stem, KVStore('tpu'), sgd-momentum, bf16 compute)
    — one definition so both gates certify the same config."""
    net = get_resnet(num_classes=classes, num_layers=18,
                     image_shape=(3, 64, 64), layout="NHWC",
                     stem="space_to_depth")
    mod = mx.mod.Module(net, context=[mx.cpu()])
    mod.bind(data_shapes=[("data", (batch, 64, 64, 3))],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.0))
    mod.init_optimizer(
        kvstore="tpu", optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9,
                          "wd": 1e-4})
    mod.cast_compute(jnp.bfloat16)
    return mod


def test_flagship_bench_config_trains():
    np.random.seed(0)
    batch, classes = 8, 5
    mod = _flagship_module(batch, classes)

    rs = np.random.RandomState(0)
    data = mx.nd.array(rs.uniform(-1, 1, (batch, 64, 64, 3))
                       .astype("float32"))
    label = mx.nd.array(rs.randint(0, classes, (batch,))
                        .astype("float32"))
    b = mx.io.DataBatch(data=[data], label=[label])

    before = {k: v.asnumpy().copy()
              for k, v in mod.get_params()[0].items()}
    for _ in range(3):
        mod.forward_backward(b)
        mod.update()
    mod.sync()

    out = None
    mod.forward(b, is_train=False)
    out = mod.get_outputs()[0].asnumpy()
    assert np.isfinite(out).all(), "non-finite outputs on bench path"

    after = mod.get_params()[0]
    moved = sum(
        float(np.abs(after[k].asnumpy() - before[k]).max()) > 0
        for k in before)
    assert moved > len(before) * 0.8, "most params must update"
    # the step accounting the bench divides by must be positive
    assert mod.train_step_flops() > 0


def test_flagship_bench_multistep_config_trains():
    """steps_per_dispatch > 1: run_steps with stacked per-step batches
    over the same flagship config — must train finitely and report
    positive per-step flops through the k-loop estimate."""
    np.random.seed(0)
    batch, classes, k = 4, 5, 3
    mod = _flagship_module(batch, classes)

    rs = np.random.RandomState(0)
    Xs = rs.uniform(-1, 1, (k, batch, 64, 64, 3)).astype("float32")
    Ys = rs.randint(0, classes, (k, batch)).astype("float32")
    stacked = mx.io.DataBatch(data=[mx.nd.array(Xs)],
                              label=[mx.nd.array(Ys)])

    before = {n: v.asnumpy().copy()
              for n, v in mod.get_params()[0].items()}
    for _ in range(2):
        mod.run_steps(stacked, k, stacked=True)
        # the COMPILED k-loop must have run, not the eager fallback
        # (which never populates _staged_outputs)
        assert mod._staged_outputs is not None
    assert (int(k), True) in mod._fused_step._multi_cache
    mod.sync()
    out = mod.get_outputs()[0].asnumpy()
    assert np.isfinite(out).all()
    after = mod.get_params()[0]
    moved = sum(
        float(np.abs(after[n].asnumpy() - before[n]).max()) > 0
        for n in before)
    assert moved > len(before) * 0.8, "most params must update"
    assert mod.train_step_flops() > 0
