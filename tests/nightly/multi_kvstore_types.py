#!/usr/bin/env python
"""KVStore types cross-check (the reference tests/nightly/
multi_lenet.py role, :1-13 — train the same model under each kvstore
type and require the results to agree).

Single-process: trains an identical MLP from identical init under
kvstore local / device / tpu and compares final params; determinism
comes from fixed seeds and identical batch order. Run directly:

  python tests/nightly/multi_kvstore_types.py
"""
import os
import sys

# single-host CPU determinism — same pin every other harness applies
# (tests/conftest.py, tools/launch.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
)

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def build_net():
    s = mx.sym.Variable("data")
    s = mx.sym.FullyConnected(s, name="fc1", num_hidden=32)
    s = mx.sym.Activation(s, act_type="relu")
    s = mx.sym.FullyConnected(s, name="fc2", num_hidden=4)
    return mx.sym.SoftmaxOutput(s, name="softmax")


def train_with(kv_type, X, y):
    it = mx.io.NDArrayIter(X, y, batch_size=32, shuffle=False)
    mod = mx.mod.Module(build_net(), context=[mx.cpu()])
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Uniform(0.07))  # seeded globally
    mod.init_optimizer(
        kvstore=kv_type, optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    for _ in range(3):
        it.reset()
        for batch in it:
            mod.forward_backward(batch)
            mod.update()
    args, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in args.items()}


def main():
    rs = np.random.RandomState(0)
    X = rs.randn(256, 16).astype(np.float32)
    w = rs.randn(16, 4).astype(np.float32)
    y = (X @ w).argmax(axis=1).astype(np.float32)

    results = {}
    for kv_type in ("local", "device", "tpu"):
        mx.random.seed(7)
        results[kv_type] = train_with(kv_type, X, y)

    base = results["local"]
    for kv_type, params in results.items():
        if kv_type == "local":
            continue
        for name, val in params.items():
            np.testing.assert_allclose(
                val, base[name], rtol=2e-3, atol=2e-4,
                err_msg=f"{kv_type}:{name} diverged from local")
    print("multi_kvstore_types OK:",
          {k: round(float(np.abs(v['fc1_weight']).mean()), 4)
           for k, v in results.items()})


if __name__ == "__main__":
    main()
