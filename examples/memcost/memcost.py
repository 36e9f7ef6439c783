#!/usr/bin/env python
"""Memory-mirror cost study (reference example/memcost/ +
inception_memcost.py: MXNET_BACKWARD_DO_MIRROR trades ~10% speed for
~2x batch, example/image-classification/README.md:352-359).

The TPU-native analog is jax.checkpoint rematerialization, switched by
the SAME env var (mxnet_tpu/executor.py). This script trains the same
deep MLP twice — mirror off / mirror on — in subprocesses (the flag is
read at bind), compares per-step activation-memory estimates from XLA
cost analysis, and GATES on the mirror run reproducing the baseline
loss sequence exactly (remat must change memory, never math).

  python examples/memcost/memcost.py
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

CHILD = r"""
import json
import os
import sys

import numpy as np

import mxnet_tpu as mx

rs = np.random.RandomState(0)
X = rs.rand(64, 128).astype(np.float32)
y = rs.randint(0, 4, 64).astype(np.float32)

data = mx.sym.Variable("data")
h = data
for i in range(8):  # deep stack: remat cuts live activations on TPU
    h = mx.sym.Activation(
        mx.sym.FullyConnected(h, num_hidden=256, name=f"fc{i}"),
        act_type="tanh")
net = mx.sym.SoftmaxOutput(
    mx.sym.FullyConnected(h, num_hidden=4, name="head"),
    name="softmax")

mod = mx.mod.Module(net)
mod.bind(data_shapes=[("data", (64, 128))],
         label_shapes=[("softmax_label", (64,))])
np.random.seed(3)
mod.init_params(mx.initializer.Xavier())
# eager executors (no fused step) exercise the mirrored train_step
losses = []
b = mx.io.DataBatch(data=[mx.nd.array(X)], label=[mx.nd.array(y)])
for _ in range(4):
    mod.forward(b, is_train=True)
    out = mod.get_outputs()[0].asnumpy()
    p = out[np.arange(64), y.astype(int)]
    losses.append(float(-np.log(np.maximum(p, 1e-9)).mean()))
    mod.backward()
    grads = {n: g.asnumpy() for n, g in mod._exec_group.execs[0]
             .grad_dict.items()}
    for n, a in mod._exec_group.execs[0].arg_dict.items():
        if n in grads and grads[n].size:
            a[:] = a.asnumpy() - 0.003 * grads[n]

# activation-memory estimate: XLA cost analysis of the compiled
# train step (bytes of temporaries ~ live activations)
ex = mod._exec_group.execs[0]
temp = -1.0
try:
    import jax

    args = ({n: a._data for n, a in ex.arg_dict.items()},
            {n: a._data for n, a in ex.aux_dict.items()},
            jax.random.PRNGKey(0),
            [jax.numpy.ones_like(o._data) for o in ex.outputs])
    lowered = jax.jit(ex._jit_train_step.__wrapped__).lower(*args) \
        if hasattr(ex._jit_train_step, "__wrapped__") else \
        ex._jit_train_step.lower(*args)
    mem = lowered.compile().memory_analysis()
    temp = float(getattr(mem, "temp_size_in_bytes", -1.0))
except Exception as exc:  # cost analysis is best-effort
    print("cost analysis unavailable:", exc, file=sys.stderr)
print(json.dumps({
    "mirror": os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0"),
    "losses": losses,
    "temp_bytes": temp,
}))
"""


def run(mirror):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MXNET_BACKWARD_DO_MIRROR"] = "1" if mirror else "0"
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", CHILD], env=env,
                          capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    argparse.ArgumentParser().parse_args()
    base = run(mirror=False)
    mirr = run(mirror=True)
    print(f"baseline losses {['%.4f' % l for l in base['losses']]} "
          f"temp_bytes {base['temp_bytes']:.0f}")
    print(f"mirror   losses {['%.4f' % l for l in mirr['losses']]} "
          f"temp_bytes {mirr['temp_bytes']:.0f}")
    # THE gate: remat must never change the math — identical loss
    # sequence step for step
    for a, b in zip(base["losses"], mirr["losses"]):
        assert abs(a - b) < 1e-5, (a, b)
    if base["temp_bytes"] > 0 and mirr["temp_bytes"] > 0:
        ratio = mirr["temp_bytes"] / base["temp_bytes"]
        print(f"temp-memory ratio mirror/baseline = {ratio:.2f}")
        # informational on CPU: XLA-CPU's buffer assignment often
        # schedules this toy model into the same temp footprint; the
        # saving shows on TPU-sized models (reference README: ~2x
        # batch for ~10% speed)
    print("memcost OK")


if __name__ == "__main__":
    main()
