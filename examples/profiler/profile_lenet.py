#!/usr/bin/env python
"""Profiler walkthrough (reference example/profiler/profiler_executor.py):
turn on the merged host+device profiler around a few training steps and
dump a Chrome trace-event JSON you can load in chrome://tracing or
Perfetto — host-side engine/io events plus, on an accelerator, the
device's operations with the named scope of each (mxnet_tpu/profiler.py;
a CPU capture has no device plane, so there the merged file holds the
host events alone).

  python examples/profiler/profile_lenet.py --out /tmp/profile.json
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
)

import numpy as np

import mxnet_tpu as mx


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/mxnet_tpu_profile.json")
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    logging.basicConfig(level=logging.WARNING)

    rs = np.random.RandomState(0)
    X = rs.rand(64, 1, 28, 28).astype(np.float32)
    y = rs.randint(0, 10, 64).astype(np.float32)
    it = mx.io.NDArrayIter(X, y, batch_size=16)

    data = mx.sym.Variable("data")
    c = mx.sym.Convolution(data, num_filter=8, kernel=(5, 5),
                           name="conv1")
    c = mx.sym.Activation(c, act_type="tanh")
    c = mx.sym.Pooling(c, kernel=(2, 2), stride=(2, 2),
                       pool_type="max")
    f = mx.sym.FullyConnected(c, num_hidden=10, name="fc")
    net = mx.sym.SoftmaxOutput(f, name="softmax")

    mod = mx.mod.Module(net)
    it.reset()
    mod.bind(data_shapes=it.provide_data,
             label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.1})

    # reference flow: set_config -> state 'run' -> train -> state
    # 'stop'. MXNET_TPU_XLA_TRACE_DIR additionally captures the XLA
    # device timeline (jax.profiler) and merges it into the same
    # Chrome trace next to the host events.
    import tempfile

    trace_dir = os.environ.setdefault(
        "MXNET_TPU_XLA_TRACE_DIR", tempfile.mkdtemp(prefix="xlatrace"))
    mx.profiler.profiler_set_config(mode="all", filename=args.out)
    mx.profiler.profiler_set_state("run")
    it.reset()
    for i, b in enumerate(it):
        if i >= args.steps:
            break
        mod.forward_backward(b)
        mod.update()
    mod.sync()
    mx.profiler.profiler_set_state("stop")

    with open(args.out) as fjson:
        trace = json.load(fjson)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    names = {e.get("name") for e in events if isinstance(e, dict)}
    host = [e for e in events if isinstance(e, dict)
            and e.get("cat") == "executor"]
    device = [e for e in events if isinstance(e, dict)
              and e.get("pid", 0) >= 1000]
    print(f"trace: {len(events)} events ({len(host)} host, "
          f"{len(device)} device slices), {len(names)} names "
          f"-> {args.out} (device capture under {trace_dir})")
    assert host, "no host executor events"
    # the capture itself always lands; its device plane exists only
    # where there is a device beside the host
    from mxnet_tpu.profiling import timeline

    capture = timeline.read_xplane(trace_dir)
    assert capture is not None, "no profiler capture under " + trace_dir
    import jax

    if jax.default_backend() != "cpu":
        assert device, "no merged device slices"
        assert trace.get("deviceTimelineStats"), "no per-scope totals"
    print("profile_lenet OK")


if __name__ == "__main__":
    main()
