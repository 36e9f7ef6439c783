#!/usr/bin/env python
"""Profiled short training run on the chip.

Runs the ResNet-50 training configuration for a handful of steps with
the merged host+device profiler armed (docs/perf.md method: jax.profiler trace +
HLO-attributed device timeline), then writes

    <outdir>/profile_merged.json   — one merged Chrome trace
    <outdir>/step_summary.json     — per-step wall times

so a chip run leaves OPTIMIZABLE evidence (where the step time
goes), not just a throughput number. Kept separate from the
benchmark's untraced runs on purpose (tracing skews throughput).
One process holds the chip: run this alone.

Usage: python tools/tpu_profile_capture.py [outdir]  (default
<repo>/chiprun_out/profile — the directory the chip tool brings back)
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    outdir = sys.argv[1] if len(sys.argv) > 1 else \
        os.path.join(ROOT, "chiprun_out", "profile")
    os.makedirs(outdir, exist_ok=True)
    os.environ["MXNET_TPU_XLA_TRACE_DIR"] = os.path.join(
        outdir, "xla_trace")

    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models import get_resnet

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"profile capture: needs a TPU, found {dev.platform!r}",
              file=sys.stderr)
        return 2

    batch = int(os.environ.get("BENCH_BATCH", "256"))
    net = get_resnet(num_classes=1000, num_layers=50,
                     image_shape=(3, 224, 224), layout="NHWC",
                     stem=os.environ.get("BENCH_STEM",
                                         "space_to_depth"))
    mod = mx.mod.Module(net, context=[mx.tpu()])
    dshape = (batch, 224, 224, 3)
    mod.bind(data_shapes=[("data", dshape)],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier(factor_type="in",
                                          magnitude=2.0))
    mod.init_optimizer(
        kvstore="tpu", optimizer="sgd",
        optimizer_params=(("learning_rate", 0.1), ("momentum", 0.9),
                          ("wd", 1e-4)))
    mod.cast_compute(jnp.bfloat16)

    rs = np.random.RandomState(0)
    data = mx.nd.array(rs.uniform(-1, 1, dshape).astype("float32"),
                       ctx=mx.tpu())
    label = mx.nd.array(
        rs.randint(0, 1000, (batch,)).astype("float32"), ctx=mx.tpu())
    b = mx.io.DataBatch(data=[data], label=[label])

    # compile outside the trace window
    mod.forward_backward(b)
    mod.update()
    mod.sync()

    mx.profiler.profiler_set_config(
        mode="all", filename=os.path.join(outdir,
                                          "profile_merged.json"))
    mx.profiler.profiler_set_state("run")
    steps = []
    for _ in range(3):
        t0 = time.perf_counter()
        mod.forward_backward(b)
        mod.update()
        mod.sync()
        steps.append(time.perf_counter() - t0)
    mx.profiler.profiler_set_state("stop")

    with open(os.path.join(outdir, "step_summary.json"), "w") as f:
        json.dump({"device_kind": dev.device_kind,
                   "batch": batch,
                   "synced_step_seconds": steps}, f)
    print("profile capture done:", steps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
