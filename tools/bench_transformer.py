#!/usr/bin/env python
"""Transformer training throughput benchmark (the long-context /
attention counterpart of the ResNet bench.py): one fused train step of
models/transformer.py, reporting tokens/s, analytic MFU, and step
FLOPs. Emits ONE JSON line like the other tools.

  python tools/bench_transformer.py [--d-model 512 --seq 2048 ...]

On a mesh (e.g. the virtual CPU mesh) --mesh data=2,seq=4 runs the
same step with ring-attention sequence parallelism through the Module
API.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def transformer_flops(batch, seq, d_model, d_ff, num_layers,
                      num_heads, causal):
    """Analytic fwd FLOPs at 2 FLOPs/MAC: per layer QKVO projections
    (4 * B*T*d^2 MACs), attention scores+values (2 * B*T^2*d MACs,
    halved when causal), FFN (2 * B*T*d*d_ff MACs)."""
    proj = 4 * batch * seq * d_model * d_model
    attn = 2 * batch * seq * seq * d_model
    if causal:
        attn //= 2
    ffn = 2 * batch * seq * d_model * d_ff
    return 2 * num_layers * (proj + attn + ffn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--d-ff", type=int, default=1024)
    ap.add_argument("--num-layers", type=int, default=4)
    ap.add_argument("--num-heads", type=int, default=8)
    ap.add_argument("--impl", default="ring",
                    choices=["ring", "ulysses", "dense"])
    ap.add_argument("--mesh", default=None,
                    help="e.g. data=2,seq=4 (needs that many devices)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--multistep", type=int, default=1,
                    help="k steps per dispatch (Module.run_steps; "
                         "amortizes remote-dispatch latency)")
    ap.add_argument("--dtype", default=None,
                    choices=[None, "float32", "bfloat16"])
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.transformer import get_transformer

    dev = jax.devices()[0]
    on_accel = dev.platform == "tpu"
    dtype = args.dtype or ("bfloat16" if on_accel else "float32")

    mesh_shape = None
    data_shardings = None
    if args.mesh:
        mesh_shape = {}
        for part in args.mesh.split(","):
            k, _, v = part.partition("=")
            mesh_shape[k] = int(v)
        if "seq" in mesh_shape:
            data_shardings = {"data": "data,seq,None",
                              "label": "data,seq,None"}

    net = get_transformer(
        d_model=args.d_model, num_heads=args.num_heads,
        d_ff=args.d_ff, num_layers=args.num_layers, impl=args.impl)
    ctx = mx.tpu() if on_accel else mx.cpu()
    mod = mx.mod.Module(net, data_names=("data",),
                        label_names=("label",), context=[ctx],
                        mesh_shape=mesh_shape,
                        data_shardings=data_shardings)
    shape = (args.batch, args.seq, args.d_model)
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("label", shape)])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(kvstore="tpu", optimizer="adam",
                       optimizer_params={"learning_rate": 1e-4})
    if dtype == "bfloat16":
        mod.cast_compute(jnp.bfloat16)

    rs = np.random.RandomState(0)
    k = args.multistep
    if k > 1:
        # stacked per-step batches through the compiled k-loop
        # (Module.run_steps) — one dispatch per k steps, like
        # BENCH_MULTISTEP in bench.py
        Xs = rs.randn(k, *shape).astype("float32")
        Ys = rs.randn(k, *shape).astype("float32")
        stacked = mx.io.DataBatch(
            data=[mx.nd.array(Xs, ctx=ctx)],
            label=[mx.nd.array(Ys, ctx=ctx)])
        mod.run_steps(stacked, k, stacked=True)
        mod.sync()
        iters = max(k, (args.iters // k) * k)
        args.iters = iters
        t0 = time.perf_counter()
        for _ in range(iters // k):
            mod.run_steps(stacked, k, stacked=True)
        mod.sync()
        dt = time.perf_counter() - t0
    else:
        batch = mx.io.DataBatch(
            data=[mx.nd.array(rs.randn(*shape).astype("float32"),
                              ctx=ctx)],
            label=[mx.nd.array(rs.randn(*shape).astype("float32"),
                               ctx=ctx)])
        mod.forward_backward(batch)
        mod.update()
        mod.sync()

        t0 = time.perf_counter()
        for _ in range(args.iters):
            mod.forward_backward(batch)
            mod.update()
        mod.sync()
        dt = time.perf_counter() - t0

    tokens_s = args.batch * args.seq * args.iters / dt
    fwd = transformer_flops(args.batch, args.seq, args.d_model,
                            args.d_ff, args.num_layers,
                            args.num_heads, causal=True)
    step = 3 * fwd
    # chip peak from bench.py's table when on an accelerator
    peak = 0.0
    if on_accel:
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from bench import _detect_peak_flops

        peak = _detect_peak_flops(dev)
    print(json.dumps({
        "metric": f"transformer_train_tokens_{dev.platform}"
                  f"_b{args.batch}_s{args.seq}_{args.impl}_{dtype}",
        "value": round(tokens_s, 1),
        "unit": "tokens/s",
        "step_flops_analytic": step,
        "mfu": round(step * args.iters / dt / peak, 4) if peak else 0.0,
        "mesh": args.mesh or "",
        "seq": args.seq,
        "impl": args.impl,
    }))


if __name__ == "__main__":
    main()
