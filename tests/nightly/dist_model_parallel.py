#!/usr/bin/env python
"""Multi-host model parallelism through the product API (VERDICT r3 #2).

The SP+TP transformer config from tests/test_module_mesh.py trains over
mesh_shape={'data': 2, 'seq': 4} in TWO modes:

  - standalone (no launcher env): one process, 8 virtual CPU devices —
    writes final parameters to --ref-out;
  - launched (tools/launch.py -n 2): two processes x 4 devices, the SAME
    global mesh — the 'data' axis spans the processes (make_mesh lays it
    process-major) and each rank feeds its contiguous half of the global
    batch. Rank 0 compares final parameters against --ref-out.

Identical data + identical init => the two modes must compute the same
math; this is the reference's cross-node parallelism composition
(graph_executor.cc:242-318 ctx groups + kvstore_dist.h:35-51) redone as
one GSPMD program per step.
"""
import argparse
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
)

_DIST = "MXNET_TPU_NUM_WORKERS" in os.environ
# device count must be set before jax import: 4 per process launched
# (2 procs x 4 = the same 8-device global mesh), 8 standalone
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count="
    + ("4" if _DIST else "8"))
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu.models import get_transformer  # noqa: E402

D_MODEL, HEADS, D_FF, LAYERS = 16, 4, 32, 2
B, T = 8, 16  # GLOBAL batch
STEPS = 3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref-out", default="/tmp/dist_mp_ref.npz")
    args = ap.parse_args()

    if _DIST:
        kv = mx.kv.create("tpu")  # initializes jax.distributed
        import jax

        rank, nproc = kv.rank, kv.num_workers
        assert jax.device_count() == 8, jax.device_count()
    else:
        kv, rank, nproc = None, 0, 1

    net = get_transformer(d_model=D_MODEL, num_heads=HEADS, d_ff=D_FF,
                          num_layers=LAYERS, causal=True, tp_axis="seq")
    mod = mx.mod.Module(
        net, label_names=("label",), context=[mx.cpu()],
        mesh_shape={"data": 2, "seq": 4},
        data_shardings={"data": "data,seq", "label": "data,seq"},
    )
    local_b = B // nproc
    mod.bind(data_shapes=[("data", (local_b, T, D_MODEL))],
             label_shapes=[("label", (local_b, T, D_MODEL))])
    np.random.seed(11)  # identical Xavier draws on every process
    mod.init_params(mx.initializer.Xavier(rnd_type="gaussian",
                                          magnitude=1.0))
    if kv is not None:
        mod.init_optimizer(kvstore=kv, optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))
    else:
        mod.init_optimizer(optimizer="sgd",
                           optimizer_params=(("learning_rate", 0.1),))

    fs = mod._fused_step
    assert fs is not None, "fused step inactive"
    assert fs._mesh is not None and fs._mesh.size == 8
    if _DIST:
        # the real thing under test: a model mesh spanning processes,
        # with TP shardings intact
        assert fs._nproc == 2 and fs._batch_scale == 2
        assert fs._param_specs, "param shardings were dropped"
        spec = fs._param_specs["layer0_ffn_w1_weight"]
        assert tuple(spec) == ("seq", None), spec

    rs = np.random.RandomState(7)
    for _ in range(STEPS):
        x = rs.uniform(-1, 1, (B, T, D_MODEL)).astype("float32")
        y = rs.uniform(-1, 1, (B, T, D_MODEL)).astype("float32")
        sl = slice(rank * local_b, (rank + 1) * local_b)
        batch = mx.io.DataBatch(data=[mx.nd.array(x[sl])],
                                label=[mx.nd.array(y[sl])])
        mod.forward_backward(batch)
        mod.update()
        out = mod.get_outputs()[0].asnumpy()
        assert np.isfinite(out).all()
        assert out.shape[0] == local_b, out.shape

    params = {k: v.asnumpy() for k, v in mod.get_params()[0].items()}

    if _DIST:
        # distributed sharded checkpoint: every rank writes ONLY its
        # shards, a fresh module restores them (orbax collective IO)
        ckpt = args.ref_out + ".ckpt"
        mx.save_sharded(mod, ckpt)
        mod2 = mx.mod.Module(
            net, label_names=("label",), context=[mx.cpu()],
            mesh_shape={"data": 2, "seq": 4},
            data_shardings={"data": "data,seq", "label": "data,seq"},
        )
        mod2.bind(data_shapes=[("data", (local_b, T, D_MODEL))],
                  label_shapes=[("label", (local_b, T, D_MODEL))])
        np.random.seed(12)  # different init: restore must override it
        mod2.init_params(mx.initializer.Xavier())
        # fresh store: the first module's kv already holds these keys
        mod2.init_optimizer(kvstore=mx.kv.create("tpu"),
                            optimizer="sgd",
                            optimizer_params=(("learning_rate", 0.1),))
        meta = mx.load_sharded(mod2, ckpt)
        assert meta["t"] == STEPS, meta
        got = {k: v.asnumpy() for k, v in mod2.get_params()[0].items()}
        for k in params:
            np.testing.assert_allclose(got[k], params[k], rtol=1e-6,
                                       atol=1e-7, err_msg=k)

    params.update(run_pipeline())
    if not _DIST:
        np.savez(args.ref_out, **params)
        print("dist_model_parallel REF saved", flush=True)
        return
    if rank == 0:
        ref = np.load(args.ref_out)
        for k in params:
            np.testing.assert_allclose(
                params[k], ref[k], rtol=5e-4, atol=5e-5, err_msg=k)
    print(f"worker {rank}/{nproc}: dist_model_parallel OK", flush=True)


def run_pipeline():
    """The dryrun PP config (__graft_entry__._dryrun_pp) with the
    8-stage 'pipe' axis spanning both processes; every rank feeds the
    identical replicated batch. Returns final params, 'pipe/'-keyed."""
    d = mx.sym.Variable("data")
    stage = mx.sym.Activation(
        mx.sym.FullyConnected(d, num_hidden=8, flatten=False,
                              no_bias=True, name="fc"),
        act_type="tanh", name="act")
    pm = mx.mod.PipelineModule(stage, num_stages=8,
                               num_microbatches=16, context=mx.cpu())
    batch = 32
    pm.bind(data_shapes=[("data", (batch, 2, 8))])
    np.random.seed(13)
    pm.init_params(mx.initializer.Xavier())
    pm.init_optimizer(optimizer="sgd",
                      optimizer_params=(("learning_rate", 0.05),))
    rs = np.random.RandomState(3)
    for _ in range(2):
        b = mx.io.DataBatch(
            data=[mx.nd.array(rs.rand(batch, 2, 8).astype("float32"))],
            label=[mx.nd.array(np.zeros((batch, 2, 8), "float32"))])
        pm.forward_backward(b)
        pm.update()
    assert np.isfinite(pm.loss_value)
    assert np.isfinite(pm.get_outputs()[0].asnumpy()).all()
    return {f"pipe/{k}": v.asnumpy()
            for k, v in pm.get_params()[0].items()}


if __name__ == "__main__":
    main()
