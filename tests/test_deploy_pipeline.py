"""End-to-end deployment pipeline (docs/deploy.md's story, all steps
chained): Module training -> checkpoint -> accnn low-rank compression
-> predict C ABI serving of the COMPRESSED model, with numerics
checked against the Python forward at every hop."""
import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_train_compress_predict(tmp_path):
    # --- 1. train a small conv net and checkpoint it -----------------
    np.random.seed(0)
    rs = np.random.RandomState(0)
    X = rs.rand(64, 1, 12, 12).astype(np.float32)
    y = (X.mean(axis=(1, 2, 3)) > 0.5).astype(np.float32)
    net = mx.sym.Variable("data")
    net = mx.sym.Convolution(net, name="conv1", num_filter=6,
                             kernel=(3, 3), pad=(1, 1))
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), name="fc1",
                                num_hidden=2)
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.2})
    prefix = str(tmp_path / "trained")
    mod.save_checkpoint(prefix, 2)

    # reference logits from the live module
    probe = X[:4]
    pit = mx.io.NDArrayIter(probe, np.zeros(4, np.float32),
                            batch_size=4)
    want = mod.predict(pit).asnumpy()

    # --- 2. accnn low-rank compression -------------------------------
    comp = str(tmp_path / "compressed")
    subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools/accnn.py"),
         prefix, "2", comp, "--rank", "conv1=3", "--rank", "fc1=64"],
        check=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    csym, cargs, cauxs = mx.model.load_checkpoint(comp, 2)
    ex = csym.simple_bind(ctx=mx.cpu(), grad_req="null",
                          data=(4, 1, 12, 12), softmax_label=(4,))
    ex.copy_params_from(cargs, cauxs)
    ex.arg_dict["data"][:] = probe
    got_py = ex.forward(is_train=False)[0].asnumpy()
    # conv rank 3 = full for a (6,1,3,3) kernel (min(1*3, 6*3)=3):
    # exact; fc rank clamps to full: exact
    np.testing.assert_allclose(got_py, want, rtol=1e-4, atol=1e-5)

    # --- 3. serve the compressed model via the predict C ABI ---------
    so = native.build_predict_lib()
    lib = ctypes.CDLL(so)
    lib.MXTpuGetLastError.restype = ctypes.c_char_p
    with open(comp + "-symbol.json") as f:
        sym_json = f.read().encode()
    with open(comp + "-0002.params", "rb") as f:
        params = f.read()

    keys = (ctypes.c_char_p * 1)(b"data")
    shape_ind = (ctypes.c_uint * 2)(0, 4)
    shape_data = (ctypes.c_uint * 4)(4, 1, 12, 12)
    pred = ctypes.c_void_p()
    rc = lib.MXTpuPredCreate(sym_json, params, len(params), 1, keys,
                             shape_ind, shape_data,
                             ctypes.byref(pred))
    assert rc == 0, lib.MXTpuGetLastError().decode()
    flat = probe.ravel()
    buf = (ctypes.c_float * flat.size)(*flat)
    assert lib.MXTpuPredSetInput(pred, b"data", buf, flat.size) == 0
    assert lib.MXTpuPredForward(pred) == 0
    out = (ctypes.c_float * 8)()
    n = lib.MXTpuPredGetOutput(pred, 0, out, 8)  # returns elem count
    assert n == 8, lib.MXTpuGetLastError().decode()
    got_c = np.array(out[:8], np.float32).reshape(4, 2)
    np.testing.assert_allclose(got_c, want, rtol=1e-4, atol=1e-5)
    lib.MXTpuPredFree(pred)
