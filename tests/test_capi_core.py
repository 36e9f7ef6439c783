"""Core C API + cpp-package tests (VERDICT r1 item 6).

Builds native/capi_core.cc, exercises the NDArray/imperative/Symbol/
Executor ABI through ctypes, then compiles and runs the cpp-package
MLP example — a C++ program training through the C API (the reference
cpp-package/example/mlp.cpp milestone).
"""
import ctypes
import os
import subprocess

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    return env


@pytest.fixture(scope="module")
def lib():
    so = native.build_core_lib()
    lib = ctypes.CDLL(so)
    lib.MXTpuGetLastError.restype = ctypes.c_char_p
    lib.MXTpuNDArrayCopyOut.restype = ctypes.c_long
    return lib


def _err(lib):
    return lib.MXTpuGetLastError().decode()


def test_ndarray_roundtrip(lib):
    shape = (ctypes.c_int * 2)(2, 3)
    data = (ctypes.c_float * 6)(*range(6))
    h = ctypes.c_void_p()
    assert lib.MXTpuNDArrayCreate(shape, 2, data,
                                  ctypes.byref(h)) == 0, _err(lib)
    dims = (ctypes.c_int * 8)()
    ndim = ctypes.c_int()
    assert lib.MXTpuNDArrayGetShape(h, dims, 8,
                                    ctypes.byref(ndim)) == 0
    assert ndim.value == 2 and list(dims[:2]) == [2, 3]
    buf = (ctypes.c_float * 6)()
    assert lib.MXTpuNDArrayCopyOut(h, buf, 6) == 6
    np.testing.assert_allclose(list(buf), list(range(6)))
    lib.MXTpuHandleFree(h)


def test_imperative_invoke(lib):
    shape = (ctypes.c_int * 2)(2, 2)
    a = ctypes.c_void_p()
    d = (ctypes.c_float * 4)(1, 2, 3, 4)
    lib.MXTpuNDArrayCreate(shape, 2, d, ctypes.byref(a))
    ins = (ctypes.c_void_p * 2)(a, a)
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXTpuImperativeInvoke(
        b"elemwise_add", 2, ins, 0, None, None,
        ctypes.byref(n_out), ctypes.byref(outs)) == 0, _err(lib)
    assert n_out.value == 1
    buf = (ctypes.c_float * 4)()
    assert lib.MXTpuNDArrayCopyOut(ctypes.c_void_p(outs[0]), buf, 4) == 4
    np.testing.assert_allclose(list(buf), [2, 4, 6, 8])
    # in-place form: sgd_update into the weight
    keys = (ctypes.c_char_p * 1)(b"lr")
    vals = (ctypes.c_char_p * 1)(b"0.5")
    tgt = (ctypes.c_void_p * 1)(a)
    assert lib.MXTpuImperativeInvokeInto(
        b"sgd_update", 2, ins, 1, keys, vals, 1, tgt) == 0, _err(lib)
    assert lib.MXTpuNDArrayCopyOut(a, buf, 4) == 4
    np.testing.assert_allclose(list(buf), [0.5, 1.0, 1.5, 2.0])


def test_symbol_and_executor(lib):
    data = ctypes.c_void_p()
    assert lib.MXTpuSymbolCreateVariable(
        b"data", ctypes.byref(data)) == 0, _err(lib)
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"4")
    in_keys = (ctypes.c_char_p * 1)(b"data")
    in_syms = (ctypes.c_void_p * 1)(data)
    fc = ctypes.c_void_p()
    assert lib.MXTpuSymbolCreate(
        b"FullyConnected", 1, keys, vals, b"fc", 1, in_keys, in_syms,
        ctypes.byref(fc)) == 0, _err(lib)

    n = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuSymbolList(fc, b"arg", ctypes.byref(n),
                               ctypes.byref(names)) == 0
    args = [names[i].decode() for i in range(n.value)]
    assert args == ["data", "fc_weight", "fc_bias"]

    js = ctypes.c_char_p()
    assert lib.MXTpuSymbolToJSON(fc, ctypes.byref(js)) == 0
    assert b"FullyConnected" in js.value

    bind_names = (ctypes.c_char_p * 1)(b"data")
    ind = (ctypes.c_int * 2)(0, 2)
    dims = (ctypes.c_int * 2)(3, 5)
    ex = ctypes.c_void_p()
    assert lib.MXTpuExecutorSimpleBind(
        fc, b"cpu", 0, b"write", 1, bind_names, ind, dims,
        ctypes.byref(ex)) == 0, _err(lib)
    assert lib.MXTpuExecutorForward(ex, 0) == 0, _err(lib)
    n_out = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXTpuExecutorOutputs(ex, ctypes.byref(n_out),
                                    ctypes.byref(outs)) == 0
    assert n_out.value == 1
    assert lib.MXTpuNDArrayCopyOut(ctypes.c_void_p(outs[0]), None, 0) == 12  # (3,4)


def test_error_is_thread_local(lib):
    """Each thread sees only its own last error (reference
    c_api_error.cc TLS semantics)."""
    import threading

    def fail_with(op):
        rc = lib.MXTpuImperativeInvoke(
            op, 0, None, 0, None, None,
            ctypes.byref(ctypes.c_int()),
            ctypes.byref(ctypes.POINTER(ctypes.c_void_p)()))
        assert rc != 0
        return _err(lib)

    main_msg = fail_with(b"bogus_op_main")
    assert "bogus_op_main" in main_msg

    other = {}

    def worker():
        other["msg"] = fail_with(b"bogus_op_worker")

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert "bogus_op_worker" in other["msg"]
    # the worker's failure must NOT clobber this thread's message
    assert "bogus_op_main" in _err(lib)


def _build_cpp_example(tmp_path, name):
    """Compile cpp-package/example/<name>.cc against the core lib;
    returns the executable path."""
    so = native.build_core_lib()
    src = os.path.join(REPO, "cpp-package", "example", name + ".cc")
    exe = str(tmp_path / name)
    cfg = subprocess.run(
        ["python3-config", "--includes", "--ldflags", "--embed"],
        capture_output=True, text=True, check=True,
    )
    subprocess.run(
        ["g++", "-O2", "-std=c++17", src, so, "-o", exe,
         f"-Wl,-rpath,{os.path.dirname(so)}"] + cfg.stdout.split(),
        check=True, capture_output=True, text=True,
    )
    return exe


def test_cpp_package_mlp_trains(tmp_path):
    """Compile and run the cpp-package MLP example: a C++ program
    training through the C API (reference cpp-package milestone)."""
    exe = _build_cpp_example(tmp_path, "mlp")
    proc = subprocess.run(
        [exe], env=_child_env(), capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "accuracy" in proc.stdout


def test_cpp_lenet_dataiter(tmp_path):
    """Compile and run the cpp-package LeNet example: a C++ convnet
    trained from a C-API DataIter with KVStore push/pull + C updater
    (VERDICT r2 next-round #7)."""
    exe = _build_cpp_example(tmp_path, "lenet")
    proc = subprocess.run(
        [exe], env=_child_env(), capture_output=True, text=True,
        timeout=600,
    )
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "OK" in proc.stdout


def test_cpp_recordio_rtc(tmp_path):
    """Compile and run the cpp-package RecordIO+RTC+profiler example:
    C++ dataset packing and a source-text Pallas kernel through the
    tier-3/4 C surfaces."""
    exe = _build_cpp_example(tmp_path, "recordio_rtc")
    rec = str(tmp_path / "pack.rec")
    trace = str(tmp_path / "trace.json")
    proc = subprocess.run(
        [exe, rec, trace], env=_child_env(), capture_output=True,
        text=True, timeout=600,
    )
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "rtc saxpy ok" in proc.stdout
    assert "recordio_rtc done" in proc.stdout
    import json as _json

    assert "traceEvents" in _json.load(open(trace))
