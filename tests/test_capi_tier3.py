"""C API tier 3: NDArray views/introspection, Symbol attributes and
structure, op listing/docs, RecordIO, profiler, and runtime surfaces
(reference c_api.h MXNDArraySlice/At/Reshape/GetDType/GetContext/Wait*,
MXSymbol{Get,Set,List}Attr/GetInternals/GetOutput/GetChildren/Copy/
InferType, MXListAllOpNames, MXRecordIO*, MXSetProfilerConfig/State,
MXDumpProfile, MXRandomSeed, MXInitPSEnv, MXKVStoreIs*Node)."""
import ctypes
import json
import os
import sys

import numpy as np
import pytest

from mxnet_tpu import native


@pytest.fixture(scope="module")
def lib():
    so = native.build_core_lib()
    lib = ctypes.CDLL(so)
    lib.MXTpuGetLastError.restype = ctypes.c_char_p
    lib.MXTpuNDArrayCopyOut.restype = ctypes.c_long
    return lib


def _err(lib):
    return lib.MXTpuGetLastError().decode()


def _make_nd(lib, values, shape):
    cs = (ctypes.c_int * len(shape))(*shape)
    flat = np.asarray(values, np.float32).ravel()
    cd = (ctypes.c_float * flat.size)(*flat)
    h = ctypes.c_void_p()
    assert lib.MXTpuNDArrayCreate(cs, len(shape), cd,
                                  ctypes.byref(h)) == 0, _err(lib)
    return h


def _read_nd(lib, h, n):
    buf = (ctypes.c_float * n)()
    got = lib.MXTpuNDArrayCopyOut(h, buf, n)
    assert got == n, _err(lib)
    return np.array(buf[:n], np.float32)


def test_ndarray_slice_at_reshape(lib):
    a = _make_nd(lib, np.arange(12, dtype=np.float32), (4, 3))

    s = ctypes.c_void_p()
    assert lib.MXTpuNDArraySlice(a, 1, 3, ctypes.byref(s)) == 0, _err(lib)
    np.testing.assert_allclose(_read_nd(lib, s, 6), np.arange(3, 9))

    at = ctypes.c_void_p()
    assert lib.MXTpuNDArrayAt(a, 2, ctypes.byref(at)) == 0, _err(lib)
    np.testing.assert_allclose(_read_nd(lib, at, 3), [6, 7, 8])

    dims = (ctypes.c_int * 2)(6, 2)
    r = ctypes.c_void_p()
    assert lib.MXTpuNDArrayReshape(a, 2, dims, ctypes.byref(r)) == 0, \
        _err(lib)
    shape = (ctypes.c_int * 8)()
    ndim = ctypes.c_int()
    assert lib.MXTpuNDArrayGetShape(r, shape, 8,
                                    ctypes.byref(ndim)) == 0
    assert list(shape[:ndim.value]) == [6, 2]

    for h in (a, s, at, r):
        lib.MXTpuHandleFree(h)


def test_ndarray_dtype_context_wait(lib):
    a = _make_nd(lib, [1.0, 2.0], (2,))
    dt = ctypes.c_int(-1)
    assert lib.MXTpuNDArrayGetDType(a, ctypes.byref(dt)) == 0, _err(lib)
    assert dt.value == 0  # float32 in the save-format code space

    dev_type = ctypes.c_char_p()
    dev_id = ctypes.c_int(-1)
    assert lib.MXTpuNDArrayGetContext(
        a, ctypes.byref(dev_type), ctypes.byref(dev_id)) == 0, _err(lib)
    assert dev_type.value.decode() in ("cpu", "gpu", "tpu", "cpu_pinned")
    assert dev_id.value >= 0

    assert lib.MXTpuNDArrayWaitToRead(a) == 0, _err(lib)
    assert lib.MXTpuNDArrayWaitAll() == 0, _err(lib)
    lib.MXTpuHandleFree(a)


def test_ndarray_raw_bytes_roundtrip(lib):
    a = _make_nd(lib, [3.0, 1.0, 4.0, 1.5], (2, 2))
    buf = ctypes.c_char_p()
    size = ctypes.c_long()
    assert lib.MXTpuNDArraySaveRawBytes(
        a, ctypes.byref(buf), ctypes.byref(size)) == 0, _err(lib)
    raw = ctypes.string_at(buf, size.value)
    assert size.value > 16

    b = ctypes.c_void_p()
    assert lib.MXTpuNDArrayLoadFromRawBytes(
        raw, len(raw), ctypes.byref(b)) == 0, _err(lib)
    np.testing.assert_allclose(_read_nd(lib, b, 4), [3.0, 1.0, 4.0, 1.5])
    lib.MXTpuHandleFree(a)
    lib.MXTpuHandleFree(b)


def _mlp_symbol(lib):
    data = ctypes.c_void_p()
    assert lib.MXTpuSymbolCreateVariable(b"data",
                                         ctypes.byref(data)) == 0
    keys = (ctypes.c_char_p * 1)(b"num_hidden")
    vals = (ctypes.c_char_p * 1)(b"8")
    in_keys = (ctypes.c_char_p * 1)(b"data")
    in_syms = (ctypes.c_void_p * 1)(data)
    fc = ctypes.c_void_p()
    assert lib.MXTpuSymbolCreate(
        b"FullyConnected", 1, keys, vals, b"fc1", 1, in_keys, in_syms,
        ctypes.byref(fc)) == 0, _err(lib)
    return data, fc


def test_symbol_attr_get_set_list(lib):
    _, fc = _mlp_symbol(lib)
    assert lib.MXTpuSymbolSetAttr(fc, b"__lr_mult__", b"2.0") == 0, \
        _err(lib)

    out = ctypes.c_char_p()
    ok = ctypes.c_int(-1)
    assert lib.MXTpuSymbolGetAttr(fc, b"__lr_mult__", ctypes.byref(out),
                                  ctypes.byref(ok)) == 0, _err(lib)
    assert ok.value == 1 and out.value.decode() == "2.0"

    assert lib.MXTpuSymbolGetAttr(fc, b"__nope__", ctypes.byref(out),
                                  ctypes.byref(ok)) == 0
    assert ok.value == 0

    num = ctypes.c_int()
    pairs = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuSymbolListAttr(fc, ctypes.byref(num),
                                   ctypes.byref(pairs)) == 0, _err(lib)
    flat = [pairs[i].decode() for i in range(2 * num.value)]
    kv = dict(zip(flat[::2], flat[1::2]))
    assert kv.get("fc1$__lr_mult__") == "2.0"


def test_symbol_structure(lib):
    data, fc = _mlp_symbol(lib)

    name = ctypes.c_char_p()
    ok = ctypes.c_int(-1)
    assert lib.MXTpuSymbolGetName(fc, ctypes.byref(name),
                                  ctypes.byref(ok)) == 0, _err(lib)
    assert ok.value == 1 and name.value.decode() == "fc1"

    internals = ctypes.c_void_p()
    assert lib.MXTpuSymbolGetInternals(fc,
                                       ctypes.byref(internals)) == 0
    num = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuSymbolList(internals, b"out", ctypes.byref(num),
                               ctypes.byref(names)) == 0, _err(lib)
    outs = [names[i].decode() for i in range(num.value)]
    assert "fc1_output" in outs and "data" in outs

    head = ctypes.c_void_p()
    assert lib.MXTpuSymbolGetOutput(internals, outs.index("fc1_output"),
                                    ctypes.byref(head)) == 0, _err(lib)

    children = ctypes.c_void_p()
    assert lib.MXTpuSymbolGetChildren(fc, ctypes.byref(children)) == 0
    assert lib.MXTpuSymbolList(children, b"out", ctypes.byref(num),
                               ctypes.byref(names)) == 0
    child_names = [names[i].decode() for i in range(num.value)]
    assert "data" in child_names  # weight/bias are auto-created vars too

    cp = ctypes.c_void_p()
    assert lib.MXTpuSymbolCopy(fc, ctypes.byref(cp)) == 0, _err(lib)
    js1 = ctypes.c_char_p()
    assert lib.MXTpuSymbolToJSON(cp, ctypes.byref(js1)) == 0
    assert json.loads(js1.value.decode())
    # the copy is independent: attrs set on it must not leak back
    assert lib.MXTpuSymbolSetAttr(cp, b"__only_copy__", b"1") == 0
    ok2 = ctypes.c_int(-1)
    val = ctypes.c_char_p()
    assert lib.MXTpuSymbolGetAttr(fc, b"__only_copy__",
                                  ctypes.byref(val),
                                  ctypes.byref(ok2)) == 0
    assert ok2.value == 0

    for h in (data, fc, internals, head, children, cp):
        lib.MXTpuHandleFree(h)


def test_symbol_infer_type(lib):
    _, fc = _mlp_symbol(lib)
    names = (ctypes.c_char_p * 1)(b"data")
    dtypes = (ctypes.c_int * 1)(0)  # float32
    num = ctypes.c_int()
    arg_t = ctypes.POINTER(ctypes.c_int)()
    assert lib.MXTpuSymbolInferType(
        fc, 1, names, dtypes, ctypes.byref(num),
        ctypes.byref(arg_t)) == 0, _err(lib)
    got = [arg_t[i] for i in range(num.value)]
    assert len(got) == 3 and all(t == 0 for t in got)  # data/weight/bias


def test_list_all_op_names_and_info(lib):
    num = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuListAllOpNames(ctypes.byref(num),
                                   ctypes.byref(names)) == 0, _err(lib)
    all_ops = {names[i].decode() for i in range(num.value)}
    assert num.value > 150
    assert {"Convolution", "FullyConnected", "softmax"} <= all_ops

    desc = ctypes.c_char_p()
    n_args = ctypes.c_int()
    arg_names = ctypes.POINTER(ctypes.c_char_p)()
    n_params = ctypes.c_int()
    param_keys = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuOpGetInfo(
        b"Convolution", ctypes.byref(desc), ctypes.byref(n_args),
        ctypes.byref(arg_names), ctypes.byref(n_params),
        ctypes.byref(param_keys)) == 0, _err(lib)
    args = [arg_names[i].decode() for i in range(n_args.value)]
    params = [param_keys[i].decode() for i in range(n_params.value)]
    assert "data" in args and "weight" in args
    assert "kernel" in params and "num_filter" in params

    assert lib.MXTpuOpGetInfo(
        b"NoSuchOp", ctypes.byref(desc), ctypes.byref(n_args),
        ctypes.byref(arg_names), ctypes.byref(n_params),
        ctypes.byref(param_keys)) != 0
    assert "NoSuchOp" in _err(lib)


def test_recordio_roundtrip(lib, tmp_path):
    path = str(tmp_path / "t3.rec").encode()
    w = ctypes.c_void_p()
    assert lib.MXTpuRecordIOWriterCreate(path, ctypes.byref(w)) == 0, \
        _err(lib)
    # the empty record mid-stream must NOT read as end-of-file
    records = [b"hello", b"", b"x" * 1000, b"tail"]
    for rec in records:
        assert lib.MXTpuRecordIOWriterWriteRecord(w, rec,
                                                  len(rec)) == 0
    pos = ctypes.c_long()
    assert lib.MXTpuRecordIOWriterTell(w, ctypes.byref(pos)) == 0
    assert pos.value > 1000
    assert lib.MXTpuRecordIOWriterFree(w) == 0

    r = ctypes.c_void_p()
    assert lib.MXTpuRecordIOReaderCreate(path, ctypes.byref(r)) == 0
    buf = ctypes.c_char_p()
    size = ctypes.c_long()
    got = []
    while True:
        assert lib.MXTpuRecordIOReaderReadRecord(
            r, ctypes.byref(buf), ctypes.byref(size)) == 0, _err(lib)
        if buf.value is None:  # EOF contract: NULL buffer
            break
        got.append(ctypes.string_at(buf, size.value))
    assert got == records

    # rewind and re-read the first record
    assert lib.MXTpuRecordIOReaderSeek(r, 0) == 0, _err(lib)
    assert lib.MXTpuRecordIOReaderReadRecord(
        r, ctypes.byref(buf), ctypes.byref(size)) == 0
    assert ctypes.string_at(buf, size.value) == records[0]
    assert lib.MXTpuRecordIOReaderFree(r) == 0


def test_profiler_c_surface(lib, tmp_path):
    out = str(tmp_path / "ctrace.json").encode()
    assert lib.MXTpuSetProfilerConfig(1, out) == 0, _err(lib)
    assert lib.MXTpuSetProfilerState(1) == 0, _err(lib)
    a = _make_nd(lib, [1.0, 2.0], (2,))
    h = ctypes.c_void_p()
    num = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXTpuImperativeInvoke(
        b"relu", 1, (ctypes.c_void_p * 1)(a), 0, None, None,
        ctypes.byref(num), ctypes.byref(outs)) == 0, _err(lib)
    assert lib.MXTpuSetProfilerState(0) == 0
    assert lib.MXTpuDumpProfile() == 0, _err(lib)
    trace = json.loads((tmp_path / "ctrace.json").read_text())
    assert "traceEvents" in trace
    lib.MXTpuHandleFree(a)


def test_runtime_surface(lib):
    assert lib.MXTpuRandomSeed(42) == 0, _err(lib)
    keys = (ctypes.c_char_p * 2)(b"DMLC_ROLE", b"T3_SENTINEL")
    vals = (ctypes.c_char_p * 2)(b"worker", b"1")
    assert lib.MXTpuInitPSEnv(2, keys, vals) == 0, _err(lib)
    assert os.environ.get("T3_SENTINEL") == "1"

    is_w = ctypes.c_int(-1)
    is_s = ctypes.c_int(-1)
    is_c = ctypes.c_int(-1)
    assert lib.MXTpuKVStoreIsWorkerNode(ctypes.byref(is_w)) == 0
    assert lib.MXTpuKVStoreIsServerNode(ctypes.byref(is_s)) == 0
    assert lib.MXTpuKVStoreIsSchedulerNode(ctypes.byref(is_c)) == 0
    assert (is_w.value, is_s.value, is_c.value) == (1, 0, 0)
    del os.environ["T3_SENTINEL"]
    os.environ.pop("DMLC_ROLE", None)

    assert lib.MXTpuNotifyShutdown() == 0, _err(lib)


def test_executor_reshape_copy_print(lib):
    _, fc = _mlp_symbol(lib)
    names = (ctypes.c_char_p * 1)(b"data")
    ind = (ctypes.c_int * 2)(0, 2)
    dims = (ctypes.c_int * 2)(4, 16)
    ex = ctypes.c_void_p()
    assert lib.MXTpuExecutorSimpleBind(
        fc, b"cpu", 0, b"null", 1, names, ind, dims,
        ctypes.byref(ex)) == 0, _err(lib)

    # reshape to a new batch size; params shared
    dims2 = (ctypes.c_int * 2)(8, 16)
    ex2 = ctypes.c_void_p()
    assert lib.MXTpuExecutorReshape(
        ex, 1, names, ind, dims2, ctypes.byref(ex2)) == 0, _err(lib)
    assert lib.MXTpuExecutorForward(ex2, 0) == 0, _err(lib)
    num = ctypes.c_int()
    outs = ctypes.POINTER(ctypes.c_void_p)()
    assert lib.MXTpuExecutorOutputs(ex2, ctypes.byref(num),
                                    ctypes.byref(outs)) == 0
    shape = (ctypes.c_int * 4)()
    nd_ = ctypes.c_int()
    h0 = ctypes.c_void_p(outs[0])
    assert lib.MXTpuNDArrayGetShape(h0, shape, 4,
                                    ctypes.byref(nd_)) == 0
    assert list(shape[:nd_.value]) == [8, 8]

    # copy_params_from: overwrite fc1_weight with ones
    w = _make_nd(lib, np.ones(8 * 16, np.float32), (8, 16))
    pnames = (ctypes.c_char_p * 1)(b"fc1_weight")
    handles = (ctypes.c_void_p * 1)(w)
    assert lib.MXTpuExecutorCopyParamsFrom(
        ex2, 1, pnames, handles, 0) == 0, _err(lib)
    bad = (ctypes.c_char_p * 1)(b"nope_weight")
    assert lib.MXTpuExecutorCopyParamsFrom(
        ex2, 1, bad, handles, 0) != 0  # rejected without allow_extra
    assert lib.MXTpuExecutorCopyParamsFrom(
        ex2, 1, bad, handles, 1) == 0, _err(lib)

    dbg = ctypes.c_char_p()
    assert lib.MXTpuExecutorPrint(ex2, ctypes.byref(dbg)) == 0
    assert b"fc1" in dbg.value


def test_kvstore_set_optimizer_run_server(lib):
    kv = ctypes.c_void_p()
    assert lib.MXTpuKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    keys = (ctypes.c_char_p * 1)(b"learning_rate")
    vals = (ctypes.c_char_p * 1)(b"0.5")
    assert lib.MXTpuKVStoreSetOptimizer(
        kv, b"sgd", 1, keys, vals) == 0, _err(lib)
    assert lib.MXTpuKVStoreRunServer(kv) == 0, _err(lib)

    # push/pull now applies the sgd update: w <- w - 0.5 * g
    ikeys = (ctypes.c_int * 1)(3)
    w = _make_nd(lib, [1.0, 2.0], (2,))
    assert lib.MXTpuKVStoreInit(kv, 1, ikeys,
                                (ctypes.c_void_p * 1)(w)) == 0
    g = _make_nd(lib, [1.0, 1.0], (2,))
    assert lib.MXTpuKVStorePush(kv, 1, ikeys,
                                (ctypes.c_void_p * 1)(g)) == 0
    out = _make_nd(lib, [0.0, 0.0], (2,))
    assert lib.MXTpuKVStorePull(kv, 1, ikeys,
                                (ctypes.c_void_p * 1)(out)) == 0
    np.testing.assert_allclose(_read_nd(lib, out, 2), [0.5, 1.5])


def test_set_memory_fraction_env(tmp_path):
    import subprocess

    code = (
        "import mxnet_tpu as mx, os\n"
        "mx.set_memory_fraction(0.4, preallocate=False)\n"
        "assert os.environ['XLA_PYTHON_CLIENT_MEM_FRACTION'] == '0.4'\n"
        "assert os.environ['XLA_PYTHON_CLIENT_PREALLOCATE'] == 'false'\n"
        "import numpy as np\n"
        "mx.nd.array(np.ones(2)).asnumpy()\n"  # backend init
        "try:\n"
        "    mx.set_memory_fraction(0.5)\n"
        "    raise SystemExit('expected failure after init')\n"
        "except mx.base.MXNetError:\n"
        "    pass\n"
        "print('ok')\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True)
    assert proc.returncode == 0 and "ok" in proc.stdout, proc.stderr


def test_symbol_file_roundtrip_and_iter_info(lib, tmp_path):
    _, fc = _mlp_symbol(lib)
    path = str(tmp_path / "net.json").encode()
    assert lib.MXTpuSymbolSaveToFile(fc, path) == 0, _err(lib)
    loaded = ctypes.c_void_p()
    assert lib.MXTpuSymbolCreateFromFile(path,
                                         ctypes.byref(loaded)) == 0
    num = ctypes.c_int()
    names = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuSymbolList(loaded, b"arg", ctypes.byref(num),
                               ctypes.byref(names)) == 0
    assert b"fc1_weight" in [names[i] for i in range(num.value)]

    desc = ctypes.c_char_p()
    n_par = ctypes.c_int()
    pars = ctypes.POINTER(ctypes.c_char_p)()
    assert lib.MXTpuDataIterGetIterInfo(
        b"NDArrayIter", ctypes.byref(desc), ctypes.byref(n_par),
        ctypes.byref(pars)) == 0, _err(lib)
    params = [pars[i].decode() for i in range(n_par.value)]
    assert "batch_size" in params and desc.value


def test_dataiter_index_and_kv_barrier_flag(lib, tmp_path):
    it = ctypes.c_void_p()
    csv_file = tmp_path / "t3_idx.csv"
    csv_file.write_text("".join(f"{i},{i + 1}\n" for i in range(4)))
    csv = str(csv_file)
    ckeys = (ctypes.c_char_p * 3)(b"data_csv", b"data_shape",
                                  b"batch_size")
    cvals = (ctypes.c_char_p * 3)(csv.encode(), b"(2,)", b"2")
    assert lib.MXTpuDataIterCreate(b"CSVIter", 3, ckeys, cvals,
                                   ctypes.byref(it)) == 0, _err(lib)
    has = ctypes.c_int()
    assert lib.MXTpuDataIterNext(it, ctypes.byref(has)) == 0
    assert has.value == 1
    n_idx = ctypes.c_int(-1)
    idx = ctypes.POINTER(ctypes.c_int)()
    assert lib.MXTpuDataIterGetIndex(it, ctypes.byref(n_idx),
                                     ctypes.byref(idx)) == 0, _err(lib)
    assert n_idx.value >= 0  # 0 legal when untracked

    kv = ctypes.c_void_p()
    assert lib.MXTpuKVStoreCreate(b"local", ctypes.byref(kv)) == 0
    assert lib.MXTpuKVStoreSetBarrierBeforeExit(kv, 0) == 0, _err(lib)
