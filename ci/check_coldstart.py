#!/usr/bin/env python
"""Cold-start CI gate: the AOT-bundle restart contract, proven with
real process boundaries.

Three subprocesses against one bundle directory:

1. warm     — loads + warms a bucket-grid model (paying the full
              trace/compile grid), probes it, snapshots the bundle.
2. restore  — a FRESH interpreter mounts the bundle and serves. The
              gate: zero traces, zero XLA compiles (the executables
              come off disk — totals.disk_loads > 0), and the probe
              output is bit-identical to the warm process's.
3. tampered — the parent flips one parameter inside params.npz; the
              restore must be REJECTED (BundleError naming the
              content hash), never served.

MXNET_EXEC_CACHE_DIR is explicitly emptied in the children so the
bundle alone carries the restore — nothing may leak through a shared
primary cache dir.
"""
import json
import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_COMMON = """
import json, os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import exec_cache, serving
from mxnet_tpu.profiling import device_stats

BUNDLE = os.environ["COLDSTART_BUNDLE"]

def net():
    data = mx.sym.Variable("data")
    emb = mx.sym.Embedding(data, input_dim=50, output_dim=16,
                           name="emb")
    pooled = mx.sym.mean(emb, axis=1, name="pool")
    fc = mx.sym.FullyConnected(pooled, num_hidden=8, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")

def params():
    rs = np.random.RandomState(0)
    return {
        "arg:emb_weight": rs.rand(50, 16).astype("float32"),
        "arg:fc_weight": rs.rand(8, 16).astype("float32"),
        "arg:fc_bias": np.zeros(8, "float32"),
    }

def probe(model):
    x = np.zeros((2, 8), "int32")
    x[:, :5] = np.random.RandomState(7).randint(0, 50, (2, 5))
    out = np.asarray(model.infer({"data": x}, 2, 8)[0])
    return [float(v) for v in out.ravel()]

def report(extra):
    s = exec_cache.cache_stats()
    t = device_stats().get("totals", {})
    rec = {"traces": s["traces"], "compiles": t.get("compiles", 0),
           "disk_loads": t.get("disk_loads", 0)}
    rec.update(extra)
    print(json.dumps(rec))
"""

_WARM = _COMMON + """
reg = serving.ModelRegistry()
model = reg.load("clf", net().tojson(), params(), {"data": ("L",)},
                 input_dtypes={"data": "int32"},
                 batch_buckets=(1, 2), length_buckets=(4, 8))
out = probe(model)
serving.save_bundle(model, BUNDLE)
report({"out": out})
"""

_RESTORE = _COMMON + """
reg = serving.ModelRegistry()
model = reg.load_bundle(BUNDLE)
out = probe(model)
report({"out": out})
"""

_TAMPERED = _COMMON + """
try:
    serving.ModelRegistry().load_bundle(BUNDLE)
except serving.BundleError as e:
    print(json.dumps({"rejected": True, "error": str(e)[:120]}))
else:
    print(json.dumps({"rejected": False}))
"""


def _run(code, bundle):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_EXEC_CACHE_DIR="",
               COLDSTART_BUNDLE=bundle)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise SystemExit(
            f"coldstart child failed:\n{proc.stderr[-3000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    failures = []

    def check(name, ok, detail=""):
        print(f"  {'ok  ' if ok else 'FAIL'} {name}"
              + (f" ({detail})" if detail else ""))
        if not ok:
            failures.append(name)

    with tempfile.TemporaryDirectory(prefix="mx_coldstart_") as work:
        bundle = os.path.join(work, "clf.bundle")

        print("coldstart gate: warm process (trace+compile the grid, "
              "snapshot)")
        warm = _run(_WARM, bundle)
        check("warm process traced and compiled",
              warm["traces"] > 0 and warm["compiles"] > 0,
              f"traces={warm['traces']} compiles={warm['compiles']}")

        print("coldstart gate: restore process (fresh interpreter, "
              "bundle only)")
        restore = _run(_RESTORE, bundle)
        check("restore pays zero traces", restore["traces"] == 0,
              f"traces={restore['traces']}")
        check("restore pays zero compiles", restore["compiles"] == 0,
              f"compiles={restore['compiles']}")
        check("restore loaded executables from the bundle",
              restore["disk_loads"] > 0,
              f"disk_loads={restore['disk_loads']}")
        check("restore output bit-identical to warm",
              restore["out"] == warm["out"])

        print("coldstart gate: tampered bundle must be rejected")
        import numpy as np
        npz = os.path.join(bundle, "params.npz")
        with np.load(npz) as z:
            arrays = {k: z[k] for k in z.files}
        arrays["arg:fc_bias"] = arrays["arg:fc_bias"] + 1.0
        np.savez(npz, **arrays)
        tampered = _run(_TAMPERED, bundle)
        check("tampered params rejected with BundleError",
              tampered.get("rejected") is True,
              tampered.get("error", ""))

    if failures:
        print(f"coldstart gate: FAIL — {', '.join(failures)}")
        return 1
    print("coldstart gate: OK — zero-trace, zero-compile restore "
          "with exact parity; tampering rejected")
    return 0


if __name__ == "__main__":
    sys.exit(main())
