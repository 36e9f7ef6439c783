"""The exec-cache disk tier (exec_cache_disk) + AOT serving bundles:
a process restart that rebinds a seen graph restores with zero traces
and zero compiles; stale/corrupt artifacts degrade to a plain
re-trace (counted), never an error; bundles refuse tampered params;
the primary dir is LRU-evicted to MXNET_EXEC_CACHE_DISK_BYTES."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import exec_cache, exec_cache_disk, serving
from mxnet_tpu.utils.persist import atomic_write_json, read_json


@pytest.fixture(autouse=True)
def _fresh(monkeypatch, tmp_path):
    """Each test gets its own disk root + zeroed counters (the
    conftest-wide per-run dir stays untouched)."""
    monkeypatch.setenv("MXNET_EXEC_CACHE_DIR", str(tmp_path / "root"))
    monkeypatch.delenv("MXNET_EXEC_CACHE_DISK_BYTES", raising=False)
    exec_cache.clear()
    exec_cache.reset_stats()
    exec_cache_disk.clear_overlays()
    yield
    exec_cache.clear()
    exec_cache.reset_stats()
    exec_cache_disk.clear_overlays()


def _mlp():
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=5, name="fc")
    return mx.sym.SoftmaxOutput(fc, name="softmax")


# --------------------------------------------------- unit: record layer
def _write_foreign_record(digest, env=None, root=None):
    """A record some OTHER process wrote (bypasses the module, so it
    is not in the self-written skip set)."""
    root = root or exec_cache_disk.cache_dir()
    rec = {"digest": digest,
           "env": env or exec_cache_disk.env_fingerprint()}
    path = os.path.join(exec_cache_disk.entry_dir(root, digest),
                        "record.json")
    atomic_write_json(path, rec)
    return path


def test_lookup_hit_miss_and_stale_counting():
    assert exec_cache_disk.lookup_record("aaa0") is None
    assert exec_cache_disk.counters()["disk_misses"] == 1

    _write_foreign_record("bbb0")
    rec = exec_cache_disk.lookup_record("bbb0")
    assert rec is not None and rec["digest"] == "bbb0"
    assert exec_cache_disk.counters()["disk_hits"] == 1

    # an incompatible env (other jaxlib) is STALE, not a hit and not
    # an error — the caller re-traces
    bad = dict(exec_cache_disk.env_fingerprint(), jaxlib="0.0.0")
    _write_foreign_record("ccc0", env=bad)
    assert exec_cache_disk.lookup_record("ccc0") is None
    assert exec_cache_disk.counters()["disk_stale"] == 1


def test_corrupt_record_quarantined_not_fatal():
    path = _write_foreign_record("ddd0")
    with open(path, "w") as f:
        f.write('{"torn": tru')  # torn write from a dying process
    assert exec_cache_disk.lookup_record("ddd0") is None
    c = exec_cache_disk.counters()
    assert c["disk_quarantined"] == 1
    assert not os.path.exists(path)  # moved aside, not left to re-fail
    qdir = os.path.join(exec_cache_disk.cache_dir(), "quarantine")
    assert os.listdir(qdir)


def test_corrupt_exe_blob_quarantined_and_skipped():
    root = exec_cache_disk.cache_dir()
    path = exec_cache_disk.exe_path(root, "eee0", "fwd", "s" * 16)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(b"\x00not a pickle")
    assert exec_cache_disk.load_executable("eee0", "fwd",
                                           "s" * 16) is None
    assert exec_cache_disk.counters()["disk_quarantined"] == 1
    assert not os.path.exists(path)


def test_self_written_entries_skipped_in_process():
    """In-process counts stay identical to the no-disk world: the
    record a bind just wrote is never read back by the same process."""
    net = _mlp()
    net.simple_bind(mx.cpu(), data=(4, 3))
    s = exec_cache.cache_stats()
    assert s["disk_writes"] == 1 and s["disk_hits"] == 0

    exec_cache.clear()  # drop in-memory entry: next bind re-misses
    net.simple_bind(mx.cpu(), data=(4, 3))
    s = exec_cache.cache_stats()
    # the disk record exists but was self-written: a real trace, not
    # a disk hit — pinned trace counts elsewhere stay valid
    assert s["disk_hits"] == 0 and s["traces"] == 2, s


def test_lru_size_cap_evicts_oldest_entries(monkeypatch):
    root = exec_cache_disk.cache_dir()
    for i, digest in enumerate(["old0", "mid0", "new0"]):
        path = _write_foreign_record(digest)
        blob = os.path.join(os.path.dirname(path), "exe-fwd-x.bin")
        with open(blob, "wb") as f:
            f.write(b"x" * 10_000)
        os.utime(path, (1_000_000 + i, 1_000_000 + i))
    # cap admits roughly two 10KB entries; the write below evicts the
    # least-recently-used ones until the subtree fits
    monkeypatch.setenv("MXNET_EXEC_CACHE_DISK_BYTES", "25000")
    exec_cache_disk.write_record("fresh0")
    entries = set(os.listdir(os.path.join(root, "entries")))
    assert "fresh0" in entries
    assert "old0" not in entries, entries
    assert exec_cache_disk.counters()["disk_evictions"] >= 1


# --------------------------------------- integration: process restart
_CHILD = """
import json, os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import exec_cache
from mxnet_tpu.profiling import device_stats

data = mx.sym.Variable("data")
fc = mx.sym.FullyConnected(data, num_hidden=5, name="fc")
net = mx.sym.SoftmaxOutput(fc, name="softmax")
exe = net.simple_bind(mx.cpu(), data=(4, 3))
x = np.random.RandomState(0).rand(4, 3).astype("float32")
out = exe.forward(is_train=False, data=mx.nd.array(x))[0].asnumpy()
s = exec_cache.cache_stats()
t = device_stats().get("totals", {})
print(json.dumps({
    "traces": s["traces"], "disk_hits": s["disk_hits"],
    "disk_stale": s["disk_stale"], "compiles": t.get("compiles", 0),
    "disk_loads": t.get("disk_loads", 0),
    "out": [float(v) for v in out.ravel()],
}))
"""


def _run_child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_EXEC_CACHE_DIR=str(cache_dir))
    proc = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_process_restart_restores_without_traces(tmp_path):
    """The tentpole contract: warm → exit → restore pays zero traces
    and zero compiles, and serves bit-identical outputs."""
    cache = tmp_path / "disk"
    warm = _run_child(cache)
    assert warm["traces"] == 1 and warm["compiles"] == 1, warm
    restore = _run_child(cache)
    assert restore["traces"] == 0, restore
    assert restore["compiles"] == 0, restore
    assert restore["disk_hits"] > 0, restore
    assert restore["disk_loads"] > 0, restore
    assert restore["out"] == warm["out"]  # exact: same executable


def test_stale_version_entry_retraces(tmp_path):
    """A jaxlib upgrade (simulated by doctoring the fingerprints)
    falls back to a full re-trace — counted disk_stale, no error."""
    import pickle

    cache = tmp_path / "disk"
    _run_child(cache)
    entries = os.path.join(str(cache), "entries")
    for digest in os.listdir(entries):
        edir = os.path.join(entries, digest)
        rpath = os.path.join(edir, "record.json")
        rec = read_json(rpath)
        rec["env"]["jaxlib"] = "0.0.0"
        atomic_write_json(rpath, rec)
        for fn in os.listdir(edir):  # the exe blobs carry their own
            if fn.startswith("exe-"):  # fingerprint — age those too
                bpath = os.path.join(edir, fn)
                with open(bpath, "rb") as f:
                    blob = pickle.loads(f.read())
                blob["env"]["jaxlib"] = "0.0.0"
                with open(bpath, "wb") as f:
                    f.write(pickle.dumps(blob))
    restore = _run_child(cache)
    assert restore["traces"] == 1 and restore["compiles"] == 1, restore
    assert restore["disk_stale"] > 0, restore


# ------------------------------------------------------------- bundles
def _served_model(reg):
    params = {
        "arg:fc_weight": np.random.RandomState(0)
        .rand(5, 3).astype("float32"),
        "arg:fc_bias": np.zeros(5, "float32"),
    }
    return reg.load("clf", _mlp().tojson(), params, {"data": (3,)},
                    batch_buckets=(1, 2))


def test_bundle_roundtrip_in_process(tmp_path):
    reg = serving.ModelRegistry()
    model = _served_model(reg)
    out_dir = str(tmp_path / "clf.bundle")
    serving.save_bundle(model, out_dir)

    manifest = serving.read_manifest(out_dir)
    assert manifest["kind"] == "served"
    assert manifest["programs"], "no AOT executables captured"
    assert manifest["params"]["content_hash"]

    reg2 = serving.ModelRegistry()
    m2 = reg2.load_bundle(out_dir)
    x = np.random.RandomState(1).rand(2, 3).astype("float32")
    a = model.infer({"data": x}, 2, 0)[0]
    b = m2.infer({"data": x}, 2, 0)[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bundle_rejects_tampered_params(tmp_path):
    reg = serving.ModelRegistry()
    out_dir = str(tmp_path / "clf.bundle")
    serving.save_bundle(_served_model(reg), out_dir)

    with np.load(os.path.join(out_dir, "params.npz")) as z:
        arrays = {k: z[k] for k in z.files}
    arrays["arg:fc_bias"] = arrays["arg:fc_bias"] + 1.0  # the tamper
    np.savez(os.path.join(out_dir, "params.npz"), **arrays)

    with pytest.raises(serving.BundleError, match="content hash"):
        serving.ModelRegistry().load_bundle(out_dir)


def test_bundle_refuses_cold_model_and_existing_target(tmp_path):
    reg = serving.ModelRegistry()
    params = {"arg:fc_weight": np.zeros((5, 3), "float32"),
              "arg:fc_bias": np.zeros(5, "float32")}
    cold = reg.load("cold", _mlp().tojson(), params, {"data": (3,)},
                    batch_buckets=(1,), warmup=False)
    with pytest.raises(serving.BundleError, match="warm"):
        serving.save_bundle(cold, str(tmp_path / "cold.bundle"))

    warm = _served_model(reg)
    target = tmp_path / "exists"
    target.mkdir()
    with pytest.raises(serving.BundleError, match="exists"):
        serving.save_bundle(warm, str(target))


def test_bundle_not_a_bundle(tmp_path):
    with pytest.raises(serving.BundleError, match="manifest"):
        serving.read_manifest(str(tmp_path))


def test_calibration_skip_is_counted(monkeypatch, tmp_path):
    """Satellite of the warmup contract: a failing calibration harvest
    no longer vanishes — it is counted per model and the snapshot
    exposes it."""
    from mxnet_tpu.serving import registry as _registry

    monkeypatch.setattr(_registry, "_calibration_warned", False)
    # a cache path that cannot be a file → every persist fails, but
    # record() raising is what we simulate harder below
    import mxnet_tpu.profiling as _profiling

    def _boom():
        raise RuntimeError("no store today")

    monkeypatch.setattr(_profiling, "calibration_store", _boom)
    reg = serving.ModelRegistry()
    model = _served_model(reg)  # warmup inside — must not raise
    snap = model.stats.snapshot()
    assert snap["calibration_skipped"] == len(model.spec.all_buckets())
