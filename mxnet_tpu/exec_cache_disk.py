"""Disk tier under the in-process executable cache — warm restarts.

The in-process cache (`exec_cache`) dedups traces within ONE process;
every restart still pays the full trace+compile bill. This module is
the tier below it: a directory of per-entry records keyed by the same
canonical cache-key digest, holding the optimized canonical graph, the
input signatures, the sharding-plan digest, and AOT-serialized
executables (`jax.experimental.serialize_executable`). A fresh process
that binds the same graph finds the record, deserializes the
executables, and serves with ZERO traces and ZERO compiles.

Two storage layers cooperate:

  * the XLA layer — jax's own persistent compilation cache, at
    `<dir>/xla` unless JAX_COMPILATION_CACHE_DIR places it elsewhere
    (`place_jax_cache`). Even when our executable blobs are stale
    (jaxlib upgrade), re-compiles hit jax's cache and only the cheap
    re-trace is paid.
  * our layer — `<dir>/entries/<digest>/record.json` plus
    `exe-<kind>-<sighash>.bin` blobs. record.json carries an
    environment fingerprint (format version, framework + jaxlib
    versions, platform); a mismatch is counted `disk_stale` and falls
    back to a normal re-trace, never an error.

Activation: set MXNET_EXEC_CACHE_DIR (registered in `utils`). Unset
(the default) the tier is inert — zero behavior change. Serving
bundles (`serving.bundle`) mount their embedded `exec_cache/` subtree
as a read-only OVERLAY root: lookups consult the primary dir first,
then overlays; writes go to the primary dir only (or nowhere when only
overlays are mounted).

Robustness contract (tested in tests/test_disk_cache.py):

  * corrupted / torn entries are QUARANTINED (moved aside into
    `<root>/quarantine/`), counted, and treated as a miss — never
    fatal;
  * entries this process wrote are skipped on lookup, so in-process
    trace/compile accounting is bit-identical to the no-disk-tier
    world (tests that pin exact trace counts stay valid);
  * the primary dir is LRU-evicted (whole entries, record mtime as
    recency) to MXNET_EXEC_CACHE_DISK_BYTES; the `xla/` subtree is
    jax's to manage and is not counted.

All counters live under one module lock; ALL file I/O happens outside
it (MX006 — the snapshot pattern, see utils.persist).
"""
from __future__ import annotations

import os
import pickle
import re
import shutil
import threading

from .utils.persist import atomic_write_json, read_json

#: record.json / exe blob format — bump on incompatible layout change
#: (2: exe blobs name the devices they were compiled for)
RECORD_VERSION = 2

_lock = threading.Lock()
_stats = {
    "disk_hits": 0,        # record found on disk and compatible
    "disk_misses": 0,      # no record anywhere (tier active)
    "disk_stale": 0,       # record/blob from an incompatible env
    "disk_writes": 0,      # records written by this process
    "disk_evictions": 0,   # whole entries LRU-evicted over the cap
    "disk_quarantined": 0,  # corrupt records/blobs moved aside
    "exe_loads": 0,        # executables deserialized from disk
    "exe_stores": 0,       # executables serialized to disk
}
#: absolute paths written by THIS process — lookups skip them so the
#: in-process cache keeps its exact pre-disk trace/compile accounting
_self_written = set()
#: read-only bundle roots consulted after the primary dir
_overlays = []
_jax_cache_configured_for = None


# --------------------------------------------------------------- paths
def cache_dir():
    """Primary (writable) cache root from MXNET_EXEC_CACHE_DIR, or
    None when the tier is unset."""
    raw = os.environ.get("MXNET_EXEC_CACHE_DIR", "")
    return os.path.expanduser(raw) if raw else None


def tier_active():
    """True when any root (primary or overlay) is mounted."""
    return bool(cache_dir()) or bool(_overlays)


def _roots():
    """Search order: primary first (fresh writes win), then overlays."""
    primary = cache_dir()
    roots = [primary] if primary else []
    roots.extend(_overlays)
    return roots


def entry_dir(root, digest):
    return os.path.join(root, "entries", str(digest))


def add_overlay(path):
    """Mount a read-only exec-cache root (a bundle's `exec_cache/`
    subtree). Idempotent; overlays are searched after the primary."""
    path = os.path.abspath(path)
    with _lock:
        if path not in _overlays:
            _overlays.append(path)


def remove_overlay(path):
    path = os.path.abspath(path)
    with _lock:
        if path in _overlays:
            _overlays.remove(path)


def clear_overlays():
    with _lock:
        _overlays.clear()


# ----------------------------------------------------- jax's own cache
#: where the program keeps jax's persistent compile cache when the
#: environment names no place: one fixed, git-ignored directory inside
#: the checkout. The path is part of what lets a later run hit, so it
#: is never built from /tmp, a pid, a time or a flag set.
DEFAULT_JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def place_jax_cache(default=None):
    """Decide where jax's persistent compilation cache lives — the ONE
    setter of `jax_compilation_cache_dir` in the program; call it
    before the first compile. JAX_COMPILATION_CACHE_DIR, when set,
    wins: jax has read it from the environment already and nothing is
    set in code, so the cache can be placed from outside (the chip
    tool's output directory). Otherwise the cache goes to `default`,
    or to DEFAULT_JAX_CACHE_DIR. Returns the directory in effect."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = default or DEFAULT_JAX_CACHE_DIR
    # the dir must exist BEFORE the config update — jax resolves it
    # eagerly
    os.makedirs(path, exist_ok=True)
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    return path


def configure_jax_cache():
    """The disk tier's XLA layer: jax's persistent compilation cache
    at `<dir>/xla` (once per dir) unless the environment placed it
    elsewhere (`place_jax_cache`), caching every compile however
    short."""
    global _jax_cache_configured_for
    root = cache_dir()
    if not root or _jax_cache_configured_for == root:
        return
    try:
        place_jax_cache(default=os.path.join(root, "xla"))
    except OSError:
        return  # unwritable root: the record layer will say so too
    import jax

    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax_cache_configured_for = root


# --------------------------------------------------------- fingerprint
def env_fingerprint():
    """What must match for a disk entry to be trusted. Serialized
    executables are jaxlib+platform artifacts; the framework version
    rides along for diagnostics (not checked — our record layout is
    covered by `format`)."""
    import jax
    import jaxlib

    from . import __version__ as framework_version

    return {
        "format": RECORD_VERSION,
        "framework": framework_version,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
    }


def _compatible(env):
    if not isinstance(env, dict):
        return False
    want = env_fingerprint()
    return (env.get("format") == want["format"]
            and env.get("jaxlib") == want["jaxlib"]
            and env.get("platform") == want["platform"])


# ---------------------------------------------------------- quarantine
def _quarantine(root, path):
    """Move a corrupt file (or whole entry dir) aside — never delete
    evidence, never raise. Quarantined entries read as misses."""
    qdir = os.path.join(root, "quarantine")
    try:
        os.makedirs(qdir, exist_ok=True)
        os.replace(path, os.path.join(qdir, os.path.basename(path)
                                      + f".{os.getpid()}"))
    except OSError:
        try:
            os.unlink(path)
        except OSError:
            pass
    with _lock:
        _stats["disk_quarantined"] += 1


# -------------------------------------------------------------- records
def lookup_record(digest):
    """The record dict for `digest` from the first root that has a
    compatible one, else None. Counts disk_hits / disk_misses /
    disk_stale; corrupt records are quarantined and skipped."""
    if not tier_active():
        return None
    stale_seen = False
    for root in _roots():
        path = os.path.join(entry_dir(root, digest), "record.json")
        if path in _self_written or not os.path.exists(path):
            continue
        rec = read_json(path)
        if rec is None:
            _quarantine(root, path)
            continue
        if not _compatible(rec.get("env")):
            stale_seen = True
            continue
        try:  # LRU recency for the eviction walk
            os.utime(path)
        except OSError:
            pass
        with _lock:
            _stats["disk_hits"] += 1
        return rec
    with _lock:
        if stale_seen:
            _stats["disk_stale"] += 1
        else:
            _stats["disk_misses"] += 1
    return None


def write_record(digest, canonical=None, meta_fn=None, root=None):
    """Persist the record for a freshly-built entry into the primary
    root (overlays are read-only). Best-effort: a full disk or
    read-only root costs only the next process a re-trace.

    `root` overrides the destination (serving.bundle writes a bundle's
    self-contained `exec_cache/` subtree); explicit-root writes are
    NOT marked self-written — a bundle is a separate namespace the
    writing process may legitimately mount and read back."""
    explicit = root is not None
    root = root or cache_dir()
    if not root:
        return None
    rec = {"digest": str(digest), "env": env_fingerprint()}
    if canonical:
        rec["canonical"] = canonical
    if meta_fn is not None:
        try:
            meta = meta_fn()
            if meta:
                rec.update(meta)
        except Exception:
            pass  # meta is advisory; the record still marks the entry
    path = os.path.join(entry_dir(root, digest), "record.json")
    try:
        atomic_write_json(path, rec)
    except OSError:
        return None
    with _lock:
        if not explicit:
            _self_written.add(path)
        _stats["disk_writes"] += 1
    if not explicit:
        _maybe_evict()
    return path


# ---------------------------------------------------------- executables
def _safe_kind(kind):
    return re.sub(r"[^A-Za-z0-9_.@-]", "_", str(kind))


def sig_hash(sig_key):
    """Deterministic cross-process hash of profiling's signature key
    (treedef, tuple-of-aval-sigs). str(PyTreeDef) is deterministic and
    dicts flatten in sorted key order, so two processes tracing the
    same call shapes agree."""
    import hashlib

    treedef, sig = sig_key
    return hashlib.sha1(
        repr((str(treedef), sig)).encode()).hexdigest()[:16]


def exe_path(root, digest, kind, sighash):
    return os.path.join(entry_dir(root, digest),
                        f"exe-{_safe_kind(kind)}-{sighash}.bin")


def store_executable(digest, kind, sighash, compiled, root=None):
    """AOT-serialize `compiled` into the primary root (or an explicit
    `root` — the serving.bundle path, not self-marked, not evicted).
    Returns the path, or None (tier unset / serialization
    unsupported / disk full) — all soft failures."""
    explicit = root is not None
    root = root or cache_dir()
    if not root:
        return None
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps({
            "env": env_fingerprint(),
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
            # jax reloads an executable onto ALL the backend's devices
            # unless told which ones it was compiled for
            "device_ids": [d.id for d in
                           compiled.runtime_executable().local_devices()],
        })
    except Exception:
        return None
    path = exe_path(root, digest, kind, sighash)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError:
        return None
    with _lock:
        if not explicit:
            _self_written.add(path)
        _stats["exe_stores"] += 1
    if not explicit:
        _maybe_evict()
    return path


def load_executable(digest, kind, sighash):
    """Deserialize an AOT executable from the first root that has a
    compatible blob. None on miss/stale/corrupt (caller re-traces)."""
    if not tier_active():
        return None
    for root in _roots():
        path = exe_path(root, digest, kind, sighash)
        if path in _self_written or not os.path.exists(path):
            continue
        try:
            with open(path, "rb") as f:
                blob = pickle.loads(f.read())
            if not isinstance(blob, dict):
                raise ValueError("not an exe blob")
        except Exception:
            _quarantine(root, path)
            continue
        if not _compatible(blob.get("env")):
            with _lock:
                _stats["disk_stale"] += 1
            continue
        try:
            import jax
            from jax.experimental import serialize_executable as _se

            by_id = {d.id: d for d in jax.devices()}
            compiled = _se.deserialize_and_load(
                blob["payload"], blob["in_tree"], blob["out_tree"],
                execution_devices=[by_id[i]
                                   for i in blob["device_ids"]])
        except Exception:
            # a payload this jaxlib can't rehydrate IS staleness,
            # whatever the fingerprint claimed
            with _lock:
                _stats["disk_stale"] += 1
            continue
        try:
            os.utime(os.path.join(entry_dir(root, digest),
                                  "record.json"))
        except OSError:
            pass
        with _lock:
            _stats["exe_loads"] += 1
        return compiled
    return None


# ------------------------------------------------------------- eviction
def disk_cap_bytes():
    from .utils import getenv

    try:
        return int(getenv("MXNET_EXEC_CACHE_DISK_BYTES"))
    except Exception:
        return 0


def _entry_sizes(root):
    """[(mtime, bytes, path)] per entry dir under `root`."""
    base = os.path.join(root, "entries")
    out = []
    try:
        names = os.listdir(base)
    except OSError:
        return out
    for name in names:
        d = os.path.join(base, name)
        if not os.path.isdir(d):
            continue
        size = 0
        try:
            for fn in os.listdir(d):
                try:
                    size += os.path.getsize(os.path.join(d, fn))
                except OSError:
                    pass
            mtime = os.path.getmtime(os.path.join(d, "record.json"))
        except OSError:
            mtime = 0.0
        out.append((mtime, size, d))
    return out


def _maybe_evict():
    """Drop least-recently-used WHOLE entries until the primary root's
    entries/ subtree fits MXNET_EXEC_CACHE_DISK_BYTES (0 = uncapped).
    jax's xla/ subtree is its own cache and is not counted."""
    cap = disk_cap_bytes()
    root = cache_dir()
    if not root or cap <= 0:
        return
    entries = _entry_sizes(root)
    total = sum(size for _, size, _ in entries)
    if total <= cap:
        return
    evicted = 0
    for _, size, d in sorted(entries):
        if total <= cap:
            break
        shutil.rmtree(d, ignore_errors=True)
        total -= size
        evicted += 1
    if evicted:
        with _lock:
            _stats["disk_evictions"] += evicted


# ------------------------------------------------------------- counters
def counters():
    with _lock:
        return dict(_stats)


def reset_counters():
    """Zero the counters. `_self_written` is deliberately NOT cleared:
    it is process-lifetime identity (which entries THIS process
    produced), and clearing it mid-process would let tests that reset
    stats start disk-hitting their own writes — changing the pinned
    in-process trace counts the skip exists to protect."""
    with _lock:
        for k in _stats:
            _stats[k] = 0


def disk_stats():
    """telemetry view: all-numeric so the Prometheus flattening emits
    every field. Empty dict when the tier never activated (omit_empty
    hides it from views())."""
    snap = counters()
    active = tier_active()
    if not active and not any(snap.values()):
        return {}
    snap["enabled"] = bool(active)
    snap["overlays"] = len(_overlays)
    snap["cap_bytes"] = disk_cap_bytes()
    return snap


def _register_view():
    try:
        from .telemetry import register_view

        register_view("diskCacheStats", disk_stats,
                      prom_prefix="disk_cache", omit_empty=True)
    except Exception:  # pragma: no cover - telemetry is optional
        pass


_register_view()
