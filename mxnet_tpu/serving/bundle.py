"""AOT serving bundles: one directory artifact = one warm model.

`ModelRegistry.load(...)` + `warmup()` pays the full trace + XLA
compile grid on every process start. A bundle snapshots everything the
warm process learned into one atomic directory, so the NEXT process
restores with ZERO traces and ZERO compiles (execCacheStats /
deviceStats prove it — ci/check_coldstart.py gates on exactly that):

    bundle/
      manifest.json     format, kind, env fingerprint, grids, hashes
      params.npz        the parameter set (content-hashed)
      symbol.json       the bound graph (kind "served" only)
      exec_cache/       a self-contained exec_cache_disk subtree:
        entries/<digest>/record.json + exe-<kind>-<sighash>.bin

Restore (`load_bundle`) mounts `exec_cache/` as a read-only OVERLAY in
`exec_cache_disk` and replays the ordinary load path: every bind finds
its record (no trace billed), every jit deserializes its executable
(no compile). Warmup still runs its per-bucket forwards — those are
readiness + calibration, and they dispatch pre-compiled programs.

Integrity + compatibility:

  * `manifest.params.content_hash` is sha256 over the ARRAY BYTES
    (sorted (name, dtype, shape, data)), not the npz file — zip
    headers embed timestamps. MXNET_BUNDLE_VERIFY=1 (default) checks
    it on load; a mismatch ALWAYS raises `BundleError` (a tampered or
    half-copied bundle must not serve).
  * the env fingerprint (jaxlib + platform, exec_cache_disk's rule)
    gates the overlay only: an incompatible bundle still loads — it
    just re-traces like a plain `load` — unless MXNET_BUNDLE_STRICT=1
    turns the fallback into a `BundleError`.

Tuner + calibration records ride along in the manifest and are seeded
into the local stores on load, so the restored process also starts
with the warm process's measured-cost evidence.
"""
from __future__ import annotations

import hashlib
import logging
import os
import shutil

import numpy as np

from .. import exec_cache_disk as _disk
from ..utils import getenv
from ..utils.persist import atomic_write_json, read_json
from . import quant as _squant
from .batcher import ServingError

log = logging.getLogger(__name__)

#: bundle directory layout version — bump on incompatible change
BUNDLE_FORMAT = 1

MANIFEST = "manifest.json"
PARAMS = "params.npz"
SYMBOL = "symbol.json"
EXEC_CACHE = "exec_cache"


class BundleError(ServingError):
    """A bundle cannot be written or trusted: target exists, manifest
    missing/corrupt, param content-hash mismatch, or (strict mode) an
    env-incompatible artifact."""


# ------------------------------------------------------------- hashing
def param_content_hash(params):
    """sha256 over the sorted array CONTENT — stable across npz
    re-zips, sensitive to any byte of any parameter."""
    h = hashlib.sha256()
    for name in sorted(params):
        arr = np.ascontiguousarray(_as_numpy(params[name]))
        h.update(name.encode())
        h.update(str(arr.dtype).encode())
        h.update(repr(tuple(arr.shape)).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def _as_numpy(v):
    if hasattr(v, "asnumpy"):  # NDArray
        return v.asnumpy()
    return np.asarray(v)


# ------------------------------------------------------ program harvest
def _instrumented(fn):
    """The InstrumentedJit under `fn`, or None (profiling disabled or
    a raw jit) — bundles need the captured Compiled objects."""
    from ..profiling.device_stats import InstrumentedJit

    return fn if isinstance(fn, InstrumentedJit) else None


def _snapshot_jits(jits, exec_root):
    """AOT-serialize every captured executable of `jits` into the
    bundle's exec_cache subtree. Returns the manifest program list."""
    from ..profiling.device_stats import _FailedSig

    programs = []
    for jit in jits:
        for sig_key, compiled in sorted(
                jit._compiled.items(), key=lambda kv: repr(kv[0])):
            if isinstance(compiled, _FailedSig):
                continue
            sighash = _disk.sig_hash(sig_key)
            path = _disk.store_executable(
                jit.digest, jit.kind, sighash, compiled,
                root=exec_root)
            if path is not None:
                programs.append({
                    "digest": jit.digest, "kind": jit.kind,
                    "sighash": sighash,
                    "file": os.path.relpath(
                        path, os.path.dirname(exec_root)),
                })
    return programs


def _served_payload(model, exec_root):
    """Harvest a warm ServedModel: symbol, params, program grid."""
    preds, seen = [], set()
    for pred in [model._base, *model._by_bucket.values()]:
        if id(pred) not in seen:
            seen.add(id(pred))
            preds.append(pred)
    jits, digests = [], []
    for pred in preds:
        compiled = getattr(pred._exec, "_compiled", None)
        if compiled is None:
            continue
        if compiled.digest not in digests:
            digests.append(compiled.digest)
            _disk.write_record(
                compiled.digest, canonical=compiled.canonical,
                meta_fn=getattr(pred._exec, "_disk_record_meta", None),
                root=exec_root)
        for fn in compiled._jit_fwd.values():
            jit = _instrumented(fn)
            if jit is not None and jit not in jits:
                jits.append(jit)
    spec = model.spec
    base = model._base
    params = {f"arg:{k}": _as_numpy(v)
              for k, v in base._arg_params.items()}
    params.update({f"aux:{k}": _as_numpy(v)
                   for k, v in base._aux_params.items()})
    manifest = {
        "kind": "served",
        "symbol": SYMBOL,
        "input_specs": {k: list(v)
                        for k, v in spec.input_specs.items()},
        "input_dtypes": {k: str(v)
                         for k, v in base._input_dtypes.items()},
        "batch_buckets": list(spec.batch_buckets),
        "length_buckets": (list(spec.length_buckets)
                           if spec.ragged else None),
        "pad_value": spec.pad_value,
        "digests": digests,
        "canonicals": sorted(
            {c.canonical for p in preds
             for c in [getattr(p._exec, "_compiled", None)]
             if c is not None and c.canonical}),
    }
    # Predictor applied output_names BEFORE storing _symbol, so the
    # serialized graph is already the final one: restore with
    # output_names=None
    return manifest, params, base._symbol.tojson(), jits


def _decoded_payload(model, exec_root):
    """Harvest a warm DecodedModel: config, params, decode grid."""
    eng = model.engine
    if eng.chunk_buckets:
        # the manifest records the dense block's fields and grid
        # (prefill + decode programs); a chunk-prefilled block has
        # another configuration class and program family
        raise BundleError(
            "bundles hold the dense decoder block's program grid; a "
            "chunk-prefilled configuration is not bundled yet")
    jits = [f for f in [eng._copy_fn, *eng._prefill_fns.values(),
                        *eng._decode_fns.values()]
            if _instrumented(f) is not None]
    import dataclasses

    _disk.write_record(
        eng._digest,
        meta_fn=lambda: {
            "decoder": dataclasses.asdict(model.cfg),
            "kinds": sorted({j.kind for j in jits}),
        },
        root=exec_root)
    manifest = {
        "kind": "decoded",
        "decoder": dataclasses.asdict(model.cfg),
        "max_batch": eng.max_batch,
        "page_size": eng.page_size,
        "num_pages": eng.num_pages,
        "page_buckets": list(eng.page_buckets),
        "kernel": eng.kernel_name,
        "ring_prefill": eng.ring_prefill,
        "kv_dtype": eng.kv_dtype,
        # the program grid is a function of these too — restoring
        # with different values would rebuild a grid none of the
        # saved executables match (full re-compile)
        "prefix_cache": eng.prefix_cache_enabled,
        "merged_step": eng.merged_step_enabled,
        "digests": [eng._digest],
        "decode_kinds": sorted({j.kind for j in jits}),
    }
    params = {k: _as_numpy(v) for k, v in eng._params.items()}
    return manifest, params, None, jits


def _harvest_tuning(canonicals):
    """Tuner choices + calibration evidence for the bundle's graphs —
    the warm process's measured-cost records travel with it."""
    tuner, calib = {}, {}
    try:
        from ..passes.tuner import Autotuner

        table = Autotuner()._load()
        tuner = {k: v for k, v in table.items()
                 if any(k.startswith(f"{c}:") for c in canonicals)}
    except Exception:
        pass
    try:
        from ..profiling import calibration_store

        store = calibration_store()
        for c in canonicals:
            calib.update(store.records(digest=c))
    except Exception:
        pass
    return tuner, calib


# ---------------------------------------------------------------- save
def save_bundle(model, out_dir, quantize=None):
    """Snapshot a WARM model (ServedModel or DecodedModel) into the
    atomic directory artifact `out_dir` (must not exist; built in a
    sibling tmp dir and published by one `os.replace`). Returns
    `out_dir`.

    `quantize="int8"` (default: MXNET_BUNDLE_QUANTIZE) stores the
    parameter set weight-only int8 with per-channel scales — see
    serving/quant.py for the scheme and the dequant-on-load
    rationale. The content hash covers the STORED (quantized)
    arrays, so verification needs no dequantization pass."""
    from .registry import ServedModel

    if quantize is None:
        quantize = getenv("MXNET_BUNDLE_QUANTIZE") or None
    if quantize and quantize not in _squant.SCHEMES:
        raise BundleError(
            f"unknown bundle quantization {quantize!r} "
            f"(this build writes {_squant.SCHEMES})")
    out_dir = os.path.abspath(out_dir)
    if os.path.exists(out_dir):
        raise BundleError(f"bundle target exists: {out_dir}")
    if isinstance(model, ServedModel):
        if not model._warm:
            raise BundleError(
                "bundle a WARM model: call warmup() first — the "
                "bundle snapshots the compiled program grid")
        payload_fn = _served_payload
    else:
        if not getattr(model.engine, "_warm", False):
            raise BundleError(
                "bundle a WARM model: call warmup() first — the "
                "bundle snapshots the compiled program grid")
        payload_fn = _decoded_payload

    tmp = f"{out_dir}.tmp.{os.getpid()}"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    try:
        exec_root = os.path.join(tmp, EXEC_CACHE)
        manifest, params, symbol_json, jits = payload_fn(
            model, exec_root)
        programs = _snapshot_jits(jits, exec_root)
        if not programs:
            raise BundleError(
                "no AOT-serializable executables captured — this "
                "jax/jaxlib cannot export compiled programs, so a "
                "bundle would not avoid any compile")
        if quantize:
            params, qrecord = _squant.quantize_params(
                params, scheme=quantize)
            manifest["quantization"] = qrecord
        np.savez(os.path.join(tmp, PARAMS), **params)
        if symbol_json is not None:
            with open(os.path.join(tmp, SYMBOL), "w") as f:
                f.write(symbol_json)
        tuner, calib = _harvest_tuning(
            manifest.get("canonicals", []))
        manifest.update({
            "format": BUNDLE_FORMAT,
            "name": model.name,
            "version": model.version,
            "env": _disk.env_fingerprint(),
            "params": {
                "file": PARAMS,
                "count": len(params),
                "content_hash": param_content_hash(params),
            },
            "programs": programs,
            "tuner": tuner,
            "calibration": calib,
        })
        atomic_write_json(os.path.join(tmp, MANIFEST), manifest)
        os.replace(tmp, out_dir)  # atomic publish
    except BundleError:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    except OSError as e:
        shutil.rmtree(tmp, ignore_errors=True)
        raise BundleError(f"bundle write failed: {e}") from e
    return out_dir


# ---------------------------------------------------------------- load
def read_manifest(path):
    """The bundle's manifest dict; raises BundleError when `path` is
    not a bundle (missing/corrupt/foreign-format manifest)."""
    manifest = read_json(os.path.join(path, MANIFEST))
    if not isinstance(manifest, dict):
        raise BundleError(f"not a bundle (no readable manifest): "
                          f"{path}")
    if manifest.get("format") != BUNDLE_FORMAT:
        raise BundleError(
            f"unsupported bundle format {manifest.get('format')!r} "
            f"(this build reads format {BUNDLE_FORMAT})")
    return manifest


def _load_params(path, manifest):
    rec = manifest.get("params") or {}
    fpath = os.path.join(path, rec.get("file", PARAMS))
    try:
        with np.load(fpath) as z:
            params = {k: z[k] for k in z.files}
    except Exception as e:
        raise BundleError(f"bundle params unreadable: {e}") from e
    if getenv("MXNET_BUNDLE_VERIFY"):
        want = rec.get("content_hash")
        got = param_content_hash(params)
        if want != got:
            raise BundleError(
                f"bundle param content hash mismatch (manifest "
                f"{str(want)[:12]}…, actual {got[:12]}…): refusing "
                f"to serve a tampered or torn artifact")
    return params


def _seed_tuning(manifest):
    """Merge the bundle's tuner/calibration records into the local
    stores (best-effort — both are advisory evidence)."""
    try:
        from ..passes.tuner import Autotuner

        tuner = Autotuner()
        for key, rec in (manifest.get("tuner") or {}).items():
            if isinstance(rec, dict):
                tuner._persist(key, rec)
    except Exception:
        pass
    try:
        from ..profiling import calibration_store

        store = calibration_store()
        for rec in (manifest.get("calibration") or {}).values():
            if isinstance(rec, dict):
                store.record(rec.get("digest"), rec.get("platform"),
                             rec.get("kind"), rec.get("seconds"),
                             meta=rec.get("meta"))
    except Exception:
        pass


def load_bundle(path, registry, name=None, version=None, warmup=True):
    """Restore a bundle into `registry` — the zero-trace,
    zero-compile process restart. Mounts the bundle's exec_cache
    subtree as a read-only overlay (when env-compatible), then replays
    the ordinary load path: binds hit disk records, jits deserialize
    AOT executables, warmup dispatches pre-compiled programs.

    An env-incompatible bundle (other jaxlib/platform) degrades to a
    plain load-and-retrace unless MXNET_BUNDLE_STRICT=1."""
    path = os.path.abspath(path)
    manifest = read_manifest(path)
    compatible = _disk._compatible(manifest.get("env"))
    if not compatible:
        if getenv("MXNET_BUNDLE_STRICT"):
            raise BundleError(
                f"bundle env {manifest.get('env')} is incompatible "
                f"with this process ({_disk.env_fingerprint()}) and "
                f"MXNET_BUNDLE_STRICT=1")
        log.warning(
            "bundle %s built under %s; this process is %s — loading "
            "WITHOUT AOT executables (full re-trace)", path,
            manifest.get("env"), _disk.env_fingerprint())
    params = _load_params(path, manifest)
    qrecord = manifest.get("quantization")
    if bool(qrecord) != _squant.is_quantized(params):
        # the manifest and the stored arrays disagree about
        # precision — a stripped quantization record (or stripped
        # scale planes) silently changes what the model computes, so
        # it is a refusal, not a warning
        if not getenv("MXNET_BUNDLE_QUANTIZE_OVERRIDE"):
            raise BundleError(
                f"bundle precision mismatch: manifest says "
                f"{'quantized ' + str(qrecord.get('scheme')) if qrecord else 'full precision'}, "
                f"stored params are "
                f"{'quantized' if _squant.is_quantized(params) else 'full precision'} "
                f"— refusing (set MXNET_BUNDLE_QUANTIZE_OVERRIDE=1 "
                f"to load anyway)")
        log.warning("bundle %s precision mismatch overridden "
                    "(MXNET_BUNDLE_QUANTIZE_OVERRIDE=1)", path)
    if qrecord or _squant.is_quantized(params):
        # dequant-on-load: restore float32 so the saved AOT
        # executables (compiled against f32 signatures) still match
        # — zero traces, zero compiles (see serving/quant.py)
        params = _squant.dequantize_params(params, qrecord)
    if compatible:
        _disk.add_overlay(os.path.join(path, EXEC_CACHE))
    _seed_tuning(manifest)
    name = name or manifest["name"]
    version = manifest["version"] if version is None else version
    if manifest["kind"] == "decoded":
        from ..decoding.model import DecoderConfig

        cfg = DecoderConfig(**manifest["decoder"])
        return registry.load_decoder(
            name, params, cfg, version=version, warmup=warmup,
            max_batch=manifest["max_batch"],
            page_size=manifest["page_size"],
            num_pages=manifest["num_pages"],
            page_buckets=tuple(manifest["page_buckets"]),
            kernel=manifest["kernel"],
            ring_prefill=manifest["ring_prefill"],
            kv_dtype=manifest.get("kv_dtype", "float32"),
            # older bundles predate these keys: leave the env-default
            # behavior (their grids were also built under it)
            **{k: manifest[k] for k in ("prefix_cache", "merged_step")
               if k in manifest})
    with open(os.path.join(path, manifest["symbol"])) as f:
        symbol_json = f.read()
    length_buckets = manifest.get("length_buckets")
    return registry.load(
        name, symbol_json, params,
        {k: tuple(v) for k, v in manifest["input_specs"].items()},
        version=version,
        input_dtypes=manifest.get("input_dtypes") or None,
        batch_buckets=tuple(manifest["batch_buckets"]),
        length_buckets=(tuple(length_buckets)
                        if length_buckets else None),
        pad_value=manifest.get("pad_value", 0.0),
        warmup=warmup)
