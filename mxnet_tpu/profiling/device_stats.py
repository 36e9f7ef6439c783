"""Per-executable accounting: HBM footprint, compile time, flops.

`instrument(jitted, digest=..., kind=...)` wraps a `jax.jit` callable
in an `InstrumentedJit`. The wrapper compiles ahead-of-time on the
first call of each input signature (`fn.lower(*args).compile()` — the
dp_step AOT idiom, generalized), records the executable's
`memory_analysis()` / `cost_analysis()` / wall trace+compile seconds
into the process-wide record table, and then dispatches every call
through the captured `Compiled`. One compile total: the record costs
nothing the plain jit would not have paid.

Fallbacks keep the wrapper strictly weaker than jit, never stronger:
a tracer argument (nested trace), an unhashable signature, a failed
lower/compile, or an aval drift at call time (a differently-sized
final batch) all re-dispatch through the raw jit — the dp_step
`except (TypeError, ValueError)` convention. MXNET_PROFILING=0
bypasses everything.

Records key on (digest, kind): `digest` is the executable family (the
exec cache hands its entry digest; the decode engine a config hash;
jit_sharded a caller label), `kind` the program flavor ("fwd",
"train_step", "decode@8", ...). Multiple signatures of one family
merge: compile/trace seconds accumulate, byte/flop fields keep the
largest signature seen (the footprint that matters for HBM planning).

Each record also names its compiled MODULE (`jit_decode_p8`: what a
profiler capture's `XLA Modules` line shows), and the module's SCOPE
MAP — {instruction name: named-scope path}, parsed from the
`compiled.as_text()` kept at capture — stays beside the table under
that name (`scope_map`). Both outlive the executable: a capture is reduced
after the engine that launched the programs is gone.

The `deviceStats` registry view serves /statusz and dump_profile;
native Prometheus instruments cover the scrape path.
"""
from __future__ import annotations

import collections
import os
import threading
import time

import jax

from ..telemetry import register_view as _register_view
from ..telemetry import registry as _treg

_DEFAULT_MAX_SIGS = 64

_lock = threading.Lock()
# (digest, kind) -> record dict (see _new_record)
_records: "dict[tuple, dict]" = {}
# module name -> the compiled program's text until `scope_map` is first
# asked for it, its scope map (timeline.parse_scope_map) from then on;
# oldest first, and a later program of the same module name replaces
# the earlier one's. On the chip as_text() takes most of the cost and
# is paid at capture; the parse waits for a reader (PERF.md, PR 25).
_MAX_SCOPE_MAPS = 64
_scope_maps: "collections.OrderedDict[str, str | dict]" = \
    collections.OrderedDict()
_totals = {"fallbacks": 0, "compile_errors": 0,
           "compiles": 0,      # real XLA compiles this process paid
           "disk_loads": 0,    # executables restored AOT from the
                               # exec_cache_disk tier (compile_s≈0)
           "scope_parse_s": 0.0}   # seconds scope_map spent parsing


def _disk_tier():
    """The exec_cache_disk module when a cache dir / bundle overlay is
    mounted, else None — the single gate every disk hook goes
    through, so an unset MXNET_EXEC_CACHE_DIR costs one attr check."""
    try:
        from .. import exec_cache_disk as _disk

        return _disk if _disk.tier_active() else None
    except Exception:
        return None

# native Prometheus companions of the deviceStats snapshot
_EXECUTABLES = _treg.gauge(
    "mxnet_tpu_profiling_executables",
    "Distinct device executables captured by the profiling layer")
_COMPILE_SECONDS = _treg.counter(
    "mxnet_tpu_profiling_compile_seconds_total",
    "Wall seconds spent in XLA compilation, by program kind")
_HBM_PEAK = _treg.gauge(
    "mxnet_tpu_profiling_executable_hbm_bytes_peak",
    "Largest single-executable HBM footprint (args+outputs+temps+code)")


def profiling_enabled():
    # registered in mxnet_tpu.utils; raw read keeps the hot path
    # import-light (the exec_cache MXNET_EXEC_CACHE convention)
    return os.environ.get("MXNET_PROFILING", "1").lower() not in (
        "0", "false", "off")


def _max_sigs():
    try:
        return max(1, int(os.environ.get("MXNET_PROFILING_MAX_SIGS",
                                         _DEFAULT_MAX_SIGS)))
    except ValueError:
        return _DEFAULT_MAX_SIGS


def _new_record(digest, kind, canonical, label):
    return {
        "digest": digest, "kind": kind,
        "canonical": canonical, "label": label,
        "executables": 0,
        "trace_s": 0.0, "compile_s": 0.0,
        "arg_bytes": 0, "out_bytes": 0, "temp_bytes": 0,
        "code_bytes": 0, "alias_bytes": 0, "hbm_bytes": 0,
        "flops": 0.0, "bytes_accessed": 0.0,
        "platform": None,
        # the compiled module's name, and what as_text() cost at
        # capture (the parse is paid on request: totals.scope_parse_s)
        "module": None, "scope_text_s": 0.0,
    }


def _keep_scope_source(compiled):
    """Keep one executable's text under its module's name, for
    `scope_map` to parse on first request. Returns (module name,
    seconds spent); (None, seconds) where the backend gives no text."""
    from . import timeline as _timeline

    t0 = time.perf_counter()
    try:
        text = compiled.as_text()
        module = _timeline.module_name(text)
    except Exception:
        module = None
    if module:
        with _lock:
            _scope_maps.pop(module, None)
            _scope_maps[module] = text
            while len(_scope_maps) > _MAX_SCOPE_MAPS:
                _scope_maps.popitem(last=False)
    return module, time.perf_counter() - t0


def scope_map(module):
    """{instruction name: scope path} of the newest captured program
    whose compiled module has this name; None if there is none. The
    first request parses the kept text (`timeline.parse_scope_map`)
    and keeps the map in its place."""
    module = str(module)
    with _lock:
        src = _scope_maps.get(module)
    if isinstance(src, str):
        from . import timeline as _timeline

        t0 = time.perf_counter()
        smap = _timeline.parse_scope_map(src)
        with _lock:
            if _scope_maps.get(module) is src:
                _scope_maps[module] = smap
            _totals["scope_parse_s"] += time.perf_counter() - t0
        src = smap
    return dict(src) if src is not None else None


def record_executable(digest, kind, compiled, trace_s, compile_s,
                      canonical=None, label=None, from_disk=False):
    """Merge one captured executable into the record table. Analyses
    that a backend does not implement degrade to zeros — the record
    (and its compile-time fields) exists regardless. `from_disk=True`
    marks an executable restored AOT by the exec_cache_disk tier: it
    bills `totals.disk_loads` instead of `totals.compiles` and carries
    compile_s≈0 (the restart win the deviceStats view exposes)."""
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    cost = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if isinstance(ca, dict):
            cost = ca
    except Exception:
        pass
    arg_b = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
    out_b = int(getattr(mem, "output_size_in_bytes", 0) or 0)
    tmp_b = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    code_b = int(getattr(mem, "generated_code_size_in_bytes", 0) or 0)
    alias_b = int(getattr(mem, "alias_size_in_bytes", 0) or 0)
    # donated (aliased) bytes live inside the argument allocation —
    # don't double-count them in the footprint
    hbm = arg_b + out_b + tmp_b + code_b
    flops = float((cost or {}).get("flops", 0.0) or 0.0)
    bytes_acc = float((cost or {}).get("bytes accessed", 0.0) or 0.0)
    try:
        platform = jax.default_backend()
    except Exception:
        platform = None
    module, text_s = _keep_scope_source(compiled)

    key = (str(digest), str(kind))
    with _lock:
        rec = _records.get(key)
        if rec is None:
            rec = _records[key] = _new_record(digest, kind, canonical,
                                              label)
        rec["executables"] += 1
        rec["trace_s"] += trace_s
        rec["compile_s"] += compile_s
        for field, val in (("arg_bytes", arg_b), ("out_bytes", out_b),
                           ("temp_bytes", tmp_b), ("code_bytes", code_b),
                           ("alias_bytes", alias_b), ("hbm_bytes", hbm),
                           ("flops", flops),
                           ("bytes_accessed", bytes_acc)):
            if val > rec[field]:
                rec[field] = val
        if canonical and not rec["canonical"]:
            rec["canonical"] = canonical
        rec["platform"] = platform
        rec["module"] = module or rec["module"]
        rec["scope_text_s"] += text_s
        if from_disk:
            _totals["disk_loads"] += 1
            rec["disk_loads"] = rec.get("disk_loads", 0) + 1
        else:
            _totals["compiles"] += 1
        n_records = len(_records)
        peak = max(r["hbm_bytes"] for r in _records.values())
    _COMPILE_SECONDS.inc(compile_s, kind=str(kind))
    _EXECUTABLES.set(n_records)
    _HBM_PEAK.set(peak)
    return hbm


def note_fallback(digest=None, kind=None, compile_error=False):
    with _lock:
        _totals["fallbacks"] += 1
        if compile_error:
            _totals["compile_errors"] += 1


def device_stats():
    """Snapshot: {"executables": {"digest:kind": record},
    "totals": {...}, "preflight": last pre-flight report (if any)}.
    Empty dict while nothing was captured (omit_empty view)."""
    with _lock:
        recs = {f"{d}:{k}": dict(r) for (d, k), r in _records.items()}
        totals = dict(_totals)
    from . import preflight as _pf

    pf = _pf.last_preflight()
    if not recs and pf is None:
        return {}
    totals.update({
        "count": len(recs),
        "compile_s": round(sum(r["compile_s"] for r in recs.values()),
                           6),
        "trace_s": round(sum(r["trace_s"] for r in recs.values()), 6),
        "hbm_peak_bytes": max(
            [r["hbm_bytes"] for r in recs.values()], default=0),
    })
    out = {"executables": recs, "totals": totals}
    if pf is not None:
        out["preflight"] = pf
    return out


def records_for(canonical=None, digest=None, kind=None):
    """Record list filtered by canonical digest / family digest /
    kind — the CI gate's join key against execCacheStats."""
    with _lock:
        recs = [dict(r) for r in _records.values()]
    if canonical is not None:
        recs = [r for r in recs if r["canonical"] == canonical]
    if digest is not None:
        recs = [r for r in recs if r["digest"] == digest]
    if kind is not None:
        recs = [r for r in recs if r["kind"] == kind]
    return recs


def reset_device_stats():
    with _lock:
        _records.clear()
        _scope_maps.clear()
        for k in _totals:
            _totals[k] = 0


_register_view("deviceStats", device_stats, prom_prefix="device",
               omit_empty=True)


# --------------------------------------------------------- the wrapper
def _sig_key(args, kwargs):
    """Hashable signature of a call: aval-shaped for array leaves,
    type+value for python scalars (static args bake into the compile).
    None => a tracer is present (nested trace: bypass AOT)."""
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for x in leaves:
        if isinstance(x, jax.core.Tracer):
            return None
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            sig.append((tuple(x.shape), str(x.dtype),
                        bool(getattr(x, "weak_type", False))))
        elif isinstance(x, (bool, int, float, complex, str, bytes,
                            type(None))):
            sig.append((type(x).__name__, x))
        else:
            raise TypeError(f"unhashable jit argument {type(x)}")
    return (treedef, tuple(sig))


class _FailedSig:
    """Sentinel: AOT capture unusable for this signature; dispatch raw."""

    __slots__ = ()


_FAILED = _FailedSig()


class _RecordingLowered:
    """Wraps `jax.stages.Lowered` so callers running the AOT protocol
    themselves (FusedTrainStep does `fn.lower(*args).compile()`) still
    land a record at compile time."""

    __slots__ = ("_lowered", "_wrapper", "_lower_s")

    def __init__(self, lowered, wrapper, lower_s):
        self._lowered = lowered
        self._wrapper = wrapper
        self._lower_s = lower_s

    def compile(self, *args, **kwargs):
        t0 = time.perf_counter()
        compiled = self._lowered.compile(*args, **kwargs)
        w = self._wrapper
        record_executable(w.digest, w.kind, compiled,
                          trace_s=self._lower_s,
                          compile_s=time.perf_counter() - t0,
                          canonical=w.canonical, label=w.label)
        return compiled

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class InstrumentedJit:
    """AOT-capturing wrapper around one `jax.jit` callable (see module
    docstring). Strictly transparent: same results, one compile, jit
    fallback on anything unusual."""

    __slots__ = ("fn", "digest", "kind", "canonical", "label",
                 "_compiled", "_lock")

    def __init__(self, fn, digest, kind, canonical=None, label=None):
        self.fn = fn
        self.digest = str(digest)
        self.kind = str(kind)
        self.canonical = canonical
        self.label = label
        self._compiled = {}
        self._lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        if not profiling_enabled():
            return self.fn(*args, **kwargs)
        try:
            key = _sig_key(args, kwargs)
        except TypeError:
            return self.fn(*args, **kwargs)
        if key is None:  # nested trace
            return self.fn(*args, **kwargs)
        entry = self._compiled.get(key)
        if entry is None:
            entry = self._capture(key, args, kwargs)
        if entry is _FAILED:
            return self.fn(*args, **kwargs)
        try:
            return entry(*args, **kwargs)
        except (TypeError, ValueError):
            # aval drift the signature key was too coarse to see —
            # the exact-shape executable refuses; jit re-dispatches
            note_fallback(self.digest, self.kind)
            return self.fn(*args, **kwargs)

    def _capture(self, key, args, kwargs):
        """lower+compile+record for one signature. Compilation runs
        OUTSIDE the instance lock (a concurrent duplicate costs one
        wasted compile; a lock held across XLA would serialize every
        signature of this family behind the compiler).

        Disk tier first: when exec_cache_disk is mounted, a compatible
        AOT-serialized executable for this exact (digest, kind,
        signature) deserializes in place of the lower+compile — zero
        trace, zero compile, recorded with from_disk=True. A fresh
        compile is serialized back so the NEXT process restores."""
        if len(self._compiled) >= _max_sigs():
            with self._lock:
                self._compiled.setdefault(key, _FAILED)
            return self._compiled[key]
        disk = _disk_tier()
        sighash = None
        if disk is not None:
            try:
                sighash = disk.sig_hash(key)
                restored = disk.load_executable(self.digest, self.kind,
                                                sighash)
            except Exception:
                restored = None
            if restored is not None:
                record_executable(self.digest, self.kind, restored,
                                  trace_s=0.0, compile_s=0.0,
                                  canonical=self.canonical,
                                  label=self.label, from_disk=True)
                with self._lock:
                    self._compiled.setdefault(key, restored)
                return self._compiled[key]
        try:
            t0 = time.perf_counter()
            lowered = self.fn.lower(*args, **kwargs)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        except Exception:
            note_fallback(self.digest, self.kind, compile_error=True)
            with self._lock:
                self._compiled.setdefault(key, _FAILED)
            return self._compiled[key]
        record_executable(self.digest, self.kind, compiled,
                          trace_s=t1 - t0, compile_s=t2 - t1,
                          canonical=self.canonical, label=self.label)
        if disk is not None and sighash is not None:
            try:
                disk.store_executable(self.digest, self.kind, sighash,
                                      compiled)
            except Exception:
                pass  # serialization support is best-effort
        with self._lock:
            self._compiled.setdefault(key, compiled)
        return self._compiled[key]

    def lower(self, *args, **kwargs):
        """AOT protocol passthrough; the Lowered records on compile."""
        t0 = time.perf_counter()
        lowered = self.fn.lower(*args, **kwargs)
        return _RecordingLowered(lowered, self,
                                 time.perf_counter() - t0)

    def __getattr__(self, name):
        return getattr(self.fn, name)


def instrument(fn, digest, kind, canonical=None, label=None):
    """Wrap `fn` (a jax.jit callable) for executable accounting. A
    falsy digest returns `fn` unchanged — unkeyed programs stay raw."""
    if not digest:
        return fn
    return InstrumentedJit(fn, digest, kind, canonical=canonical,
                           label=label)
