"""Profiler: Chrome trace-event output + TPU/XLA trace capture.

Capability parity with the reference profiler (src/engine/profiler.{h,cc}
— OprExecStat records per-op begin/end dumped as Chrome trace-event JSON
by DumpProfile, python/mxnet/profiler.py facade). TPU-native twist: the
heavy device-side timeline comes from jax.profiler (XLA trace →
TensorBoard/Perfetto), while host-side framework events (executor
forward/backward, io, kvstore push/pull) are recorded here and dumped in
the same Chrome trace-event JSON format the reference emits, so existing
chrome://tracing workflows keep working.
"""
from __future__ import annotations

import json
import os
import threading
import time

from . import telemetry as _telemetry

_state = {
    "mode": "symbolic",
    "filename": "profile.json",
    "running": False,
    "ever_ran": False,
    "jax_trace_dir": None,
}
_events = []
_lock = threading.Lock()
_t0 = time.perf_counter()

# ---- host<->device sync accounting (hostSyncStats) ----------------
# The pipelined training loop's invariant is "zero per-step blocking
# syncs"; these counters make it measurable (and CI-enforceable, see
# ci/check_no_perstep_sync.py). Incremented from the few chokepoints
# every sync funnels through: NDArray.asnumpy (blocking_fetches),
# NDArray.wait_to_read / engine.wait_for_all / FusedTrainStep.sync
# (blocking_waits), EvalMetric drain (metric_fetches), and the
# dispatch-ahead window in BaseModule.fit (dispatch_stalls /
# steps_in_flight_peak).
_sync_lock = threading.Lock()
_SYNC_KEYS = (
    "blocking_fetches", "blocking_waits", "metric_fetches",
    "dispatch_stalls", "stall_time_us", "steps_in_flight_peak",
)
_sync_stats = {k: 0 for k in _SYNC_KEYS}

# a wait shorter than this was already complete — dispatch kept ahead,
# nothing stalled
_STALL_THRESHOLD_S = 1e-4


def count_host_sync(kind, n=1):
    """Count a host<->device sync point of the given kind
    ('blocking_fetches' | 'blocking_waits' | 'metric_fetches')."""
    with _sync_lock:
        _sync_stats[kind] += n


def note_dispatch_stall(seconds):
    """Record one dispatch-window wait; counts as a stall only when the
    fenced step was genuinely unfinished."""
    with _sync_lock:
        _sync_stats["stall_time_us"] += seconds * 1e6
        if seconds > _STALL_THRESHOLD_S:
            _sync_stats["dispatch_stalls"] += 1


def note_steps_in_flight(n):
    """Track the high-water mark of in-flight dispatched steps."""
    with _sync_lock:
        if n > _sync_stats["steps_in_flight_peak"]:
            _sync_stats["steps_in_flight_peak"] = n


def host_sync_stats():
    """Snapshot of the sync counters (embedded in dump_profile as
    `hostSyncStats` next to execCacheStats/servingStats)."""
    with _sync_lock:
        out = dict(_sync_stats)
    out["stall_time_us"] = round(out["stall_time_us"], 1)
    return out


def reset_host_sync_stats():
    with _sync_lock:
        for k in _SYNC_KEYS:
            _sync_stats[k] = 0


# hostSyncStats is the registry view owned by this module; the other
# four silos register theirs at their own import (exec_cache,
# serving.stats, data.stats, passes.manager)
_telemetry.register_view("hostSyncStats", host_sync_stats,
                         prom_prefix="host_sync")


def profiler_set_config(mode="symbolic", filename="profile.json"):
    """Configure profiler output (reference profiler.py:10
    MXSetProfilerConfig). mode: 'symbolic' (executor-level events) or
    'all' (also imperative ops)."""
    _state["mode"] = mode
    _state["filename"] = filename


def profiler_set_state(state="stop"):
    """'run' starts collection, 'stop' ends it and dumps
    (reference profiler.py:25 MXSetProfilerState)."""
    if state == "run":
        _state["running"] = True
        _state["ever_ran"] = True
        trace_dir = os.environ.get("MXNET_TPU_XLA_TRACE_DIR")
        if trace_dir:
            try:
                import jax

                jax.profiler.start_trace(trace_dir)
                _state["jax_trace_dir"] = trace_dir
                # device events are timestamped relative to capture
                # start; remember where that sits on the host timeline
                # so the merge can re-base them (one unified clock)
                _state["trace_t0_us"] = (
                    time.perf_counter() - _t0) * 1e6
            except Exception:
                _state["jax_trace_dir"] = None
    elif state == "stop":
        device_trace = None
        if _state["jax_trace_dir"]:
            try:
                import jax

                jax.profiler.stop_trace()
                device_trace = _state["jax_trace_dir"]
            except Exception:
                pass
            _state["jax_trace_dir"] = None
        _state["running"] = False
        # no collection ever ran in this process: there is nothing to
        # dump, and writing an empty profile.json into the cwd as a
        # side effect of a defensive stop() call is pure pollution
        if not _state["ever_ran"]:
            return None
        return dump_profile(device_trace_dir=device_trace)
    else:
        raise ValueError("state must be 'run' or 'stop'")


def is_running():
    return _state["running"]


def record_event(name, category, begin_s, end_s, force=False):
    """Record one host-side event (seconds since profiler import).
    `force` bypasses the running check for callers that latched the
    record decision earlier (scope)."""
    if not force and not _state["running"]:
        return
    with _lock:
        _events.append((name, category, begin_s, end_s))


class scope:
    """Context manager timing a host-side region into the profile.

    The record decision is latched at __enter__: a region that began
    while the profiler was running is recorded even if collection
    stops before __exit__ (previously the region silently vanished),
    and symmetrically a region that began before 'run' stays out."""

    def __init__(self, name, category="host"):
        self.name = name
        self.category = category

    def __enter__(self):
        self._record = _state["running"]
        self._b = time.perf_counter() - _t0
        return self

    def __exit__(self, *exc):
        if self._record:
            record_event(
                self.name, self.category, self._b,
                time.perf_counter() - _t0, force=True,
            )
        return False


def _collect_device_events(trace_dir):
    """Chrome slices of the device operations in the newest jax
    capture under trace_dir (this JAX writes one `.xplane.pb` per
    capture), each attributed to a named scope through the scope map
    of the module launch that covers it (`profiling.timeline`): one
    process lane per device (pid 1001, 1002, ...) next to the host
    (pid 0) timeline. Timestamps are shifted onto the host timeline:
    the capture's clock starts at its own beginning, which
    profiler_set_state('run') recorded as trace_t0_us."""
    from .profiling import timeline as _timeline

    return _timeline.device_slices(
        _timeline.read_xplane(trace_dir),
        base_us=_state.get("trace_t0_us", 0.0))


def _view(key, import_module):
    """Thin read over the telemetry registry: the silo registers its
    snapshot function as a view at ITS import; the lazy import here
    only triggers that registration for callers that never imported
    the silo themselves."""
    if not _telemetry.has_view(key):
        import importlib

        importlib.import_module(import_module, __package__)
    return _telemetry.view_snapshot(key)


def exec_cache_stats():
    """Counters of the process-wide compiled-computation cache
    (exec_cache): hits/misses/traces/evictions + size. A thin read of
    the telemetry registry's `execCacheStats` view; also embedded in
    every dump_profile output."""
    return _view("execCacheStats", ".exec_cache")


def graph_pass_stats():
    """Counters of the graph-optimization pass pipeline
    (mxnet_tpu.passes): pipeline runs / memo hits, nodes in/out/
    eliminated, folds, CSE merges, fusion groups, layout rewrites,
    per-pass wall time — the registry's `graphPassStats` view,
    embedded in every dump_profile output."""
    return _view("graphPassStats", ".passes.manager")


def serving_stats():
    """Per-served-model counters of the serving tier (qps, queue depth,
    batch fill, padding waste, latency percentiles, retrace guard) —
    the registry's `servingStats` view, embedded in every dump_profile
    output."""
    return _view("servingStats", ".serving.stats")


def input_pipeline_stats():
    """Input-pipeline counters (wait-for-data per step, device-prefetch
    queue depth, bytes/s, stall count) — the registry's
    `inputPipelineStats` view, embedded in every dump_profile output.
    The "is my step waiting on input?" answer: stall_count > 0 in
    steady state means the data tier, not the device, bounds
    throughput (docs/faq.md)."""
    return _view("inputPipelineStats", ".data.stats")


def _ensure_silo_views():
    """Trigger registration of any legacy silo view not yet imported
    (each wrapped: an unimportable silo — e.g. jax missing pieces —
    must not break the dump, matching the old per-silo try/except)."""
    for fn in (exec_cache_stats, serving_stats, input_pipeline_stats,
               graph_pass_stats):
        try:
            fn()
        except Exception:
            pass


def dump_profile(device_trace_dir=None):
    """Write collected events as ONE Chrome trace-event JSON (the
    reference emits a single unified trace, src/engine/profiler.cc:134):
    host-side framework events on pid 0, and — when a jax device
    capture ran — the device operations of its `.xplane.pb` merged in
    under pids 1001.., each with its named scope in `args`. Every subsystem view registered in the telemetry registry is
    embedded top-level under its legacy key (`execCacheStats`,
    `servingStats`, `hostSyncStats`, `inputPipelineStats`,
    `graphPassStats`, in that historical order — chrome://tracing
    ignores unknown keys).

    Durability (round-7 satellite): the event buffer is cleared only
    AFTER the file is durably on disk, and the write goes through
    tmp + os.replace — a failed or interrupted dump neither loses the
    buffered events nor leaves a torn/partial profile behind."""
    with _lock:
        events = list(_events)
    # device events are collected BEFORE the view snapshot: feeding
    # them into the timeline aggregator first means the
    # deviceTimelineStats view embedded in THIS dump already reflects
    # the capture the same file carries (previously the per-op
    # aggregation lagged one dump behind its own events)
    device_events = []
    if device_trace_dir:
        try:
            device_events = _collect_device_events(device_trace_dir)
            if device_events:
                from .profiling import ingest_device_events

                ingest_device_events(device_events)
        except Exception:
            pass  # the device timeline is advisory; the dump must land
    trace = {"traceEvents": [], "displayTimeUnit": "ms"}
    _ensure_silo_views()
    for key, snap in _telemetry.view_items():
        trace[key] = snap
    for name, cat, b, e in events:
        trace["traceEvents"].append({
            "name": name, "cat": cat, "ph": "B",
            "ts": b * 1e6, "pid": 0, "tid": 0,
        })
        trace["traceEvents"].append({
            "name": name, "cat": cat, "ph": "E",
            "ts": e * 1e6, "pid": 0, "tid": 0,
        })
    trace["traceEvents"].extend(device_events)
    filename = _state["filename"]
    tmp = f"{filename}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(trace, f)
        os.replace(tmp, filename)
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise  # events stay buffered: nothing was dropped
    # success: drop exactly the events that were written (events that
    # arrived during the dump stay for the next one)
    with _lock:
        del _events[:len(events)]
    return filename
