"""Always-on, overhead-bounded structured tracing over a span ring.

The profiler (mxnet_tpu.profiler) answers "what did the process do
while I was profiling" — it buffers unboundedly and only between
explicit run/stop calls. This layer answers the production question
"what is the process doing RIGHT NOW / what was it doing when it
died": every request and every training step records a handful of
spans into a fixed-size ring buffer, always on, so `/statusz` and the
flight recorder can reconstruct the recent past of a live server
without anyone having arranged a profiling session first.

Overhead contract: one span record is two `time.perf_counter()` reads,
one tuple construction, and one deque append under a lock — no
allocation proportional to history (the ring evicts), no I/O, no
device interaction. `ci/check_telemetry.sh` gates the end-to-end cost
at <= 3% of step time; `MXNET_TELEMETRY_SPANS=0` disables recording
entirely (the A/B arm of that gate).

Causation: a `with span(...)` block pushes its name on a thread-local
stack, so every span recorded inside it — by a nested block or by
`record_span` after the fact — carries `parent`, the enclosing span on
the same thread. A layer's self time is its span less its children.

One clock with the device: the same block opens a
`jax.profiler.TraceAnnotation` of the span's name. With no profiler
session live that is a TraceMe that records nothing (well under a
microsecond); inside ANY `jax.profiler` capture the program's spans sit
in the host plane on the profiler's own clock, next to the device's
operations, with no alignment by the reader. `record_span` cannot do
that (its region is already over), which is why the hot loops use
`with` blocks.

Correlation: `new_trace_id()` mints a process-unique id; serving
threads it `submit -> enqueue -> batch_flush -> execute -> reply`
(the request's Future carries it as `.trace_id`), and `fit` stamps
per-step ids on its data-wait/dispatch/metric-drain spans. Batch-level
spans cover many requests at once: they carry the member ids in a
`trace_ids` attr, and `spans_for_trace` matches both forms.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time

now = time.perf_counter

_DEFAULT_CAPACITY = 2048


def _env_capacity():
    # registered as MXNET_TELEMETRY_SPANS in mxnet_tpu.utils; read raw
    # here so the ring exists before (and without) the full package
    try:
        return max(0, int(os.environ.get("MXNET_TELEMETRY_SPANS",
                                         _DEFAULT_CAPACITY)))
    except ValueError:
        return _DEFAULT_CAPACITY


_lock = threading.Lock()
_tls = threading.local()        # .stack: names of the open spans
_annotation = None              # jax.profiler.TraceAnnotation, or False
_capacity = _env_capacity()
_ring = collections.deque(maxlen=_capacity or 1)
_recorded = 0
_id_counter = itertools.count(1)


class Span:
    """One recorded region: (name, trace_id, begin, end, attrs,
    parent). Times are `time.perf_counter()` seconds (same clock
    family as the profiler's host events); `parent` is the name of the
    span that was open on the recording thread, or None."""

    __slots__ = ("name", "trace_id", "t0", "t1", "attrs", "parent")

    def __init__(self, name, trace_id, t0, t1, attrs, parent=None):
        self.name = name
        self.trace_id = trace_id
        self.t0 = t0
        self.t1 = t1
        self.attrs = attrs
        self.parent = parent

    @property
    def duration_us(self):
        return (self.t1 - self.t0) * 1e6

    def covers(self, trace_id):
        if self.trace_id == trace_id:
            return True
        attrs = self.attrs
        return bool(attrs) and trace_id in attrs.get("trace_ids", ())

    def to_dict(self):
        out = {
            "name": self.name,
            "trace_id": self.trace_id,
            "t0_us": round(self.t0 * 1e6, 1),
            "dur_us": round(self.duration_us, 1),
        }
        if self.parent:
            out["parent"] = self.parent
        if self.attrs:
            out["attrs"] = {
                k: (list(v) if isinstance(v, tuple) else v)
                for k, v in self.attrs.items()
            }
        return out


def new_trace_id(prefix="req"):
    """Process-unique correlation id (no RNG, no wall clock: a pid-
    scoped monotonic counter, deterministic under mx.random.seed)."""
    return f"{prefix}-{os.getpid():x}-{next(_id_counter):x}"


def _open_spans():
    try:
        return _tls.stack
    except AttributeError:
        stack = _tls.stack = []
        return stack


def _trace_annotation():
    """jax.profiler.TraceAnnotation, imported on first use (the ring
    exists before, and without, jax); False where it cannot be."""
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        except Exception:
            _annotation = False
    return _annotation


def record_span(name, trace_id, t0, t1, attrs=None, parent=None):
    """Append one finished span to the ring (the single hot-path
    recording chokepoint — listed in mxlint's HOT_PATH_MANIFEST).
    `parent` defaults to the span open on this thread, if any."""
    global _recorded
    if _capacity <= 0:
        return
    if parent is None:
        stack = _open_spans()
        if stack:
            parent = stack[-1]
    span_obj = Span(name, trace_id, t0, t1, attrs, parent)
    with _lock:
        _ring.append(span_obj)
        _recorded += 1


class span:
    """Context manager recording one region:

        with telemetry.span("serving.execute", trace_id=tid, batch=8):
            ...

    The record decision is latched nowhere — the ring is always on —
    but a zero capacity (MXNET_TELEMETRY_SPANS=0) makes the block a
    no-op: no record, no profiler annotation. Attributes known only
    inside the block are added with `note(**attrs)`."""

    __slots__ = ("name", "trace_id", "attrs", "_t0", "_ann", "_pushed")

    def __init__(self, name, trace_id=None, **attrs):
        self.name = name
        self.trace_id = trace_id
        self.attrs = attrs or None

    def note(self, **attrs):
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        self._ann = None
        self._pushed = _capacity > 0
        if self._pushed:
            _open_spans().append(self.name)
            ann_cls = _annotation or _trace_annotation()
            if ann_cls:
                self._ann = ann_cls(self.name)
                self._ann.__enter__()
        self._t0 = now()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = now()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._pushed:
            _open_spans().pop()
        if exc_type is not None:
            attrs = dict(self.attrs or ())
            attrs["error"] = exc_type.__name__
            self.attrs = attrs
        record_span(self.name, self.trace_id, self._t0, t1,
                    self.attrs)
        return False


def recent_spans(n=None):
    """Newest-last list of the ring's spans (all of them by default)."""
    with _lock:
        spans = list(_ring)
    if _capacity <= 0:
        return []
    return spans if n is None else spans[-int(n):]


def spans_for_trace(trace_id):
    """Every retained span carrying this correlation id — directly or
    through a batch-level `trace_ids` attr."""
    return [s for s in recent_spans() if s.covers(trace_id)]


def trace_stats():
    """Ring counters for /statusz and the flight recorder."""
    with _lock:
        retained = len(_ring) if _capacity > 0 else 0
        recorded = _recorded
    return {
        "capacity": _capacity,
        "retained": retained,
        "recorded": recorded,
        "evicted": max(0, recorded - retained),
    }


def span_summary():
    """{name: {count, total_us}} aggregated over the retained ring."""
    out = {}
    for s in recent_spans():
        agg = out.setdefault(s.name, {"count": 0, "total_us": 0.0})
        agg["count"] += 1
        agg["total_us"] += s.duration_us
    for agg in out.values():
        agg["total_us"] = round(agg["total_us"], 1)
    return out


def set_capacity(n):
    """Resize (and clear) the ring — tests and the overhead A/B gate.
    0 disables recording."""
    global _capacity, _ring, _recorded
    n = max(0, int(n))
    with _lock:
        _capacity = n
        _ring = collections.deque(maxlen=n or 1)
        _recorded = 0


def clear():
    """Drop retained spans, keep capacity."""
    global _recorded
    with _lock:
        _ring.clear()
        _recorded = 0
