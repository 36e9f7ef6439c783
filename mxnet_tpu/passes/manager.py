"""Pass manager: ordered pipeline execution with a safety contract.

Relay's lesson (PAPERS.md): transforms are only trustworthy when the
infrastructure, not each transform author, enforces validity. After
EVERY pass the manager (1) compacts the graph — orphans a rewrite left
behind are swept by the same traversal the verifier uses to find them —
(2) re-checks the structural invariants (`Graph.validate`), and (3)
runs the PR 5 graph verifier on the pass output, so a transform can
never ship an invalid graph into the executor: it raises right here,
naming the pass.

`optimize_for_bind` is the executor entry point: behind
`MXNET_GRAPH_PASSES` (default on; "0"/"off" bypasses; a comma list
selects/orders passes explicitly, e.g. "dce,fold,cse,layout,
canonicalize"), memoized per (raw structure key, pipeline spec) so a
rebind/reshape of an already-seen graph pays a dict lookup, not a
pipeline run.

All counters live in module stats, exposed as
`graph_pass_stats()` / `reset_pass_stats()` and embedded by the
profiler as `graphPassStats`.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

from ..base import MXNetError
from ..telemetry import register_view as _register_view
from . import transforms as _t
from .ir import Graph

# ------------------------------------------------------------- registry
# name -> (fn, default_on); insertion order defines pipeline order
_PASS_REGISTRY: "OrderedDict[str, tuple]" = OrderedDict()


def register_pass(name, fn=None, *, default_on=True):
    """Register a graph pass (`fn(graph) -> n_rewrites`). Usable as a
    decorator. Registration order fixes the default pipeline position;
    `default_on=False` passes run only when named in
    MXNET_GRAPH_PASSES (e.g. the layout rewrite)."""
    def _add(f):
        if name in _PASS_REGISTRY:
            raise MXNetError(f"graph pass {name!r} registered twice")
        _PASS_REGISTRY[name] = (f, default_on)
        return f

    return _add(fn) if fn is not None else _add


def list_passes():
    """Registered pass names in pipeline order."""
    return list(_PASS_REGISTRY)


register_pass("dce", _t.dce)
register_pass("fold", _t.fold)
register_pass("cse", _t.cse)
register_pass("layout", _t.layout_nhwc, default_on=False)
register_pass("canonicalize", _t.canonicalize)


def default_pipeline():
    return [n for n, (_, on) in _PASS_REGISTRY.items() if on]


# ---------------------------------------------------------------- stats
_STATS_LOCK = threading.Lock()


def _zero_stats():
    return {
        "pipeline_runs": 0,
        "pipeline_cached": 0,
        "nodes_in": 0,
        "nodes_out": 0,
        "nodes_eliminated": 0,
        "folds": 0,
        "cse_hits": 0,
        "layout_rewrites": 0,
        "canonical_rewrites": 0,
        "verify_failures": 0,
        "pass_time_us": {},
    }


_stats = _zero_stats()

# which top-level counter a pass's rewrite count feeds
_PASS_COUNTERS = {
    "dce": "nodes_eliminated",
    "fold": "folds",
    "cse": "cse_hits",
    "layout": "layout_rewrites",
    "canonicalize": "canonical_rewrites",
}


def graph_pass_stats():
    with _STATS_LOCK:
        out = dict(_stats)
        out["pass_time_us"] = dict(_stats["pass_time_us"])
    return out


def reset_pass_stats():
    global _stats
    with _STATS_LOCK:
        _stats = _zero_stats()


# live view in the central telemetry registry: /statusz and /metrics
# read the same counters dump_profile embeds as `graphPassStats`
_register_view("graphPassStats", graph_pass_stats,
               prom_prefix="graph_passes")


# -------------------------------------------------------------- manager
class PassManager:
    """Runs a pass list over a Graph with per-pass compaction,
    validation, and verification."""

    def __init__(self, passes=None, verify=True, collect_stats=True):
        names = list(passes) if passes is not None else default_pipeline()
        unknown = [n for n in names if n not in _PASS_REGISTRY]
        if unknown:
            raise MXNetError(
                f"unknown graph pass(es) {unknown}; registered: "
                f"{list_passes()} (MXNET_GRAPH_PASSES)")
        self.passes = [(n, _PASS_REGISTRY[n][0]) for n in names]
        self.verify = verify
        # collect_stats=False for KEY computation (canonical_digest):
        # the pipeline runs only to name the graph family, not to
        # optimize a bind — graphPassStats must stay a ledger of real
        # bind-time pipeline work (MXNET_GRAPH_PASSES=0 pins 0 runs
        # even though digests still canonicalize)
        self.collect_stats = collect_stats

    def run(self, graph):
        from ..analysis.graph_verify import verify_graph

        if self.collect_stats:
            with _STATS_LOCK:
                _stats["pipeline_runs"] += 1
                _stats["nodes_in"] += len(graph)
        for name, fn in self.passes:
            t0 = time.perf_counter()
            try:
                applied = int(fn(graph) or 0)
                # orphans stranded by the rewrite die here, so the
                # verifier below sees only the graph that would ship
                swept = graph.compact()
                graph.validate()
                issues = (verify_graph(graph, raise_on_issue=False)
                          if self.verify else [])
            except MXNetError:
                if self.collect_stats:
                    with _STATS_LOCK:
                        _stats["verify_failures"] += 1
                raise
            dt_us = int((time.perf_counter() - t0) * 1e6)
            if self.collect_stats:
                with _STATS_LOCK:
                    _stats["pass_time_us"][name] = (
                        _stats["pass_time_us"].get(name, 0) + dt_us)
                    counter = _PASS_COUNTERS.get(name)
                    if counter:
                        _stats[counter] += applied
                    if name != "dce":
                        _stats["nodes_eliminated"] += swept
            if issues:
                if self.collect_stats:
                    with _STATS_LOCK:
                        _stats["verify_failures"] += 1
                detail = "; ".join(
                    f"[{i.kind}] {i.message}" for i in issues)
                raise MXNetError(
                    f"graph pass {name!r} produced an invalid graph: "
                    f"{detail}")
        if self.collect_stats:
            with _STATS_LOCK:
                _stats["nodes_out"] += len(graph)
        return graph


# -------------------------------------------------------- entry points
def pipeline_spec():
    """Parse MXNET_GRAPH_PASSES: None = disabled, else pass-name list.
    The knob is registered in mxnet_tpu.utils; read raw to keep the
    bind path cheap."""
    raw = os.environ.get("MXNET_GRAPH_PASSES", "1").strip()
    if raw in ("0", "off", "false", "False", "none"):
        return None
    if raw in ("", "1", "on", "true", "True", "default"):
        return default_pipeline()
    return [p.strip() for p in raw.split(",") if p.strip()]


def optimize(symbol, passes=None, verify=True, collect_stats=True):
    """Run the pipeline over a Symbol, returning the optimized Symbol.
    (The Graph-level API is `PassManager.run` directly.)"""
    graph = Graph.from_symbol(symbol)
    PassManager(passes, verify=verify,
                collect_stats=collect_stats).run(graph)
    return graph.to_symbol()


# memo: raw structure key + pipeline spec -> optimized Symbol
_MEMO_LOCK = threading.Lock()
_memo: "OrderedDict" = OrderedDict()
_MEMO_CAP = 128


def optimize_for_bind(symbol):
    """Executor._build hook: the MXNET_GRAPH_PASSES pipeline, memoized.
    Returns `symbol` itself when disabled; the memo makes repeated
    binds of one graph (reshape revisits, bucketing sweeps) cost a
    lookup — the exec-cache's zero-steady-state-retrace discipline
    extends to zero steady-state pipeline runs."""
    spec = pipeline_spec()
    if spec is None:
        return symbol
    key = (symbol.structure_key(), tuple(spec))
    with _MEMO_LOCK:
        hit = _memo.get(key)
        if hit is not None:
            _memo.move_to_end(key)
    if hit is not None:
        with _STATS_LOCK:
            _stats["pipeline_cached"] += 1
        return hit
    optimized = optimize(symbol, passes=spec)
    with _MEMO_LOCK:
        _memo[key] = optimized
        while len(_memo) > _MEMO_CAP:
            _memo.popitem(last=False)
    return optimized


def clear_memo():
    with _MEMO_LOCK:
        _memo.clear()
