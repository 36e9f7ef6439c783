"""mxnet_tpu.passes: graph-optimization pass pipeline + tuning.

The Relay-style layer between Symbol construction and the executor
(ROADMAP item 2): graph-to-graph transforms over the node-list IR
(`ir.Graph`), run by a `PassManager` that compacts and re-verifies
after every pass, wired into `Executor._build` ahead of the exec-cache
lookup (MXNET_GRAPH_PASSES, default on) so the cache keys on the
optimized canonical graph — isomorphic-but-differently-built networks
collide onto one compiled program. `cost_model`/`Autotuner` pick
layout / multistep-k / bucket-grid per (canonical graph, platform),
analytic-first, persisted at MXNET_TUNING_CACHE.

See docs/passes.md for the pass catalog and custom-pass registration.
"""
from __future__ import annotations

import hashlib

from . import cost_model, ir, transforms, tuner  # noqa: F401
from .ir import Graph, GraphNode  # noqa: F401
from .manager import (  # noqa: F401
    PassManager,
    clear_memo,
    default_pipeline,
    graph_pass_stats,
    list_passes,
    optimize,
    optimize_for_bind,
    pipeline_spec,
    register_pass,
    reset_pass_stats,
)
from .tuner import Autotuner  # noqa: F401


def canonical_digest(symbol):
    """Stable hex digest of the canonicalized graph — the
    cross-process analog of `Symbol.structure_key()` (which contains
    unpicklable leaves). Runs the full default pipeline, so any two
    graphs the pipeline maps to one canonical form share a digest.
    Keys the tuning cache (tuner.py). Stats are suppressed: this is a
    KEY computation, not bind-time optimization work, so
    graphPassStats stays a ledger of real pipeline runs."""
    js = optimize(symbol, collect_stats=False).tojson()
    return hashlib.sha256(js.encode("utf-8")).hexdigest()[:16]
