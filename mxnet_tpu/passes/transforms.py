"""The built-in graph-to-graph transforms.

Each pass is a function `pass_fn(graph) -> int` mutating a
`passes.ir.Graph` in place and returning how many rewrites it applied
(0 = fixpoint). The manager compacts (sweeps orphans) and re-verifies
after every pass, so a pass may freely strand producers it rewired
around. Pipeline order (manager.DEFAULT_PIPELINE):

  dce          delete head-unreachable nodes (the verifier's
               `dead_node` finding, executed instead of reported)
  fold         evaluate constant-rooted subgraphs into
               `_graph_constant` leaves + algebraic identities
               (x*1, x/1, x+0, x-0)
  cse          merge structurally identical subexpressions
  layout       (opt-in) NCHW Convolution/Pooling -> NHWC, the
               TPU-native orientation, via inserted transposes
  canonicalize stable topo order, canonical op names, normalized
               params, dense renaming of auto-named nodes — runs LAST
               of the structural passes so names reflect the final
               graph (and a second pipeline run is a no-op)

Invariants every pass preserves: variable nodes are never renamed,
created, or merged away (binding is by-name against the ORIGINAL
symbol); head count and order never change; head values are
numerically identical (fold/cse/dce cannot change a head's value,
layout wraps in transpose pairs that cancel).
"""
from __future__ import annotations

import re

from ..base import MXNetError

# Ops that materialize a deterministic value from params alone.
CONST_SOURCE_OPS = frozenset({
    "_zeros", "_ones", "_full", "_arange", "_graph_constant",
})


def _fold_cap():
    from ..utils import getenv

    return int(getenv("MXNET_PASS_FOLD_MAX"))


# ------------------------------------------------------------------ dce
def dce(graph):
    """Dead-node elimination: `Graph.compact` runs the verifier's
    reachability traversal and deletes what it finds."""
    return graph.compact()


# ----------------------------------------------------------------- fold
def _is_foldable_op(gn):
    if gn.is_variable:
        return False
    try:
        od = gn.opdef()
    except MXNetError:
        return False
    return (not od.needs_rng and not od.needs_mode and not od.aux_names
            and od.name != "Custom"
            and od.resolved_num_outputs(od.normalize_params(gn.attrs))
            == 1)


def _shape_guard(gn, cap):
    """Pre-evaluation size guard for const-source ops: refuse to
    materialize a `shape` param bigger than the fold cap."""
    shape = gn.params().get("shape")
    if not shape:
        return True
    n = 1
    for d in shape:
        n *= int(d)
    return n <= cap


def fold(graph):
    """Constant folding: every op whose inputs are all constant-valued
    collapses into a `_graph_constant` leaf holding the evaluated
    result (as nested python lists, so it survives tojson round-trips).
    Plus the algebraic identities x*1, x/1, x+0, x-0 — except at graph
    heads, where removing the computing op would re-create the
    donation-alias hazard the verifier rejects (`x * 1` is its
    documented workaround)."""
    import numpy as np

    cap = _fold_cap()
    n = len(graph.nodes)
    is_const = [False] * n
    for i, gn in enumerate(graph.nodes):
        if not _is_foldable_op(gn):
            continue
        if gn.inputs:
            is_const[i] = all(is_const[s] for s, _ in gn.inputs)
        else:
            is_const[i] = (gn.op in CONST_SOURCE_OPS
                           and _shape_guard(gn, cap))

    # fold boundaries: const nodes with at least one input (a leafless
    # const source is already as cheap as a _graph_constant)
    targets = [i for i in range(n)
               if is_const[i] and graph.nodes[i].inputs]
    memo = {}

    def _eval(i):
        if i in memo:
            return memo[i]
        gn = graph.nodes[i]
        vals = [_eval(s) for s, _ in gn.inputs]
        memo[i] = gn.opdef().fn(*vals, **gn.params())
        return memo[i]

    folds = 0
    taken = {gn.name for gn in graph.nodes}
    for i in targets:
        try:
            val = np.asarray(_eval(i))
        except Exception:
            continue  # op rejected the const inputs — leave it traced
        if val.size > cap:
            continue
        gn = graph.nodes[i]
        gn.op = "_graph_constant"
        gn.attrs = {"value": val.tolist(), "dtype": val.dtype.name}
        gn.inputs = []
        # auto-style rename so canonicalize renumbers it like any other
        # auto-named node (keeping the replaced op's name would leak the
        # BUILD-TIME numbering into the canonical signature)
        name, k = f"graph_constant{i}", i
        while name in taken:
            k += len(graph.nodes)
            name = f"graph_constant{k}"
        taken.discard(gn.name)
        taken.add(name)
        gn.name = name
        folds += 1

    folds += _fold_identities(graph)
    return folds


_IDENTITY_OPS = {
    "_mul_scalar": 1.0, "_div_scalar": 1.0,
    "_plus_scalar": 0.0, "_minus_scalar": 0.0,
}


def _fold_identities(graph):
    head_nodes = {s for s, _ in graph.heads}
    redirect = {}
    for i, gn in enumerate(graph.nodes):
        neutral = _IDENTITY_OPS.get(gn.op)
        if neutral is None or i in head_nodes:
            continue
        if float(gn.params().get("scalar", neutral)) != neutral:
            continue
        src = gn.inputs[0]
        # chase through identities folded earlier in this sweep
        while src[0] in redirect:
            src = redirect[src[0]]
        redirect[i] = src
    if not redirect:
        return 0
    for gn in graph.nodes:
        gn.inputs = [redirect.get(s, (s, j)) for s, j in gn.inputs]
    graph.heads = [redirect.get(s, (s, j)) for s, j in graph.heads]
    return len(redirect)


# ------------------------------------------------------------------ cse
def cse(graph):
    """Common-subexpression elimination: nodes with the same op,
    normalized params, ctx-group, and (already-deduplicated) input
    wiring compute the same value — all consumers move to the first
    occurrence. Variables merge by name (binding is by-name, so two
    same-named variable nodes are one buffer regardless); stateful ops
    (rng draws, aux-carrying ops like BatchNorm) never merge."""
    from ..symbol import _canon

    canonical = {}
    replace = {}
    for i, gn in enumerate(graph.nodes):
        if gn.is_variable:
            key = ("var", gn.name, gn.is_aux)
        else:
            try:
                od = gn.opdef()
            except MXNetError:
                continue
            if od.needs_rng or od.aux_names:
                continue
            key = (
                "op", od.name, _canon(od.normalize_params(gn.attrs)),
                gn.extra.get("__ctx_group__"),
                tuple((replace.get(s, s), j) for s, j in gn.inputs),
            )
        if key in canonical:
            replace[i] = canonical[key]
        else:
            canonical[key] = i
    if not replace:
        return 0
    for gn in graph.nodes:
        gn.inputs = [(replace.get(s, s), j) for s, j in gn.inputs]
    graph.heads = [(replace.get(s, s), j) for s, j in graph.heads]
    return len(replace)


# --------------------------------------------------------------- layout
_NHWC_DATA = (0, 2, 3, 1)   # NCHW -> NHWC (and OIHW -> OHWI)
_NCHW_DATA = (0, 3, 1, 2)   # NHWC -> NCHW


def layout_nhwc(graph):
    """Opt-in NCHW->NHWC rewrite for 2-D Convolution/Pooling: on TPU,
    channels-last puts C on the 128-wide lane dimension, so the op
    skips XLA's internal relayout. Bind shapes are untouched — the op
    is wrapped in transpose pairs (data/weight in, output back out),
    and XLA cancels adjacent pairs between consecutive rewritten ops.
    Idempotent: a rewritten op carries layout='NHWC' and is skipped."""
    targets = []
    for i, gn in enumerate(graph.nodes):
        if gn.op not in ("Convolution", "Pooling"):
            continue
        params = gn.params()
        if str(params.get("layout") or "NCHW") != "NCHW":
            continue
        if len(params.get("kernel") or ()) != 2:
            continue  # rank unknown (global_pool) or not 2-D
        targets.append(i)
    if not targets:
        return 0

    from .ir import GraphNode

    consumers = graph.consumers()
    for i in targets:
        gn = graph.nodes[i]

        def _transpose(name, axes, src):
            graph.nodes.append(GraphNode(
                "transpose", name, attrs={"axes": axes}, inputs=[src]))
            return len(graph.nodes) - 1

        old_consumers = list(consumers[i])
        old_head_slots = [k for k, (s, _) in enumerate(graph.heads)
                          if s == i]
        tin = _transpose(f"{gn.name}_nhwc_data", _NHWC_DATA,
                         gn.inputs[0])
        gn.inputs[0] = (tin, 0)
        if gn.op == "Convolution":
            tw = _transpose(f"{gn.name}_nhwc_weight", _NHWC_DATA,
                            gn.inputs[1])
            gn.inputs[1] = (tw, 0)
        gn.attrs["layout"] = "NHWC"
        tout = _transpose(f"{gn.name}_nchw_out", _NCHW_DATA, (i, 0))
        for ci, pos in old_consumers:
            graph.nodes[ci].inputs[pos] = (tout, 0)
        for k in old_head_slots:
            graph.heads[k] = (tout, graph.heads[k][1])
    graph.toposort()
    return len(targets)


# --------------------------------------------------------- canonicalize
def canonicalize(graph):
    """Canonical form: (1) DFS-post-order node list from the heads — a
    pure function of the wiring, so construction order stops mattering;
    (2) alias op names -> canonical registry names; (3) params
    normalized (defaults filled, values coerced); (4) AUTO-NAMED op
    nodes renamed to dense per-op counters in topo order. User-named
    nodes and ALL variables keep their names (binding and the public
    output surface are by-name). Runs last of the structural passes, so
    the names — and the exec-cache key derived from them — describe the
    graph that actually executes."""
    from ..symbol import _canon

    graph.toposort()
    changed = 0
    for gn in graph.nodes:
        if gn.is_variable:
            continue
        try:
            od = gn.opdef()
        except MXNetError:
            continue
        if gn.op != od.name:
            gn.op = od.name
            changed += 1
        norm = od.normalize_params(gn.attrs)
        if _canon(norm) != _canon(gn.attrs):
            changed += 1
        gn.attrs = norm

    # rename pass: only names that LOOK auto-generated for their own op
    # (exactly `{base}{digits}` with base = _create's auto-name prefix)
    auto = []
    taken = set()
    for gn in graph.nodes:
        base = None if gn.is_variable else gn.op.lower().lstrip("_")
        if base is not None and re.fullmatch(
                re.escape(base) + r"\d+", gn.name):
            auto.append((gn, base))
        else:
            taken.add(gn.name)
    counters = {}
    assigned = set()
    for gn, base in auto:
        k = counters.get(base, 0)
        while f"{base}{k}" in taken or f"{base}{k}" in assigned:
            k += 1
        counters[base] = k + 1
        new = f"{base}{k}"
        assigned.add(new)
        if new != gn.name:
            gn.name = new
            changed += 1
    return changed
