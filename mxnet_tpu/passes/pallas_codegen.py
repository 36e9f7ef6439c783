"""Pallas codegen: lower `__fusion_group__` chains to generated kernels.

`fusion_hints` (PR 6) finds single-consumer elementwise chains and tags
them — annotation only, no kernel was ever generated. This stage is the
lowering step (the TVM/Glow move, PAPERS.md): it consumes those tags and
emits one generated Pallas kernel per group from a small template
library, with a composed lax-path twin that is ALWAYS available.

Two halves, two call sites:

  pallas_codegen(graph)   the registered pass. Absorbs an eligible
                          trailing full reduction into its producer
                          chain, then stamps every group's output node
                          with `__fusion_codegen__`:

                            candidate:<digest>   structurally lowerable
                            fallback:<reason>    counted static reject
                                                 (disabled / too_small /
                                                 unsupported_op:<name>)

                          The stamp is platform-independent on purpose:
                          the canonical graph digest (disk exec-cache,
                          AOT bundles) must not change with the backend.

  plan_for(symbol, ...)   executor-side lowering of an OPTIMIZED
                          symbol: resolves each candidate to a built,
                          parity-verified kernel or a counted fallback
                          reason (platform / irregular_shapes /
                          unsupported_dtype / calibrated_slower /
                          compile_refused / parity), and returns the
                          node-index routing
                          plus the exec-cache key component — fused and
                          fallback binds never collide on one program.

Templates (all (8, 128)-tile-aware through cost_model.tile_sublanes):

  elementwise     same-shape chain on a grid of VMEM-sized blocks (a
                  whole number of register tiles each, `_tiling`) when
                  the 2-D view divides the register tile, whole-array
                  single block in interpret mode otherwise
  reduction       chain + absorbed axis=None reduce over the same grid:
                  each step reduces its block and folds the partial
                  into the (1, 1) SMEM scalar
  scale_bias_act  the mul -> add -> activation special case of the
                  elementwise emitter (classified so the stats view and
                  the calibration records can tell it apart)

Every generated kernel is compiled ALONE at build time, ahead of the
step that will inline it: on a TPU that is the Mosaic compile, and a
refusal is a counted `compile_refused` fallback found here, not an
error inside the whole step's compile. The executable that comes out —
compiled on a TPU, interpreted only off it or under
MXNET_FUSION_INTERPRET (utils.pallas_interpret) — is the one verified
against its jitted lax twin (fwd; bwd is the lax twin's vjp by
construction via custom_vjp) and both are timed, warm, into the
profiling `CalibrationStore` under kind="kernel" / "kernel_lax" — the
autotuner's `choose_fusion_kernel` reads them back, so fuse-vs-fallback
is a measured decision, never a guess. Groups that do not lower are
never dropped silently: each carries a counted reason in the
`fusionStats` view (Prometheus prefix `fusion`).

Env knobs (registered in mxnet_tpu/utils): MXNET_FUSION_CODEGEN,
MXNET_FUSION_MIN_GROUP, MXNET_FUSION_INTERPRET; MXNET_DECODE_KERNEL is
folded into the same `codegen_config()` so the decode tier's kernel
choice and graph codegen share one switch surface.
"""
from __future__ import annotations

import hashlib
import logging
import threading
import time
from dataclasses import dataclass

import numpy as np

from ..base import MXNetError
from ..telemetry import register_view as _register_view
from .cost_model import TILE_LANES, tile_sublanes
from .transforms import ELEMWISE_OPS

# trailing reductions absorbable into a chain: axis=None (full) only —
# the reduction template reduces its single block down to one scalar
REDUCE_OPS = frozenset({"sum", "mean", "max", "min"})

# the scale_bias_act classifier's per-position op sets
_MUL_OPS = frozenset({"broadcast_mul", "elemwise_mul", "_mul_scalar"})
_ADD_OPS = frozenset({"broadcast_add", "elemwise_add", "_plus_scalar"})
_ACT_OPS = frozenset({"relu", "sigmoid", "tanh", "Activation"})

PARITY_RTOL = 1e-6
PARITY_ATOL = 1e-6


def _parity_rtol(template, dtype):
    """Build-time parity tolerance, set from the dtype: PARITY_RTOL for
    an f32 elementwise chain (the same ops in the same order), a few
    ulps of a narrower output (kernel and twin may round in different
    places), and 10x that for a reduction, which adds in another order
    than XLA's reduce."""
    import jax.numpy as jnp  # np.finfo does not know bfloat16

    rtol = max(PARITY_RTOL, 4 * float(jnp.finfo(dtype).eps))
    return 10 * rtol if template == "reduction" else rtol


# VMEM one grid step's operand blocks may take together. The pipeline
# double-buffers every block, so this is half of what the kernel holds;
# 4 MiB keeps it under the smallest default scoped-VMEM limit (16 MiB,
# v5e) with room for the chain's temporaries.
_BLOCK_BYTES = 4 << 20


class _Unsupported(Exception):
    """Raised by an emitter when a group cannot take its template; the
    message is the counted fallback reason."""


# ---------------------------------------------------------------- config
@dataclass(frozen=True)
class CodegenConfig:
    """The one switch surface for kernel generation (env-derived)."""

    enabled: bool       # MXNET_FUSION_CODEGEN
    min_group: int      # MXNET_FUSION_MIN_GROUP
    interpret: bool     # MXNET_FUSION_INTERPRET (force interpret mode)
    decode_kernel: str  # MXNET_DECODE_KERNEL (decoding tier choice)


def codegen_config():
    """Read the codegen knobs (fresh each call — they are env vars)."""
    from .. import utils as _utils

    return CodegenConfig(
        enabled=bool(_utils.getenv("MXNET_FUSION_CODEGEN")),
        min_group=int(_utils.getenv("MXNET_FUSION_MIN_GROUP")),
        interpret=bool(_utils.getenv("MXNET_FUSION_INTERPRET")),
        decode_kernel=str(_utils.getenv("MXNET_DECODE_KERNEL")),
    )


# ----------------------------------------------------------------- state
_LOCK = threading.RLock()
# digest -> {"tag", "ops", "template", "decision", "reason"} — latest
# decision per group; the no-silent-drops ledger ci/check_fusion.py
# audits (groups_seen == groups_lowered + groups_fallback)
_GROUPS = {}
_COUNTS = {"kernels_built": 0, "parity_checks": 0, "parity_failures": 0}
# (digest, ext aval sig, interpret) -> ("ok", callable) | ("demoted",
# reason) — kernels build (and parity-verify, and time) once per
# process+shape, so repeat binds are table lookups
_KERNELS = {}
_CAL_RECORDED = set()   # (digest, platform): one timing record each


def fusion_stats():
    """Aggregate codegen counters (`fusionStats` view / Prometheus
    `fusion_*`): groups seen/lowered/fallback, per-reason fallback
    counts, per-template kernel counts, parity totals."""
    with _LOCK:
        groups = [dict(v) for v in _GROUPS.values()]
        counts = dict(_COUNTS)
    reasons = {}
    templates = {}
    lowered = 0
    for g in groups:
        if g["decision"] == "pallas":
            lowered += 1
            templates[g["template"]] = templates.get(g["template"], 0) + 1
        else:
            reasons[g["reason"]] = reasons.get(g["reason"], 0) + 1
    out = {
        "groups_seen": len(groups),
        "groups_lowered": lowered,
        "groups_fallback": len(groups) - lowered,
        "fallback_reasons": reasons,
        "templates": templates,
    }
    out.update(counts)
    return out


def fusion_group_records():
    """Per-group drill-down: {digest: {tag, ops, template, decision,
    reason}} — the FAQ's "why did my group fall back" answer."""
    with _LOCK:
        return {d: dict(v) for d, v in _GROUPS.items()}


def reset_fusion_stats():
    """Test/CI hook: forget decisions, kernels, and counters."""
    with _LOCK:
        _GROUPS.clear()
        _KERNELS.clear()
        _CAL_RECORDED.clear()
        for k in _COUNTS:
            _COUNTS[k] = 0


_register_view("fusionStats", fusion_stats, prom_prefix="fusion")


def _note_group(digest, tag, ops, template, decision, reason=None):
    with _LOCK:
        _GROUPS[digest] = {"tag": tag, "ops": tuple(ops),
                           "template": template, "decision": decision,
                           "reason": reason}


# ------------------------------------------------------- group structure
def _groups_in(nodes):
    """{tag: [member indices, topo order]} over a node sequence whose
    records expose `.extra` (passes.ir.GraphNode)."""
    groups = {}
    for i, gn in enumerate(nodes):
        tag = gn.extra.get("__fusion_group__")
        if tag is not None:
            groups.setdefault(tag, []).append(i)
    return groups


def _absorb_reductions(graph, groups):
    """Extend each chain by its sole-consumer trailing FULL reduction
    (axis=None, exclude off): the reduction template then computes the
    chain and its scalar in one kernel. Mirrors the fusion_hints join
    rule — sole consumer, producer not a head — so the group stays a
    chain with one external output."""
    consumers = graph.consumers()
    heads = {s for s, _ in graph.heads}
    changed = 0
    for tag, members in groups.items():
        out = members[-1]
        if out in heads or len(consumers[out]) != 1:
            continue
        ci, _ = consumers[out][0]
        gn = graph.nodes[ci]
        if gn.is_variable or gn.extra.get("__fusion_group__"):
            continue
        try:
            od = gn.opdef()
        except MXNetError:
            continue
        if od.name not in REDUCE_OPS:
            continue
        params = gn.params()
        if params.get("axis") is not None or params.get("exclude"):
            continue
        if any(s != out for s, _ in gn.inputs):
            continue
        gn.extra["__fusion_group__"] = tag
        members.append(ci)
        changed += 1
    return changed


def _group_spec(nodes, members):
    """Normalize a chain into (spec, ext): spec is one
    (op_name, params, wired_inputs) per member, wired entries are
    ("m", member_pos) for in-group values and ("x", ext_pos) for
    external tensors; ext lists the external (node_index, out_index)
    keys in first-use order."""
    pos = {m: j for j, m in enumerate(members)}
    ext, ext_index, spec = [], {}, []
    for m in members:
        gn = nodes[m]
        wired = []
        for src, oi in gn.inputs:
            if src in pos:
                wired.append(("m", pos[src]))
            else:
                key = (src, oi)
                if key not in ext_index:
                    ext_index[key] = len(ext)
                    ext.append(key)
                wired.append(("x", ext_index[key]))
        spec.append((gn.opdef().name, gn.params(), tuple(wired)))
    return spec, ext


def group_digest(spec, n_ext):
    """Deterministic structural digest of one group: ops, canonical
    params, internal wiring, external arity. Shapes are NOT part of it
    — calibration records aggregate over shapes per group."""
    from ..symbol import _canon

    payload = tuple((op, _canon(params), wired)
                    for op, params, wired in spec)
    return hashlib.sha256(repr((payload, n_ext)).encode()).hexdigest()[:16]


def _template_of(spec):
    ops = [s[0] for s in spec]
    if ops[-1] in REDUCE_OPS:
        return "reduction"
    if (len(ops) == 3 and ops[0] in _MUL_OPS and ops[1] in _ADD_OPS
            and ops[2] in _ACT_OPS):
        return "scale_bias_act"
    return "elementwise"


def _static_reason(nodes, members, cfg):
    """Platform-independent eligibility (the pass-time half of the
    decision). None = candidate."""
    if not cfg.enabled:
        return "disabled"
    n_elem = 0
    for m in members:
        gn = nodes[m]
        try:
            od = gn.opdef()
        except MXNetError:
            return "unsupported_op:unknown"
        if od is None:
            return "unsupported_op:variable"
        name = od.name
        if name in ELEMWISE_OPS:
            n_elem += 1
        elif name in REDUCE_OPS:
            if m != members[-1]:
                return f"unsupported_op:{name}"
        else:
            return f"unsupported_op:{name}"
        if od.needs_rng or od.needs_mode or od.aux_names:
            return f"unsupported_op:{name}"
        if od.resolved_num_outputs(gn.params()) != 1:
            return f"unsupported_op:{name}"
    if n_elem < cfg.min_group:
        return "too_small"
    return None


# ------------------------------------------------------------- the pass
def pallas_codegen(graph):
    """The registered pipeline stage (runs after fusion_hints): absorb
    trailing reductions, then stamp every group's output node with its
    lowering verdict (`candidate:<digest>` / `fallback:<reason>`).
    Returns the candidate count (0 = fixpoint, the manager's
    idempotence idiom)."""
    cfg = codegen_config()
    groups = _groups_in(graph.nodes)
    changed = _absorb_reductions(graph, groups)
    stamps = {}
    n_candidates = 0
    for tag in sorted(groups):
        members = sorted(groups[tag])
        out = members[-1]
        reason = _static_reason(graph.nodes, members, cfg)
        if reason is None:
            spec, ext = _group_spec(graph.nodes, members)
            stamps[out] = f"candidate:{group_digest(spec, len(ext))}"
            n_candidates += 1
        else:
            stamps[out] = f"fallback:{reason}"
    for i, gn in enumerate(graph.nodes):
        want = stamps.get(i)
        have = gn.extra.get("__fusion_codegen__")
        if want != have:
            changed += 1
            if want is None:
                del gn.extra["__fusion_codegen__"]
            else:
                gn.extra["__fusion_codegen__"] = want
    return changed and n_candidates


# ------------------------------------------------------ lax twin + vjp
def group_lax_fn(spec):
    """Compose the group's registry op fns into ONE callable over the
    external inputs — the always-available lax fallback path, and the
    vjp reference of every generated kernel."""
    from ..ops import registry as _registry

    steps = [(_registry.get(op).fn, dict(params), wired)
             for op, params, wired in spec]

    def lax_fn(*ext_vals):
        vals = []
        for fn, params, wired in steps:
            ins = [ext_vals[w[1]] if w[0] == "x" else vals[w[1]]
                   for w in wired]
            vals.append(fn(*ins, **params))
        return vals[-1]

    return lax_fn


def _make_fused_callable(lax_fn, kernel_call):
    """Differentiable fused entry: forward through the generated
    kernel, backward through the lax twin's vjp (the parallel/attention
    custom_vjp pattern — gradients are exact because fwd parity is)."""
    import jax

    @jax.custom_vjp
    def fused(*xs):
        return kernel_call(*xs)

    def fwd(*xs):
        return kernel_call(*xs), xs

    def bwd(res, g):
        _, vjp = jax.vjp(lax_fn, *res)
        return vjp(g)

    fused.defvjp(fwd, bwd)
    return fused


# ------------------------------------------------------ kernel emitters
def _norm2d(shape):
    """(rows, cols) 2-D view: minor dim on lanes, everything else on
    sublanes (the cost-model tiling convention)."""
    shape = tuple(int(d) for d in shape)
    if not shape:
        return (1, 1)
    if len(shape) == 1:
        return (1, shape[0])
    r = 1
    for d in shape[:-1]:
        r *= d
    return (r, shape[-1])


def _largest_divisor(n, unit, cap):
    """Largest multiple of `unit` that divides `n` and is <= cap
    (`unit` itself when nothing larger fits; n % unit == 0)."""
    best = unit
    for m in range(unit, min(n, cap) + 1, unit):
        if n % m == 0:
            best = m
    return best


def _tiling(r, c, dtype, interpret, n_operands):
    """(block, grid) over the 2-D view. Tile-regular views get blocks
    of whole (sublanes, 128) register tiles sized so that all
    `n_operands` blocks of one grid step fit `_BLOCK_BYTES` — lanes
    first (contiguous HBM rows), then sublanes; one register tile per
    step would make a ResNet-sized activation a 100k-step grid.
    Irregular views run as one whole-array block in interpret mode and
    are unsupported otherwise (real-TPU ragged tails fall back to lax
    rather than pad inside a generated kernel)."""
    sub = tile_sublanes(dtype)
    if r % sub or c % TILE_LANES:
        if interpret:
            return (r, c), (1, 1)
        raise _Unsupported("irregular_shapes")
    # budget in 4-byte elements: chains compute in f32 whatever they
    # load
    cap = max(sub * TILE_LANES, _BLOCK_BYTES // (4 * n_operands))
    bc = _largest_divisor(c, TILE_LANES, cap // sub)
    br = _largest_divisor(r, sub, cap // bc)
    return (br, bc), (r // br, c // bc)


def _load_f32(refs):
    """Block values widened to f32: the VPU computes in f32 (v5e has
    no narrower vector ALU) and Mosaic refuses a chain's scalar
    constants at bf16; the emitters round once, on the store — what
    XLA's own fusion of the lax twin does."""
    import jax.numpy as jnp

    return [ref[...].astype(jnp.float32) for ref in refs]


def _elementwise_kernel(spec, ext_avals, out_aval, interpret):
    """Tiled elementwise-chain kernel: every external input shares the
    output shape, each grid step evaluates the whole chain on one
    block."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    out_shape = tuple(out_aval.shape)
    for s, _ in ext_avals:
        if tuple(s) != out_shape:
            raise _Unsupported("irregular_shapes")
    r, c = _norm2d(out_shape)
    block, grid = _tiling(r, c, out_aval.dtype, interpret,
                          len(ext_avals) + 1)
    chain = group_lax_fn(spec)

    def kernel(*refs):
        out_ref = refs[-1]
        out_ref[...] = chain(*_load_f32(refs[:-1])).astype(out_ref.dtype)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))
                  for _ in ext_avals],
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_aval.dtype),
        interpret=interpret,
    )

    def run(*vals):
        flat = [jnp.reshape(v, (r, c)) for v in vals]
        return jnp.reshape(call(*flat), out_shape)

    return run


def _scale_bias_act_kernel(spec, ext_avals, out_aval, interpret):
    """Fused scale+bias+activation: the mul -> add -> activation chain
    (tensor or scalar-param scale/bias). Validates the pattern, then
    shares the tiled elementwise emitter — the fusion win is identical
    (one HBM round-trip instead of three), the classification feeds the
    stats view and the per-template calibration records."""
    if _template_of(spec) != "scale_bias_act":
        raise _Unsupported("irregular_shapes")
    return _elementwise_kernel(spec, ext_avals, out_aval, interpret)


def _reduction_kernel(spec, ext_avals, out_aval, interpret):
    """Chain + absorbed axis=None reduction in one kernel, on the
    elementwise grid: each step evaluates the chain (reduce included)
    on its block and folds that partial into the (1, 1) result, which
    lives in SMEM across the grid — Mosaic stores vectors, not
    scalars, to VMEM. No padded lanes enter the reduction."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shapes = {tuple(s) for s, _ in ext_avals}
    if len(shapes) != 1:
        raise _Unsupported("irregular_shapes")
    r, c = _norm2d(shapes.pop())
    block, grid = _tiling(r, c, out_aval.dtype, interpret,
                          len(ext_avals))
    # the chain WITHOUT its trailing reduce (whose only input is the
    # chain's last value, _absorb_reductions): the kernel reduces each
    # block itself so the partial is a true scalar
    body = group_lax_fn(spec[:-1])
    reduce_op = spec[-1][0]
    partial, fold = {
        "sum": (jnp.sum, jnp.add), "mean": (jnp.sum, jnp.add),
        "max": (jnp.max, jnp.maximum), "min": (jnp.min, jnp.minimum),
    }[reduce_op]

    def kernel(*refs):
        out_ref = refs[-1]
        part = partial(body(*_load_f32(refs[:-1])))
        if reduce_op == "mean":
            part = part * (1.0 / (r * c))
        part = part.astype(out_ref.dtype)
        first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

        @pl.when(first)
        def _init():
            out_ref[0, 0] = part

        @pl.when(jnp.logical_not(first))
        def _fold():
            out_ref[0, 0] = fold(out_ref[0, 0], part)

    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i, j: (i, j))
                  for _ in ext_avals],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), out_aval.dtype),
        interpret=interpret,
    )

    def run(*vals):
        flat = [jnp.reshape(v, (r, c)) for v in vals]
        return jnp.reshape(call(*flat), tuple(out_aval.shape))

    return run


_EMITTERS = {
    "elementwise": _elementwise_kernel,
    "scale_bias_act": _scale_bias_act_kernel,
    "reduction": _reduction_kernel,
}


# ------------------------------------------------- parity + calibration
def _seeded_inputs(ext_avals, digest):
    """Concrete parity inputs, seeded from the group digest: floats in
    [0.5, 1.5] (away from activation kinks and division zeros), small
    positive ints elsewhere."""
    rs = np.random.RandomState(int(digest[:8], 16) & 0x7FFFFFFF)
    out = []
    for s, d in ext_avals:
        if np.issubdtype(d, np.floating):
            out.append(rs.uniform(0.5, 1.5, s).astype(d))
        else:
            out.append(rs.randint(1, 5, s).astype(d))
    return out


def _parity_and_time(kernel_call, lax_call, ext_avals, digest, rtol):
    """(ok, kernel_s, lax_s): the built kernel's output vs its lax twin
    on seeded concrete inputs. Both callables are already jitted; the
    first call of each is the warm-up (and the parity sample), the
    second is the one timed, to completion."""
    import jax
    import jax.numpy as jnp

    ins = [jnp.asarray(a) for a in _seeded_inputs(ext_avals, digest)]

    def sample(fn):
        out = np.asarray(fn(*ins))
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ins))
        return out, time.perf_counter() - t0

    got, t_kernel = sample(kernel_call)
    want, t_lax = sample(lax_call)
    ok = (got.shape == want.shape
          and np.allclose(got.astype(np.float32),
                          want.astype(np.float32), rtol=rtol,
                          atol=PARITY_ATOL))
    return ok, t_kernel, t_lax


def _record_calibration(digest, platform, t_kernel, t_lax):
    """Measured kernel-vs-lax seconds into the CalibrationStore
    (kind="kernel" / "kernel_lax") — once per (group, platform,
    process). Advisory: failures never block a build."""
    key = (digest, platform)
    with _LOCK:
        if key in _CAL_RECORDED:
            return
        _CAL_RECORDED.add(key)
    try:
        from ..profiling import calibration_store

        store = calibration_store()
        store.record(digest, platform, "kernel", t_kernel)
        store.record(digest, platform, "kernel_lax", t_lax)
    except Exception:
        pass


def _tuned_choice(digest, platform):
    try:
        from .tuner import choose_fusion_kernel

        return choose_fusion_kernel(digest, platform)
    except Exception:
        return "pallas"


def _build_and_verify(spec, ext_avals, digest, template, platform):
    """Build one group's kernel for one shape signature: emit, compile
    it alone, verify that executable against the jitted lax twin, time
    both into calibration, wrap in custom_vjp. Returns ("ok",
    callable) or ("demoted", reason)."""
    import jax

    from .. import utils as _utils

    lax_fn = group_lax_fn(spec)
    structs = [jax.ShapeDtypeStruct(s, d) for s, d in ext_avals]
    try:
        out_aval = jax.eval_shape(lax_fn, *structs)
    except Exception:
        return ("demoted", "irregular_shapes")
    if (not all(np.issubdtype(d, np.floating) for _, d in ext_avals)
            or not np.issubdtype(np.dtype(out_aval.dtype), np.floating)):
        return ("demoted", "unsupported_dtype")
    try:
        kernel = _EMITTERS[template](spec, ext_avals, out_aval,
                                     _utils.pallas_interpret())
    except _Unsupported as e:
        return ("demoted", str(e))
    except Exception:
        return ("demoted", "irregular_shapes")
    try:
        compiled = jax.jit(kernel).lower(*structs).compile()
    except Exception as e:
        logging.getLogger(__name__).warning(
            "fusion group %s (%s): generated kernel refused by the "
            "compiler: %s", digest, template, e)
        return ("demoted", "compile_refused")
    try:
        ok, t_kernel, t_lax = _parity_and_time(
            compiled, jax.jit(lax_fn), ext_avals, digest,
            _parity_rtol(template, out_aval.dtype))
    except Exception:
        return ("demoted", "parity")
    with _LOCK:
        _COUNTS["parity_checks"] += 1
        if not ok:
            _COUNTS["parity_failures"] += 1
    if not ok:
        return ("demoted", "parity")
    _record_calibration(digest, platform, t_kernel, t_lax)
    with _LOCK:
        _COUNTS["kernels_built"] += 1
    return ("ok", _make_fused_callable(lax_fn, kernel))


# -------------------------------------------------------------- planning
@dataclass(frozen=True)
class CodegenPlan:
    """Executor routing for one optimized symbol: `skip` are node
    indices computed INSIDE a fused kernel, `fused` maps each group's
    output index to (callable, external (index, out_i) keys), and
    `cache_component` is the exec-cache key term recording every
    group's final decision."""

    skip: frozenset
    fused: dict
    cache_component: tuple


_EMPTY_PLAN = CodegenPlan(frozenset(), {}, ())


def _lower_group(graph, members, digest, cfg, platform, order,
                 shapes, dtypes):
    """Final per-group decision for one bind. Returns
    ("pallas", (callable, ext)) or ("fallback", reason)."""
    spec, ext = _group_spec(graph.nodes, members)
    if platform != "tpu" and not cfg.interpret:
        return ("fallback", "platform"), spec
    # MXNET_FUSION_INTERPRET forces the generated kernel even where
    # the store says lax wins (interpret-mode timings WOULD say that
    # everywhere — the flag exists to exercise the kernel path anyway)
    if not cfg.interpret and _tuned_choice(digest, platform) == "lax":
        return ("fallback", "calibrated_slower"), spec
    if shapes is None:
        return ("fallback", "irregular_shapes"), spec
    avals = []
    for src, oi in ext:
        s = shapes.get((order[src], oi))
        if s is None:
            return ("fallback", "irregular_shapes"), spec
        dt = np.dtype(dtypes.get((order[src], oi), np.float32))
        avals.append((tuple(int(d) for d in s), dt))
    template = _template_of(spec)
    key = (digest, tuple(avals), bool(cfg.interpret))
    with _LOCK:
        cached = _KERNELS.get(key)
    if cached is None:
        cached = _build_and_verify(spec, avals, digest, template,
                                   platform)
        with _LOCK:
            _KERNELS[key] = cached
    status, payload = cached
    if status != "ok":
        return ("fallback", payload), spec
    return ("pallas", (payload, ext)), spec


def plan_for(symbol, input_shapes=None):
    """Codegen plan for an OPTIMIZED (pipeline-stamped) symbol.

    `input_shapes` maps variable names to shapes (args + auxs — the
    executor's bind signature); without it every candidate falls back
    with reason "irregular_shapes". Node indices refer to
    `symbol._topo` order — identical to the executor's trace order and
    to `Graph.from_symbol`. The returned `cache_component` joins the
    exec-cache key, so a fused program and its fallback twin can never
    collide."""
    from ..symbol import _graph_infer, _topo
    from .ir import Graph

    graph = Graph.from_symbol(symbol)
    groups = _groups_in(graph.nodes)
    if not groups:
        return _EMPTY_PLAN
    import jax

    platform = jax.default_backend()
    cfg = codegen_config()
    order = _topo(symbol._outputs)
    shapes = dtypes = None
    if input_shapes:
        try:
            shapes, dtypes = _graph_infer(
                symbol._outputs,
                {k: tuple(v) for k, v in input_shapes.items()}, {},
                partial=True)
        except Exception:
            shapes = dtypes = None
    skip, fused, component = set(), {}, []
    for tag in sorted(groups):
        members = sorted(groups[tag])
        out = members[-1]
        stamp = graph.nodes[out].extra.get("__fusion_codegen__", "")
        if not cfg.enabled:
            # live check, independent of the stamp: optimize_for_bind
            # memoizes the stamped graph, so a candidate stamp may
            # predate the knob flip — the OFF switch must win anyway
            spec, ext = _group_spec(graph.nodes, members)
            digest = group_digest(spec, len(ext))
            decision = ("fallback", "disabled")
        elif stamp.startswith("candidate:"):
            digest = stamp[len("candidate:"):]
            decision, spec = _lower_group(
                graph, members, digest, cfg, platform, order, shapes,
                dtypes)
        else:
            spec, ext = _group_spec(graph.nodes, members)
            digest = group_digest(spec, len(ext))
            if stamp.startswith("fallback:"):
                decision = ("fallback", stamp[len("fallback:"):])
            else:
                # tagged by fusion_hints but never stamped (codegen
                # stage off in the pipeline spec): counted, not dropped
                decision = ("fallback", "unplanned")
        ops = [s[0] for s in spec]
        if decision[0] == "pallas":
            fn, ext_keys = decision[1]
            skip.update(members[:-1])
            fused[out] = (fn, tuple(ext_keys))
            component.append((tag, f"pallas:{digest}"))
            _note_group(digest, tag, ops, _template_of(spec), "pallas")
        else:
            component.append((tag, f"fallback:{decision[1]}"))
            _note_group(digest, tag, ops, _template_of(spec),
                        "fallback", decision[1])
    return CodegenPlan(frozenset(skip), fused, tuple(component))
