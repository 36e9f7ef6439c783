"""Pieces of a layer that more than one block of the decode tier is
made of: the RMS norm, the matrix product in the weights' type, the
gated feed-forward, rotary positions, the router over all experts and
the held experts' grouped computation, the tail of a decode step that
counts. `sparse_latent` and
`window_mixed` both import them from here; all are pure jax on traced
values, inside the jitted chunk-prefill and decode programs.

The router and the experts read a configuration's `n_group`,
`topk_group`, `experts_per_token`, `routed_scale` and `experts_held`,
and the weights `l{i}.gate`, `l{i}.gate_bias` and
`l{i}.experts_w1|w3|w2`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .model import _sample_rows

_HI = jax.lax.Precision.HIGHEST


def rotate(x, pos, freqs, interleaved):
    """Rotary positions on the last axis of x (..., T, heads.., R) at
    `pos` (..., T): pairs (x0,x1),(x2,x3).. when `interleaved`, else
    the first half with the second. float32 in and out."""
    ang = pos[..., None].astype(jnp.float32) * freqs
    ang = ang.reshape(ang.shape[:-1] + (1,) * (x.ndim - ang.ndim)
                      + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    half = x.shape[-1] // 2
    if interleaved:
        a, b = x[..., 0::2], x[..., 1::2]
    else:
        a, b = x[..., :half], x[..., half:]
    ra, rb = a * cos - b * sin, a * sin + b * cos
    if interleaved:
        return jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    return jnp.concatenate([ra, rb], axis=-1)


def rms(x, g, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)


def mm(a, w):
    """a @ w, operands in the weights' type, float32 out."""
    return jnp.dot(a.astype(w.dtype), w,
                   preferred_element_type=jnp.float32)


def swiglu(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def route(params, i, xh, cfg):
    """The router over ALL experts for rows xh (N, D) float32:
    (chosen expert ids (N, k) int32, their weights (N, k) float32)."""
    n = xh.shape[0]
    s = jax.nn.sigmoid(jnp.dot(
        xh, params[f"l{i}.gate"].astype(jnp.float32), precision=_HI))
    biased = s + params[f"l{i}.gate_bias"].astype(jnp.float32)
    if cfg.n_group > 1:     # one group: a plain top-k over all experts
        groups = biased.reshape(n, cfg.n_group, -1)
        group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
        _, keep = jax.lax.top_k(group_score, cfg.topk_group)
        kept = jnp.any(keep[..., None] == jnp.arange(cfg.n_group), axis=1)
        biased = jnp.where(jnp.repeat(kept, groups.shape[-1], axis=1),
                           biased, -jnp.inf)
    _, chosen = jax.lax.top_k(biased, cfg.experts_per_token)
    w = jnp.take_along_axis(s, chosen, axis=1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale
    return chosen.astype(jnp.int32), w


def held_experts(params, i, xh, chosen, weights, counted, cfg):
    """The held experts' part of the expert layer for rows xh (N, D):
    sum over held e of weight[n, e] * E_e(xh[n]), one grouped
    computation over all held experts and all rows. Returns (out
    (N, D) float32, [assignments, experts hit, busiest expert's rows]
    int32 over the rows `counted` (N,) bool)."""
    first, held = cfg.experts_held
    hit = chosen[..., None] == (first + jnp.arange(held))   # (N, k, E)
    comb = jnp.sum(jnp.where(hit, weights[..., None], 0.0), axis=1)
    w1, w3, w2 = (params[f"l{i}.experts_{n}"] for n in ("w1", "w3", "w2"))
    x = xh.astype(w1.dtype)
    h = jax.nn.silu(jnp.einsum("nd,edf->enf", x, w1,
                               preferred_element_type=jnp.float32)) \
        * jnp.einsum("nd,edf->enf", x, w3,
                     preferred_element_type=jnp.float32)
    h = h * comb.T[..., None]
    out = jnp.einsum("enf,efd->nd", h.astype(w2.dtype), w2,
                     preferred_element_type=jnp.float32)
    rows = jnp.sum(jnp.any(hit, axis=1) & counted[:, None], axis=0)
    stats = jnp.stack([jnp.sum(rows), jnp.sum(rows > 0), jnp.max(rows)])
    return out, stats.astype(jnp.int32)


def step_output(logits, counters, pools, lengths, active, seeds, temps,
                top_ks, top_ps, with_stats):
    """What a counting block's decode step returns from its logits
    (B, V): (out (B + counters,) int32, pools) — out[:B] each row's next
    token, drawn on its (seed, position) stream, the rest the block's
    `step_counters`, so that they come back in the step's one fetch.
    `with_stats` appends the numerics guard's [nonfinite rows, quant
    clips]."""
    with jax.named_scope("sample"):
        next_tokens = _sample_rows(logits, seeds, lengths + 1, temps,
                                   top_ks, top_ps)
    out = jnp.concatenate([next_tokens, counters])
    if with_stats:
        bad = jnp.any(~jnp.isfinite(logits), axis=-1)
        guard = jnp.stack([jnp.sum((active & bad).astype(jnp.int32)),
                           jnp.int32(0)])
        return out, pools, guard
    return out, pools
