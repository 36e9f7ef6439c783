"""A decoder that mixes sliding-window and full attention layers, each
kind with its own KV head count, and routed experts, served through the
same engine as the other blocks: the third instance of the model
contract (`model.DecoderConfig`).

What a token caches depends on the layer's kind. A FULL layer keeps a
K row of `kv_heads * head_dim` and a V row of `kv_heads * v_head_dim`
for as long as the request lives (planes `k`, `v`, page group `full`);
a WINDOW layer keeps rows of `window_kv_heads` heads (planes `k_win`,
`v_win`, page group `window`) that only the next `window` queries read,
so the group's pages behind a row's window go back to the allocator
while the row decodes (`blocks.PageGroup`). Each plane covers the
layers of its kind alone; layer i's index in its plane is the count of
earlier layers of the same kind.

A layer, for queries at positions `pos` over the pages:

  qkv        x^ = RMS(x); [q | k | v] = x^ W_qkv (one fused product):
             H query heads and the layer's kind's KV heads of
             `head_dim`, values of `v_head_dim` scaled by `value_scale`;
             rotary on the first `rotary_dim` dimensions of every q and
             k head (half-split pairs), base `rope_theta` in full
             layers and `window_rope_theta` in window layers
  kv_write   both planes of the layer's kind, in place, through that
             group's page table
  attn       full layers: query head j against KV head j // (H / kv),
             every earlier position, scores q.k / sqrt(head_dim)
  attn_window  window layers: the same over the last `window`
             positions, with a learned logit a head (`sink`) in the
             softmax's denominator
  out        concat(heads) W_o
  mlp        gated feed-forward (layers whose `expert_layers` entry
             is 0), or
  router     sigmoid scores over ALL experts, a bias for choosing only,
             a plain top-k, weights normalised (`layers.route`)
  experts    the terms of the experts HELD HERE (`layers.held_experts`);
             there is no shared expert: a token none of whose experts
             is held gets a zero feed-forward term

A decode step attends through the engine's single-query paged attention
(`kernels.attn`: the in-place kernel on a TPU), a prompt's chunk through
the multi-query form (`kernels.attn_multi`), both with `kv_heads`,
`window` and `sink`. Weights live in a flat {name: array} dict;
`init_window_mixed_params` builds a seeded one for tests. Matrix
products take their operands in the weights' type and accumulate in
float32; the residual stream, the norms, the rotary angles, the softmax
and the router's scores are float32; queries are rounded to the pool's
type for the scores, as the cached keys are.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import quant as _quant
from .blocks import SCRATCH_PAGE, PageGroup
from .layers import held_experts, mm, rms, rotate, route, step_output, \
    swiglu
from .model import _pick_token


@dataclass(frozen=True)
class WindowMixedConfig:
    """Architecture hyperparameters (static under jit). `layer_pattern`
    has one entry a layer, 1 for a window layer and 0 for a full one;
    `expert_layers` likewise, 1 for routed experts and 0 for the dense
    feed-forward. `vocab` is the slice of the vocabulary held here;
    `experts_held` = (first, count) is the range of the router's
    `n_experts` outputs whose experts this chip computes."""

    vocab: int = 64
    d_model: int = 64
    n_heads: int = 8
    head_dim: int = 12
    v_head_dim: int = 8
    kv_heads: int = 2
    window_kv_heads: int = 4
    window: int = 8
    layer_pattern: tuple = (0, 1, 1, 1, 1, 0, 1)
    expert_layers: tuple = (0, 1, 1, 1, 1, 1, 1)
    rotary_dim: int = 4
    rope_theta: float = 1e7
    window_rope_theta: float = 1e4
    value_scale: float = 0.707
    d_ff: int = 128
    d_expert: int = 32
    n_experts: int = 16
    experts_held: tuple = (0, 16)
    experts_per_token: int = 2
    rms_eps: float = 1e-5
    max_len: int = 1048576
    eos_id: int = 1
    prefill_chunk: int = 512

    # ---- the model contract (see model.DecoderConfig) ----
    program_family = "window_mixed_"
    step_counters = ("expert_rows", "experts_hit", "expert_rows_max")
    # the router's (`layers.route`): one group, weights not rescaled
    n_group = 1
    topk_group = 1
    routed_scale = 1.0

    @property
    def n_layers(self):
        return len(self.layer_pattern)

    @property
    def page_groups(self):
        return (PageGroup("full"), PageGroup("window", self.window))

    @property
    def planes(self):
        n_win = sum(self.layer_pattern)
        n_full = self.n_layers - n_win
        kv, kvw = self.kv_heads, self.window_kv_heads
        return (
            _quant.Plane("k", kv * self.head_dim, kv, n_full, "full"),
            _quant.Plane("v", kv * self.v_head_dim, kv, n_full, "full"),
            _quant.Plane("k_win", kvw * self.head_dim, kvw, n_win,
                         "window"),
            _quant.Plane("v_win", kvw * self.v_head_dim, kvw, n_win,
                         "window"))

    def kv_heads_of(self, i):
        return self.window_kv_heads if self.layer_pattern[i] \
            else self.kv_heads

    def decode_step(self, params, tokens, pools, page_table, lengths,
                    active, seeds=None, temps=None, top_ks=None,
                    top_ps=None, *, kernels, with_stats=False):
        return decode_forward(params, tokens, pools, page_table, lengths,
                              active, seeds, temps, top_ks, top_ps,
                              cfg=self, kernels=kernels,
                              with_stats=with_stats)

    def chunk_step(self, params, tokens, start, length, pools, page_ids,
                   seed=None, temperature=None, top_k=None, top_p=None,
                   *, kernels):
        return chunk_prefill_forward(params, tokens, start, length, pools,
                                     page_ids, seed, temperature, top_k,
                                     top_p, cfg=self, kernels=kernels)

    def probe_step(self, params, tokens, pools, page_table, lengths,
                   active, *, kernels):
        """(logits, None: the block attends every position in reach)
        of a decode step that writes nothing back."""
        logits, _pools, _c = decode_logits(
            params, tokens, pools, page_table, lengths, active, cfg=self,
            kernels=kernels)
        return logits, None


# ------------------------------------------------------------- weights
def param_shapes(cfg):
    """{name: shape} of the flat params dict, the held share only."""
    d, h = cfg.d_model, cfg.n_heads
    held = cfg.experts_held[1]
    s = {"embed": (cfg.vocab, d), "head": (d, cfg.vocab), "norm_f": (d,)}
    for i in range(cfg.n_layers):
        p = f"l{i}."
        kv = cfg.kv_heads_of(i)
        s.update({
            p + "attn_norm": (d,), p + "ffn_norm": (d,),
            p + "wqkv": (d, (h + kv) * cfg.head_dim + kv * cfg.v_head_dim),
            p + "wo": (h * cfg.v_head_dim, d)})
        if cfg.layer_pattern[i]:
            s[p + "sink"] = (h,)
        if cfg.expert_layers[i]:
            s.update({
                p + "gate": (d, cfg.n_experts),
                p + "gate_bias": (cfg.n_experts,),
                p + "experts_w1": (held, d, cfg.d_expert),
                p + "experts_w3": (held, d, cfg.d_expert),
                p + "experts_w2": (held, cfg.d_expert, d)})
        else:
            s.update({p + "w1": (d, cfg.d_ff), p + "w3": (d, cfg.d_ff),
                      p + "w2": (cfg.d_ff, d)})
    return s


def init_window_mixed_params(cfg, seed=0):
    """Seeded random float32 weights (explicit generator): matrices
    uniform(+-1/sqrt(fan_in)), gains 1, the router's choosing bias
    uniform(+-0.01) and the sinks uniform(+-1) — not zero, so that a
    forgotten one shows."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(param_shapes(cfg).items()):
        if name.endswith("gate_bias"):
            out[name] = rs.uniform(-0.01, 0.01, shape).astype(np.float32)
        elif name.endswith("sink"):
            out[name] = rs.uniform(-1.0, 1.0, shape).astype(np.float32)
        elif len(shape) == 1:
            out[name] = np.ones(shape, np.float32)
        else:
            scale = 1.0 / math.sqrt(shape[-2])
            out[name] = rs.uniform(-scale, scale, shape).astype(np.float32)
    return out


def rotary_freqs(cfg, window_layer):
    """The rotary frequencies (rotary_dim / 2,) float32 of a layer's
    kind: the base differs between window and full layers."""
    theta = cfg.window_rope_theta if window_layer else cfg.rope_theta
    dim = cfg.rotary_dim
    return (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64)
                            / dim)).astype(np.float32)


def _rotary(x, pos, freqs):
    """Rotary on the first `2 * len(freqs)` dimensions of every head of
    x (B, T, heads, D), half-split pairs; the rest untouched."""
    r = 2 * freqs.shape[0]
    return jnp.concatenate(
        [rotate(x[..., :r], pos, freqs, False), x[..., r:]], axis=-1)


# ----------------------------------------------------------------- trunk
def _forward(params, tokens, pos, valid, pools, page_table, cfg, kernels):
    """The trunk over queries tokens (B, T) at absolute positions pos
    (B, T): writes the planes of every valid query, attends each over
    its row's pages, returns (x (B, T, D) float32 before the final
    norm, pools, counters (3,) int32 as `cfg.step_counters`).
    `page_table` (2, B, Bp) holds the full and the window group's
    tables. One query a row (T == 1) is a decode step and attends
    through `kernels.attn`; a chunk through `kernels.attn_multi`."""
    pools = [_quant.as_pool(p) for p in pools]
    page_size = pools[0].page_size
    b, t = tokens.shape
    bp = page_table.shape[-1]
    h, dk, dv = cfg.n_heads, cfg.head_dim, cfg.v_head_dim
    eps = cfg.rms_eps
    valid = valid & (pos < bp * page_size)
    at = jnp.clip(pos // page_size, 0, bp - 1)
    w_pages = [jnp.where(valid, jnp.take_along_axis(tbl, at, axis=1),
                         SCRATCH_PAGE) for tbl in page_table]
    slots = pos % page_size
    # an inactive row attends nothing (the kernel returns zeros for it)
    ctx_len = jnp.where(valid[:, 0], pos[:, 0] + 1, 0)
    counted = valid.reshape(-1)
    routed = jnp.zeros((3,), jnp.int32)
    seen = [0, 0]                       # layers of each kind so far

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    for i, windowed in enumerate(cfg.layer_pattern):
        p = f"l{i}."
        kv = cfg.kv_heads_of(i)
        j = seen[windowed]
        seen[windowed] += 1
        with jax.named_scope(f"l{i}"):
            with jax.named_scope("qkv"):
                xh = rms(x, params[p + "attn_norm"], eps)
                qkv = mm(xh, params[p + "wqkv"])
                freqs = jnp.asarray(rotary_freqs(cfg, windowed))
                q = _rotary(qkv[..., :h * dk].reshape(b, t, h, dk), pos,
                            freqs)
                k = _rotary(qkv[..., h * dk:(h + kv) * dk].reshape(
                    b, t, kv, dk), pos, freqs).reshape(b, t, kv * dk)
                v = qkv[..., (h + kv) * dk:] * cfg.value_scale
            with jax.named_scope("kv_write"):
                kp, vp = pools[2 * windowed], pools[2 * windowed + 1]
                kp, _ = _quant.kv_scatter(kp, j, w_pages[windowed], slots,
                                          k)
                vp, _ = _quant.kv_scatter(vp, j, w_pages[windowed], slots,
                                          v)
                pools[2 * windowed], pools[2 * windowed + 1] = kp, vp
            how = {"kv_heads": kv}
            if windowed:
                how.update(window=cfg.window, sink=params[p + "sink"])
            q = q.astype(kp.data.dtype)
            with jax.named_scope("attn_window" if windowed else "attn"):
                if t == 1:
                    o = kernels.attn(q[:, 0], kp.layer(j), vp.layer(j),
                                     page_table[windowed], ctx_len, **how)
                else:
                    o = kernels.attn_multi(q, kp.layer(j), vp.layer(j),
                                           page_table[windowed], pos, **how)
            with jax.named_scope("out"):
                x = x + mm(o.reshape(b, t, h * dv), params[p + "wo"])
            xh = rms(x, params[p + "ffn_norm"], eps)
            if not cfg.expert_layers[i]:
                with jax.named_scope("mlp"):
                    x = x + swiglu(xh, params[p + "w1"], params[p + "w3"],
                                   params[p + "w2"])
                continue
            flat = xh.reshape(b * t, -1)
            with jax.named_scope("router"):
                chosen, weights = route(params, i, flat, cfg)
            with jax.named_scope("experts"):
                y, stats = held_experts(params, i, flat, chosen, weights,
                                        counted, cfg)
                routed = jnp.stack([routed[0] + stats[0],
                                    routed[1] + stats[1],
                                    jnp.maximum(routed[2], stats[2])])
            x = x + y.reshape(b, t, -1)
    return x, tuple(pools), routed


def _logits(params, x, cfg):
    return mm(rms(x, params["norm_f"], cfg.rms_eps), params["head"])


# --------------------------------------------------------------- prefill
def chunk_prefill_forward(params, tokens, start, length, pools, page_ids,
                          seed=None, temperature=None, top_k=None,
                          top_p=None, *, cfg, kernels):
    """One chunk of a prompt through the pages (the contract of
    `sparse_latent.chunk_prefill_forward`): tokens (1, Tb) hold
    positions [start, start + Tb), real below `length`; `page_ids`
    (2, Bp) are the row's full and window tables, the window's holding
    the pages of this chunk and of the window before it. Returns (out
    (1 + counters,) int32, pools)."""
    _, t = tokens.shape
    pos = (start + jnp.arange(t))[None]
    x, pools, counters = _forward(params, tokens, pos, pos < length, pools,
                                  page_ids[:, None], cfg, kernels)
    with jax.named_scope("logits"):
        logits = _logits(params, x[0, length - 1 - start], cfg)
    with jax.named_scope("sample"):
        tok = _pick_token(logits, seed, length, temperature, top_k, top_p)
    return jnp.concatenate([tok[None], counters]), pools


# ---------------------------------------------------------------- decode
def decode_logits(params, tokens, pools, page_table, lengths, active, *,
                  cfg, kernels):
    """The decode-step body: each row's last token at position
    `lengths`, written and attended through the two page tables.
    Returns (logits (B, V) float32, pools, counters)."""
    x, pools, counters = _forward(
        params, tokens[:, None], lengths[:, None], active[:, None], pools,
        page_table, cfg, kernels)
    with jax.named_scope("logits"):
        logits = _logits(params, x[:, 0], cfg)
    return logits, pools, counters


def decode_forward(params, tokens, pools, page_table, lengths, active,
                   seeds=None, temps=None, top_ks=None, top_ps=None, *,
                   cfg, kernels, with_stats=False):
    """One decode step over the fixed-shape batch (the contract of
    `model.decode_forward`). Returns (out (B + counters,) int32,
    pools): out[:B] the next tokens, the rest `cfg.step_counters`.
    `with_stats` appends the numerics guard's [nonfinite rows, quant
    clips]."""
    logits, pools, counters = decode_logits(
        params, tokens, pools, page_table, lengths, active, cfg=cfg,
        kernels=kernels)
    return step_output(logits, counters, pools, lengths, active, seeds,
                       temps, top_ks, top_ps, with_stats)
