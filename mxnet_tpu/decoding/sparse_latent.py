"""A latent-attention decoder with learned sparse selection and routed
experts, served through the same engine as the dense block
(`model.DecoderConfig`): the second instance of the model contract.

What a token of a layer caches is ONE latent row shared by all heads
(`kv_lora_rank` normalised values + `qk_rope_head_dim` rotated key
values, plane `latent`) and ONE index key (`index_head_dim`, plane
`index_key`); both planes sit on the engine's one page table.

A layer, for queries at positions `pos` over the pages:

  q_latent   x^ = RMS(x); c_q = RMS(x^ W_qa); per head [q_nope, q_pe]
             = c_q W_qb, q_pe rotated (interleaved pairs); q_nope is
             taken through W_uk into latent space (the absorbed form);
             the indexer's q^I = c_q W^I_qb (first rope dims rotated,
             half-split pairs) and head weights w = x^ W^I_w / sqrt(J D)
  kv_latent  [c_kv, k_pe] = x^ W_kva; c = RMS(c_kv), k_pe rotated;
             k^I = LayerNorm(x^ W^I_k), first rope dims rotated
  kv_write   both planes, in place, through the page table
  index      I[t, s] = sum_j w[t, j] ReLU(q^I[t, j] . k^I[s]), s <= t,
             exact top-k (`attention.sparse_index_select`)
  attn       softmax over the selected rows only, scores and values in
             latent space (`attention.sparse_latent_attention`)
  out        through W_uv per head, then W_o
  mlp        SwiGLU (the leading dense layers), or
  router     sigmoid scores over ALL experts, a bias for choosing only,
             group-limited top-k, weights normalised and scaled
  experts    the terms of the experts HELD HERE (`experts_held`, a
             range of the router's outputs): one grouped computation
             over them for all rows, no row dropped. What the other
             experts would add is another chip's to compute
  shared     the shared expert, for every row

Positions are rotary with YaRN scaling (`yarn_freqs`). Weights live in
a flat {name: array} dict; `init_sparse_latent_params` builds a seeded
one for tests. Matrix products take their operands in the weights'
type and accumulate in float32; the residual stream, the norms, the
softmax and the router's scores are float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import attention as _attn
from . import quant as _quant
from .blocks import SCRATCH_PAGE, PageGroup
from .layers import held_experts, mm as _mm, rms as _rms, rotate, route, \
    step_output, swiglu as _swiglu
from .model import _pick_token

# queries of a chunk attended at a time: bounds the index scores
# (block x index heads x context, float32) and the gathered rows
QUERY_BLOCK = 32


@dataclass(frozen=True)
class SparseLatentConfig:
    """Architecture hyperparameters (static under jit). `vocab` is the
    slice of the vocabulary held here; `experts_held` = (first, count)
    is the range of the router's `n_experts` outputs whose experts
    this chip computes."""

    vocab: int = 64
    d_model: int = 64
    n_layers: int = 3
    n_dense_layers: int = 1
    n_heads: int = 4
    q_lora_rank: int = 32
    kv_lora_rank: int = 16
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    index_n_heads: int = 8
    index_head_dim: int = 16
    index_topk: int = 8
    d_ff: int = 128
    d_expert: int = 32
    n_experts: int = 16
    experts_held: tuple = (0, 16)
    experts_per_token: int = 2
    n_group: int = 4
    topk_group: int = 2
    routed_scale: float = 2.5
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rms_eps: float = 1e-6
    max_len: int = 163840
    eos_id: int = 1
    prefill_chunk: int = 512

    # ---- the model contract (see model.DecoderConfig) ----
    program_family = "sparse_latent_"
    step_counters = ("selected_tokens", "expert_rows", "experts_hit",
                     "expert_rows_max")
    page_groups = (PageGroup(),)

    @property
    def planes(self):
        return (_quant.Plane("latent",
                             self.kv_lora_rank + self.qk_rope_head_dim),
                _quant.Plane("index_key", self.index_head_dim))

    @property
    def softmax_scale(self):
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.max_len > self.rope_original_max_len:
            m = 0.1 * self.rope_mscale * math.log(self.rope_factor) + 1.0
            scale *= m * m
        return scale

    def decode_step(self, params, tokens, pools, page_table, lengths,
                    active, seeds=None, temps=None, top_ks=None,
                    top_ps=None, *, kernels=None, with_stats=False):
        return decode_forward(params, tokens, pools, page_table, lengths,
                              active, seeds, temps, top_ks, top_ps,
                              cfg=self, with_stats=with_stats)

    def chunk_step(self, params, tokens, start, length, pools, page_ids,
                   seed=None, temperature=None, top_k=None, top_p=None,
                   *, kernels=None):
        return chunk_prefill_forward(params, tokens, start, length, pools,
                                     page_ids, seed, temperature, top_k,
                                     top_p, cfg=self)

    def probe_step(self, params, tokens, pools, page_table, lengths,
                   active, *, kernels=None):
        return decode_probe(params, tokens, pools, page_table, lengths,
                            active, cfg=self)


# ------------------------------------------------------------- weights
def param_shapes(cfg):
    """{name: shape} of the flat params dict, the held share only."""
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    held = cfg.experts_held[1]
    s = {"embed": (cfg.vocab, d), "head": (d, cfg.vocab), "norm_f": (d,)}
    for i in range(cfg.n_layers):
        p = f"l{i}."
        s.update({
            p + "attn_norm": (d,), p + "ffn_norm": (d,),
            p + "wq_a": (d, cfg.q_lora_rank),
            p + "q_norm": (cfg.q_lora_rank,),
            p + "wq_b": (cfg.q_lora_rank, h * qk),
            p + "wkv_a": (d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
            p + "kv_norm": (cfg.kv_lora_rank,),
            p + "wkv_b": (cfg.kv_lora_rank,
                          h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            p + "wo": (h * cfg.v_head_dim, d),
            p + "idx_wq_b": (cfg.q_lora_rank,
                             cfg.index_n_heads * cfg.index_head_dim),
            p + "idx_wk": (d, cfg.index_head_dim),
            p + "idx_k_norm_g": (cfg.index_head_dim,),
            p + "idx_k_norm_b": (cfg.index_head_dim,),
            p + "idx_w": (d, cfg.index_n_heads),
        })
        if i < cfg.n_dense_layers:
            s.update({p + "w1": (d, cfg.d_ff), p + "w3": (d, cfg.d_ff),
                      p + "w2": (cfg.d_ff, d)})
        else:
            s.update({
                p + "gate": (d, cfg.n_experts),
                p + "gate_bias": (cfg.n_experts,),
                p + "experts_w1": (held, d, cfg.d_expert),
                p + "experts_w3": (held, d, cfg.d_expert),
                p + "experts_w2": (held, cfg.d_expert, d),
                p + "shared_w1": (d, cfg.d_expert),
                p + "shared_w3": (d, cfg.d_expert),
                p + "shared_w2": (cfg.d_expert, d)})
    return s


def init_sparse_latent_params(cfg, seed=0):
    """Seeded random float32 weights (explicit generator): matrices
    uniform(+-1/sqrt(fan_in)), gains 1, the router's choosing bias and
    the index key's LayerNorm bias uniform(+-0.01) — small, not zero,
    so that a forgotten bias shows."""
    rs = np.random.RandomState(seed)
    out = {}
    for name, shape in sorted(param_shapes(cfg).items()):
        if name.endswith(("gate_bias", "idx_k_norm_b")):
            out[name] = rs.uniform(-0.01, 0.01, shape).astype(np.float32)
        elif len(shape) == 1:
            out[name] = np.ones(shape, np.float32)
        else:
            scale = 1.0 / math.sqrt(shape[-2])
            out[name] = rs.uniform(-scale, scale, shape).astype(np.float32)
    return out


# ------------------------------------------------------------ positions
def yarn_freqs(cfg):
    """The rotary frequencies (qk_rope_head_dim / 2,) float32 with YaRN
    scaling: each is blended between f and f / factor by the linear
    ramp between the correction dims of beta_fast and beta_slow."""
    dim = cfg.qk_rope_head_dim
    freqs = 1.0 / (cfg.rope_theta
                   ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if cfg.max_len > cfg.rope_original_max_len:
        def correction_dim(rotations):
            return dim * math.log(cfg.rope_original_max_len
                                  / (rotations * 2 * math.pi)) \
                / (2 * math.log(cfg.rope_theta))

        low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
        high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        smooth = 1.0 - ramp
        freqs = freqs / cfg.rope_factor * (1 - smooth) + freqs * smooth
    return freqs.astype(np.float32)


# ---------------------------------------------------------------- pieces
def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g.astype(jnp.float32) \
        + b.astype(jnp.float32)


def _forward(params, tokens, pos, valid, pools, page_table, cfg,
             probe=False):
    """The trunk over queries tokens (B, T) at absolute positions pos
    (B, T): writes both planes of every valid query, attends each over
    its row's pages, returns (x (B, T, D) float32 before the final
    norm, pools, counters (4,) int32 as `cfg.step_counters`[, the
    selected positions (layers, B, T, k) when `probe`])."""
    latent, index = (_quant.as_pool(p) for p in pools)
    page_size = latent.page_size
    b, t = tokens.shape
    bp = page_table.shape[1]
    h, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    kvr, dv = cfg.kv_lora_rank, cfg.v_head_dim
    j, di = cfg.index_n_heads, cfg.index_head_dim
    eps = cfg.rms_eps
    freqs = jnp.asarray(yarn_freqs(cfg))
    valid = valid & (pos < bp * page_size)
    w_pages = jnp.where(
        valid, jnp.take_along_axis(
            page_table, jnp.clip(pos // page_size, 0, bp - 1), axis=1),
        SCRATCH_PAGE)
    slots = pos % page_size
    block = QUERY_BLOCK if t > QUERY_BLOCK and t % QUERY_BLOCK == 0 else t
    topk = min(cfg.index_topk, bp * page_size)
    counted = valid.reshape(-1)
    selected = jnp.sum(jnp.where(valid, jnp.minimum(pos + 1, topk), 0))
    routed = jnp.zeros((3,), jnp.int32)
    picks = []

    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.float32)
    for i in range(cfg.n_layers):
        p = f"l{i}."
        with jax.named_scope(f"l{i}"):
            xh = _rms(x, params[p + "attn_norm"], eps)
            with jax.named_scope("q_latent"):
                c_q = _rms(_mm(xh, params[p + "wq_a"]),
                           params[p + "q_norm"], eps)
                q = _mm(c_q, params[p + "wq_b"]).reshape(b, t, h, dn + dr)
                q_pe = rotate(q[..., dn:], pos, freqs, True)
                wkv_b = params[p + "wkv_b"].reshape(kvr, h, dn + dv)
                q_lat = jnp.einsum(
                    "bthn,chn->bthc", q[..., :dn].astype(wkv_b.dtype),
                    wkv_b[..., :dn], preferred_element_type=jnp.float32)
                q_cat = jnp.concatenate([q_lat, q_pe], axis=-1)
                q_idx = _mm(c_q, params[p + "idx_wq_b"]).reshape(
                    b, t, j, di)
                q_idx = jnp.concatenate(
                    [rotate(q_idx[..., :dr], pos, freqs, False),
                     q_idx[..., dr:]], axis=-1)
                w_idx = _mm(xh, params[p + "idx_w"]) * (j * di) ** -0.5
            with jax.named_scope("kv_latent"):
                kv = _mm(xh, params[p + "wkv_a"])
                row = jnp.concatenate(
                    [_rms(kv[..., :kvr], params[p + "kv_norm"], eps),
                     rotate(kv[..., kvr:], pos, freqs, True)], axis=-1)
                k_idx = _layer_norm(_mm(xh, params[p + "idx_wk"]),
                                    params[p + "idx_k_norm_g"],
                                    params[p + "idx_k_norm_b"], eps)
                k_idx = jnp.concatenate(
                    [rotate(k_idx[..., :dr], pos, freqs, False),
                     k_idx[..., dr:]], axis=-1)
            with jax.named_scope("kv_write"):
                latent, _ = _quant.kv_scatter(latent, i, w_pages, slots,
                                              row)
                index, _ = _quant.kv_scatter(index, i, w_pages, slots,
                                             k_idx)
            with jax.named_scope("index"):
                k_ctx = _quant.gather_plane(index.layer(i), page_table)
            lat_layer = latent.layer(i)

            def attend(args, k_ctx=k_ctx, lat_layer=lat_layer):
                q_idx_b, w_idx_b, q_cat_b, pos_b = args
                with jax.named_scope("index"):
                    sel = _attn.sparse_index_select(
                        q_idx_b, w_idx_b, k_ctx, pos_b, topk)
                with jax.named_scope("attn"):
                    o = _attn.sparse_latent_attention(
                        q_cat_b, lat_layer, page_table, sel, pos_b, kvr,
                        cfg.softmax_scale)
                return o, sel

            if block == t:
                o_lat, sel = attend((q_idx, w_idx, q_cat, pos))
            else:
                def split(a):
                    a = a.reshape((b, t // block, block) + a.shape[2:])
                    return jnp.moveaxis(a, 1, 0)

                def join(a):
                    a = jnp.moveaxis(a, 0, 1)
                    return a.reshape((b, t) + a.shape[3:])

                o_lat, sel = jax.lax.map(
                    attend, tuple(split(a) for a in
                                  (q_idx, w_idx, q_cat, pos)))
                o_lat, sel = join(o_lat), join(sel)
            if probe:
                picks.append(sel)
            with jax.named_scope("out"):
                o = jnp.einsum(
                    "bthc,chv->bthv", o_lat.astype(wkv_b.dtype),
                    wkv_b[..., dn:], preferred_element_type=jnp.float32)
                x = x + _mm(o.reshape(b, t, h * dv), params[p + "wo"])
            xh = _rms(x, params[p + "ffn_norm"], eps)
            if i < cfg.n_dense_layers:
                with jax.named_scope("mlp"):
                    x = x + _swiglu(xh, params[p + "w1"], params[p + "w3"],
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
            with jax.named_scope("shared"):
                y = y + _swiglu(flat, params[p + "shared_w1"],
                                params[p + "shared_w3"],
                                params[p + "shared_w2"])
            x = x + y.reshape(b, t, -1)
    counters = jnp.concatenate(
        [selected.astype(jnp.int32)[None], routed])
    if probe:
        return x, (latent, index), counters, jnp.stack(picks)
    return x, (latent, index), counters


def _logits(params, x, cfg):
    return _mm(_rms(x, params["norm_f"], cfg.rms_eps), params["head"])


# --------------------------------------------------------------- prefill
def chunk_prefill_forward(params, tokens, start, length, pools, page_ids,
                          seed=None, temperature=None, top_k=None,
                          top_p=None, *, cfg):
    """One chunk of a prompt through the pages: tokens (1, Tb) hold
    positions [start, start + Tb) of the prompt, of which those below
    `length` are real; positions below `start` already lie in
    `page_ids` (an earlier chunk's, or a prefix-cache hit's). Writes
    the chunk's rows, attends each query over the pages with its own
    causal bound, and returns (out (1 + counters,) int32, pools):
    out[0] is the token sampled after position length - 1 (the
    prompt's first answer token when this is its last chunk), the rest
    `cfg.step_counters`."""
    _, t = tokens.shape
    pos = (start + jnp.arange(t))[None]
    x, pools, counters = _forward(params, tokens, pos, pos < length,
                                  pools, page_ids[None], cfg)
    with jax.named_scope("logits"):
        logits = _logits(params, x[0, length - 1 - start], cfg)
    with jax.named_scope("sample"):
        tok = _pick_token(logits, seed, length, temperature, top_k, top_p)
    return jnp.concatenate([tok[None], counters]), pools


# ---------------------------------------------------------------- decode
def decode_logits(params, tokens, pools, page_table, lengths, active, *,
                  cfg, probe=False):
    """The decode-step body: each row's last token at position
    `lengths`, written and attended through the page table. Returns
    (logits (B, V) float32, pools, counters[, selected])."""
    res = _forward(params, tokens[:, None], lengths[:, None],
                   active[:, None], pools, page_table, cfg, probe=probe)
    with jax.named_scope("logits"):
        logits = _logits(params, res[0][:, 0], cfg)
    return (logits,) + res[1:]


def decode_forward(params, tokens, pools, page_table, lengths, active,
                   seeds=None, temps=None, top_ks=None, top_ps=None, *,
                   cfg, with_stats=False):
    """One decode step over the fixed-shape batch (the contract of
    `model.decode_forward`). Returns (out (B + counters,) int32,
    pools): out[:B] the next tokens, the rest `cfg.step_counters` —
    they come back in the step's one fetch. `with_stats` appends the
    numerics guard's [nonfinite rows, quant clips]."""
    logits, pools, counters = decode_logits(
        params, tokens, pools, page_table, lengths, active, cfg=cfg)
    return step_output(logits, counters, pools, lengths, active, seeds,
                       temps, top_ks, top_ps, with_stats)


def decode_probe(params, tokens, pools, page_table, lengths, active, *,
                 cfg):
    """A decode step that writes nothing back: (logits (B, V), the
    positions each row's query selected in each layer (layers, B, k),
    -1 where fewer than k tokens were in reach)."""
    logits, _pools, _c, sel = decode_logits(
        params, tokens, pools, page_table, lengths, active, cfg=cfg,
        probe=True)
    sel = sel[:, :, 0]
    return logits, jnp.where(sel <= lengths[None, :, None], sel, -1)
