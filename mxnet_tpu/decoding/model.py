"""A minimal autoregressive decoder with paged-KV decode semantics.

The decode tier needs a model contract, not a model zoo: something
with an embedding, a stack of attention+MLP blocks, and tied-logits
output, expressed as THREE pure functions over one params dict —

  reference_logits   dense causal forward over a whole (1, T) buffer
                     (the unbatched reference arm of the parity gate)
  prefill_forward    dense causal forward over a padded prompt that
                     also SCATTERS per-layer K/V into the paged pool
                     and returns the first generated token
  decode_forward     one fixed-shape decode step: embed the last
                     token of every row, append its K/V to the pool
                     through the page table, attend over the pages,
                     return each row's next greedy token

All three share the same per-row arithmetic (row-invariant matmuls,
length-masked softmax over seq-ordered pages), so a token decoded in
a continuous batch is bit-identical to the same token decoded alone —
the property ci/check_decode.py gates on.

Weights live in a flat {name: array} dict (mx checkpoint idiom);
`init_decoder_params` builds a seeded random one for tests/benches.
Real checkpoints with matching names serve unchanged.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import quant as _quant
from . import sampling as _sampling
from .blocks import SCRATCH_PAGE, PageGroup

NEG_INF = -1e30


@dataclass(frozen=True)
class DecoderConfig:
    """Architecture hyperparameters (static under jit)."""

    vocab: int = 64
    d_model: int = 32
    n_layers: int = 2
    n_heads: int = 2
    d_ff: int = 64
    max_len: int = 256
    eos_id: int = 1

    @property
    def head_dim(self):
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide into n_heads")
        return self.d_model // self.n_heads

    # ---- the model contract: what DecodeEngine takes from a
    # configuration instead of naming one block. `planes` is what a
    # token of a layer stores in the page pool; `program_family`
    # prefixes the compiled modules' names; `prefill_chunk` is None
    # where a whole prompt is one dense program (with the tail program
    # for prefix-cache hits), or the most tokens of one chunk where a
    # prompt goes through the pages in chunks (`chunk_step`);
    # `step_counters` names the int32 values a step's output carries
    # after its tokens. `kernels` is the engine's choice of paged
    # attention (`attn`, `attn_multi`), for a block that uses it.
    # `page_groups` are the page tables a sequence has: one, for every
    # plane and layer, unless layers keep different positions (a
    # `blocks.PageGroup` each, named by the planes it addresses; the
    # step functions then take the tables stacked, (groups, ..)).
    program_family = ""
    step_counters = ()
    prefill_chunk = None
    page_groups = (PageGroup(),)

    @property
    def planes(self):
        return (_quant.Plane("k", self.d_model, self.n_heads),
                _quant.Plane("v", self.d_model, self.n_heads))

    def decode_step(self, params, tokens, pools, page_table, lengths,
                    active, seeds=None, temps=None, top_ks=None,
                    top_ps=None, *, kernels, with_stats=False):
        res = decode_forward(params, tokens, pools[0], pools[1],
                             page_table, lengths, active, seeds, temps,
                             top_ks, top_ps, cfg=self, attn=kernels.attn,
                             with_stats=with_stats)
        return (res[0], res[1:3]) + res[3:]

    def prefill_step(self, params, tokens, length, pools, page_ids,
                     seed=None, temperature=None, top_k=None, top_p=None,
                     *, kernels, attn_fn=None):
        tok, k, v = prefill_forward(params, tokens, length, pools[0],
                                    pools[1], page_ids, seed, temperature,
                                    top_k, top_p, cfg=self,
                                    attn_fn=attn_fn)
        return tok, (k, v)

    def probe_step(self, params, tokens, pools, page_table, lengths,
                   active, *, kernels):
        """(logits, what each row selected: None, this block attends
        every cached token) of a decode step that writes nothing."""
        logits, _k, _v, _c = decode_logits(
            params, tokens, pools[0], pools[1], page_table, lengths,
            active, cfg=self, attn=kernels.attn)
        return logits, None

    def chunk_step(self, params, tokens, start, length, pools, page_ids,
                   seed=None, temperature=None, top_k=None, top_p=None,
                   *, kernels):
        tok, k, v = tail_prefill_forward(
            params, tokens, start, length, pools[0], pools[1], page_ids,
            seed, temperature, top_k, top_p, cfg=self,
            attn_multi=kernels.attn_multi)
        return tok, (k, v)


def init_decoder_params(cfg, seed=0):
    """Seeded random weights (explicit generator: MX005-clean)."""
    rs = np.random.RandomState(seed)

    def w(*shape):
        scale = 1.0 / math.sqrt(shape[0])
        return (rs.uniform(-scale, scale, shape)).astype(np.float32)

    params = {
        "embed": w(cfg.vocab, cfg.d_model),
        "pos": w(cfg.max_len, cfg.d_model) * 0.1,
        "ln_f": np.ones((cfg.d_model,), np.float32),
    }
    for i in range(cfg.n_layers):
        params[f"l{i}.ln1"] = np.ones((cfg.d_model,), np.float32)
        params[f"l{i}.ln2"] = np.ones((cfg.d_model,), np.float32)
        for nm in ("wq", "wk", "wv", "wo"):
            params[f"l{i}.{nm}"] = w(cfg.d_model, cfg.d_model)
        params[f"l{i}.w1"] = w(cfg.d_model, cfg.d_ff)
        params[f"l{i}.w2"] = w(cfg.d_ff, cfg.d_model)
    return params


def _rms(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6) * g


def _qkv(params, i, x, cfg):
    """(..., D) -> q/k/v each (..., H, Dh)."""
    h, dh = cfg.n_heads, cfg.head_dim
    shape = x.shape[:-1] + (h, dh)
    q = (x @ params[f"l{i}.wq"]).reshape(shape)
    k = (x @ params[f"l{i}.wk"]).reshape(shape)
    v = (x @ params[f"l{i}.wv"]).reshape(shape)
    return q, k, v


def _mlp(params, i, x):
    return jax.nn.relu(x @ params[f"l{i}.w1"]) @ params[f"l{i}.w2"]


def _dense_causal_attention(q, k, v, scale):
    """(B, T, H, Dh) causal attention, fp32 softmax."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    w = e / e.sum(axis=-1, keepdims=True)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------- reference
def reference_logits(params, tokens, cfg, attn_fn=None):
    """Dense causal forward: tokens (B, T) int32 -> logits (B, T, V).

    `attn_fn(q, k, v)` overrides the attention (the ring-attention
    prefill path routes through here with a sharded implementation);
    default is the in-process dense kernel.
    """
    scale = 1.0 / math.sqrt(cfg.head_dim)
    b, t = tokens.shape
    x = params["embed"][tokens] + params["pos"][:t][None]
    for i in range(cfg.n_layers):
        h1 = _rms(x, params[f"l{i}.ln1"])
        q, k, v = _qkv(params, i, h1, cfg)
        if attn_fn is None:
            o = _dense_causal_attention(q, k, v, scale)
        else:
            o = attn_fn(q, k, v)
        x = x + o.reshape(b, t, cfg.d_model) @ params[f"l{i}.wo"]
        x = x + _mlp(params, i, _rms(x, params[f"l{i}.ln2"]))
    x = _rms(x, params["ln_f"])
    return x @ params["embed"].T


# -------------------------------------------------------------- sampling
def _pick_token(logits, seed, position, temperature, top_k, top_p):
    """Single-position token choice: greedy argmax when no sampling
    params were threaded through (seed None — the PR 8 call shape) or
    the request's temperature is 0, else the counter-keyed sampler
    (sampling.sample_token); the branch is taken on the device."""
    if seed is None:
        return jnp.argmax(logits).astype(jnp.int32)
    return jax.lax.cond(
        temperature > 0.0,
        lambda lg: _sampling.sample_token(lg, seed, position, temperature,
                                          top_k, top_p),
        lambda lg: jnp.argmax(lg).astype(jnp.int32), logits)


def _sample_rows(logits, seeds, positions, temps, top_ks, top_ps):
    """Each row's next token: argmax without sampling arrays, else
    drawn per row on its (seed, position) stream
    (sampling.sample_rows)."""
    if seeds is None:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return _sampling.sample_rows(logits, seeds, positions, temps, top_ks,
                                 top_ps)


# --------------------------------------------------------------- prefill
def prefill_forward(params, tokens, length, k_pages, v_pages,
                    page_ids, seed=None, temperature=None, top_k=None,
                    top_p=None, *, cfg, attn_fn=None):
    """Prompt pass: tokens (1, Tb) padded to a length bucket, length
    () int32 the true prompt length, page_ids (ceil(Tb/P),) int32 the
    sequence's allocated pages (padded with scratch 0).

    Scatters every layer's K/V for positions < length into the pool
    (positions >= length land in the scratch page) and returns
    (first_token (), k_pages, v_pages). The first token is sampled on
    the (seed, position=length) stream when sampling params are
    given, greedy argmax otherwise.
    """
    scale = 1.0 / math.sqrt(cfg.head_dim)
    k_pages = _quant.as_pool(k_pages)
    v_pages = _quant.as_pool(v_pages)
    page_size = k_pages.page_size
    _, t = tokens.shape
    pos = jnp.arange(t)
    # per-position scatter targets: (page, slot) through the table,
    # scratch for the padded tail
    tgt_pages = jnp.where(length > pos, page_ids[pos // page_size],
                          SCRATCH_PAGE)
    slots = pos % page_size

    with jax.named_scope("embed"):
        x = params["embed"][tokens] + params["pos"][:t][None]
    for i in range(cfg.n_layers):
        with jax.named_scope(f"l{i}"):
            with jax.named_scope("qkv"):
                h1 = _rms(x, params[f"l{i}.ln1"])
                q, k, v = _qkv(params, i, h1, cfg)
            with jax.named_scope("kv_write"):
                k_pages, _ = _quant.kv_scatter(k_pages, i, tgt_pages,
                                               slots, k[0])
                v_pages, _ = _quant.kv_scatter(v_pages, i, tgt_pages,
                                               slots, v[0])
            with jax.named_scope("attn"):
                if attn_fn is None:
                    o = _dense_causal_attention(q, k, v, scale)
                else:
                    o = attn_fn(q, k, v)
            with jax.named_scope("out"):
                x = x + o.reshape(1, t, cfg.d_model) @ params[f"l{i}.wo"]
            with jax.named_scope("mlp"):
                x = x + _mlp(params, i, _rms(x, params[f"l{i}.ln2"]))
    with jax.named_scope("logits"):
        x = _rms(x, params["ln_f"])
        last = x[0, length - 1]
        logits = last @ params["embed"].T
    with jax.named_scope("sample"):
        tok = _pick_token(logits, seed, length, temperature, top_k,
                          top_p)
    return tok, k_pages, v_pages


# ---------------------------------------------------- prefix-cache tail
def tail_prefill_forward(params, tokens, start, length, k_pages,
                         v_pages, page_ids, seed=None, temperature=None,
                         top_k=None, top_p=None, *, cfg, attn_multi):
    """Tail-only prompt pass for a prefix-cache hit: positions
    [0, start) already live in shared pages (K/V is a pure function of
    the token prefix from position 0, so pages cached for one sequence
    are exact for any sequence with the same prefix); only the tail
    [start, length) is computed here.

    tokens (1, Tb) holds the TAIL tokens padded to a length bucket;
    start/length are () int32 (absolute); page_ids covers the FULL
    table padded to the engine's largest bucket (one static shape per
    tail bucket). Each layer scatters the tail K/V into its pages and
    attends the tail queries over the context gathered from pages
    (shared prefix + just-written tail) with per-query causal masks —
    FLOPs scale with tail x context instead of prompt^2.
    """
    k_pages = _quant.as_pool(k_pages)
    v_pages = _quant.as_pool(v_pages)
    page_size = k_pages.page_size
    _, t = tokens.shape
    cap = page_ids.shape[0] * page_size
    pos = start + jnp.arange(t)                      # absolute
    valid = (pos < length) & (pos < cap)
    tgt_pages = jnp.where(
        valid, page_ids[jnp.clip(pos // page_size, 0,
                                 page_ids.shape[0] - 1)], SCRATCH_PAGE)
    slots = pos % page_size
    pos_safe = jnp.clip(pos, 0, cfg.max_len - 1)

    with jax.named_scope("embed"):
        x = params["embed"][tokens] + params["pos"][pos_safe][None]
    for i in range(cfg.n_layers):
        with jax.named_scope(f"l{i}"):
            with jax.named_scope("qkv"):
                h1 = _rms(x, params[f"l{i}.ln1"])
                q, k, v = _qkv(params, i, h1, cfg)
            with jax.named_scope("kv_write"):
                k_pages, _ = _quant.kv_scatter(k_pages, i, tgt_pages,
                                               slots, k[0])
                v_pages, _ = _quant.kv_scatter(v_pages, i, tgt_pages,
                                               slots, v[0])
            with jax.named_scope("attn"):
                o = attn_multi(q, k_pages.layer(i), v_pages.layer(i),
                               page_ids[None], pos_safe[None])
            with jax.named_scope("out"):
                x = x + o.reshape(1, t, cfg.d_model) @ params[f"l{i}.wo"]
            with jax.named_scope("mlp"):
                x = x + _mlp(params, i, _rms(x, params[f"l{i}.ln2"]))
    with jax.named_scope("logits"):
        x = _rms(x, params["ln_f"])
        last = x[0, length - 1 - start]
        logits = last @ params["embed"].T
    with jax.named_scope("sample"):
        tok = _pick_token(logits, seed, length, temperature, top_k,
                          top_p)
    return tok, k_pages, v_pages


# ---------------------------------------------------------------- decode
def decode_logits(params, tokens, k_pages, v_pages, page_table,
                  lengths, active, *, cfg, attn):
    """The shared decode-step body: embed each row's last token, append
    its K/V at index `lengths` through the page table, attend over the
    pages, return (logits (B, V), k_pages, v_pages, clips). `clips` is
    the summed dequant-overflow clip count of this step's quantized
    K/V writes (always 0 for float pools — and for healthy int8 ones;
    see quant.quantize_values). decode_forward and the speculative
    draft proposer both build on this."""
    k_pages = _quant.as_pool(k_pages)
    v_pages = _quant.as_pool(v_pages)
    page_size = k_pages.page_size
    b = tokens.shape[0]
    bp = page_table.shape[1]
    rows = jnp.arange(b)
    in_cap = lengths < bp * page_size
    w_pages = jnp.where(
        active & in_cap,
        page_table[rows, jnp.clip(lengths // page_size, 0, bp - 1)],
        SCRATCH_PAGE)
    slots = lengths % page_size
    # an inactive slot attends nothing: the in-place kernel starts no
    # copy for it and returns zeros, the lax form the mean of what it
    # gathered (finite); the engine discards the row either way
    ctx_len = jnp.where(active, lengths + 1, 0)

    clips = jnp.int32(0)
    with jax.named_scope("embed"):
        x = params["embed"][tokens] + params["pos"][
            jnp.clip(lengths, 0, cfg.max_len - 1)]
    for i in range(cfg.n_layers):
        with jax.named_scope(f"l{i}"):
            with jax.named_scope("qkv"):
                h1 = _rms(x, params[f"l{i}.ln1"])
                q, k, v = _qkv(params, i, h1, cfg)
            with jax.named_scope("kv_write"):
                k_pages, ck = _quant.kv_scatter(k_pages, i, w_pages,
                                                slots, k)
                v_pages, cv = _quant.kv_scatter(v_pages, i, w_pages,
                                                slots, v)
                clips = clips + ck + cv
            with jax.named_scope("attn"):
                o = attn(q, k_pages.layer(i), v_pages.layer(i),
                         page_table, ctx_len)
            with jax.named_scope("out"):
                x = x + o.reshape(b, cfg.d_model) @ params[f"l{i}.wo"]
            with jax.named_scope("mlp"):
                x = x + _mlp(params, i, _rms(x, params[f"l{i}.ln2"]))
    with jax.named_scope("logits"):
        x = _rms(x, params["ln_f"])
        logits = x @ params["embed"].T
    return logits, k_pages, v_pages, clips


def decode_forward(params, tokens, k_pages, v_pages, page_table,
                   lengths, active, seeds=None, temps=None,
                   top_ks=None, top_ps=None, *, cfg, attn,
                   with_stats=False):
    """One decode step over the full fixed-shape batch.

    tokens (B,) int32 last emitted token per row; lengths (B,) tokens
    already in cache; active (B,) bool. Inactive rows write to / read
    from the scratch page and their outputs are ignored by the host.
    With seeds/temps/top_ks/top_ps (B,) arrays the next token is drawn
    per row on its (seed, position=lengths+1) stream (temperature 0 =
    exact greedy); without them it is the argmax (PR 8 behavior).
    Returns (next_tokens (B,), k_pages, v_pages); with_stats=True
    (the MXNET_NUMERICS_DECODE_GUARD path) appends a (2,) int32
    vector [nonfinite_rows, quant_clips]: ACTIVE rows whose logits
    hold any NaN/Inf, and K/V values this step's quantized writes had
    to clip (dequant-overflow events — 0 on float pools). Both are
    computed inside the jit, so the guard adds zero host syncs.
    """
    logits, k_pages, v_pages, clips = decode_logits(
        params, tokens, k_pages, v_pages, page_table, lengths, active,
        cfg=cfg, attn=attn)
    with jax.named_scope("sample"):
        next_tokens = _sample_rows(logits, seeds, lengths + 1, temps,
                                   top_ks, top_ps)
    if with_stats:
        bad_rows = jnp.any(~jnp.isfinite(logits), axis=-1)
        nonfinite = jnp.sum(
            jnp.where(active, bad_rows, False).astype(jnp.int32))
        guard = jnp.stack([nonfinite, clips])
        return next_tokens, k_pages, v_pages, guard
    return next_tokens, k_pages, v_pages
