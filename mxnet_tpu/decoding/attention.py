"""Page-table attention: one decode step's attention over the paged
KV pool (the device half of Ragged Paged Attention, PAPERS.md).

Contract shared by both kernels:

  q           (B, H, D)        one query token per batch row
  k_pages     quant.KVLayer    one layer of the K pool as a (pool,
                               layer index) pair — `pool.layer(i)`.
                               The pool's data is (L, N, P, H*D): a
                               token's whole row, all heads side by
                               side, in the minor dimension (quant.py
                               says why). The kernels read pages
                               `data[layer, page]` straight from the
                               pool — no layer is sliced out first;
                               int8 pages dequantize INSIDE the
                               kernel. A bare float array (N, P, H*D)
                               is taken as a one-layer pool
  v_pages     quant.KVLayer    the same layer of the V pool
  page_table  (B, Bp) int32    per-row page ids, seq-ordered; padding
                               entries point at the scratch page 0
  lengths     (B,) int32       valid context tokens per row (masking;
                               rows beyond their length never read
                               foreign/stale page contents)
  -> out      (B, H, D)

Every shape is a function of (max_batch, pages_bucket) only — never of
actual sequence lengths — so the engine pre-traces one program per
pages bucket and steady-state decode provably adds zero traces.

Two implementations behind `MXNET_DECODE_KERNEL`:

  lax     (default) gather the Bp pages per row into a contiguous
          (B, Bp*P, H*D) context in the pool's storage type and run
          masked softmax attention over the rows AS STORED: the query
          is spread over the heads' lanes ((B, H, H*D), head h's
          values on its own lanes, zeros elsewhere), scores and
          values are one batched matmul each, and each head keeps its
          own lanes of the result. The rows are never split into
          (H, D): a minor dimension of head_dim 64 cannot be a bitcast
          on the chip, and XLA made the split as a padded float32 copy
          of the whole context, four times its bytes, written and read
          back in every layer (95% of OPT-1.3B's decode step). Pure
          lax, runs anywhere. The MULTI-query variant (tail prefill,
          verify) keeps the split: its S queries share one context, so
          the products dominate and a spread query would do H times
          the work.
  pallas  flash-style online-softmax kernel on a (B, Bp) grid whose
          K/V block index maps read the page table via scalar
          prefetch (PrefetchScalarGridSpec) — pages stream HBM->VMEM
          per grid step instead of materializing the gathered
          context. Compiled on a TPU, interpreted elsewhere.

The switch is read by `decoding.config.kernel()`. The
`ragged_paged_attention_*` entries below serve MIXED
prefill+decode batches for the merged-step engine
(MXNET_DECODE_MERGED_STEP).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import utils as _utils
from . import quant as _quant

NEG_INF = -1e30


def _check_pool(k_pages, v_pages, h, d):
    """The page size, once the two layers agree with each other and
    with the query's heads."""
    _, p, hd = k_pages.shape
    if k_pages.shape != v_pages.shape:
        raise ValueError("k_pages/v_pages shape mismatch")
    if hd != h * d:
        raise ValueError(
            f"pool row width {hd} != query heads*dim {h}*{d}")
    return p


def _check_shapes(q, k_pages, v_pages, page_table, lengths):
    b, h, d = q.shape
    p = _check_pool(k_pages, v_pages, h, d)
    if page_table.shape[0] != b or lengths.shape != (b,):
        raise ValueError("page_table/lengths batch mismatch")
    return b, h, d, p, page_table.shape[1]


def paged_attention_lax(q, k_pages, v_pages, page_table, lengths,
                        scale=None):
    """Gather-based single-query kernel (see module docstring): the
    gathered rows are attended AS STORED, (B, T, H*D) in the pool's
    type, with the query spread over the heads' lanes. Softmax and
    weights are float32 and the V product runs at `highest` (the
    weights are not rounded to bf16). An int8 pool's scales, per
    (token, head), go onto the scores (K) and onto the weights (V):
    the arithmetic of dequantizing the rows."""
    k_pages = _quant.as_layer(k_pages)
    v_pages = _quant.as_layer(v_pages)
    b, h, d, p, bp = _check_shapes(
        q, k_pages, v_pages, page_table, lengths)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    t = bp * p
    highest = jax.lax.Precision.HIGHEST
    # (B, Bp, P, H*D) -> (B, T, H*D): pages are seq-ordered, so the
    # flattened axis IS the token axis (positions >= length masked)
    k_rows, k_scale = _quant.gather_stored(k_pages, page_table)
    v_rows, v_scale = _quant.gather_stored(v_pages, page_table)
    own = (jnp.arange(h * d)[None, :] // d
           == jnp.arange(h)[:, None])                    # (H, H*D)
    # the products' type: the query's, or the rows' where that is
    # wider. bf16 (and int8, exact in bf16) operands multiply exactly
    # in one pass and sum in float32; float32 operands take every pass
    ct = jnp.promote_types(q.dtype, k_rows.dtype)
    q_heads = jnp.where(own, q.reshape(b, 1, h * d).astype(ct), 0)
    s = jnp.einsum("bhc,btc->bht", q_heads, k_rows.astype(ct),
                   precision=highest if ct == jnp.float32 else None,
                   preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        s = s * k_scale.transpose(0, 2, 1)
    mask = jnp.arange(t)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    w = e / e.sum(axis=-1, keepdims=True)
    if v_scale is not None:
        w = w * v_scale.transpose(0, 2, 1)
    o = jnp.einsum("bht,btc->bhc", w, v_rows.astype(jnp.float32),
                   precision=highest,
                   preferred_element_type=jnp.float32)   # (B, H, H*D)
    out = jnp.where(own, o, 0).sum(axis=1).reshape(b, h, d)
    return out.astype(q.dtype)


def paged_attention_lax_multi(q, k_pages, v_pages, page_table,
                              q_positions, scale=None):
    """Multi-query variant: S queries per row over the same paged
    context, each masked by its OWN absolute position.

      q            (B, S, H, D)   queries (tail-prefill / verify)
      q_positions  (B, S) int32   absolute position of each query;
                                  query j attends context positions
                                  <= q_positions[b, j]

    The per-query causal mask is what lets ONE fixed-shape program
    serve both the prefix-cache tail prefill (queries = the uncached
    prompt tail, context = shared pages + the tail itself) and the
    speculative verify step (queries = last_token + K drafts). Shapes
    are a function of (B, S, pages bucket) only.
    """
    k_pages = _quant.as_layer(k_pages)
    v_pages = _quant.as_layer(v_pages)
    b, s, h, d = q.shape
    p = _check_pool(k_pages, v_pages, h, d)
    if page_table.shape[0] != b or q_positions.shape != (b, s):
        raise ValueError("page_table/q_positions batch mismatch")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    t = page_table.shape[1] * p
    k_ctx = _quant.gather_ctx(k_pages, page_table, h).reshape(b, t, h, d)
    v_ctx = _quant.gather_ctx(v_pages, page_table, h).reshape(b, t, h, d)
    sc = jnp.einsum("bshd,bthd->bhst", q, k_ctx,
                    preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(t)[None, None, :]
            <= q_positions[:, :, None])          # (B, S, T)
    sc = jnp.where(mask[:, None], sc, NEG_INF)
    m = sc.max(axis=-1, keepdims=True)
    e = jnp.exp(sc - m)
    w = e / e.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhst,bthd->bshd", w, v_ctx,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# ------------------------------------------- sparse selection over pages
def sparse_index_select(q_idx, w_idx, k_ctx, q_pos, topk):
    """Learned sparse selection: which cached tokens each query
    attends.

      q_idx  (B, T, J, D)   index queries, J index heads
      w_idx  (B, T, J) f32  the query's weight of each index head
      k_ctx  (B, S, D)      the row's index keys, position-ordered
                            (`quant.gather_plane` of the index plane)
      q_pos  (B, T) int32   absolute position of each query

    I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . k[s]) for s <= q_pos[t];
    returns the positions of the `topk` largest, (B, T, topk) int32 —
    EXACT (`lax.top_k`: ties to the lower position; an approximate
    top-k at recall under 1 would be another model). A query with
    fewer than topk tokens in reach gets them all, and past them
    positions beyond its own, which `sparse_latent_attention` masks.
    The (B, T, J, S) float32 scores are this function's temporary: the
    caller bounds T."""
    s = jnp.einsum("btjd,bsd->btjs", q_idx.astype(k_ctx.dtype), k_ctx,
                   preferred_element_type=jnp.float32)
    score = jnp.sum(jax.nn.relu(s) * w_idx[..., None], axis=2)
    reach = jnp.arange(k_ctx.shape[1])[None, None, :] <= q_pos[..., None]
    score = jnp.where(reach, score, -jnp.inf)
    # queries as rows of ONE matrix: with a decode step's single query
    # a row (B, 1, S) the sort would run one sublane of eight
    b, t, n = score.shape
    picked = jax.lax.top_k(score.reshape(b * t, n), topk)[1]
    return picked.reshape(b, t, topk).astype(jnp.int32)


def sparse_latent_attention(q, latent, page_table, selected, q_pos,
                            value_width, scale):
    """Attention over the selected rows only, in latent space.

      q          (B, T, H, W)   queries already taken into the latent
                                row's space (W = the plane's width)
      latent     quant.KVLayer  one layer of the latent plane
      page_table (B, Bp) int32
      selected   (B, T, K)      positions from `sparse_index_select`
      q_pos      (B, T) int32

    Gathers the K rows of each query through the page table (token
    granular, straight from the pool), scores q . row over the whole
    row, softmax in float32 over the rows in reach, and returns
    sum_k p[k] * row[k, :value_width] as (B, T, H, value_width)
    float32: all heads share the one gathered row."""
    rows = _quant.gather_rows(latent, page_table,
                              selected)[..., :q.shape[-1]]
    s = jnp.einsum("bthw,btkw->bthk", q.astype(rows.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    reach = selected <= q_pos[..., None]
    s = jnp.where(reach[:, :, None, :], s, NEG_INF)
    e = jnp.exp(s - s.max(axis=-1, keepdims=True))
    w = e / e.sum(axis=-1, keepdims=True)
    return jnp.einsum("bthk,btkc->bthc", w.astype(rows.dtype),
                      rows[..., :value_width],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------- pallas
def _paged_attn_kernel(page_size, heads, quantized):
    """Kernel body on a (B, Bp) grid: one (page, row) tile per step,
    online-softmax accumulated in VMEM scratch across the Bp axis.

    A page lands in VMEM as the pool stores it, (P, H*D): every head
    of a token side by side on the lanes. Mosaic will not split the
    lane dimension into (H, D) for D under 128, so the heads are never
    split: the elementwise product q*K is summed per head by a matmul
    with the 0/1 segment matrix `seg` (H, H*D) (seg[h, j] = 1 where
    lane j belongs to head h), giving scores (P, H); the same matrix
    spreads the softmax weights (P, H) back over their head's lanes
    for the product with V. Running max and sum stay (1, H), the
    accumulator (1, H*D). Quantized pools carry two extra scale refs
    (P, H), one per K/V page, applied to the scores and to the weights
    — per (slot, head), the same arithmetic as dequantizing the page —
    so the pool is never upcast in HBM, which is the whole point of
    int8 pages."""
    from jax.experimental import pallas as pl

    def per_head(x, seg):
        # (R, H*D) -> (R, H): sum each head's lanes
        return jax.lax.dot_general(
            x, seg, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def over_lanes(x, seg):
        # (R, H) -> (R, H*D): repeat each head's value over its lanes
        return jnp.dot(x, seg, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)

    def kernel(pt_ref, len_ref, layer_ref, q_ref, *refs):
        if quantized:
            k_ref, ks_ref, v_ref, vs_ref = refs[:4]
        else:
            k_ref, v_ref = refs[:2]
        o_ref, acc_ref, m_ref, l_ref = refs[-4:]
        i = pl.program_id(1)
        nbp = pl.num_programs(1)
        b = pl.program_id(0)

        @pl.when(i == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

        qb = q_ref[0].astype(jnp.float32)          # (1, H*D)
        kb = k_ref[0, 0].astype(jnp.float32)       # (P, H*D)
        vb = v_ref[0, 0].astype(jnp.float32)
        hd = qb.shape[-1]
        d = hd // heads
        seg = (jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 1) // d
               == jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 0)
               ).astype(jnp.float32)               # (H, H*D)
        s = per_head(qb * kb, seg) * (1.0 / math.sqrt(d))   # (P, H)
        if quantized:
            s = s * ks_ref[0, 0]
        pos = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        s = jnp.where(pos < len_ref[b], s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]         # (1, H)
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)                          # (P, H)
        l_ref[...] = l_prev * corr + e.sum(axis=0, keepdims=True)
        w = e * vs_ref[0, 0] if quantized else e
        # one spread for the weights and the correction: (P + 1, H)
        spread = over_lanes(jnp.concatenate([w, corr], axis=0), seg)
        acc_ref[...] = acc_ref[...] * spread[page_size:] + jnp.sum(
            spread[:page_size] * vb, axis=0, keepdims=True)
        m_ref[...] = m_new

        @pl.when(i == nbp - 1)
        def _flush():
            norm = over_lanes(1.0 / l_ref[...], seg)
            o_ref[0] = (acc_ref[...] * norm).astype(o_ref.dtype)

    return kernel


def paged_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                           scale=None):
    """Flash-style paged kernel; the layer index and the page ids
    drive the K/V block index maps through scalar prefetch, so only
    the pages a row actually owns ever move HBM->VMEM, straight from
    the pool as it is stored. A quantized pool stores a page's scales
    as one row of page_size*heads lanes, which Mosaic cannot turn into
    the (P, H) the scores want; they come as a per-call view of the
    GATHERED scale rows (B, Bp, P, H) — 1/head_dim of the context's
    bytes — never of the pool. Compiled on a TPU, interpreted
    elsewhere (utils.pallas_interpret)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    k_pages = _quant.as_layer(k_pages)
    v_pages = _quant.as_layer(v_pages)
    b, h, d, p, bp = _check_shapes(
        q, k_pages, v_pages, page_table, lengths)
    if scale is not None and not math.isclose(
            scale, 1.0 / math.sqrt(d)):
        raise ValueError(
            "pallas kernel hard-codes scale=1/sqrt(head_dim)")
    quantized = k_pages.pool.scale is not None

    def page_spec(width):
        return pl.BlockSpec(
            (1, 1, p, width),
            lambda bb, i, pt, ln, ly: (ly[0], pt[bb, i], 0, 0))

    row_spec = pl.BlockSpec((1, 1, h * d),
                            lambda bb, i, pt, ln, ly: (bb, 0, 0))
    # one prefetched index serves both pools: every caller reads the
    # same layer of K and V
    layer = jnp.asarray(k_pages.index, jnp.int32).reshape(1)
    in_specs = [row_spec]
    operands = [q.reshape(b, 1, h * d)]
    for layer_ in (k_pages, v_pages):
        in_specs.append(page_spec(h * d))
        operands.append(layer_.pool.data)
        if quantized:
            in_specs.append(pl.BlockSpec(
                (1, 1, p, h), lambda bb, i, pt, ln, ly: (bb, i, 0, 0)))
            operands.append(
                layer_.pool.scale[layer_.index, page_table].reshape(
                    b, bp, p, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # page_table, lengths, layer
        grid=(b, bp),
        in_specs=in_specs,
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((1, h * d), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
        ],
    )
    fn = pl.pallas_call(
        _paged_attn_kernel(p, h, quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        interpret=_utils.pallas_interpret(),
        name="paged_attention",
    )
    return fn(page_table, lengths, layer, *operands).reshape(b, h, d)


# ---------------------------------------------------------------- ragged
def ragged_paged_attention_lax(q, k_pages, v_pages, page_table,
                               lengths, scale=None):
    """Ragged paged attention (PAPERS.md), lax path: ONE fixed-shape
    kernel serving a MIXED batch of decode rows and tail-prefill rows.

    The single-query paged kernel is already position-agnostic per
    row: row b attends exactly the context positions < lengths[b] of
    its own page table. A decode row passes its full context length; a
    tail-prefill row passes `position + 1` for the prompt token it is
    processing (intra-chunk causality — the token at position p sees
    positions <= p, which its engine-side scatter has already written).
    Nothing else distinguishes the two, so prefill and decode share
    one pre-traced program per pages bucket and the warmup trace grid
    loses its per-length-bucket tail-prefill programs entirely
    (docs/serving.md)."""
    return paged_attention_lax(q, k_pages, v_pages, page_table,
                               lengths, scale=scale)


def ragged_paged_attention_pallas(q, k_pages, v_pages, page_table,
                                  lengths, scale=None):
    """Ragged mixed prefill+decode batch through the flash-style paged
    kernel — same per-row length masking as the lax twin (see
    `ragged_paged_attention_lax`), pages streamed HBM->VMEM via the
    scalar-prefetch page table."""
    return paged_attention_pallas(q, k_pages, v_pages, page_table,
                                  lengths, scale=scale)


_KERNELS = {
    "lax": paged_attention_lax,
    "pallas": paged_attention_pallas,
}

_RAGGED_KERNELS = {
    "lax": ragged_paged_attention_lax,
    "pallas": ragged_paged_attention_pallas,
}


def get_ragged_kernel(name):
    """Resolve MXNET_DECODE_KERNEL to the mixed prefill+decode ragged
    implementation (the merged-step engine path)."""
    try:
        return _RAGGED_KERNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown MXNET_DECODE_KERNEL {name!r} "
            f"(choices: {sorted(_RAGGED_KERNELS)})") from None

# the multi-query paths (tail prefill, speculative verify) have one
# implementation today; the pallas flash variant is a silicon item
_MULTI_KERNELS = {
    "lax": paged_attention_lax_multi,
    "pallas": paged_attention_lax_multi,
}


def get_multi_kernel(name):
    """Resolve MXNET_DECODE_KERNEL to a multi-query implementation."""
    try:
        return _MULTI_KERNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown MXNET_DECODE_KERNEL {name!r} "
            f"(choices: {sorted(_MULTI_KERNELS)})") from None


def get_kernel(name):
    """Resolve MXNET_DECODE_KERNEL to an implementation."""
    try:
        return _KERNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown MXNET_DECODE_KERNEL {name!r} "
            f"(choices: {sorted(_KERNELS)})") from None
