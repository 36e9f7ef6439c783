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

Two implementations behind `MXNET_DECODE_KERNEL`. Unset, the backend
decides (`decoding.config.kernel()`, the one place): `pallas` on a TPU,
`lax` elsewhere — interpreted, the kernel would make every CPU test of
the tier crawl.

  lax     gather the Bp pages per row into a contiguous
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
          lax, runs anywhere: the CPU's form and the reference the
          kernel is tested against. It writes the context out and
          reads it back, and it moves the BUCKET, not the rows: six
          passes over Bp*P tokens a row whatever the rows hold (28 of
          the 34 ms of OPT-1.3B's decode step on a v5e). The
          MULTI-query variant (tail prefill, verify) keeps the split:
          its S queries share one context, so the products dominate
          and a spread query would do H times the work.
  pallas  the same arithmetic on the pages WHERE THEY LIE: a grid over
          the rows, each row's own blocks of `_BLOCK_TOKENS` tokens
          walked by a loop as long as the row, a block's pages copied
          HBM->VMEM one `make_async_copy` each (page ids, lengths and
          the layer by scalar prefetch) with the NEXT block's copies —
          the same row's, or the next live row's first — started
          before the block is computed. No page a row does not own is
          read, no gathered context exists, an empty row costs
          nothing. Online softmax in float32 across blocks; the value
          product keeps the float32 weights unrounded as the lax form
          does (`_paged_attn_kernel`). 0.155 ms a layer where the lax
          form takes 1.16 at OPT-1.3B's chat mix (48 rows, bucket 48,
          28% of the page slots live; PERF.md section 6, PR 32), and
          ahead of it at every bucket measured, a full p16 included:
          ONE path on a TPU, no choice by shape. Compiled on a TPU,
          interpreted elsewhere.

The `ragged_paged_attention_*` entries below serve MIXED
prefill+decode batches for the merged-step engine
(MXNET_DECODE_MERGED_STEP).

Three arguments, in every form, for a block whose layers are not the
dense block's (`window_mixed`); left out, a form is what it was:

  kv_heads   KV heads of the stored rows where they are fewer than the
             query's heads: query head j reads KV head
             j // (heads / kv_heads). The K row is kv_heads*D wide and
             the V row kv_heads*Dv, Dv its own; out is (B, H, Dv)
  window     positions a query reads, its own included: a query at
             position p attends p - window + 1 .. p. The lax forms
             gather, and the kernel copies, the pages of those
             positions alone; pages behind them may have been released
             (their table entries then point at the scratch page)
  sink       (H,) float32, a learned logit a head that joins the
             softmax's denominator and has no value row:
             P = exp(s - m) / (sum_j exp(s_j - m) + exp(sink - m))

With any of them the pools are float (no int8 scales).
"""
from __future__ import annotations

import functools
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
                        scale=None, *, kv_heads=None, window=None,
                        sink=None):
    """Gather-based single-query kernel (see module docstring): the
    gathered rows are attended AS STORED, (B, T, H*D) in the pool's
    type, with the query spread over the heads' lanes. Softmax and
    weights are float32 and the V product runs at `highest` (the
    weights are not rounded to bf16). An int8 pool's scales, per
    (token, head), go onto the scores (K) and onto the weights (V):
    the arithmetic of dequantizing the rows."""
    k_pages = _quant.as_layer(k_pages)
    v_pages = _quant.as_layer(v_pages)
    if kv_heads or window or sink is not None:
        return _grouped_attention_lax(
            q, k_pages, v_pages, page_table, lengths, scale,
            kv_heads or q.shape[1], window, sink)
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


def _check_grouped(q_heads, d, k_pages, v_pages, kv_heads):
    """(page size, value head size) of a grouped read: float pools whose
    K row is kv_heads*d wide."""
    _, p, wk = k_pages.shape
    if k_pages.pool.scale is not None or v_pages.pool.scale is not None:
        raise ValueError("grouped or windowed attention reads float pools")
    if q_heads % kv_heads or wk != kv_heads * d \
            or v_pages.shape[2] % kv_heads or v_pages.shape[:2] != (
                k_pages.shape[0], p):
        raise ValueError(
            f"pool rows {wk}/{v_pages.shape[2]} do not hold {kv_heads} KV "
            f"heads for {q_heads} query heads of {d}")
    return p, v_pages.shape[2] // kv_heads


def _softmax_with_sink(s, sink):
    """Softmax over the last axis of s (B, G, Q, ..., T) float32 with
    the masked scores at NEG_INF; `sink` (G, Q) joins the denominator."""
    m = s.max(axis=-1, keepdims=True)
    if sink is not None:
        sink = sink.reshape((1,) + sink.shape + (1,) * (s.ndim - 3))
        m = jnp.maximum(m, sink)
    e = jnp.exp(s - m)
    den = e.sum(axis=-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink - m)
    return e / den


def _grouped_attention_lax(q, k_pages, v_pages, page_table, lengths,
                           scale, kv_heads, window, sink):
    """`paged_attention_lax` with `kv_heads`, `window` or `sink` (module
    docstring): the gathered rows are split into their KV heads and a
    head's group of queries is batched against it. With a window only
    its ceil(window / P) + 1 pages are gathered, from the page the
    row's first attended position lies in."""
    b, h, d = q.shape
    p, dv = _check_grouped(h, d, k_pages, v_pages, kv_heads)
    bp = page_table.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    lower = jnp.zeros_like(lengths)
    offset = lower
    if window:
        n = min(bp, -(-window // p) + 1)
        lower = jnp.maximum(lengths - window, 0)
        first = jnp.minimum(lower // p, bp - n)
        page_table = jnp.take_along_axis(
            page_table, first[:, None] + jnp.arange(n)[None], axis=1)
        offset = first * p
    k_rows, _ = _quant.gather_stored(k_pages, page_table)
    v_rows, _ = _quant.gather_stored(v_pages, page_table)
    t = k_rows.shape[1]
    highest = jax.lax.Precision.HIGHEST
    ct = jnp.promote_types(q.dtype, k_rows.dtype)
    s = jnp.einsum(
        "bgqd,btgd->bgqt", q.reshape(b, kv_heads, -1, d).astype(ct),
        k_rows.reshape(b, t, kv_heads, d).astype(ct),
        precision=highest if ct == jnp.float32 else None,
        preferred_element_type=jnp.float32) * scale
    pos = offset[:, None] + jnp.arange(t)[None]
    mask = (pos >= lower[:, None]) & (pos < lengths[:, None])
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = _softmax_with_sink(
        s, None if sink is None
        else sink.astype(jnp.float32).reshape(kv_heads, -1))
    o = jnp.einsum("bgqt,btgd->bgqd", w,
                   v_rows.reshape(b, t, kv_heads, dv).astype(jnp.float32),
                   precision=highest, preferred_element_type=jnp.float32)
    return o.reshape(b, h, dv).astype(q.dtype)


def paged_attention_lax_multi(q, k_pages, v_pages, page_table,
                              q_positions, scale=None, *, kv_heads=None,
                              window=None, sink=None):
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
    if kv_heads or window or sink is not None:
        return _grouped_attention_lax_multi(
            q, k_pages, v_pages, page_table, q_positions, scale,
            kv_heads or q.shape[2], window, sink)
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


# key positions a block of the grouped multi-query form scores at once:
# bounds its (B, H, S, block) float32 scores whatever the context
KEY_BLOCK = 512


def _grouped_attention_lax_multi(q, k_pages, v_pages, page_table,
                                 q_positions, scale, kv_heads, window,
                                 sink):
    """`paged_attention_lax_multi` with `kv_heads`, `window` or `sink`:
    the keys are walked in blocks of `KEY_BLOCK` positions, gathered
    from their pages as stored, with the softmax online in float32
    across them, from the block the first query's window begins in to
    the block of the last query — the work is the queries' reach, not
    the table's, and no score matrix over the whole context exists.
    Operands are in the wider of the query's and the pool's type
    (float32 ones at `highest`); the weights are rounded to a bfloat16
    pool's type for the value product."""
    b, s, h, d = q.shape
    p, dv = _check_grouped(h, d, k_pages, v_pages, kv_heads)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    bp = page_table.shape[1]
    kb = max(1, min(KEY_BLOCK // p, bp))        # pages a block
    kt = kb * p
    blocks = -(-bp // kb)
    table = jnp.pad(page_table, ((0, 0), (0, blocks * kb - bp)))
    highest = jax.lax.Precision.HIGHEST
    ct = jnp.promote_types(q.dtype, k_pages.pool.data.dtype)
    exact = highest if ct == jnp.float32 else None
    qg = q.reshape(b, s, kv_heads, h // kv_heads, d).astype(ct)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(
            1, kv_heads, h // kv_heads, 1, 1)
    last = jnp.clip(jnp.max(q_positions) // kt, 0, blocks - 1)
    first = 0
    if window:
        first = jnp.clip((jnp.min(q_positions) - window + 1) // kt, 0, last)

    def block(i, carry):
        m_prev, l_prev, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(table, i * kb, kb, axis=1)
        k_rows, _ = _quant.gather_stored(k_pages, pages)
        v_rows, _ = _quant.gather_stored(v_pages, pages)
        sc = jnp.einsum(
            "bsgqd,btgd->bgqst", qg,
            k_rows.reshape(b, kt, kv_heads, d).astype(ct), precision=exact,
            preferred_element_type=jnp.float32) * scale
        pos = i * kt + jnp.arange(kt)
        mask = pos[None, None, :] <= q_positions[:, :, None]
        if window:
            mask = mask & (pos[None, None, :]
                           > q_positions[:, :, None] - window)
        sc = jnp.where(mask[:, None, None], sc, NEG_INF)
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(sc - m_new)
        l_new = l_prev * corr + e.sum(axis=-1, keepdims=True)
        vt = v_rows.dtype
        pv = jnp.einsum(
            "bgqst,btgd->bgqsd", e.astype(vt),
            v_rows.reshape(b, kt, kv_heads, dv),
            precision=highest if vt == jnp.float32 else None,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    shape = (b, kv_heads, h // kv_heads, s)
    init = (jnp.full(shape + (1,), NEG_INF, jnp.float32),
            jnp.zeros(shape + (1,), jnp.float32)) if sink is None else (
        jnp.broadcast_to(sink, shape + (1,)),
        jnp.ones(shape + (1,), jnp.float32))
    _, l_end, acc = jax.lax.fori_loop(
        first, last + 1, block,
        init + (jnp.zeros(shape + (dv,), jnp.float32),))
    out = (acc / l_end).transpose(0, 3, 1, 2, 4).reshape(b, s, h, dv)
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
# tokens a block of pages holds, where the bucket allows: one MXU tile
# of keys, and at OPT-1.3B's row (2048 bf16) 2 x (K, V) x 512 KB of VMEM
_BLOCK_TOKENS = 128


def _paged_attn_kernel(rows, bucket, heads, page_size, block_pages, scale,
                       quantized, kv_heads=None, window=None, sink=False):
    """Kernel body on a grid over the batch rows: a row's own blocks of
    `block_pages` pages are walked by a loop whose trip count is the
    row's length, so neither a padding page nor an empty row costs a
    step.

    The pools stay in HBM (`pl.ANY`). A block's pages are copied as
    they are stored, page (P, H*D) by page, by one `make_async_copy`
    each from `pool[layer, page_table[b, i*G + j]]` into slot j of a
    (2, G*P, H*D) VMEM buffer: the page ids, lengths and the layer come
    by scalar prefetch. Before a block is computed the copies of the
    NEXT block that has work (this row's next, else the next live
    row's first) are started into the other buffer; the first block of
    the call is started at grid step 0. Slots past a row's
    `ceil(length / P)` pages are neither copied nor waited for: they
    hold what an earlier block left, which the position mask keeps out
    of the scores and whose weights are exactly 0 (the V buffers are
    zeroed once, so what is left is finite pool content).

    The arithmetic is `paged_attention_lax`'s: the query spread over
    its heads' lanes, (H, H*D), scores as ONE product with the block
    (bf16 and int8-as-bf16 operands exact in one pass, float32 ones at
    `highest`), the softmax online in float32 across blocks, and the
    value product with the float32 weights unrounded, head h keeping
    its own lanes of the (H, H*D) accumulator at the end. Against bf16
    (or int8) values the weights are split into three bf16 terms,
    stacked (3H, G*P), so ONE pass over the block keeps exactly the
    products that `highest` keeps of a float32 weight and a value whose
    low terms are zero; a float32 pool takes `highest` itself. An int8
    pool's scales, (H, G*P) a block, go onto the scores and weights.

    With `kv_heads` (the grouped form; module docstring) the query comes
    ALREADY spread over its KV head's lanes, (H, kv_heads*D) a row, the
    V row is kv_heads*Dv wide, and each KV head's group of query heads
    keeps that head's Dv lanes of the (H, kv_heads*Dv) accumulator: out
    is (H, Dv) float32 a row. With `window` a row's walk begins at the
    block its first attended position lies in, pages behind that
    position are neither copied nor waited for, and positions before it
    are masked. With `sink` a (H, 1) float32 input is the softmax's
    starting maximum beside a starting denominator of 1."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    g, p = block_pages, page_size
    gp = g * p
    highest = jax.lax.Precision.HIGHEST
    # named, so that a caller's `jax.default_matmul_precision` cannot
    # ask Mosaic for float32 passes over bf16 operands (it refuses)
    one_pass = jax.lax.Precision.DEFAULT

    def kernel(pt_ref, len_ref, layer_ref, q_ref, *refs):
        k_hbm, v_hbm = refs[:2]
        ks_ref, vs_ref = refs[2:4] if quantized else (None, None)
        sink_ref = refs[4 if quantized else 2] if sink else None
        o_ref, k_buf, v_buf, acc_ref, sems, slot_ref = refs[-6:]
        b = pl.program_id(0)
        layer = layer_ref[0]

        def length_of(row):
            # a length past the table reads the table's pages, as the
            # lax form's mask over the gathered context does
            return jnp.minimum(len_ref[row], bucket * p)

        def lower_of(row):
            # the first position a row's query attends
            return jnp.maximum(length_of(row) - window, 0) if window else 0

        def first_block_of(row):
            return lower_of(row) // gp if window else 0

        length = length_of(b)

        def copies(row, blk, slot, act):
            # start (or wait for) the copies of the pages row `row`
            # owns in its block `blk`: the same descriptors both times
            first = blk * g
            owned = jnp.minimum(pl.cdiv(length_of(row), p) - first, g)
            begin = jnp.maximum(lower_of(row) // p - first, 0) \
                if window else 0

            def one(j, _):
                page = pt_ref[row, first + j]
                at = pl.ds(pl.multiple_of(j * p, p), p)
                for n, (hbm, buf) in enumerate(((k_hbm, k_buf),
                                                (v_hbm, v_buf))):
                    act(pltpu.make_async_copy(
                        hbm.at[layer, page], buf.at[slot, at],
                        sems.at[n, slot]))

            jax.lax.fori_loop(begin, owned, one, None)

        def start(row, blk, slot):
            copies(row, blk, slot, lambda c: c.start())

        def start_first_block_from(row, slot):
            # the first live row at or after `row`, if there is one
            nxt = jax.lax.while_loop(
                lambda r: jnp.logical_and(
                    r < rows, len_ref[jnp.minimum(r, rows - 1)] == 0),
                lambda r: r + 1, row)

            @pl.when(nxt < rows)
            def _():
                start(nxt, first_block_of(jnp.minimum(nxt, rows - 1)), slot)

        @pl.when(b == 0)
        def _first():
            v_buf[...] = jnp.zeros_like(v_buf)
            slot_ref[0] = 0
            start_first_block_from(0, 0)

        @pl.when(length == 0)
        def _empty():
            o_ref[...] = jnp.zeros_like(o_ref)

        @pl.when(length > 0)
        def _row():
            ct = jnp.promote_types(
                q_ref.dtype,
                jnp.bfloat16 if quantized else k_buf.dtype)
            if kv_heads:
                q_heads = q_ref[0].astype(ct)       # (H, kv_heads*D)
            else:
                hd = q_ref.shape[-1]
                d = hd // heads
                own = (jax.lax.broadcasted_iota(
                    jnp.int32, (heads, hd), 1) // d
                    == jax.lax.broadcasted_iota(jnp.int32, (heads, hd), 0))
                # (H, H*D); the select in float32: Mosaic has no
                # relayout of the 32-bit mask for 16-bit operands
                q_heads = jnp.where(own, q_ref[0].astype(jnp.float32),
                                    0).astype(ct)
            blocks = pl.cdiv(length, gp)
            lower = lower_of(b)
            acc_ref[...] = jnp.zeros_like(acc_ref)

            def block(i, carry):
                m_prev, l_prev = carry                  # (H, 1) float32
                slot = slot_ref[0]

                @pl.when(i + 1 < blocks)
                def _():
                    start(b, i + 1, 1 - slot)

                @pl.when(i + 1 == blocks)
                def _():
                    start_first_block_from(b + 1, 1 - slot)

                slot_ref[0] = 1 - slot
                copies(b, i, slot, lambda c: c.wait())
                s = jax.lax.dot_general(
                    q_heads, k_buf[slot].astype(ct),
                    (((1,), (1,)), ((), ())),
                    precision=highest if ct == jnp.float32 else one_pass,
                    preferred_element_type=jnp.float32) * scale  # (H, G*P)
                if quantized:
                    s = s * ks_ref[0, i]
                pos = i * gp + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1)
                seen = pos < length
                if window:
                    seen = seen & (pos >= lower)
                s = jnp.where(seen, s, NEG_INF)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                corr = jnp.exp(m_prev - m_new)
                e = jnp.exp(s - m_new)
                l_new = l_prev * corr + e.sum(axis=1, keepdims=True)
                w = e * vs_ref[0, i] if quantized else e
                v = v_buf[slot]
                if v.dtype == jnp.float32:
                    pv = jnp.dot(w, v, precision=highest,
                                 preferred_element_type=jnp.float32)
                else:
                    terms, rest = [], w
                    for _ in range(3):
                        terms.append(rest.astype(jnp.bfloat16))
                        rest = rest - terms[-1].astype(jnp.float32)
                    pv = jnp.dot(jnp.concatenate(terms, axis=0),
                                 v.astype(jnp.bfloat16), precision=one_pass,
                                 preferred_element_type=jnp.float32)
                    pv = pv[:heads] + pv[heads:2 * heads] + pv[2 * heads:]
                acc_ref[...] = acc_ref[...] * corr + pv
                return m_new, l_new

            init = (sink_ref[...], jnp.ones((heads, 1), jnp.float32)) \
                if sink else (jnp.full((heads, 1), NEG_INF, jnp.float32),
                              jnp.zeros((heads, 1), jnp.float32))
            _, l_end = jax.lax.fori_loop(first_block_of(b), blocks, block,
                                         init)
            if kv_heads:
                # a KV head's group of query heads keeps its Dv lanes
                out = acc_ref[...] / l_end
                grp, dv = heads // kv_heads, out.shape[1] // kv_heads
                for j in range(kv_heads):
                    o_ref[0, j * grp:(j + 1) * grp, :] = out[
                        j * grp:(j + 1) * grp,
                        j * dv:(j + 1) * dv].astype(o_ref.dtype)
            else:
                out = jnp.where(own, acc_ref[...] / l_end, 0)
                o_ref[0] = out.sum(axis=0, keepdims=True).astype(
                    o_ref.dtype)

    return kernel


def paged_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                           scale=None, block_pages=None, *, kv_heads=None,
                           window=None, sink=None):
    """The in-place kernel (`_paged_attn_kernel`): only the pages a
    row owns ever move HBM->VMEM, straight from the pool as it is
    stored, `block_pages` of them a block (default: 128 tokens' worth,
    at most the bucket). A row of length 0 returns zeros. Compiled on
    a TPU, interpreted elsewhere (utils.pallas_interpret)."""
    k_pages = _quant.as_layer(k_pages)
    v_pages = _quant.as_layer(v_pages)
    grouped = bool(kv_heads or window or sink is not None)
    if grouped:
        b, h, d = q.shape
        kv_heads = kv_heads or h
        p, _ = _check_grouped(h, d, k_pages, v_pages, kv_heads)
        bp = page_table.shape[1]
    else:
        b, h, d, p, bp = _check_shapes(
            q, k_pages, v_pages, page_table, lengths)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    g = min(block_pages or max(1, _BLOCK_TOKENS // p), bp)
    # one prefetched index serves both pools: every caller reads the
    # same layer of K and V
    layer = jnp.asarray(k_pages.index, jnp.int32).reshape(1)
    return _paged_call(q, k_pages.pool, v_pages.pool, page_table, lengths,
                       layer, sink, scale=float(scale), block_pages=g,
                       interpret=_utils.pallas_interpret(),
                       kv_heads=kv_heads if grouped else None,
                       window=window)


@functools.partial(jax.jit,
                   static_argnames=("scale", "block_pages", "interpret",
                                    "kv_heads", "window"))
def _paged_call(q, k_pool, v_pool, page_table, lengths, layer, sink=None,
                *, scale, block_pages, interpret, kv_heads=None,
                window=None):
    """The kernel's call, a jitted function of its own with the layer
    a traced index: a decode program's 24 layers then share ONE trace
    and ONE lowering of the kernel (Mosaic's lowering runs in every
    process, whatever jax's compile cache holds: 15 s a program of 24
    separate calls on the chip's host, 0.6 s so). A quantized pool
    stores a page's scales as one row of page_size*heads lanes, which
    Mosaic cannot turn into the (H, G*P) the scores want; they come as
    a per-call view of the GATHERED scale rows, (B, blocks, H, G*P) —
    1/head_dim of the context's bytes — never of the pool.

    The grouped form's query is spread over its KV head's lanes HERE,
    (B, H, kv_heads*D): in the kernel the spread would be a
    concatenation at lanes no multiple of 128."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    p = k_pool.page_size
    bp = page_table.shape[1]
    g = block_pages
    blocks = -(-bp // g)
    quantized = k_pool.scale is not None
    wk, wv = k_pool.data.shape[-1], v_pool.data.shape[-1]
    if kv_heads:
        own = (jnp.arange(wk)[None, :] // d
               == jnp.arange(h)[:, None] // (h // kv_heads))
        q_rows = jnp.where(own, jnp.tile(q, (1, 1, kv_heads)), 0)
        row_spec = pl.BlockSpec((1, h, wk),
                                lambda bb, pt, ln, ly: (bb, 0, 0))
        out_spec = pl.BlockSpec((1, h, wv // kv_heads),
                                lambda bb, pt, ln, ly: (bb, 0, 0))
        out_shape = jax.ShapeDtypeStruct((b, h, wv // kv_heads),
                                         jnp.float32)
    else:
        q_rows = q.reshape(b, 1, h * d)
        row_spec = out_spec = pl.BlockSpec(
            (1, 1, h * d), lambda bb, pt, ln, ly: (bb, 0, 0))
        out_shape = jax.ShapeDtypeStruct((b, 1, h * d), q.dtype)
    pools = (k_pool, v_pool)
    in_specs = [row_spec] + [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands = [q_rows] + [pool.data for pool in pools]
    if sink is not None:
        operands.append(sink.astype(jnp.float32).reshape(h, 1))
        in_specs.append(pl.BlockSpec((h, 1),
                                     lambda bb, pt, ln, ly: (0, 0)))
    if quantized:
        pad = [(0, 0), (0, blocks * g - bp), (0, 0)]
        for pool in pools:
            rows_ = jnp.pad(pool.scale[layer[0], page_table], pad)
            operands.append(rows_.reshape(b, blocks, g * p, h)
                            .transpose(0, 1, 3, 2))
            in_specs.append(pl.BlockSpec(
                (1, blocks, h, g * p),
                lambda bb, pt, ln, ly: (bb, 0, 0, 0)))
    stored = k_pool.data.dtype
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,   # page_table, lengths, layer
        grid=(b,),
        in_specs=in_specs,
        out_specs=out_spec,
        scratch_shapes=[
            pltpu.VMEM((2, g * p, wk), stored),             # K blocks
            pltpu.VMEM((2, g * p, wv), stored),             # V blocks
            pltpu.VMEM((h, wv), jnp.float32),               # accumulator
            pltpu.SemaphoreType.DMA((2, 2)),                # (K|V, buffer)
            pltpu.SMEM((1,), jnp.int32),                    # buffer in use
        ],
    )
    fn = pl.pallas_call(
        _paged_attn_kernel(b, bp, h, p, g, scale, quantized, kv_heads,
                           window, sink is not None),
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="paged_attention",
    )
    out = fn(page_table, lengths, layer, *operands)
    return out.astype(q.dtype) if kv_heads else out.reshape(b, h, d)


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
    """Ragged mixed prefill+decode batch through the in-place kernel —
    same per-row length masking as the lax twin (see
    `ragged_paged_attention_lax`), each row's live pages copied
    HBM->VMEM from where the page table says they lie."""
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
