"""Decode tier (mxnet_tpu.decoding): allocator invariants under
adversarial alloc/free patterns, COW fork correctness, paged-attention
kernel parity (lax vs pallas vs dense), continuous-batching greedy
parity against an unbatched reference loop, preempt-then-readmit
bit-identical continuations, per-step deadlines, streaming, the
zero-retrace guarantee over the pre-traced decode grid, and the
`decodingStats` view's pinned key shape."""
import random
import time
from unittest import mock

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import decoding as dec
from mxnet_tpu import serving, utils
from mxnet_tpu.decoding import attention as attn
from mxnet_tpu.decoding.blocks import (BlockAllocator, PageError,
                                       PagePoolExhausted, SCRATCH_PAGE,
                                       pages_needed)

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    for var in ("MXNET_DECODE_PAGE_SIZE", "MXNET_DECODE_PAGES",
                "MXNET_DECODE_MAX_BATCH", "MXNET_DECODE_PAGE_BUCKETS",
                "MXNET_DECODE_KERNEL", "MXNET_DECODE_RING_PREFILL",
                "MXNET_DECODE_MAX_TOKENS", "MXNET_DECODE_QUEUE_CAP",
                "MXNET_DECODE_PREFIX_CACHE", "MXNET_DECODE_SPEC_K",
                "MXNET_DECODE_SPEC_DRAFT", "MXNET_DECODE_KV_DTYPE",
                "MXNET_DECODE_SAMPLING_TEMPERATURE",
                "MXNET_DECODE_SAMPLING_TOP_K",
                "MXNET_DECODE_SAMPLING_TOP_P",
                "MXNET_DECODE_SAMPLING_SEED"):
        monkeypatch.delenv(var, raising=False)
    dec.stats._registry.clear()
    yield


CFG = dec.DecoderConfig(vocab=32, d_model=16, n_layers=2, n_heads=2,
                        d_ff=32, max_len=64)
PARAMS = dec.init_decoder_params(CFG, seed=0)


def _model(tpu=False, **kw):
    """A toy decoder; `tpu` builds it where the backend reads as a TPU,
    the fact the tier's defaults are resolved from (the kernel is then
    named, so that nothing is built for the chip)."""
    kw.setdefault("max_batch", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_buckets", (1, 2, 4))
    kw.setdefault("max_tokens", 8)
    if not tpu:
        return dec.DecodedModel("lm", 1, PARAMS, CFG, **kw)
    with mock.patch.object(utils, "pallas_interpret", lambda: False):
        return dec.DecodedModel("lm", 1, PARAMS, CFG, kernel="lax", **kw)


def _ref_greedy(prompt, n, cfg=CFG, eos=None, max_context=None):
    """Unbatched single-request reference: one dense forward per
    token — the parity oracle for every scheduler test. With
    `max_context` it also stops where the engine's capacity stop
    (finish reason "length") does: once the cache would hold
    max_context tokens, the token just emitted is the last."""
    eos = cfg.eos_id if eos is None else eos
    toks, out = list(prompt), []
    for _ in range(n):
        lg = dec.reference_logits(PARAMS,
                                  np.asarray([toks], np.int32), cfg)
        nxt = int(jnp.argmax(lg[0, -1]))
        if nxt == eos:
            break
        out.append(nxt)
        if max_context is not None and len(toks) >= max_context:
            break
        toks.append(nxt)
    return out


# ----------------------------------------------------------- allocator
def test_alloc_free_refcount_invariants():
    a = BlockAllocator(8, 4)
    assert a.capacity() == 7 and a.free_pages() == 7
    t = a.alloc(3)
    assert len(set(t)) == 3 and SCRATCH_PAGE not in t
    assert all(a.refcount(p) == 1 for p in t)
    assert a.pages_in_use() == 3
    a.check()
    a.free(t)
    assert a.free_pages() == 7
    with pytest.raises(PageError):
        a.free(t)            # double free
    a.check()
    # all-or-nothing: a too-large request leaves the pool untouched
    with pytest.raises(PagePoolExhausted):
        a.alloc(8)
    assert a.free_pages() == 7
    assert a.low_watermark() == 4  # the alloc(3) high-water point


def test_pages_needed():
    assert pages_needed(0, 4) == 0
    assert pages_needed(1, 4) == 1
    assert pages_needed(4, 4) == 1
    assert pages_needed(5, 4) == 2


def test_fragmentation_adversarial():
    """Interleaved variable-size alloc/free must never corrupt the
    free list and pages must be perfectly recyclable (no external
    fragmentation: any page serves any sequence)."""
    rng = mx.random.py_rng()
    a = BlockAllocator(33, 4)
    live = []
    for _ in range(300):
        if live and rng.random() < 0.45:
            a.free(live.pop(rng.randrange(len(live))))
        else:
            n = rng.randint(1, 5)
            try:
                live.append(a.alloc(n))
            except PagePoolExhausted:
                assert a.free_pages() < n
                if live:
                    a.free(live.pop(0))
        a.check()
        assert a.free_pages() + sum(len(t) for t in live) == 32
    for t in live:
        a.free(t)
    a.check()
    assert a.free_pages() == 32
    # after heavy churn the whole pool is still allocatable at once
    whole = a.alloc(32)
    assert sorted(whole) == list(range(1, 33))
    a.free(whole)


def test_cow_fork():
    a = BlockAllocator(8, 4)
    t1 = a.alloc(2)
    t2 = a.fork(t1)
    assert t2 == t1 and all(a.refcount(p) == 2 for p in t1)
    # first write through the fork allocates a private copy
    page, copy_from = a.make_writable(t2, 1)
    assert copy_from == t1[1] and page != t1[1]
    assert t2[1] == page and t1[1] == copy_from
    assert a.refcount(t1[1]) == 1 and a.refcount(page) == 1
    assert a.refcount(t1[0]) == 2    # index 0 is still shared
    # exclusively-owned page: no copy
    t3 = a.alloc(1)
    page2, copy2 = a.make_writable(t3, 0)
    assert copy2 is None and page2 == t3[0]
    a.check()
    a.free(t1)
    a.free(t2)
    a.free(t3)
    assert a.free_pages() == 7
    a.check()


def test_cow_page_copy_on_device():
    m = _model()
    try:
        eng = m.engine
        t1 = eng.allocator.alloc(1)
        # stamp recognizable content into the page via prefill
        m.generate([5, 6, 7, 8], max_new_tokens=1, timeout=30)
        src = t1[0]
        t2 = eng.allocator.fork(t1)
        page, copy_from = eng.allocator.make_writable(t2, 0)
        assert copy_from == src
        eng.copy_page(copy_from, page)
        k_src, v_src = eng.read_page(0, src)
        k_dst, v_dst = eng.read_page(0, page)
        np.testing.assert_array_equal(k_src, k_dst)
        np.testing.assert_array_equal(v_src, v_dst)
        eng.allocator.free(t1)
        eng.allocator.free(t2)
    finally:
        m.close()


# ----------------------------------------------------------- attention
def test_paged_attention_kernels_match_dense():
    rs = np.random.RandomState(3)
    b, h, d, p, bp, n = 3, 2, 8, 4, 3, 16
    q = rs.randn(b, h, d).astype(np.float32)
    k_pages = rs.randn(n, p, h, d).astype(np.float32)
    v_pages = rs.randn(n, p, h, d).astype(np.float32)
    table = rs.choice(np.arange(1, n), size=(b, bp),
                      replace=False).astype(np.int32)
    lengths = np.asarray([5, 12, 1], np.int32)

    # the pool's layout: a token's heads side by side in one row
    k_rows = k_pages.reshape(n, p, h * d)
    v_rows = v_pages.reshape(n, p, h * d)
    out_lax = np.asarray(dec.paged_attention_lax(
        q, k_rows, v_rows, table, lengths))

    # dense oracle: gather each row's true context and softmax it
    scale = 1.0 / np.sqrt(d)
    for row in range(b):
        ctx_k = k_pages[table[row]].reshape(bp * p, h, d)
        ctx_v = v_pages[table[row]].reshape(bp * p, h, d)
        ln = lengths[row]
        s = np.einsum("hd,thd->ht", q[row], ctx_k[:ln]) * scale
        e = np.exp(s - s.max(axis=-1, keepdims=True))
        w = e / e.sum(axis=-1, keepdims=True)
        ref = np.einsum("ht,thd->hd", w, ctx_v[:ln])
        np.testing.assert_allclose(out_lax[row], ref, atol=1e-5)


@pytest.mark.parametrize("kv_dtype,q_dtype,atol", [
    ("float32", "float32", 1e-5), ("bf16", "float32", 1e-5),
    ("int8", "float32", 1e-5),
    # the served case: bf16 activations, one-pass products, a bf16 result
    ("bf16", "bfloat16", 2e-2), ("int8", "bfloat16", 2e-2)])
def test_single_query_lax_attends_stored_rows(kv_dtype, q_dtype, atol):
    """`paged_attention_lax` works on the gathered rows as the pool
    stores them (no split into heads) for every storage type. Oracle: a
    plain dense softmax attention per row over the values the pool
    holds, no pool and no pages. Ragged lengths: one token, a full
    bucket, a half-filled last page, padding entries on the scratch
    page. EVERY slot no row may read is poisoned — the scratch page
    and each page's slots past its row's length: K rows (an int8
    pool's K scales) NaN, V rows and scales 1e30 (a weight of exactly 0
    times 1e30 is 0; times NaN it would be NaN in any kernel) — so a
    masked row read into a result shows. (The in-place kernel against
    this form: `test_in_place_kernel_matches_lax` below.)"""
    from mxnet_tpu.decoding import quant

    rs = np.random.RandomState(11)
    b, h, d, p, bp, n = 4, 4, 8, 4, 3, 16
    lengths = np.asarray([1, bp * p, 5, 9], np.int32)
    q = jnp.asarray(rs.randn(b, h, d), q_dtype)
    table = np.zeros((b, bp), np.int32)            # padding: scratch page 0
    free = iter(rs.permutation(np.arange(1, n)))
    held = {"k": [], "v": []}
    pools = {}
    for name, poison in (("k", np.nan), ("v", 1e30)):
        pool = quant.make_pool((1, n, p, h, d), kv_dtype)
        data = jnp.full_like(pool.data, 127 if kv_dtype == "int8"
                             else poison)
        scale = None if pool.scale is None \
            else jnp.full_like(pool.scale, poison)
        pools[name] = quant.KVPool(data, scale)
    for row, ln in enumerate(lengths):
        pages = [int(next(free)) for _ in range(pages_needed(ln, p))]
        table[row, :len(pages)] = pages
        at = np.arange(ln)
        for name in ("k", "v"):
            vals = jnp.asarray(rs.randn(ln, h, d), jnp.float32)
            pools[name], _ = quant.kv_scatter(
                pools[name], 0, np.asarray(pages)[at // p], at % p, vals)
            if kv_dtype == "int8":
                qv, sc, _ = quant.quantize_values(vals)
                vals = quant.dequantize_values(qv, sc)
            elif kv_dtype == "bf16":
                vals = vals.astype(jnp.bfloat16)
            held[name].append(np.asarray(vals, np.float64))

    layers = pools["k"].layer(0), pools["v"].layer(0)
    out = np.asarray(dec.paged_attention_lax(
        q, *layers, table, lengths), np.float64)
    assert np.isfinite(out).all()
    q64 = np.asarray(q, np.float64)
    for row in range(b):
        sc = np.einsum("hd,thd->ht", q64[row], held["k"][row]) / np.sqrt(d)
        e = np.exp(sc - sc.max(axis=-1, keepdims=True))
        ref = np.einsum("ht,thd->hd", e / e.sum(axis=-1, keepdims=True),
                        held["v"][row])
        np.testing.assert_allclose(out[row], ref, atol=atol,
                                   err_msg=f"row {row}")


# the in-place kernel, interpreted: page_size 4, blocks of 2 pages, a
# bucket of 5 pages (not a multiple of the block). Lengths: an empty
# row, one token, exactly one page, a block's edge, one past it, two
# blocks, the full bucket, and empty rows between and after live ones
_KERNEL_LENGTHS = (0, 1, 4, 8, 9, 0, 16, 20, 13, 0)


def _ragged_pools(kv_dtype, lengths, page, bucket, heads, dim, seed,
                  foreign):
    """(K layer, V layer, page table) of two-layer pools holding
    `lengths` tokens a row on layer 1. Every slot no row may read —
    pages no row owns, the scratch page the table's padding points at,
    and the slots of owned pages past each length — holds `foreign`
    values (seeded), finite as pool contents are."""
    from mxnet_tpu.decoding import quant

    rs = np.random.RandomState(seed)
    n = 1 + sum(pages_needed(ln, page) for ln in lengths) + 6
    fs = np.random.RandomState(foreign)
    pools = []
    for _ in "kv":
        pool = quant.make_pool((2, n, page, heads, dim), kv_dtype)
        pool, _ = quant.kv_scatter(
            pool, 1, np.repeat(np.arange(n), page),
            np.tile(np.arange(page), n),
            jnp.asarray(3.0 * fs.randn(n * page, heads, dim), jnp.float32))
        pools.append(pool)
    table = np.zeros((len(lengths), bucket), np.int32)
    free = iter(rs.permutation(np.arange(1, n)))
    for row, ln in enumerate(lengths):
        pages = np.asarray(
            [next(free) for _ in range(pages_needed(ln, page))], np.int32)
        table[row, :len(pages)] = pages
        at = np.arange(ln)
        for i in range(2):
            pools[i], _ = quant.kv_scatter(
                pools[i], 1, pages[at // page], at % page,
                jnp.asarray(rs.randn(ln, heads, dim), jnp.float32))
    return pools[0].layer(1), pools[1].layer(1), table


@pytest.mark.parametrize("kv_dtype,q_dtype,atol", [
    ("float32", "float32", 1e-5), ("bf16", "float32", 1e-5),
    ("int8", "float32", 1e-5),
    ("bf16", "bfloat16", 2e-2), ("int8", "bfloat16", 2e-2)])
@pytest.mark.parametrize("block_pages", [2, None])
def test_in_place_kernel_matches_lax(kv_dtype, q_dtype, atol, block_pages):
    """`paged_attention_pallas` against `paged_attention_lax` on the
    same pools, for every storage type, over `_KERNEL_LENGTHS` (blocks
    of 2 pages; the default, which the bucket caps: all 5 pages in one
    block). An empty row comes out zeros (nothing reads it).
    INDEPENDENCE: pools that differ only in what no row owns — foreign
    pages, the scratch page, an owned page's slots past the length —
    give the same output to the last bit: nothing a row does not own
    reaches a sum."""
    h, d, page, bucket = 4, 8, 4, 5
    lengths = np.asarray(_KERNEL_LENGTHS, np.int32)
    q = jnp.asarray(np.random.RandomState(5).randn(len(lengths), h, d),
                    q_dtype)
    outs = []
    for foreign in (101, 202):
        k, v, table = _ragged_pools(kv_dtype, lengths, page, bucket, h, d,
                                    seed=17, foreign=foreign)
        outs.append(np.asarray(dec.paged_attention_pallas(
            q, k, v, table, lengths, block_pages=block_pages), np.float32))
    live = lengths > 0
    ref = np.asarray(dec.paged_attention_lax(q, k, v, table, lengths),
                     np.float32)
    assert np.isfinite(outs[0]).all()
    np.testing.assert_allclose(outs[0][live], ref[live], atol=atol)
    np.testing.assert_array_equal(outs[0][~live], 0.0)
    np.testing.assert_array_equal(outs[0], outs[1])


def _grouped_pools(kv_dtype, lengths, page, bucket, kv_heads, dk, dv,
                   window, seed, foreign):
    """(K layer, V layer, page table, K rows, V rows) of two-layer
    pools for grouped queries: `kv_heads` heads of `dk` in a K row and
    of `dv` in a V row, `lengths` tokens a row on layer 1. With a
    `window` the pages wholly behind a row's last `window` positions
    are RELEASED: their table entries point at the scratch page. What no
    row may read holds `foreign` values."""
    from mxnet_tpu.decoding import quant

    rs = np.random.RandomState(seed)
    n = 1 + sum(pages_needed(ln, page) for ln in lengths) + 6
    fs = np.random.RandomState(foreign)
    table = np.zeros((len(lengths), bucket), np.int32)
    free = iter(rs.permutation(np.arange(1, n)))
    owned = [np.asarray([next(free) for _ in range(pages_needed(ln, page))],
                        np.int32) for ln in lengths]
    layers, rows = [], []
    for name, width in (("k", dk), ("v", dv)):
        pool = quant.make_plane(
            2, n, page, quant.Plane(name, kv_heads * width, kv_heads),
            kv_dtype)
        pool, _ = quant.kv_scatter(
            pool, 1, np.repeat(np.arange(n), page),
            np.tile(np.arange(page), n), jnp.asarray(
                3.0 * fs.randn(n * page, kv_heads * width), jnp.float32))
        kept = []
        for row, ln in enumerate(lengths):
            at = np.arange(ln)
            vals = rs.randn(ln, kv_heads * width).astype(np.float32)
            if ln:
                pool, _ = quant.kv_scatter(
                    pool, 1, owned[row][at // page], at % page,
                    jnp.asarray(vals))
            kept.append(np.asarray(jnp.asarray(vals).astype(
                pool.data.dtype).astype(jnp.float32)))
        layers.append(pool.layer(1))
        rows.append(kept)
    for row, ln in enumerate(lengths):
        first = max(0, ln - window) // page if window else 0
        table[row, first:len(owned[row])] = owned[row][first:]
    return layers[0], layers[1], table, rows[0], rows[1]


@pytest.mark.parametrize("kv_dtype,atol", [("float32", 1e-5),
                                           ("bf16", 1e-5)])
@pytest.mark.parametrize("window,with_sink", [
    (None, False), (8, False), (8, True), (None, True)])
def test_grouped_kernel_matches_lax_and_dense(kv_dtype, atol, window,
                                              with_sink):
    """The in-place kernel (interpreted) against the lax form, and both
    against attention worked out row by row, for what the window-mixed
    block asks of them: a group of 4 query heads against each of 2 KV
    heads, keys of 12 beside values of 8 (the served 192 and 128 in
    proportion), a window of 8 = 2 pages with the pages behind it
    released, a sink a head, on ragged lengths with rows shorter than
    the window. Pools that differ only in what no row may read (the
    released pages' scratch page among it) give the same output to the
    last bit."""
    h, kv, dk, dv, page, bucket = 8, 2, 12, 8, 4, 5
    lengths = np.asarray(_KERNEL_LENGTHS, np.int32)
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(len(lengths), h, dk), jnp.float32)
    sink = jnp.asarray(rs.randn(h), jnp.float32) if with_sink else None
    how = {"kv_heads": kv, "window": window, "sink": sink}
    outs = []
    for foreign in (101, 202):
        k, v, table, k_rows, v_rows = _grouped_pools(
            kv_dtype, lengths, page, bucket, kv, dk, dv, window, seed=17,
            foreign=foreign)
        outs.append(np.asarray(dec.paged_attention_pallas(
            q, k, v, table, lengths, block_pages=2, **how), np.float32))
    lax = np.asarray(dec.paged_attention_lax(q, k, v, table, lengths,
                                             **how), np.float32)
    live = lengths > 0
    assert outs[0].shape == (len(lengths), h, dv)
    np.testing.assert_allclose(outs[0][live], lax[live], atol=atol)
    np.testing.assert_array_equal(outs[0][~live], 0.0)
    np.testing.assert_array_equal(outs[0], outs[1])
    for row in np.nonzero(live)[0]:
        ln = int(lengths[row])
        lo = max(0, ln - window) if window else 0
        for head in range(h):
            g = head // (h // kv)
            keys = k_rows[row][lo:, g * dk:(g + 1) * dk]
            vals = v_rows[row][lo:, g * dv:(g + 1) * dv]
            sc = keys @ np.asarray(q[row, head]) / np.sqrt(dk)
            m = max(sc.max(), float(sink[head]) if with_sink else -np.inf)
            e = np.exp(sc - m)
            den = e.sum() + (np.exp(float(sink[head]) - m)
                             if with_sink else 0.0)
            np.testing.assert_allclose(lax[row, head], (e / den) @ vals,
                                       atol=1e-5)


def test_grouped_multi_query_form_matches_single_queries():
    """The multi-query form with grouped heads, a window and a sink,
    its keys walked in blocks with the softmax online across them,
    against the single-query form one query at a time."""
    from mxnet_tpu.decoding import attention

    h, kv, dk, dv, page, bucket, window = 8, 2, 12, 8, 4, 8, 8
    k, v, table, _, _ = _grouped_pools(
        "float32", [30], page, bucket, kv, dk, dv, None, seed=3, foreign=4)
    rs = np.random.RandomState(6)
    q = jnp.asarray(rs.randn(1, 9, h, dk), jnp.float32)
    sink = jnp.asarray(rs.randn(h), jnp.float32)
    pos = np.arange(21, 30, dtype=np.int32)[None]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(attention, "KEY_BLOCK", 8)
        for how in ({"kv_heads": kv},
                    {"kv_heads": kv, "window": window, "sink": sink}):
            many = np.asarray(dec.attention.paged_attention_lax_multi(
                q, k, v, table, pos, **how))
            for j in range(9):
                one = dec.paged_attention_lax(
                    q[:, j], k, v, table, pos[:, j] + 1, **how)
                np.testing.assert_allclose(many[:, j], np.asarray(one),
                                           atol=1e-5)


def test_in_place_kernel_reads_no_page_past_the_table():
    """A length past the bucket (a row at its cap) attends the table's
    pages and no further, as the lax form's mask over the gathered
    context does: the page ids past the table are not there to read."""
    h, d, page, bucket = 4, 8, 4, 5
    k, v, table = _ragged_pools("float32", [bucket * page, 7], page,
                                bucket, h, d, seed=3, foreign=4)
    q = jnp.asarray(np.random.RandomState(6).randn(2, h, d), jnp.float32)
    past = np.asarray([bucket * page + 3, 7], np.int32)
    np.testing.assert_allclose(
        np.asarray(dec.paged_attention_pallas(q, k, v, table, past,
                                              block_pages=2)),
        np.asarray(dec.paged_attention_lax(q, k, v, table, past)),
        atol=1e-5)


@pytest.mark.parametrize("kv_dtype", ["float32", "bf16", "int8"])
@pytest.mark.parametrize("kernel", ["lax", "pallas"])
def test_engine_tokens_match_dense_reference(kernel, kv_dtype):
    """The pool's layout end to end: a prefill, a copy-on-write fork of
    the prompt's pages (the half-filled last page is COPIED on the
    device), then 20 decode steps of the original and the fork side by
    side. Both rows must emit the dense `reference_logits` greedy
    stream, through either kernel, at every page precision."""
    prompt, steps = [3, 7, 11, 2, 9, 14, 5, 21, 8, 30], 20
    ref = _ref_greedy(prompt, steps + 1, eos=-1)
    eng = dec.DecodeEngine(
        PARAMS, CFG, max_batch=2, page_size=4, num_pages=32,
        page_buckets=(8,), kernel=kernel, prefix_cache=True,
        merged_step=False, kv_dtype=kv_dtype).warmup()
    alloc = eng.allocator
    need = pages_needed(len(prompt) + steps, eng.page_size)
    n_prompt = pages_needed(len(prompt), eng.page_size)
    t1 = alloc.alloc(need)
    first = eng.prefill(prompt, t1[:n_prompt])
    # the fork shares the prompt's pages and owns the rest; its first
    # write lands in the shared, half-filled page: copy, then write
    t2 = alloc.fork(t1[:n_prompt]) + alloc.alloc(need - n_prompt)
    page, copy_from = alloc.make_writable(t2, n_prompt - 1)
    assert copy_from == t1[n_prompt - 1] and page != copy_from
    eng.copy_page(copy_from, page)
    np.testing.assert_array_equal(eng.read_page(1, copy_from)[0],
                                  eng.read_page(1, page)[0])
    floor = eng.traces()

    table = np.asarray([t1, t2], np.int32)
    out = [[first], [first]]
    for t in range(steps):
        toks = eng.step([row[-1] for row in out], table,
                        [len(prompt) + t] * 2, [True, True])
        for row, tok in zip(out, toks):
            row.append(int(tok))
    assert out[0] == ref and out[1] == ref
    assert eng.traces() == floor
    # both rows wrote the same stream: the original's page and the
    # fork's copy of it hold the same rows, each a token's H*D values
    k1, _ = eng.read_page(0, t1[n_prompt - 1])
    k2, _ = eng.read_page(0, t2[n_prompt - 1])
    assert k1.shape == (eng.page_size, CFG.d_model)
    np.testing.assert_array_equal(k1, k2)


def test_pool_layout_is_part_of_the_engine_digest(monkeypatch):
    """An AOT bundle's programs take the pools as arguments: one
    compiled around another storage order must not be found."""
    from mxnet_tpu.decoding import quant as kvq

    def digest():
        return dec.DecodeEngine(PARAMS, CFG, max_batch=2, page_size=4,
                                num_pages=8, page_buckets=(1,))._digest

    was = digest()
    assert digest() == was
    monkeypatch.setattr(kvq, "POOL_LAYOUT", "layers,pages,slots,heads,dim")
    assert digest() != was


def test_get_kernel():
    assert dec.get_kernel("lax") is dec.paged_attention_lax
    assert dec.get_kernel("pallas") is dec.paged_attention_pallas
    with pytest.raises(ValueError):
        dec.get_kernel("nope")


@pytest.mark.parametrize("named,backend,want", [
    (None, "cpu", "lax"), (None, "tpu", "pallas"),
    ("lax", "tpu", "lax"), ("pallas", "cpu", "pallas")])
def test_default_kernel_follows_the_backend(monkeypatch, named, backend,
                                            want):
    """With MXNET_DECODE_KERNEL unset the backend decides — the lax
    form off the TPU, where the kernel would be interpreted, the
    in-place kernel on one — and an explicit value wins."""
    from mxnet_tpu.decoding import config

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if named is None:
        monkeypatch.delenv("MXNET_DECODE_KERNEL", raising=False)
    else:
        monkeypatch.setenv("MXNET_DECODE_KERNEL", named)
    assert config.kernel() == want


def test_kernel_switch_is_read_from_the_environment(monkeypatch):
    """MXNET_DECODE_KERNEL picks the engine's attention kernel where
    the caller names none (the chip's lax-against-Pallas reading went
    through it); an engine built that way serves the same greedy
    tokens, and a caller's own choice wins over the variable."""
    monkeypatch.setenv("MXNET_DECODE_KERNEL", "pallas")
    for named, want in ((None, "pallas"), ("lax", "lax")):
        m = _model(kernel=named)
        try:
            assert m.engine.kernel_name == want
            assert m.generate([5, 6, 7], max_new_tokens=4, timeout=60) \
                == _ref_greedy([5, 6, 7], 4)
        finally:
            m.close()


# ------------------------------------------- paged vs rectangular cache
def test_paged_cache_reserves_less_than_a_rectangular_one():
    """What the pages are for, as a count: over ragged requests the
    pool reserves ceil(context / page_size) pages a sequence, so the
    share of reserved slots that never hold a token is under what the
    (request, max_context) rectangle of a one-shot batcher leaves
    empty. The pages are the allocator's own count."""
    m = _model(prefix_cache=False)
    try:
        page = m.engine.allocator.page_size
        prompts = [[5, 6, 7], [3], list(range(2, 13)), [9] * 6]
        before = m.engine.pool_stats()["pages_allocated"]
        outs = [m.generate(p, max_new_tokens=5, timeout=60)
                for p in prompts]
        pages = m.engine.pool_stats()["pages_allocated"] - before
        # a sequence holds its prompt and every token it emitted but
        # the last, which no step has fed back
        held = [len(p) + len(o) - 1 for p, o in zip(prompts, outs)]
        assert pages == sum(pages_needed(c, page) for c in held)
        tokens = sum(held)
        waste_paged = 1 - tokens / (pages * page)
        waste_oneshot = 1 - tokens / (len(prompts)
                                      * m.engine.max_context)
        assert 0 <= waste_paged < waste_oneshot
        assert m.stats.snapshot()["traces_since_warmup"] == 0
    finally:
        m.close()


# ----------------------------------------------- parity + zero retrace
def test_single_request_parity_and_trace_grid():
    m = _model()
    try:
        # the warmup grid with the merged step (default): one prefill
        # per length bucket, one ragged decode per pages bucket, plus
        # the page-copy program. The per-length-bucket tail-prefill
        # programs are GONE — prompt tails after a prefix-cache hit
        # ride the decode step's extra rows instead of a dedicated
        # program (MXNET_DECODE_MERGED_STEP=0 restores the old grid).
        counts = m.engine.trace_counts()
        assert counts == {"copy_page": 1, "prefill@4": 1,
                          "prefill@8": 1, "prefill@16": 1,
                          "decode@1": 1, "decode@2": 1, "decode@4": 1}
        floor = m.engine.traces()
        for prompt in ([5, 6, 7], [3], list(range(2, 13))):
            out = m.generate(prompt, max_new_tokens=6, timeout=60)
            assert out == _ref_greedy(prompt, 6)
        assert m.engine.traces() == floor
        assert m.stats.snapshot()["traces_since_warmup"] == 0
    finally:
        m.close()


def test_continuous_batching_parity_concurrent():
    """Mid-stream admissions and evictions: more requests than batch
    rows, mixed lengths/budgets — every output token-identical to the
    unbatched reference, zero retraces."""
    m = _model(max_batch=4, num_pages=64, page_buckets=(1, 2, 4))
    try:
        floor = m.engine.traces()
        # a generator of its own: the jobs must not depend on which
        # tests drew from the process-wide one before this
        rng = random.Random(0)
        jobs = [(
            [rng.randrange(2, CFG.vocab) for _ in
             range(rng.randint(1, 12))],
            rng.randint(1, 8),
        ) for _ in range(12)]
        # 12 prompt tokens + 8 new ones overrun the 16-token context
        # (4 pages of 4): such a stream ends at capacity, in the
        # engine and in the reference alike
        cap = m.engine.max_context
        assert any(len(p) + n - 1 > cap for p, n in jobs)
        futs = [m.submit(p, max_new_tokens=n) for p, n in jobs]
        for (p, n), f in zip(jobs, futs):
            assert f.result(120) == _ref_greedy(p, n, max_context=cap)
        assert m.engine.traces() == floor
        snap = m.stats.snapshot()
        assert snap["completed"] == 12
        # every non-free page is held by the prefix cache, not leaked
        assert snap["pages_free"] == 63 - snap["prefix_cached_pages"]
    finally:
        m.close()


# with steps in flight a victim's tokens come out before it is preempted;
# the third keeps them by the backend's default, the argument unset
AHEAD = [{}, {"run_ahead": 3, "merged_step": False},
         {"tpu": True, "merged_step": False}]
AHEAD_IDS = ["one_step", "run_ahead", "tpu_default"]


@pytest.mark.parametrize("ahead", AHEAD, ids=AHEAD_IDS)
def test_preempt_then_readmit_bit_identical(ahead):
    """A pool far too small for the offered load: sequences are
    preempted (pages dropped) and readmitted (re-prefilled); the
    continuation must be BIT-identical to an uninterrupted run."""
    m = _model(max_batch=4, num_pages=9, page_buckets=(1, 2, 4),
               max_tokens=12, queue_cap=64, **ahead)
    try:
        floor = m.engine.traces()
        prompts = [[int(t) for t in
                    np.random.RandomState(i).randint(2, 32, size=6)]
                   for i in range(6)]
        futs = [m.submit(p, max_new_tokens=10, priority=i % 2)
                for i, p in enumerate(prompts)]
        for p, f in zip(prompts, futs):
            assert f.result(240) == _ref_greedy(p, 10)
        snap = m.stats.snapshot()
        assert snap["preemptions"] > 0
        assert snap["readmissions"] == snap["preemptions"]
        assert m.engine.traces() == floor  # readmission retraces nothing
        # only prefix-cached pages may remain; flushing the cache must
        # drain the pool to empty (nothing leaked by preempt/readmit)
        m.scheduler.cache.release_all()
        assert m.engine.allocator.stats()["pages_in_use"] == 0
        m.engine.allocator.check()
    finally:
        m.close()


@pytest.mark.parametrize("ahead", AHEAD, ids=AHEAD_IDS)
def test_pool_exhaustion_never_crashes(ahead):
    """CI gate iii at unit scale: offered load >> pool capacity keeps
    resolving every future (no OOM, no dead scheduler)."""
    m = _model(max_batch=4, num_pages=5, page_buckets=(1, 2),
               max_tokens=6, queue_cap=64, **ahead)
    try:
        futs = [m.submit([2 + i, 3, 4], max_new_tokens=5)
                for i in range(10)]
        for f in futs:
            assert f.result(240) is not None
        assert m.engine.allocator.stats()["pages_in_use"] == 0
    finally:
        m.close()


# ------------------------------------------------- deadlines/streaming
def test_deadline_resolves_mid_generation_and_frees_pages():
    m = _model()
    try:
        f = m.submit([3, 4, 5], max_new_tokens=8, deadline_ms=0.001)
        with pytest.raises(serving.DeadlineExceededError):
            f.result(60)
        deadline = time.monotonic() + 10
        while (m.engine.allocator.stats()["pages_in_use"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert m.engine.allocator.stats()["pages_in_use"] == 0
        m.engine.allocator.check()
        assert m.stats.snapshot()["expired"] == 1
    finally:
        m.close()


def test_streaming_matches_result():
    m = _model()
    try:
        fut = m.submit([3, 4], max_new_tokens=5)
        streamed = list(fut.stream(timeout=60))
        assert streamed == fut.result(1) == _ref_greedy([3, 4], 5)
    finally:
        m.close()


def test_finish_reasons():
    # max_tokens
    m = _model()
    try:
        f = m.submit([5, 6, 7], max_new_tokens=2)
        f.result(60)
        assert f.finish_reason == "max_tokens"
        # length: the context hits max_context (= 4 pages * 4 tokens)
        f2 = m.submit(list(range(2, 16)), max_new_tokens=8)
        out2 = f2.result(60)
        assert f2.finish_reason == "length"
        assert len(out2) + 14 == m.engine.max_context + 1
    finally:
        m.close()
    # eos: rebuild the model declaring a token we KNOW it emits as EOS
    known = _ref_greedy([5, 6, 7], 4)
    import dataclasses
    cfg_eos = dataclasses.replace(CFG, eos_id=known[0])
    m2 = dec.DecodedModel("lm-eos", 1, PARAMS, cfg_eos, max_batch=2,
                          page_size=4, num_pages=32,
                          page_buckets=(1, 2, 4), max_tokens=8)
    try:
        f3 = m2.submit([5, 6, 7], max_new_tokens=8)
        out3 = f3.result(60)
        assert f3.finish_reason == "eos"
        assert out3 == _ref_greedy([5, 6, 7], 8, eos=known[0])
    finally:
        m2.close()


def test_admission_errors():
    m = _model(queue_cap=0)
    try:
        with pytest.raises(serving.ServerBusyError):
            m.submit([3, 4])
        assert m.stats.snapshot()["rejected"] == 1
        with pytest.raises(serving.ServingError):
            m.submit([])
        with pytest.raises(serving.ServingError):
            m.submit([CFG.vocab + 5])
        with pytest.raises(serving.ServingError):
            m.submit(list(range(2, 2 + 17)))  # > max_context 16
    finally:
        m.close()
    with pytest.raises(serving.ServerClosedError):
        m.submit([3, 4])


# ------------------------------------------------------- randomized soak
@pytest.mark.parametrize("ahead", AHEAD, ids=AHEAD_IDS)
def test_randomized_soak(ahead):
    """Randomized continuous traffic (seeded via mx.random.py_rng —
    MX005-clean): mixed lengths, budgets, priorities, deadlines. Every
    future resolves, non-expired outputs match the reference exactly,
    the allocator ends clean, and the trace count never moves."""
    rng = mx.random.py_rng()
    m = _model(max_batch=3, num_pages=12, page_buckets=(1, 2, 4),
               queue_cap=128, max_tokens=10, **ahead)
    try:
        floor = m.engine.traces()
        jobs = []
        for _ in range(16):
            prompt = [rng.randrange(2, CFG.vocab)
                      for _ in range(rng.randint(1, 10))]
            n = rng.randint(1, 7)
            dl = 0.001 if rng.random() < 0.2 else None
            fut = m.submit(prompt, max_new_tokens=n,
                           priority=rng.randint(0, 2), deadline_ms=dl)
            jobs.append((prompt, n, dl, fut))
            if rng.random() < 0.3:
                time.sleep(0.002)
        for prompt, n, dl, fut in jobs:
            try:
                out = fut.result(240)
                assert out == _ref_greedy(prompt, n)
            except serving.DeadlineExceededError:
                assert dl is not None
        assert m.engine.traces() == floor
        m.scheduler.cache.release_all()
        assert m.engine.allocator.stats()["pages_in_use"] == 0
        m.engine.allocator.check()
    finally:
        m.close()


# ----------------------------------------------------- ring prefill path
def test_seq_mesh_for_divisibility():
    from mxnet_tpu.parallel.ring_attention import seq_mesh_for
    mesh = seq_mesh_for(16)
    assert 16 % mesh.shape["seq"] == 0 and mesh.shape["seq"] > 1
    assert seq_mesh_for(7).shape["seq"] == 7   # 7 of 8 devices divide
    assert seq_mesh_for(13).shape["seq"] == 1  # prime > devices: degrade


def test_ring_prefill_long_prompt():
    """Prompts at/above MXNET_DECODE_RING_PREFILL prefill through ring
    attention (sequence sharded over the 8-device CPU mesh); greedy
    tokens must match the dense reference."""
    m = _model(ring_prefill=16, num_pages=32)
    try:
        prompt = list(range(2, 14))   # buckets to 16 -> ring path
        out = m.generate(prompt, max_new_tokens=4, timeout=120)
        assert out == _ref_greedy(prompt, 4)
    finally:
        m.close()


# ------------------------------------------------------- stats + server
def test_decoding_stats_view_shape_pinned():
    """The decodingStats snapshot key set is a published surface
    (dashboards, /metrics) — additions need a deliberate pin bump, and
    serving's own snapshot shape must be untouched by the decode tier."""
    m = _model()
    try:
        m.generate([5, 6, 7], max_new_tokens=3, timeout=60)
        dec.stats._register(m.key, m.stats)
        snap = dec.decoding_stats()[m.key]
        assert sorted(snap) == sorted((
            "submitted", "completed", "failed", "rejected", "expired",
            "cancelled", "preemptions", "readmissions", "prefills",
            "prefill_tokens", "decode_tokens", "steps", "greedy_steps",
            "prefill_chunks",
            "live_pages", "bucket_pages", "live_page_share",
            "ctx_tokens", "window_tokens", "pages_held",
            "window_pages_released",
            "spec_proposed", "spec_accepted", "spec_acceptance_rate",
            "tokens_per_target_step",
            "nonfinite_logit_steps", "nonfinite_logits",
            "quant_clip_steps", "quant_clip_values",
            "prefill_tokens_per_s", "decode_tokens_per_s",
            "p50_token_ms", "p95_token_ms", "p99_token_ms",
            "traces_since_warmup", "waiting", "active", "pages_total",
            "pages_free", "kv_occupancy", "free_low_watermark",
            "pages_allocated", "prefix_hits", "prefix_misses",
            "prefix_hit_rate", "prefix_pages_reused",
            "prefix_evictions", "prefix_cached_pages",
            "kv_dtype", "kv_bytes_per_token", "pool_capacity_tokens"))
        assert snap["decode_tokens"] == 2 and snap["prefills"] == 1
        assert snap["prefill_tokens"] == 3
        assert snap["traces_since_warmup"] == 0
    finally:
        dec.stats._unregister(m.key)
        m.close()


def test_model_server_integration():
    with serving.ModelServer() as srv:
        srv.load_decoder("lm", PARAMS, CFG, max_batch=2, page_size=4,
                         num_pages=32, page_buckets=(1, 2, 4),
                         max_tokens=8)
        out = srv.generate("lm", [5, 6, 7], max_new_tokens=4,
                           timeout=60)
        assert out == _ref_greedy([5, 6, 7], 4)
        assert list(srv.stream("lm", [3, 4], max_new_tokens=3,
                               timeout=60)) == _ref_greedy([3, 4], 3)
        # one-shot API refuses decoder models, and vice versa
        with pytest.raises(serving.ServingError):
            srv.submit("lm", {"data": np.zeros((3,), np.int32)})
        assert "lm:1" in dec.decoding_stats()
        srv.unload("lm")
        assert dec.decoding_stats() == {}
        with pytest.raises(serving.ServingError):
            srv.generate("lm", [5, 6])


def test_duplicate_decoder_version_rejected():
    with serving.ModelServer() as srv:
        srv.load_decoder("lm", PARAMS, CFG, max_batch=2, page_size=4,
                         num_pages=16, page_buckets=(1, 2))
        with pytest.raises(serving.ServingError):
            srv.load_decoder("lm", PARAMS, CFG, max_batch=2,
                             page_size=4, num_pages=16,
                             page_buckets=(1, 2))
        srv.unload("lm")


# -------------------------------------------- one-shot batcher deadlines
def test_batcher_pop_expired():
    """The serving-side deadline fix: expired requests leave the queue
    at the next worker wake-up, not only when their own bucket
    flushes."""
    from concurrent.futures import Future
    from mxnet_tpu.serving.batcher import (BucketSpec, DynamicBatcher,
                                           _Request)
    spec = BucketSpec({"data": ("L",)}, (1, 2), length_buckets=(8, 16))
    b = DynamicBatcher(spec, max_wait_us=10_000_000, queue_cap=8)
    now = time.monotonic()
    dead = _Request({"data": np.zeros((3,), np.int32)}, Future(),
                    now - 1.0, 3, 8)
    alive = _Request({"data": np.zeros((12,), np.int32)}, Future(),
                     now + 60.0, 12, 16)
    b.put(dead)
    b.put(alive)
    assert dead.expired() and not alive.expired()
    popped = b.pop_expired()
    assert popped == [dead]
    assert b.depth() == 1            # the live request stays queued
    assert b.pop_expired() == []


# ------------------------------------------ names inside a decode step
PARTS = ("embed", "qkv", "kv_write", "attn", "out", "mlp", "logits",
         "sample")


def test_decode_program_holds_every_part_name():
    """The compiled decode and prefill programs carry the named scopes
    (metadata only): `embed`, `logits`, `sample` outside the layers,
    `l{i}/<part>` inside, and the profiling layer's scope map places
    the programs' instructions in them after the engine is gone."""
    from mxnet_tpu import profiling

    m = _model()
    eng = m.engine
    text = eng.decode_program_text(2)
    want = [f"/{p}/" for p in ("embed", "logits", "sample")] + [
        f"/l{i}/{p}/" for i in range(CFG.n_layers)
        for p in ("qkv", "kv_write", "attn", "out", "mlp")]
    for seg in want:
        assert f"jit(decode_p2){seg}" in text, seg
    program = eng.step_program(2)
    assert program == "jit_decode_p2"
    m.close(drain=False)
    del m, eng
    smap = profiling.scope_map(program)
    scopes = set(smap.values())
    for i in range(CFG.n_layers):
        for p in ("qkv", "kv_write", "attn", "out", "mlp"):
            assert any(s == f"l{i}/{p}" or s.startswith(f"l{i}/{p}/")
                       for s in scopes), (i, p)
    assert {"embed", "logits"} <= scopes
    assert any(s.split("/")[0] == "sample" for s in scopes)
    pre = set(profiling.scope_map("jit_prefill_t4").values())
    assert {"l0/attn", "l1/mlp", "logits"} <= {
        "/".join(s.split("/")[:2]) if s.startswith("l") else s
        for s in pre}


def test_engine_programs_have_names_of_their_own():
    """Every program of the grid compiles to a module named after what
    it is: none is `jit_impl`, no two share a name, and the record of
    each carries it."""
    from mxnet_tpu import profiling

    draft_cfg = dec.DecoderConfig(vocab=32, d_model=8, n_layers=1,
                                  n_heads=1, d_ff=16, max_len=64)
    eng = dec.DecodeEngine(
        PARAMS, CFG, max_batch=2, page_size=4, num_pages=32,
        page_buckets=(1, 2), prefix_cache=True, merged_step=False,
        draft_params=dec.init_decoder_params(draft_cfg, seed=1),
        draft_cfg=draft_cfg, spec_k=2).warmup()
    recs = profiling.records_for(digest=eng._digest)
    modules = {r["kind"]: r["module"] for r in recs}
    assert len(set(modules.values())) == len(modules) == len(recs)
    assert "jit_impl" not in modules.values()
    assert modules["decode@2"] == "jit_decode_p2"
    assert modules["prefill@4"] == "jit_prefill_t4"
    assert modules["prefill_tail@8"] == "jit_prefill_tail_t8"
    assert modules["draft_prefill@4"] == "jit_draft_prefill_t4"
    assert modules["draft@1"] == "jit_draft_p1"
    assert modules["verify@2"] == "jit_verify_p2"
    assert modules["copy_page"] == "jit_copy_page"
    assert eng.step_program(2) == "jit_verify_p2"
    # the speculative bodies carry the same part names
    verify = set(profiling.scope_map("jit_verify_p2").values())
    assert any(s.startswith("l0/kv_write") for s in verify)
    draft = set(profiling.scope_map("jit_draft_p1").values())
    assert any(s.startswith("draft1/l0/attn") for s in draft)


def _spans_between(t0, t1):
    from mxnet_tpu.telemetry import trace as ttrace

    return [s for s in ttrace.recent_spans()
            if s.t0 >= t0 and s.t1 <= t1]


def test_scheduler_turn_is_partitioned_by_leaf_spans():
    """One turn of the loop = decoding.admit, decoding.pack,
    decoding.step (children engine.launch + engine.fetch),
    decoding.emit: right parents, no overlap, and the leaves sum to
    the turn."""
    from mxnet_tpu.telemetry import trace as ttrace

    ttrace.set_capacity(4096)
    try:
        m = _model(max_tokens=24, page_buckets=(1, 2, 4, 8))
        fut = m.submit([3, 4, 5, 6, 7], max_new_tokens=24)
        assert len(fut.result(timeout=120)) == 24
        m.close()
        spans = ttrace.recent_spans()
    finally:
        ttrace.set_capacity(ttrace._env_capacity())
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    assert len(by["decoding.step"]) >= 20
    assert {s.parent for s in by["decoding.step"]} == {None}
    assert {s.parent for s in by["engine.launch"]} == {"decoding.step"}
    assert {s.parent for s in by["engine.fetch"]} == {"decoding.step"}
    assert {s.parent for s in by["decoding.prefill"]} \
        == {"decoding.admit"}
    assert {s.parent for s in by["decoding.reply"]} == {"decoding.emit"}
    step = by["decoding.step"][10]
    assert step.attrs["program"] == m.engine.step_program(
        step.attrs["bucket"])
    # one live row whose context holds prompt + 10 generated tokens:
    # the step reads lengths + 1 positions
    assert step.attrs["ctx_tokens"] == 5 + 10 + 1
    assert "tokens" in by["decoding.emit"][10].attrs
    # the children of a step partition it
    # on a loaded host the loop's thread loses the CPU between two
    # spans for milliseconds, under six test workers in most turns:
    # order and no overlap hold for EVERY step and turn; the share the
    # leaves cover is a fact of the code between them, which every turn
    # runs and a lost time slice only adds to, so the BEST-covered of
    # the fifteen shows it
    shares = []
    for st in by["decoding.step"][5:20]:
        kids = sorted((s for s in spans if s.parent == "decoding.step"
                       and s.t0 >= st.t0 and s.t1 <= st.t1),
                      key=lambda s: s.t0)
        assert [k.name for k in kids] == ["engine.launch", "engine.fetch"]
        assert kids[0].t1 <= kids[1].t0
        shares.append(sum(k.t1 - k.t0 for k in kids) / (st.t1 - st.t0))
    assert max(shares) >= 0.95, shares
    # turns 5..15: admit, pack, step, emit follow each other without
    # overlap and fill the period between two admits
    admits = by["decoding.admit"]
    first = next(i for i, a in enumerate(admits)
                 if a.t0 > by["decoding.step"][5].t1)
    lo, hi = admits[first].t0, admits[first + 10].t0
    leaves = sorted((s for s in spans
                     if s.name in ("decoding.admit", "decoding.pack",
                                   "decoding.step", "decoding.emit")
                     and s.t0 >= lo and s.t1 <= hi), key=lambda s: s.t0)
    assert [s.name for s in leaves] == [
        "decoding.admit", "decoding.pack", "decoding.step",
        "decoding.emit"] * 10
    for a, b in zip(leaves, leaves[1:]):
        assert a.t1 <= b.t0
    # what the leaves leave out of a turn is the loop's own lock check
    # and the spans' bookkeeping: some tens of microseconds, a share
    # that only a toy step of a millisecond makes visible
    turns = [leaves[i:i + 4] for i in range(0, 40, 4)]
    ends = [t[0].t0 for t in turns[1:]] + [hi]
    assert max(sum(s.t1 - s.t0 for s in t) / (end - t[0].t0)
               for t, end in zip(turns, ends)) >= 0.85


# ------------------------------------------------------ steps in flight
def _run_jobs(m, jobs, **kw):
    futs = [m.submit(p, max_new_tokens=n, **kw) for p, n in jobs]
    return [f.result(120) for f in futs], futs


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("depth", [1, 3])
def test_run_ahead_gives_the_tokens_of_one_step_at_a_time(depth,
                                                          prefix_cache):
    """With steps kept in flight every stream is what the loop that
    waits for each step gives: more requests than rows, budgets that
    end mid-flight, a context that ends at capacity, shared prefixes."""
    rng = random.Random(7)
    head = [rng.randrange(2, CFG.vocab) for _ in range(5)]
    jobs = [((head if i % 2 else [])
             + [rng.randrange(2, CFG.vocab)
                for _ in range(rng.randint(1, 7))],
             rng.randint(1, 9)) for i in range(14)]
    kw = dict(max_batch=3, num_pages=64, page_buckets=(1, 2, 4),
              max_tokens=9, prefix_cache=prefix_cache,
              merged_step=False)
    m = _model(**kw)
    try:
        want, _ = _run_jobs(m, jobs)
    finally:
        m.close()
    m = _model(run_ahead=depth, **kw)
    try:
        floor = m.engine.traces()
        got, futs = _run_jobs(m, jobs)
        assert got == want
        assert any(f.finish_reason == "length" for f in futs)
        assert m.engine.traces() == floor
        snap = m.stats.snapshot()
        assert snap["completed"] == len(jobs)
        assert snap["decode_tokens"] + snap["prefills"] \
            == sum(len(g) for g in got)
        assert snap["pages_free"] == 63 - snap.get("prefix_cached_pages", 0)
    finally:
        m.close()


def test_run_ahead_sampled_streams_and_eos():
    """Sampled rows are (seed, position)-pure, so steps in flight draw
    what one step at a time draws; an `eos` ends a row unannounced and
    what was launched for it after that is dropped unread."""
    import dataclasses

    m = _model()
    try:
        known = m.generate([5, 6, 7], max_new_tokens=6)
    finally:
        m.close()
    cfg_eos = dataclasses.replace(CFG, eos_id=known[2])
    sp = dec.SamplingParams(temperature=0.9, top_k=8, seed=11)
    outs = []
    for depth in (0, 4):
        m = dec.DecodedModel("lm-eos", 1, PARAMS, cfg_eos, max_batch=2,
                             page_size=4, num_pages=32,
                             page_buckets=(1, 2, 4), max_tokens=8,
                             merged_step=False, run_ahead=depth)
        try:
            f1 = m.submit([5, 6, 7], max_new_tokens=8)
            f2 = m.submit([9, 3], max_new_tokens=8, sampling=sp)
            f3 = m.submit([4, 4, 4, 2], max_new_tokens=5)
            outs.append([f.result(60) for f in (f1, f2, f3)])
            assert f1.finish_reason == "eos"
            assert outs[-1][0] == known[:2]
            snap = m.stats.snapshot()
            assert snap["pages_free"] \
                == 31 - snap.get("prefix_cached_pages", 0)
        finally:
            m.close()
    assert outs[0] == outs[1]


def test_run_ahead_keeps_steps_in_flight_and_tiles_the_turns():
    """A lone request: `run_ahead` + 1 steps are launched before the
    first is fetched, a launch a turn after that; the `decoding.step`
    span holds the turn's pack, launches, fetch and emit and carries
    the attributes of the step it took out."""
    from mxnet_tpu.telemetry import trace as ttrace

    ttrace.set_capacity(4096)
    try:
        m = _model(max_tokens=24, page_buckets=(1, 2, 4, 8),
                   merged_step=False, run_ahead=3)
        calls = []
        launch, fetch = m.engine.launch_step, m.engine.fetch_step
        m.engine.launch_step = lambda *a, **k: (
            calls.append("launch"), launch(*a, **k))[1]
        m.engine.fetch_step = lambda *a, **k: (
            calls.append("fetch"), fetch(*a, **k))[1]
        fut = m.submit([3, 4, 5, 6, 7], max_new_tokens=24)
        assert len(fut.result(timeout=120)) == 24
        m.close()
        spans = ttrace.recent_spans()
    finally:
        ttrace.set_capacity(ttrace._env_capacity())
    # 23 steps after the prefill's token: four launched, then one in
    # and one out, then the last three taken out
    assert calls == ["launch"] * 4 + ["fetch", "launch"] * 19 \
        + ["fetch"] * 4
    steps = [s for s in spans if s.name == "decoding.step"]
    assert len(steps) == 23
    assert [s.attrs["ctx_tokens"] for s in steps] \
        == [5 + k + 1 for k in range(23)]
    assert {s.attrs["program"] for s in steps} \
        <= {m.engine.step_program(b) for b in (1, 2, 4, 8)}
    for name in ("decoding.pack", "engine.launch", "engine.fetch",
                 "decoding.emit"):
        assert {s.parent for s in spans if s.name == name} \
            == {"decoding.step"}, name
    assert sum(s.attrs["tokens"] for s in spans
               if s.name == "decoding.emit") == 23
    # the steps still launched as each one's tokens came out: three
    # behind it until the row's budget is launched, then none left
    assert [s.attrs["in_flight"] for s in steps] == [3] * 20 + [2, 1, 0]


# None: the argument unset where the backend reads as a TPU
@pytest.mark.parametrize("run_ahead", [0, 3, None])
def test_step_spans_and_stats_count_live_pages(run_ahead):
    """`live_pages` on every `decoding.step` span is the page table's
    own count: the entries of the step's active rows that hold their
    `lengths + 1` positions, none of them the scratch page and none
    left over. `DecodeStats` sums them beside the `rows x bucket` page
    slots the programs were given. `in_flight` counts the steps still
    launched as a span's tokens came out: none in the waited-for turn,
    up to the depth with steps in flight."""
    from mxnet_tpu.telemetry import trace as ttrace

    ttrace.set_capacity(4096)
    try:
        m = _model(max_tokens=24, page_buckets=(1, 2, 4, 8),
                   merged_step=False, run_ahead=run_ahead,
                   tpu=run_ahead is None)
        depth = m.scheduler.run_ahead
        assert depth == (dec.config.RUN_AHEAD if run_ahead is None
                         else run_ahead)
        seen = []
        name = "launch_step" if depth else "step"
        inner = getattr(m.engine, name)

        def step(tokens, table, lengths, active, *samp):
            seen.append((np.array(table), np.array(lengths),
                         np.array(active)))
            return inner(tokens, table, lengths, active, *samp)

        setattr(m.engine, name, step)
        futs = [m.submit([3, 4, 5, 6, 7], max_new_tokens=20),
                m.submit([9, 8, 7], max_new_tokens=9)]
        assert [len(f.result(timeout=120)) for f in futs] == [20, 9]
        snap = m.stats.snapshot()
        m.close()
        spans = [s for s in ttrace.recent_spans()
                 if s.name == "decoding.step"
                 and (s.attrs or {}).get("model") == m.key]
    finally:
        ttrace.set_capacity(ttrace._env_capacity())
    assert len(spans) == len(seen) >= 19
    page = m.engine.page_size
    for span, (table, lengths, active) in zip(spans, seen):
        owned = (table[active] != SCRATCH_PAGE).sum(axis=1)
        np.testing.assert_array_equal(
            owned, -(-(lengths[active] + 1) // page))
        assert span.attrs["live_pages"] == owned.sum()
        assert table.shape[1] == span.attrs["bucket"]
    assert snap["live_pages"] == sum(s.attrs["live_pages"] for s in spans)
    assert snap["bucket_pages"] == sum(t.size for t, _, _ in seen)
    assert snap["live_page_share"] == round(
        snap["live_pages"] / snap["bucket_pages"], 4)
    in_flight = [s.attrs["in_flight"] for s in spans]
    assert max(in_flight) == depth and min(in_flight) == 0


def test_run_ahead_admits_into_a_free_row_with_steps_in_flight():
    """A request for a free row does not wait for what is launched:
    its prefill queues behind the steps in flight, and both streams
    are what one step at a time gives."""
    kw = dict(max_tokens=40, page_buckets=(1, 2, 4, 8, 16),
              merged_step=False)
    m = _model(**kw)
    try:
        want = [m.generate([3, 4, 5], max_new_tokens=40),
                m.generate([9, 8, 7, 6], max_new_tokens=12)]
    finally:
        m.close()
    m = _model(run_ahead=4, **kw)
    try:
        in_flight = []
        launch = m.engine.launch_prefill
        m.engine.launch_prefill = lambda *a, **k: (
            in_flight.append(len(m.scheduler._ahead)), launch(*a, **k))[1]
        first = m.submit([3, 4, 5], max_new_tokens=40)
        stream = first.stream(timeout=60)
        head = [next(stream) for _ in range(5)]
        second = m.submit([9, 8, 7, 6], max_new_tokens=12)
        assert second.result(60) == want[1]
        assert head + list(stream) == want[0]
        # the first request found nothing launched, the second the
        # first's steps
        assert in_flight[0] == 0 and in_flight[1] >= 1
    finally:
        m.close()
    # the steps in flight came out before the span of the prefill that
    # waited behind them begins: the span is the prefill's own time
    from mxnet_tpu.telemetry import trace as ttrace

    spans = [s for s in ttrace.recent_spans()
             if (s.attrs or {}).get("model") == m.key]
    fill = [s for s in spans if s.name == "decoding.prefill"][-1]
    assert not [s for s in spans if s.name == "decoding.step"
                and s.t0 < fill.t1 and s.t1 > fill.t0]


def test_run_ahead_settles_before_a_decision():
    """A cancellation and a deadline are acted on with nothing in
    flight, and the pages come back."""
    m = _model(max_tokens=48, page_buckets=(1, 2, 4, 8, 16),
               merged_step=False, run_ahead=4)
    try:
        fut = m.submit([3, 4, 5], max_new_tokens=48)
        stream = fut.stream(timeout=60)
        got = [next(stream) for _ in range(6)]
        stream.close()
        late = m.submit([3, 4, 5], max_new_tokens=48, deadline_ms=1)
        with pytest.raises(serving.DeadlineExceededError):
            late.result(60)
        again = m.submit([3, 4, 5], max_new_tokens=6)
        assert again.result(60) == got
        deadline = time.time() + 30
        def held():
            snap = m.stats.snapshot()
            return 31 - snap["pages_free"] \
                - snap.get("prefix_cached_pages", 0)

        while held() and time.time() < deadline:
            time.sleep(0.01)
        assert held() == 0
        assert not m.scheduler._ahead
    finally:
        m.close()


def test_run_ahead_is_the_plain_steps():
    with pytest.raises(serving.ServingError):
        _model(run_ahead=2, draft="self", spec_k=2)


@pytest.mark.parametrize("tpu,kw,want", [
    (True, {"merged_step": False}, dec.config.RUN_AHEAD),
    (False, {"merged_step": False}, 0),
    (True, {"draft": "self", "spec_k": 2}, 0),
    (True, {}, 0),                        # the merged step: prefix cache on
    (True, {"merged_step": False, "run_ahead": 0}, 0),
    (False, {"merged_step": False, "run_ahead": 3}, 3),
    (True, {"draft": "self", "spec_k": 2, "run_ahead": 2}, "raises"),
    (True, {"run_ahead": 2}, "raises"),   # the merged step
], ids=["tpu", "cpu", "tpu_draft", "tpu_merged", "explicit_0",
        "explicit_d", "explicit_d_draft", "explicit_d_merged"])
def test_run_ahead_resolves_by_backend(tpu, kw, want):
    """Unset, the steps kept in flight follow what the engine can see:
    the default depth on a TPU for the plain step, none off the TPU
    or with a draft or the merged step. An explicit value wins, 0
    included; an explicit depth beside a draft or the merged step is
    refused."""
    if want == "raises":
        with pytest.raises(serving.ServingError):
            _model(tpu=tpu, warmup=False, **kw)
        return
    m = _model(tpu=tpu, warmup=False, **kw)
    try:
        assert m.engine.merged_step_enabled == (
            "merged_step" not in kw and "draft" not in kw)
        assert m.scheduler.run_ahead == want
    finally:
        m.close()


def test_reply_span_of_a_cancelled_request_is_the_handoff_alone():
    from mxnet_tpu.telemetry import trace as ttrace

    m = _model(max_tokens=48, page_buckets=(1, 2, 4, 8, 16))
    t_submit = ttrace.now()
    fut = m.submit([3, 4, 5], max_new_tokens=48)
    stream = fut.stream(timeout=60)
    for _ in range(6):
        next(stream)
    fut.cancel()
    fut.result(timeout=60)
    m.close()
    t_done = ttrace.now()
    reply = [s for s in ttrace.spans_for_trace(fut.trace_id)
             if s.name == "decoding.reply"]
    assert len(reply) == 1
    reply = reply[0]
    assert reply.attrs["outcome"] == "cancelled"
    lifetime = reply.attrs["latency_us"] * 1e-6
    # six steps at least lie between submit and cancel; the span holds
    # none of them
    assert 0 < lifetime <= t_done - t_submit
    assert reply.t1 - reply.t0 < lifetime / 5
    assert reply.t0 > t_submit + lifetime / 2


# ------------------------------------------------ the admission's spans
def _loop_spans(run, **kw):
    """The ring's spans of one decoder's life: `run(m)` drives it."""
    from mxnet_tpu.telemetry import trace as ttrace

    ttrace.set_capacity(8192)
    try:
        m = _model(merged_step=False, **kw)
        try:
            run(m)
        finally:
            m.close()
        return m, ttrace.recent_spans()
    finally:
        ttrace.set_capacity(ttrace._env_capacity())


def _mixed_budgets(m):
    """More requests than rows with budgets of their own, so that rows
    free up one at a time while the others decode."""
    jobs = [([3, 4, 5], 14), ([9, 8, 7, 6], 5), ([2, 6], 9),
            ([5, 5, 5, 5, 5], 3), ([7, 3], 11), ([4, 9, 2], 6)]
    futs = [m.submit(p, max_new_tokens=n) for p, n in jobs]
    assert [len(f.result(120)) for f in futs] == [n for _, n in jobs]


@pytest.mark.parametrize("run_ahead", [0, 3])
def test_prefill_span_is_in_a_profiler_capture_under_its_own_name(
        run_ahead, tmp_path):
    """`decoding.prefill` is a `with` block: a capture's host plane
    holds every one under its own name, as long as the ring's record
    of it, and its parent is still `decoding.admit`."""
    from mxnet_tpu.profiling import timeline

    def run(m):
        jax.profiler.start_trace(str(tmp_path))
        try:
            _mixed_budgets(m)
        finally:
            jax.profiler.stop_trace()

    _m, spans = _loop_spans(run, max_tokens=16, run_ahead=run_ahead,
                            page_buckets=(1, 2, 4, 8))
    fills = sorted((s for s in spans if s.name == "decoding.prefill"),
                   key=lambda s: s.t0)
    assert len(fills) == 6
    assert {s.parent for s in fills} == {"decoding.admit"}
    host = sorted((t0, t1) for n, t0, t1 in
                  timeline.read_xplane(str(tmp_path))["host"]
                  if n == "decoding.prefill")
    assert len(host) == len(fills)
    diffs = sorted(abs((b - a) - (s.t1 - s.t0))
                   for (a, b), s in zip(host, fills))
    assert diffs[len(diffs) // 2] < 200e-6, diffs


@pytest.mark.parametrize("run_ahead", [0, 3])
def test_admission_spans_say_what_a_first_token_waited_for(run_ahead):
    """`decoding.prefill` says how long its request waited for a row
    (`queued_us`, from its submit), the prefill's dispatch (`launch_us`)
    and its wait behind the steps in flight (`behind_us`: none in the
    waited-for turn); `decoding.admit` counts the prefills it launched
    and the launched steps it took out; `decoding.step` says how many
    steps were in flight at the turn's first launch: none after an
    admission, at least one on a turn that follows a turn."""
    _m, spans = _loop_spans(_mixed_budgets, max_tokens=16,
                            run_ahead=run_ahead, page_buckets=(1, 2, 4, 8))
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    submitted = {s.trace_id: s.t0 for s in by["decoding.submit"]}
    fills = by["decoding.prefill"]
    assert len(fills) == 6
    for s in fills:
        a = s.attrs
        assert not a["readmission"]
        assert min(a["queued_us"], a["launch_us"], a["behind_us"]) >= 0
        assert all(isinstance(a[k], int)
                   for k in ("queued_us", "launch_us", "behind_us"))
        # taken from the queue after its submit and before its span
        assert a["queued_us"] * 1e-6 <= s.t0 - submitted[s.trace_id] + 1e-6
        if not run_ahead:
            assert a["behind_us"] == 0
    admits = by["decoding.admit"]
    assert sum(a.attrs["prefills"] for a in admits) == len(fills)
    # what an admission took out are the step spans that lie inside it
    nested = sum(1 for s in by["decoding.step"] for a in admits
                 if a.t0 <= s.t0 and s.t1 <= a.t1)
    assert sum(a.attrs["drained"] for a in admits) == nested
    turns = sorted(admits + [s for s in by["decoding.step"]
                             if "queued" in s.attrs], key=lambda s: s.t0)
    follows = {"decoding.admit": [], "decoding.step": []}
    for prev, cur in zip(turns, turns[1:]):
        if cur.name == "decoding.step":
            follows[prev.name].append(cur.attrs["queued"])
    assert follows["decoding.admit"] and set(
        follows["decoding.admit"]) == {0}
    if run_ahead:
        # some request was admitted with the steps still in flight
        assert max(s.attrs["behind_us"] for s in fills) > 0
        assert nested > 0
        assert follows["decoding.step"] \
            and min(follows["decoding.step"]) >= 1
    else:
        assert nested == 0 and not follows["decoding.step"]
        assert {s.attrs["queued"] for s in by["decoding.step"]} == {0}


@pytest.mark.parametrize("run_ahead", [0, 3])
def test_a_readmission_waits_from_its_preemption(run_ahead):
    """A preempted request's `queued_us` runs from its preemption, not
    from its submit: shorter than the time since its first prefill."""
    def run(m):
        prompts = [[int(t) for t in
                    np.random.RandomState(i).randint(2, 32, size=6)]
                   for i in range(6)]
        futs = [m.submit(p, max_new_tokens=10, priority=i % 2)
                for i, p in enumerate(prompts)]
        for f in futs:
            f.result(240)
        assert m.stats.snapshot()["preemptions"] > 0

    _m, spans = _loop_spans(run, max_batch=4, num_pages=9, max_tokens=12,
                            queue_cap=64, run_ahead=run_ahead)
    first = {}
    again = []
    for s in sorted(spans, key=lambda s: s.t0):
        if s.name != "decoding.prefill":
            continue
        if s.attrs["readmission"]:
            again.append(s)
        else:
            first.setdefault(s.trace_id, s)
    assert again
    for s in again:
        assert 0 <= s.attrs["queued_us"] * 1e-6 \
            < s.t0 - first[s.trace_id].t1


# ------------------------------------------------- ragged attention
def test_ragged_kernel_mixed_prefill_decode_matches_dense():
    """ONE fixed-shape ragged call serving decode rows (full context)
    and tail-prefill rows (mid-prompt positions) must match a dense
    numpy softmax oracle row by row."""
    rs = np.random.RandomState(7)
    b, h, d, p, bp, n = 4, 2, 8, 4, 3, 16
    q = rs.randn(b, h, d).astype(np.float32)
    k_pages = rs.randn(n, p, h, d).astype(np.float32)
    v_pages = rs.randn(n, p, h, d).astype(np.float32)
    table = np.stack([rs.choice(np.arange(1, n), size=bp,
                                replace=False) for _ in range(b)]
                     ).astype(np.int32)
    # rows 0-1: decode rows attending their whole context; rows 2-3:
    # prompt-tail rows mid-prefill, attending only positions < their
    # own (intra-chunk causality via the per-row length)
    lengths = np.asarray([9, 12, 3, 6], np.int32)

    scale = 1.0 / np.sqrt(d)

    def oracle(row):
        ctx_k = k_pages[table[row]].reshape(bp * p, h, d)
        ctx_v = v_pages[table[row]].reshape(bp * p, h, d)
        ln = lengths[row]
        s = np.einsum("hd,thd->ht", q[row], ctx_k[:ln]) * scale
        e = np.exp(s - s.max(axis=-1, keepdims=True))
        w = e / e.sum(axis=-1, keepdims=True)
        return np.einsum("ht,thd->hd", w, ctx_v[:ln])

    for name in ("lax", "pallas"):
        out = np.asarray(attn.get_ragged_kernel(name)(
            q, k_pages.reshape(n, p, h * d),
            v_pages.reshape(n, p, h * d), table, lengths))
        for row in range(b):
            np.testing.assert_allclose(out[row], oracle(row),
                                       atol=1e-5,
                                       err_msg=f"{name} row {row}")


# ------------------------------------------------- merged decode step
def test_merged_step_shrinks_warmup_grid_and_keeps_parity():
    """The merged engine drops every per-length-bucket tail-prefill
    program from the warmup grid, and prefix-cache-hit traffic
    (which exercises the ragged tail rows) stays token-identical to
    the dense reference at zero steady-state retraces."""
    split = _model(prefix_cache=True, merged_step=False)
    split_counts = split.engine.trace_counts()
    split.close()
    assert any(k.startswith("prefill_tail@") for k in split_counts)

    m = _model(prefix_cache=True, merged_step=True)
    try:
        counts = m.engine.trace_counts()
        assert not any(k.startswith("prefill_tail@") for k in counts)
        assert sum(counts.values()) < sum(split_counts.values())

        floor = m.engine.traces()
        shared = [5, 6, 7, 8, 9, 10, 11, 12]   # two full pages
        prompts = [shared + [13], shared + [14, 15], [3, 4],
                   shared + [16, 17, 18]]
        for prompt in prompts:
            out = m.generate(prompt, max_new_tokens=6, timeout=60)
            assert out == _ref_greedy(prompt, 6), prompt
        assert m.engine.traces() == floor
        assert m.stats.snapshot()["traces_since_warmup"] == 0
    finally:
        m.close()


def test_merged_engine_rejects_dedicated_tail_prefill():
    m = _model(prefix_cache=True, merged_step=True)
    try:
        table = m.engine.allocator.alloc(2)
        with pytest.raises(PageError):
            m.engine.prefill(list(range(2, 8)), table, start=4)
        m.engine.allocator.free(table)
    finally:
        m.close()


def test_merged_step_off_without_prefix_cache():
    """No prefix cache -> no tail to merge: the engine stays on the
    split grid (speculative engines likewise keep their own step)."""
    m = _model(prefix_cache=False, merged_step=True)
    try:
        assert not m.engine.merged_step_enabled
        assert m.engine.step_rows == m.engine.max_batch
    finally:
        m.close()


# ------------------------------------------------------- sampler branch
from mxnet_tpu.decoding import sampling as _sampling  # noqa: E402

SAMPLER_ROWS = 6
# per row: temperature, top_k, top_p
SAMPLER_CASES = {
    "all_greedy": ([0.0] * 6, [0] * 6, [1.0] * 6),
    "one_sampled": ([0.0, 0.0, 0.8, 0.0, 0.0, 0.0], [0, 5, 0, 0, 5, 0],
                    [1.0, 0.9, 1.0, 1.0, 1.0, 0.9]),
    "sampled_k0_p1": ([0.7] * 6, [0] * 6, [1.0] * 6),
    "sampled_k5_p1": ([1.3] * 6, [5] * 6, [1.0] * 6),
    "sampled_k0_p09": ([0.9] * 6, [0] * 6, [0.9] * 6),
    "sampled_k5_p09": ([1.0] * 6, [5] * 6, [0.9] * 6),
}


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
@pytest.mark.parametrize("case", sorted(SAMPLER_CASES))
def test_sample_rows_is_the_per_row_sampler(case, seed):
    """`sampling.sample_rows` gives every row the token that each row's
    own `sample_token` gives it, bit for bit, whichever branch the batch
    takes; a batch of greedy rows gives the argmax whatever the seeds."""
    temps, top_ks, top_ps = (np.asarray(v, t) for v, t in zip(
        SAMPLER_CASES[case], (np.float32, np.int32, np.float32)))
    rng = np.random.default_rng(seed)
    logits = rng.normal(size=(SAMPLER_ROWS, 97)).astype(np.float32) * 3
    seeds = rng.integers(0, 2**32, SAMPLER_ROWS, dtype=np.uint32)
    positions = rng.integers(1, 4096, SAMPLER_ROWS).astype(np.int32)
    args = (logits, seeds, positions, temps, top_ks, top_ps)
    got = np.asarray(jax.jit(_sampling.sample_rows)(*args))
    per_row = np.asarray(jax.jit(jax.vmap(_sampling.sample_token))(*args))
    np.testing.assert_array_equal(got, per_row)
    assert got.dtype == np.int32
    if not temps.any():
        other = np.asarray(jax.jit(_sampling.sample_rows)(
            logits, seeds ^ np.uint32(0x5A5A5A5A), positions + 1, temps,
            top_ks, top_ps))
        np.testing.assert_array_equal(other, got)
        np.testing.assert_array_equal(got, logits.argmax(axis=-1))


def sort_places(text):
    """A compiled program's sorts by where they run, each as its
    instruction's `op_name`: (those the program always runs, those
    inside a conditional's branch). A computation reached from the
    entry through anything but a branch (a fusion, a loop, a call)
    always runs."""
    import re

    from mxnet_tpu.profiling import timeline

    entry, comps = timeline._computations(text)
    branch_of = re.compile(r"(?:branch_computations|true_computation|"
                           r"false_computation)=(\{[^}]*\}|%[\w.\-]+)")
    name_of = re.compile(r"%([\w.\-]+)")

    def refs(comp):
        always, branch = set(), set()
        for ln in comps[comp]:
            ln = ln.split("metadata={")[0]
            br = {n for g in branch_of.findall(ln)
                  for n in name_of.findall(g)}
            for n in name_of.findall(ln):
                if n in comps:
                    (branch if n in br else always).add(n)
        return always, branch

    def reach(starts, through_branches):
        seen, todo, entered = set(), list(starts), set()
        while todo:
            comp = todo.pop()
            if comp in seen:
                continue
            seen.add(comp)
            always, branch = refs(comp)
            todo.extend(always)
            if through_branches:
                todo.extend(branch)
            entered |= branch
        return seen, entered

    always_run, branches = reach([entry], False)
    in_branch, _ = reach(branches, True)

    def sorts(comp_names):
        out = []
        for comp in comp_names:
            for ln in comps[comp]:
                if " sort(" in ln:
                    m = re.search(r'op_name="([^"]*)"', ln)
                    out.append(m.group(1) if m else "")
        return out

    return sorts(always_run), sorts(in_branch - always_run)


def assert_sampler_sorts_in_branch(text):
    """The sampler's sort lies in a conditional's branch alone: the
    program always runs no sort of the `sample` scope. Returns the
    sorts it always runs (another layer's)."""
    always, branch = sort_places(text)
    assert not [s for s in always if "/sample/" in s], always
    assert [s for s in branch if "/sample/" in s], branch
    return always


def chunk_program_text(eng, tokens_bucket, pages_bucket):
    """Compiled text of a warmed chunk-prefill program (the sparse-latent
    and window-mixed blocks'), lowered over the warmup's arguments."""
    return eng._chunk_fns[tokens_bucket, pages_bucket].lower(
        eng._params, np.zeros((1, tokens_bucket), np.int32), jnp.int32(0),
        jnp.int32(0), eng._pools,
        np.zeros(eng.table_shape(pages_bucket), np.int32),
        *eng._samp_scalars()).compile().as_text()


@pytest.mark.parametrize("program", ["decode_p2", "decode_p4", "prefill_t4"])
def test_dense_programs_sort_only_in_the_sampled_branch(program):
    """`jit_decode_p{b}` and `jit_prefill_t{t}` of the dense block: the
    vocabulary's sort runs only in the branch that a batch with a
    sampled row, or a sampled prompt, takes."""
    m = _model()
    eng = m.engine
    try:
        if program.startswith("decode"):
            text = eng.decode_program_text(int(program[len("decode_p"):]))
        else:
            lb = int(program[len("prefill_t"):])
            text = eng._prefill_fns[lb].lower(
                eng._params, np.zeros((1, lb), np.int32), jnp.int32(0),
                eng._pools, np.zeros((pages_needed(lb, 4),), np.int32),
                *eng._samp_scalars()).compile().as_text()
        assert assert_sampler_sorts_in_branch(text) == []
    finally:
        m.close(drain=False)


@pytest.mark.parametrize("run_ahead", [0, 3])
def test_greedy_steps_count_the_steps_without_a_sampled_row(run_ahead):
    """`decoding.step` spans carry `sampled_rows`, the active rows above
    temperature 0, and `DecodeStats.greedy_steps` counts the steps with
    none: every step of greedy requests; none of the steps a sampled
    request is live in."""
    from mxnet_tpu.telemetry import trace as ttrace

    sp = dec.SamplingParams(temperature=0.9, top_k=8, seed=11)
    ttrace.set_capacity(4096)
    try:
        m = _model(max_tokens=16, merged_step=False, run_ahead=run_ahead)
        futs = [m.submit([3, 4, 5], max_new_tokens=10),
                m.submit([9, 8], max_new_tokens=6)]
        [f.result(timeout=120) for f in futs]
        greedy = m.stats.snapshot()
        f = m.submit([7, 7, 2], max_new_tokens=8, sampling=sp)
        f.result(timeout=120)
        snap = m.stats.snapshot()
        m.close()
        spans = [s.attrs for s in ttrace.recent_spans()
                 if s.name == "decoding.step"
                 and (s.attrs or {}).get("model") == m.key]
    finally:
        ttrace.set_capacity(ttrace._env_capacity())
    assert greedy["steps"] >= 9
    assert greedy["greedy_steps"] == greedy["steps"]
    assert [a["sampled_rows"] for a in spans[:greedy["steps"]]] \
        == [0] * greedy["steps"]
    sampled = [a["sampled_rows"] for a in spans[greedy["steps"]:]]
    assert len(sampled) == snap["steps"] - greedy["steps"] >= 7
    assert sampled == [1] * len(sampled)
    assert snap["greedy_steps"] == greedy["steps"]
