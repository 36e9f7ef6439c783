"""Precision-polymorphic KV page pool (ROADMAP item 2's decode half).

The paged KV cache is the decode tier's HBM budget: every sequence
costs `2 * n_layers * H * D * itemsize` bytes per token. Storing pages
as int8 with a float32 scale plane cuts that to `D + 4` bytes per
(token, head) against float32's `4 * D` — a `4D / (D + 4)` capacity
multiplier (3.2x at D=16, asymptotically 4x) that compounds with
prefix sharing and speculation because all three trade the SAME pool
bytes.

`KVPool` is a NamedTuple — jax registers those as pytrees — so a
quantized pool threads through every existing jit signature,
`donate_argnums` slot and device-copy exactly like the bare array it
replaces: the fixed-shape program grid is UNCHANGED IN COUNT and the
scale plane rides along wherever its pages go (COW copies, prefix
shares, fleet handoffs).

Storage layout (one for float32, bf16 and int8; no knob):

  data   [layers, pages, page_size, width]
  scale  [layers, pages, page_size*groups]   float32, int8 pools only

A model's pool is a tuple of such planes (`Plane`: a name, a width a
token, the scale groups of a row, the layers it covers, its page
group). The dense block has `k` and `v` of width heads*head_dim on one
page table; a latent-attention block has one shared latent row and one
index key a token; a block that mixes window and full layers has a
`k`/`v` pair for each kind, at each kind's own KV heads and layer
count, in two page groups (`blocks.PageGroup`), each with a page table
of its own. The allocators, the refcounts and the radix cache know page
ids alone.

The minor dimension of `data` is a whole K (or V) row of one token,
all heads side by side; that of `scale` is a page's scales, slot by
slot. Both are multiples of 128 at any served width, so the chip's
(8, 128) tile holds them without padding whatever the storage type.
(A minor dimension under 128 — head_dim 64, or 32 heads — is padded
to 128; the chip then keeps the array with ANOTHER dimension minor,
the scatter wants it otherwise, and XLA copies the WHOLE pool around
every layer's write.) With this order a token's write is an in-place
scatter on the donated buffer — one row of `data`, one window of
`heads` scales at lane `slot*heads` — a read gathers
`data[layer, page_table]` straight from the pool, the layer an index
of the gather and never a slice taken first. The single-query
attention works on the gathered rows as they are stored (the query is
spread over the heads' lanes); only the multi-query path splits heads,
on the gathered context. No decode-tier program holds a pool-sized or
layer-sized temporary, and no single-query one a float32 copy of a
context (tests/test_chip_compile.py reads the compiled text).

Quantization scheme (symmetric, zero-point-free):

  scale[l, page, slot*H + head] = max|K/V row[head*D:(head+1)*D]| / 127
  data = round(value / scale) in [-127, 127] int8

Per-(slot, head) granularity — "a per-page scale plane" in the
coarse-to-fine sense: the plane is allocated per page, with one scalar
per (slot, head) entry INSIDE the page. Anything coarser would force
re-quantizing already-written slots on every decode append (one token
lands per step), destroying the bit-identical page sharing the prefix
cache and fleet affinity routing depend on. With maxabs scaling the
round-trip error is bounded by scale/2 per element and quantizing a
value twice is idempotent — cached pages stay byte-stable.

Dequantization happens INSIDE the attention paths (the single-query
kernels put the scales onto scores and weights, the multi-query lax
gather upcasts the pages it has read), so no full-precision copy of
the pool is ever materialized.

Dtype enum (MXNET_DECODE_KV_DTYPE): float32 (default), bf16 (plain
storage cast, no scale plane), int8 (scaled), fp8 — ACCEPTED by the
enum but reserved: fp8 stores need the TPU's native f8 converts to
beat int8, a silicon-backlog item; selecting it raises today so the
knob's surface is already the final one.

Hot paths: `kv_scatter` runs inside every prefill/decode/verify
program and `gather_stored` / `gather_ctx` inside every lax attention
call — all are pure jax (listed in the mxlint HOT_PATH_MANIFEST; no
blocking calls).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .blocks import PageError

# the knob's full surface; "fp8" is reserved (see module docstring)
KV_DTYPES = ("float32", "bf16", "int8", "fp8")

# the storage order above, by name: part of the engine's digest, so an
# AOT bundle compiled around another order is refused, not loaded
POOL_LAYOUT = ("data[layers,pages,page_size,heads*head_dim] "
               "scale[layers,pages,page_size*heads]")

# scale floor: keeps an all-zero (or denormal) K/V row from dividing
# by zero; 1e-8/127 quantizes everything below float32 noise to 0
_SCALE_FLOOR = 1e-8

# lanes of the chip's (8, 128) tile: what a stored row is a multiple of
_LANES = 128


def canonical(kv_dtype):
    """Validate + normalize an MXNET_DECODE_KV_DTYPE value."""
    name = str(kv_dtype or "float32").strip().lower()
    if name in ("bfloat16",):
        name = "bf16"
    if name not in KV_DTYPES:
        raise PageError(
            f"unknown kv dtype {kv_dtype!r} "
            f"(MXNET_DECODE_KV_DTYPE choices: {KV_DTYPES})")
    if name == "fp8":
        raise PageError(
            "kv dtype 'fp8' is reserved: fp8 page stores need native "
            "f8 converts (silicon backlog); use 'int8' today")
    return name


def storage_dtype(kv_dtype):
    return {"float32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8}[canonical(kv_dtype)]


class KVPool(NamedTuple):
    """One K (or V) page pool: `data` is (layers, pages, page_size,
    heads*head_dim) in the storage dtype (see the module docstring for
    why the heads are folded into the minor dimension); `scale` is the
    (layers, pages, page_size*heads) float32 plane for int8 pools —
    one scale per (slot, head) — None otherwise.

    NamedTuple => pytree: jit, donation and device copies treat the
    pair as one value, which is what keeps the trace grid count
    identical across dtypes."""

    data: jnp.ndarray
    scale: Optional[jnp.ndarray]

    @property
    def shape(self):
        return self.data.shape

    @property
    def page_size(self):
        return self.data.shape[2]

    @property
    def kv_dtype(self):
        if self.scale is not None:
            return "int8"
        return "bf16" if self.data.dtype == jnp.bfloat16 else "float32"

    def layer(self, i):
        """Layer `i` as the attention kernels take it: the whole pool
        and the index, nothing sliced or copied."""
        return KVLayer(self, i)


class KVLayer(NamedTuple):
    """One layer of a pool, as a (pool, layer index) pair: the reads
    index `pool.data[index, pages]` in one gather."""

    pool: KVPool
    index: int

    @property
    def shape(self):
        """(pages, page_size, heads*head_dim)."""
        return self.pool.data.shape[1:]


def as_pool(x):
    """Adopt a bare (quantization-naive) pool array as a float KVPool
    so the model functions keep accepting raw arrays."""
    return x if isinstance(x, KVPool) else KVPool(x, None)


def as_layer(x):
    """What the attention kernels take for `k_pages`/`v_pages`: a
    `KVLayer`, or a bare float array (pages, page_size, heads*head_dim)
    (tests and the parity harness build those directly), adopted as a
    one-layer pool (a leading unit axis, no copy)."""
    if isinstance(x, KVLayer):
        return x
    return KVLayer(KVPool(x[None], None), 0)


class Plane(NamedTuple):
    """One plane of a model's page pool, as its configuration states
    it: what a token of a layer stores under this name. `width` values
    a token a layer; `groups` is how many int8 scales a row carries
    (the heads of a per-head K or V row; 1 for a row that is one
    vector). `layers` is how many of the model's layers store a row
    here (0: every one; the block maps a layer to its index in the
    plane) and `group` names the page group (`blocks.PageGroup`) whose
    page table addresses it. The planes of one group share that table:
    page `p`, slot `s` is the same token in each."""

    name: str
    width: int
    groups: int = 1
    layers: int = 0
    group: str = ""

    @property
    def stored_width(self):
        """The minor dimension the plane is stored with: a width past
        128 that is no multiple of it (a 576-wide latent row) is
        padded up with zeros. The chip keeps such an array in whole
        (8, 128) tiles anyway, and with a ragged minor dimension it
        picks ANOTHER dimension as minor for the program's argument —
        the whole pool is then copied in and out of every program that
        writes it (tests/test_chip_compile.py reads the compiled text).
        Widths under 128 (toy sizes) are stored as they are."""
        if self.width <= _LANES or self.width % _LANES == 0:
            return self.width
        return -(-self.width // _LANES) * _LANES


def make_plane(layers, pages, page_size, plane, kv_dtype):
    """A zeroed pool for one `Plane` at `kv_dtype`, stored in
    `POOL_LAYOUT` (minor dimension `plane.stored_width`); int8 pools
    get their scale plane, one scale per (slot, group)."""
    name = canonical(kv_dtype)
    if name == "int8" and plane.stored_width != plane.width:
        raise PageError(
            f"plane {plane.name!r} of width {plane.width} is stored "
            "padded: int8 groups would straddle the padding")
    data = jnp.zeros((layers, pages, page_size, plane.stored_width),
                     storage_dtype(name))
    if name != "int8":
        return KVPool(data, None)
    return KVPool(data, jnp.zeros(
        (layers, pages, page_size * plane.groups), jnp.float32))


def make_pool(shape, kv_dtype):
    """A zeroed pool for `shape` = (layers, pages, page_size, heads,
    head_dim) at `kv_dtype`: the per-head K (or V) plane of width
    heads*head_dim (`make_plane`)."""
    layers, pages, page_size, heads, head_dim = shape
    return make_plane(layers, pages, page_size,
                      Plane("kv", heads * head_dim, heads), kv_dtype)


def quantize_values(values):
    """Symmetric int8 quantization of K/V rows: values (..., H, D)
    float -> (q int8 (..., H, D), scale f32 (..., H), clips () i32).

    `clips` counts elements that could NOT be represented even after
    scaling — nonfinite inputs, or magnitudes beyond scale*127 when
    the scale saturated. With healthy numerics it is exactly 0 (the
    scale is derived from the row's own maxabs), so a nonzero value is
    a numerics event: MXNET_NUMERICS_DECODE_GUARD surfaces it as the
    dequant-overflow clip counter."""
    v = values.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=-1)
    amax = jnp.where(jnp.isfinite(amax), amax, 0.0)
    scale = jnp.maximum(amax, _SCALE_FLOOR) / 127.0
    q = v / scale[..., None]
    overflow = ~jnp.isfinite(v) | (jnp.abs(q) > 127.5)
    clips = jnp.sum(overflow.astype(jnp.int32))
    q = jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8)
    return q, scale, clips


def dequantize_values(q, scale):
    """Inverse of `quantize_values` (exact float arithmetic: int8 *
    f32 is lossless)."""
    return q.astype(jnp.float32) * scale[..., None]


def _fold_heads(x):
    """(..., H, D) -> (..., H*D): a K/V row as the pool stores it."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def kv_scatter(pool, layer, pages, slots, values):
    """Quantize-at-scatter: write `values` (..., H, D) float as rows
    (..., H*D) at [layer, pages, slots] (index arrays shaped like
    values minus the trailing (H, D)), quantizing INTO the pool's
    storage dtype so a full-precision K/V tensor never exists outside
    the current activations. `values` (..., W), one axis more than the
    index arrays, is a row of a plane as it is stored (`Plane.width`;
    an int8 pool splits it into its `groups`). On a donated pool the
    scatter is in place. Returns (pool', clips () i32); clips is 0 for
    non-int8 pools."""
    if values.ndim == jnp.ndim(pages) + 1:
        groups = 1 if pool.scale is None else \
            pool.scale.shape[-1] // pool.page_size
        values = values.reshape(values.shape[:-1] + (groups, -1))
    if pool.scale is None:
        row = _fold_heads(values).astype(pool.data.dtype)
        pad = pool.data.shape[-1] - row.shape[-1]
        if pad:     # a plane stored wider than its row (stored_width)
            row = jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])
        data = pool.data.at[layer, pages, slots].set(row)
        return KVPool(data, None), jnp.int32(0)
    q, scale, clips = quantize_values(values)
    data = pool.data.at[layer, pages, slots].set(_fold_heads(q))
    # the token's `heads` scales are one window of its page's row, at
    # lane slot*heads: a windowed scatter (`.at[]` could only write
    # them one element at a time)
    heads = scale.shape[-1]
    at = jnp.stack(jnp.broadcast_arrays(
        jnp.asarray(layer, jnp.int32), pages, slots * heads), axis=-1)
    sc = jax.lax.scatter(
        pool.scale, at, scale,
        jax.lax.ScatterDimensionNumbers(
            update_window_dims=(scale.ndim - 1,),
            inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1, 2)))
    return KVPool(data, sc), clips


def gather_ctx(layer, page_table, heads):
    """The MULTI-query lax path's read (tail prefill, speculative
    verify): gather page_table's pages of one layer straight from the
    pool (the layer is an index of the SAME gather) and dequantize
    them in-flight — (B, Bp) int32 -> (B, Bp, P, H, D) float32. Heads
    are split here, on the gathered context; only the gathered pages
    are ever upcast, never the pool. A head_dim under 128 cannot be a
    bitcast on the chip, so the split is a padded float32 copy of the
    context: S queries share it there. The single-query kernel reads
    `gather_stored` and never splits."""
    pool, i = as_layer(layer)
    d = pool.data[i, page_table].astype(jnp.float32)
    d = d.reshape(d.shape[:-1] + (heads, d.shape[-1] // heads))
    if pool.scale is None:
        return d
    s = pool.scale[i, page_table]
    return d * s.reshape(s.shape[:-1] + (-1, heads, 1))


def gather_stored(layer, page_table):
    """A row's whole context of one plane AS STORED, any storage type:
    (B, Bp) -> (rows (B, Bp*P, W) in the storage type, scales
    (B, Bp*P, groups) float32 or None). The layer is an index of the
    gather; nothing is upcast and no row is split. An int8 pool's
    scales are the gathered scale rows, 1/head_dim of the context's
    bytes: the single-query attention puts them onto its scores and
    weights."""
    pool, i = as_layer(layer)
    d = pool.data[i, page_table]
    rows = d.reshape(d.shape[0], -1, d.shape[-1])
    if pool.scale is None:
        return rows, None
    s = pool.scale[i, page_table]
    return rows, s.reshape(s.shape[0], rows.shape[1], -1)


def gather_rows(layer, page_table, positions):
    """Token-granular read: the rows of `positions` (B, ...) int32
    (token positions of each row's own sequence) through `page_table`
    (B, Bp), straight from the pool — (B, ..., stored width) in the
    storage type (float pools; the sparse attention's read of its
    selected rows; the caller drops a padded plane's tail). A position
    past the table reads its last page."""
    pool, i = as_layer(layer)
    if pool.scale is not None:
        raise PageError("gather_rows reads float pools only")
    p = pool.page_size
    flat = positions.reshape(positions.shape[0], -1)
    pages = jnp.take_along_axis(
        page_table, jnp.clip(flat // p, 0, page_table.shape[1] - 1),
        axis=1)
    return pool.data[i, pages, flat % p].reshape(
        positions.shape + pool.data.shape[-1:])


def gather_plane(layer, page_table):
    """A row's whole context of one plane, as stored: (B, Bp) ->
    (B, Bp*P, W) (float pools)."""
    rows, scale = gather_stored(layer, page_table)
    if scale is not None:
        raise PageError("gather_plane reads float pools only")
    return rows


def dequant_page(pool, layer, page):
    """One page, dequantized to float32 (page_size, heads*head_dim)
    (test/debug reads)."""
    d = pool.data[layer, page].astype(jnp.float32)
    if pool.scale is None:
        return d
    s = pool.scale[layer, page].reshape(d.shape[0], -1, 1)   # (P, H, 1)
    return (d.reshape(s.shape[:2] + (-1,)) * s).reshape(d.shape)


def pool_nbytes(pool):
    """Device bytes one pool owns (data + scale plane)."""
    n = int(pool.data.size) * pool.data.dtype.itemsize
    if pool.scale is not None:
        n += int(pool.scale.size) * pool.scale.dtype.itemsize
    return n


def kv_bytes_per_token(pool):
    """Measured K-or-V bytes per pooled token position (pool bytes /
    (pages * page_size)); double it for K+V. The float32-vs-int8
    ratio of this number IS the capacity multiplier the bench and CI
    gate report."""
    _, pages, page_size = pool.data.shape[:3]
    return pool_nbytes(pool) / float(pages * page_size)


def capacity_ratio(head_dim):
    """Analytic sequences-per-pool multiplier of int8 over float32:
    4D / (D + 4) for head_dim D (data shrinks 4x, the scale plane
    adds 4 bytes per (slot, head)). >= 1.9 for every D >= 4."""
    return 4.0 * head_dim / (head_dim + 4.0)


def check_capacity(head_dim, floor=1.9):
    if capacity_ratio(head_dim) < floor:
        raise PageError(
            f"int8 pages at head_dim {head_dim} only buy "
            f"{capacity_ratio(head_dim):.2f}x capacity (< {floor}); "
            "quantization is not worth the drift here")
    return True
