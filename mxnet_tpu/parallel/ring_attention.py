"""Sequence/context parallelism: ring attention and Ulysses layouts.

New capability vs the 2017 reference (SURVEY.md §5 mandates modern
equivalents of its bucketing/model-parallel-LSTM long-sequence story):
shard the sequence axis over a mesh 'seq' axis and either

- **ring attention**: K/V shards rotate around the ring via
  `lax.ppermute` (XLA lowers to ICI neighbor exchange) while each
  device's Q shard accumulates blockwise online-softmax partials — the
  per-step compute overlaps the next step's transfer, attention memory
  stays O(T_local), and total traffic is one full K/V rotation; or
- **Ulysses**: two `all_to_all`s re-layout (seq-sharded, all heads) ->
  (head-sharded, full seq), run dense local attention, and scatter
  back. Cheaper for many heads; needs heads % seq_devices == 0.

Both are pure-collective designs under `shard_map` — no parameter
server, no explicit send/recv (contrast: reference's ps-lite ZPush/ZPull
transport, src/kvstore/kvstore_dist.h).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

NEG_INF = -1e30


def _ring_attention_shard(q, k, v, *, axis_name, causal, scale):
    """Per-device body under shard_map. q/k/v: (B, T_local, H, D)."""
    p = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape

    qh = q.transpose(0, 2, 1, 3)  # (B, H, Tq, D)

    # pcast: mark the accumulators as device-varying along the ring axis
    # so the fori_loop carry types match the (varying) body outputs.
    def _varying(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    o0 = _varying(jnp.zeros((b, h, t_local, d), jnp.float32))
    m0 = _varying(jnp.full((b, h, t_local), NEG_INF, jnp.float32))
    l0 = _varying(jnp.zeros((b, h, t_local), jnp.float32))

    q_pos = my_idx * t_local + jnp.arange(t_local)

    def body(step, carry):
        o, m, l, k_cur, v_cur = carry
        src = (my_idx - step) % p  # which shard we currently hold
        kh = k_cur.transpose(0, 2, 1, 3)
        vh = v_cur.transpose(0, 2, 1, 3)
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", qh, kh,
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            k_pos = src * t_local + jnp.arange(t_local)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_new = l * corr + pexp.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", pexp, vh,
            preferred_element_type=jnp.float32,
        )
        # rotate K/V around the ring (ICI neighbor exchange)
        perm = [(i, (i + 1) % p) for i in range(p)]
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_next, v_next

    o, m, l, _, _ = jax.lax.fori_loop(
        0, p, body, (o0, m0, l0, k, v)
    )
    out = (o / l[..., None]).astype(q.dtype)
    return out.transpose(0, 2, 1, 3)  # (B, T_local, H, D)


def ring_attention(q, k, v, mesh=None, axis_name="seq", causal=False,
                   scale=None):
    """Ring attention over sequence-sharded (B, T, H, D) arrays.

    q/k/v may be global arrays (they are sharded over `axis_name` on
    dim 1 by shard_map) or already-placed sharded arrays.
    """
    from . import mesh as _mesh_mod

    if mesh is None:
        mesh = _mesh_mod.default_mesh()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ring_attention_shard, axis_name=axis_name,
            causal=causal, scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)


def seq_mesh_for(total_len, axis_name="seq", max_devices=None):
    """A 1-D 'seq' mesh sized for ring attention over `total_len`
    tokens: the largest device count that divides total_len (ring
    attention shards the sequence axis evenly). Degrades to a 1-device
    mesh — callers (e.g. the decode tier's long-prompt prefill,
    MXNET_DECODE_RING_PREFILL) can use it unconditionally."""
    import numpy as np

    devs = jax.devices()
    if max_devices is not None:
        devs = devs[:max_devices]
    n = len(devs)
    while n > 1 and total_len % n:
        n -= 1
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def _ulysses_shard(q, k, v, *, axis_name, causal, scale):
    """Per-device body: all_to_all to head-sharded full-seq layout,
    dense local attention, all_to_all back. q: (B, T_local, H, D)."""
    from .attention import attention_reference

    # (B, T_local, H, D) -> (B, T_full, H_local, D): split heads (axis 2)
    # across the seq axis, gather sequence (axis 1).
    qg = jax.lax.all_to_all(
        q, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    kg = jax.lax.all_to_all(
        k, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    vg = jax.lax.all_to_all(
        v, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    out = attention_reference(qg, kg, vg, causal=causal, scale=scale)
    # back to (B, T_local, H, D)
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def ulysses_attention(q, k, v, mesh=None, axis_name="seq", causal=False,
                      scale=None):
    """Ulysses (head-scatter / seq-gather) attention over
    sequence-sharded (B, T, H, D) arrays. Requires H % axis_size == 0."""
    from . import mesh as _mesh_mod

    if mesh is None:
        mesh = _mesh_mod.default_mesh()
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    axis_size = mesh.shape[axis_name]
    if q.shape[2] % axis_size != 0:
        raise ValueError(
            f"ulysses_attention: num heads {q.shape[2]} must be "
            f"divisible by the '{axis_name}' axis size {axis_size}"
        )
    spec = P(None, axis_name, None, None)
    fn = shard_map(
        functools.partial(
            _ulysses_shard, axis_name=axis_name, causal=causal,
            scale=scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
