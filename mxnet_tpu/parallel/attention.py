"""Attention kernels: XLA reference + Pallas flash-attention.

New capability vs the 2017 reference (SURVEY.md §5: no attention ops
exist there — its long-sequence answer was bucketing + truncated
unrolling); this is the modern TPU-native replacement the rebuild is
required to provide. The blockwise online-softmax structure follows the
public flash-attention recipe (PAPERS.md); the Pallas kernel keeps a
(block_q, head_dim) accumulator + running max/sum in VMEM and streams
K/V blocks from HBM, so attention memory is O(T·d) instead of O(T²).

Two implementations behind one entry point `attention(...)`:
- impl='xla': plain einsum+softmax, fully fused by XLA. Baseline and
  gradient path.
- impl='flash': Pallas kernel forward (MXU matmuls per block), with a
  custom_vjp whose backward recomputes via the XLA path (forward-memory
  win now; dedicated backward kernel is future work).
Interpreted off the TPU so tests exercise the same kernel code; on a
TPU the kernel is compiled or the call raises (utils.pallas_interpret).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import utils as _utils

NEG_INF = -1e30


def attention_reference(q, k, v, causal=False, scale=None):
    """(B, T, H, D) attention via XLA ops."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# ------------------------------------------------------------ pallas flash


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, block_k, causal,
                      scale, seq_k, q_block_idx):
    """One (batch*head, q_block) program: stream K/V blocks, online
    softmax."""
    q = q_ref[...]  # (block_q, d)
    block_q, d = q.shape
    num_kb = seq_k // block_k

    def body(kb, carry):
        o, m, l = carry
        k_blk = k_ref[pl.dslice(kb * block_k, block_k), :]
        v_blk = v_ref[pl.dslice(kb * block_k, block_k), :]
        s = jnp.dot(
            q, k_blk.T, preferred_element_type=jnp.float32
        ) * scale
        if causal:
            q_pos = (
                q_block_idx * block_q
                + jax.lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 0)
            )
            k_pos = (
                kb * block_k
                + jax.lax.broadcasted_iota(jnp.int32,
                                           (block_q, block_k), 1)
            )
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[:, None] + jnp.dot(
            p.astype(v_blk.dtype), v_blk,
            preferred_element_type=jnp.float32,
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    o, m, l = jax.lax.fori_loop(0, num_kb, body, (o0, m0, l0))
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype)


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret):
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0, (
        "flash attention: sequence lengths must divide block sizes"
    )
    # layout: fold (batch, head) into the grid's first axis
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)

    grid = (b * h, tq // block_q)

    def kernel(q_ref, k_ref, v_ref, o_ref):
        _flash_fwd_kernel(
            q_ref, k_ref, v_ref, o_ref, block_k=block_k,
            causal=causal, scale=scale, seq_k=tk,
            q_block_idx=pl.program_id(1),
        )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda bh, qb: (bh, qb, 0)),
            pl.BlockSpec((None, tk, d), lambda bh, qb: (bh, 0, 0)),
            pl.BlockSpec((None, tk, d), lambda bh, qb: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, block_q, d), lambda bh, qb: (bh, qb, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_attention(q, k, v, causal, scale, block_q, block_k,
                     interpret):
    return _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret
    )


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    out = _flash_forward(
        q, k, v, causal, scale, block_q, block_k, interpret
    )
    return out, (q, k, v)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_reference(
            q_, k_, v_, causal=causal, scale=scale
        ),
        q, k, v,
    )
    return vjp(g)


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention(q, k, v, causal=False, scale=None, impl="xla",
              block_q=128, block_k=128):
    """Multi-head attention on (B, T, H, D) tensors."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if impl == "xla":
        return attention_reference(q, k, v, causal=causal, scale=scale)
    if impl == "flash":
        return _flash_attention(
            q, k, v, causal, scale, block_q, block_k,
            _utils.pallas_interpret(),
        )
    raise ValueError(f"unknown attention impl {impl!r}")
