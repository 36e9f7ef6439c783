"""Long-context attention tests: flash kernel vs XLA reference, ring
attention and Ulysses vs dense attention on the virtual 8-device CPU
mesh (the suite's stand-in for the ICI ring; conftest.py sets
xla_force_host_platform_device_count=8)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.parallel import (
    attention,
    attention_reference,
    make_mesh,
    ring_attention,
    ulysses_attention,
)


def _qkv(b=2, t=64, h=4, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rs.standard_normal((b, t, h, d)).astype(np.float32)
    )
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = attention_reference(q, k, v, causal=causal)
    out = attention(
        q, k, v, causal=causal, impl="flash", block_q=16, block_k=16,
    )
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_flash_gradients_flow():
    q, k, v = _qkv(t=32)

    def loss(q, k, v):
        return attention(
            q, k, v, impl="flash", block_q=16, block_k=16,
        ).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(causal):
    mesh = make_mesh({"seq": 8})
    q, k, v = _qkv(t=64)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_ring_attention_grads():
    mesh = make_mesh({"seq": 4})
    q, k, v = _qkv(t=32)

    g = jax.grad(
        lambda q, k, v: ring_attention(
            q, k, v, mesh=mesh, causal=True
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: attention_reference(
            q, k, v, causal=True
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    mesh = make_mesh({"seq": 4})
    q, k, v = _qkv(t=32, h=8)
    ref = attention_reference(q, k, v, causal=causal)
    out = ulysses_attention(q, k, v, mesh=mesh, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )


def test_ulysses_rejects_bad_heads():
    mesh = make_mesh({"seq": 8})
    q, k, v = _qkv(t=32, h=4)  # 4 heads, 8 devices
    with pytest.raises(ValueError):
        ulysses_attention(q, k, v, mesh=mesh)


def test_ring_attention_under_jit():
    mesh = make_mesh({"seq": 8})
    q, k, v = _qkv(t=64)
    f = jax.jit(
        lambda q, k, v: ring_attention(q, k, v, mesh=mesh, causal=True)
    )
    out = f(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5
    )
