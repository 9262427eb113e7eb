"""Unit tests for the Pallas flash-attention block kernels (ops/flash.py).

Exercised in interpret mode on CPU; the same code path compiles for TPU.
The block kernel is validated against a dense einsum reference including
traced global offsets (the ring-step case) and partial causal masking.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4jax_tpu.ops.flash import _flash_fwd_block, pick_block

BH, TQ, TK, D = 3, 32, 48, 16


def _dense_block(q, k, v, q_off, k_off, scale, causal):
    s = jnp.einsum("btd,bsd->bts", q, k).astype(jnp.float32) * scale
    if causal:
        rows = q_off + np.arange(TQ)[:, None]
        cols = k_off + np.arange(TK)[None, :]
        s = jnp.where(jnp.asarray(cols <= rows)[None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)  # fully-masked rows
    p = jnp.exp(s - m)
    return jnp.einsum("bts,bsd->btd", p, v.astype(jnp.float32)), m, \
        jnp.sum(p, axis=-1, keepdims=True)


@pytest.mark.parametrize("causal,q_off,k_off", [
    (False, 0, 0),
    (True, 0, 0),       # diagonal block
    (True, 64, 0),      # k fully in the past -> unmasked
    (True, 16, 32),     # partial overlap, some rows fully masked
])
def test_flash_block_matches_dense(causal, q_off, k_off):
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(BH, TQ, D).astype(np.float32) * 0.4)
    k = jnp.asarray(rng.randn(BH, TK, D).astype(np.float32) * 0.4)
    v = jnp.asarray(rng.randn(BH, TK, D).astype(np.float32) * 0.4)
    scale = 0.25

    o, m, l = jax.jit(
        lambda a, b, c, qo, ko: _flash_fwd_block(
            a, b, c, qo, ko, scale=scale, causal=causal,
            block_q=16, block_k=16, interpret=True)
    )(q, k, v, jnp.int32(q_off), jnp.int32(k_off))
    o_ref, m_ref, l_ref = _dense_block(q, k, v, q_off, k_off, scale, causal)

    np.testing.assert_allclose(np.asarray(m), np.asarray(m_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(l), np.asarray(l_ref),
                               rtol=1e-5, atol=1e-6)
    # unnormalized partials: compare where any key is visible
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_block_bf16_inputs():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(BH, TQ, D).astype(np.float32)).astype(
        jnp.bfloat16)
    k = jnp.asarray(rng.randn(BH, TK, D).astype(np.float32)).astype(
        jnp.bfloat16)
    v = jnp.asarray(rng.randn(BH, TK, D).astype(np.float32)).astype(
        jnp.bfloat16)
    o, m, l = _flash_fwd_block(
        q, k, v, jnp.int32(0), jnp.int32(0), scale=0.25, causal=False,
        block_q=32, block_k=16, interpret=True)
    assert o.dtype == jnp.float32  # partials always accumulate in f32
    o_ref, m_ref, l_ref = _dense_block(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), 0, 0, 0.25, False)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-2, atol=2e-2)


def test_pick_block():
    assert pick_block(256, 128) == 128
    assert pick_block(96, 128) == 96
    assert pick_block(48, 32) == 24
    assert pick_block(7, 128) == 7


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_attention_value_head_differs(causal):
    """Latent attention's shapes: q.k over a head of 24 (nope + rope),
    values of 16, against plain softmax attention."""
    from mpi4jax_tpu.ops.flash import flash_attention

    b, t, h, dk, dv = 2, 32, 3, 24, 16
    rng = np.random.RandomState(3)
    q, k = (jnp.asarray(rng.randn(b, t, h, dk).astype(np.float32) * 0.5)
            for _ in range(2))
    v = jnp.asarray(rng.randn(b, t, h, dv).astype(np.float32))
    out = jax.jit(lambda a, c, e: flash_attention(
        a, c, e, causal=causal, block_q=16, block_k=8, interpret=True))(
            q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(dk)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    assert out.shape == (b, t, h, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
