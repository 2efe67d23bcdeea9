"""Attention paths: blocked==naive, windows, decode/prefill cache parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import blocked_attention, naive_attention


@pytest.mark.parametrize("B,Sq,Skv,H,K,D,Dv", [
    (1, 17, 17, 4, 4, 16, 16),
    (2, 33, 33, 4, 2, 8, 8),
    (2, 64, 64, 8, 1, 32, 32),   # MQA
    (1, 40, 40, 4, 4, 24, 16),   # MLA-shaped (Dv != Dq)
])
@pytest.mark.parametrize("causal", [True, False])
def test_blocked_matches_naive(B, Sq, Skv, H, K, D, Dv, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D))
    k = jax.random.normal(ks[1], (B, Skv, K, D))
    v = jax.random.normal(ks[2], (B, Skv, K, Dv))
    o1 = blocked_attention(q, k, v, causal=causal, q_chunk=16, kv_chunk=16)
    o2 = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


@pytest.mark.parametrize("max_bytes", [0, 1 << 30])
def test_blocked_gradients_match_naive(max_bytes, monkeypatch):
    """Past ``SAVED_SCORES_MAX_BYTES`` the backward pass recomputes each
    chunk's scores; below it keeps them.  Both give naive's gradients."""
    from repro.models import attention as A
    monkeypatch.setattr(A, "SAVED_SCORES_MAX_BYTES", max_bytes)
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (2, 40, 4, 24))
    k = jax.random.normal(ks[1], (2, 40, 4, 24))
    v = jax.random.normal(ks[2], (2, 40, 4, 16))
    ct = jax.random.normal(ks[3], (2, 40, 4, 16))

    def grads(fn):
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * ct),
                        argnums=(0, 1, 2))(q, k, v)

    g1 = grads(lambda q, k, v: blocked_attention(q, k, v, causal=True,
                                                 q_chunk=16, kv_chunk=16))
    g2 = grads(lambda q, k, v: naive_attention(q, k, v, causal=True))
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("window", [1, 4, 16, 100])
def test_window_matches_naive(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 37, 4, 16))
    k = jax.random.normal(ks[1], (2, 37, 2, 16))
    v = jax.random.normal(ks[2], (2, 37, 2, 16))
    o1 = blocked_attention(q, k, v, causal=True, window=window,
                           q_chunk=8, kv_chunk=8)
    o2 = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(o1, o2, atol=2e-5)


def test_window1_is_self_only():
    """window=1 attends only to the current position -> output == v row."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 9, 2, 8))
    k = jax.random.normal(ks[1], (1, 9, 2, 8))
    v = jax.random.normal(ks[2], (1, 9, 2, 8))
    o = naive_attention(q, k, v, causal=True, window=1)
    np.testing.assert_allclose(o[0, :, 0], v[0, :, 0], atol=1e-5)


def test_ring_positions_masked():
    """Slots with pos=-1 (unwritten ring entries) must be invisible."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, 1, 2, 8))
    k = jax.random.normal(ks[1], (1, 8, 2, 8))
    v = jax.random.normal(ks[2], (1, 8, 2, 8))
    kpos = jnp.array([0, 1, 2, 3, -1, -1, -1, -1])
    o1 = naive_attention(q, k, v, causal=True, q_positions=jnp.array([3]),
                         kv_positions=kpos)
    o2 = naive_attention(q, k[:, :4], v[:, :4], causal=True,
                         q_positions=jnp.array([3]),
                         kv_positions=jnp.arange(4))
    np.testing.assert_allclose(o1, o2, atol=1e-5)
