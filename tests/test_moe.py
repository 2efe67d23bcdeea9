"""MoE: sorted capacity dispatch and the dropless held-expert share vs
the dense oracle; router weights; the balance loss."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import replace
from repro.configs import get_smoke_config
from repro.models import moe as M
from repro.models.layers import split_tree


def _setup(arch, key):
    cfg = get_smoke_config(arch)
    p_ann = M.init_moe(cfg, key)
    p, _ = split_tree(p_ann)
    return cfg, p


@pytest.mark.parametrize("arch", ["grok-1-314b", "deepseek-v2-lite-16b"])
def test_sorted_matches_dense_at_full_capacity(arch):
    cfg, p = _setup(arch, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model)) * 0.5
    dense = M.moe_dense(p, x, cfg, None)
    # capacity = all tokens -> nothing dropped -> exact match
    srt = M.moe_sorted(p, x, cfg, None, capacity=2 * 16 * cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(srt), np.asarray(dense), atol=2e-4)


def test_capacity_drop_is_graceful():
    cfg, p = _setup("grok-1-314b", jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 32, cfg.d_model))
    tight = M.moe_sorted(p, x, cfg, None, capacity=2)
    assert bool(jnp.all(jnp.isfinite(tight)))


def test_router_topk_normalized():
    cfg, p = _setup("grok-1-314b", jax.random.PRNGKey(4))
    assert cfg.moe.norm_topk_prob
    x = jax.random.normal(jax.random.PRNGKey(5), (4 * 7, cfg.d_model))
    wk, ids, _ = M._router(p, x, cfg.moe)
    assert wk.shape == (28, cfg.moe.top_k)
    np.testing.assert_allclose(np.asarray(jnp.sum(wk, -1)), 1.0, atol=1e-5)
    assert int(jnp.max(ids)) < cfg.moe.num_experts


def test_moe_grads_flow_to_experts():
    cfg, p = _setup("grok-1-314b", jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 8, cfg.d_model))

    def loss(p):
        return jnp.sum(jnp.square(M.moe_sorted(p, x, cfg, None)))

    g = jax.grad(loss)(p)
    assert float(jnp.sum(jnp.abs(g["wi"]))) > 0
    assert float(jnp.sum(jnp.abs(g["router"]))) > 0


# ---------------------------------------------------------------------------
# The dropless held-expert share (DeepSeek-V2: no renormalisation, seq aux)
# ---------------------------------------------------------------------------

def _share(cfg, first, held):
    return replace(cfg, moe=replace(cfg.moe, num_held=held, first_held=first))


def _x(cfg, key, B=2, S=16):
    return jax.random.normal(jax.random.PRNGKey(key), (B, S, cfg.d_model))


@pytest.mark.parametrize("first,held", [(0, 8), (2, 2), (6, 2), (0, 1)])
def test_dropless_share_matches_dense_oracle(first, held):
    """The grouped-product path computes what the dense oracle gives on the
    same held experts, and counts the held assignments."""
    cfg = _share(get_smoke_config("deepseek-v2-lite-16b"), first, held)
    p, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(8)))
    assert p["wi"].shape[0] == held
    x = _x(cfg, 9)
    out, aux = M.moe_dropless(p, x, cfg, None)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(M.moe_dense(p, x, cfg, None)),
                               atol=2e-5)
    _, ids, _ = M._router(p, x.reshape(-1, cfg.d_model), cfg.moe)
    want = [int(jnp.sum(ids == first + e)) for e in range(held)]
    assert list(map(int, aux["load"])) == want


def test_dropless_share_gradients_match_dense_oracle():
    cfg = _share(get_smoke_config("deepseek-v2-lite-16b"), 2, 4)
    p, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(10)))
    x = _x(cfg, 11)

    def loss(fn, p, x):
        return jnp.sum(jnp.sin(fn(p, x)))

    grads = [jax.grad(loss, argnums=(1, 2))(fn, p, x) for fn in (
        lambda p, x: M.moe_dropless(p, x, cfg, None)[0],
        lambda p, x: M.moe_dense(p, x, cfg, None))]
    for a, b in zip(jax.tree.leaves(grads[0]), jax.tree.leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def _ragged_dot_leaving_nan_past_the_groups(weight_grad):
    """``jax.lax.ragged_dot`` as the TPU's grouped kernels give it: rows
    past the groups are never written (here NaN), forward and in the input
    gradient.  The weight gradient either reads each group's rows only
    (``"grouped"``) or masks every row by multiplication (``"every_row"``:
    a padding row's zero weight times a NaN operand reads NaN)."""
    rd = jax.lax.ragged_dot

    def inside(m, sizes):
        return (jnp.arange(m) < jnp.sum(sizes))[:, None]

    @jax.custom_vjp
    def ragged_dot(x, w, sizes):
        return jnp.where(inside(x.shape[0], sizes), rd(x, w, sizes), jnp.nan)

    def fwd(x, w, sizes):
        return ragged_dot(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        m = inside(x.shape[0], sizes)
        dx = jnp.where(m, rd(g, jnp.swapaxes(w, 1, 2), sizes), jnp.nan)
        group = jnp.searchsorted(jnp.cumsum(sizes), jnp.arange(x.shape[0]),
                                 side="right")
        if weight_grad == "grouped":
            x, g = jnp.where(m, x, 0), jnp.where(m, g, 0)
        # a padding row's group is past the last: its one-hot row is zero
        dw = jnp.einsum("mg,md,mf->gdf", jax.nn.one_hot(group, w.shape[0]),
                        x, g)
        return dx, dw, None

    ragged_dot.defvjp(fwd, bwd)
    return ragged_dot


@pytest.mark.parametrize("weight_grad", ["grouped", "every_row"])
def test_dropless_gradients_ignore_rows_past_the_groups(weight_grad,
                                                        monkeypatch):
    """Whatever the grouped kernel leaves in the padding rows reaches
    neither the output nor any gradient (the router's included)."""
    cfg = _share(get_smoke_config("deepseek-v2-lite-16b"), 2, 4)
    p, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(19)))
    x = _x(cfg, 20)

    def loss(p, x):
        return jnp.sum(jnp.sin(M.moe_dropless(p, x, cfg, None)[0]))

    want = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(M.jax.lax, "ragged_dot",
                        _ragged_dot_leaving_nan_past_the_groups(weight_grad))
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_share_weights_are_the_uncut_layers_rows():
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    whole, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(12)))
    part, _ = split_tree(M.init_moe(_share(cfg, 4, 3), jax.random.PRNGKey(12)))
    for k in whole:
        want = whole[k][4:7] if k in ("wi", "wg", "wo") else whole[k]
        np.testing.assert_array_equal(np.asarray(part[k]), np.asarray(want))


def test_shares_sum_to_the_uncut_layer():
    """Eight-way expert parallelism of the smoke layer: each chip's part,
    with the shared expert counted once, adds up to the whole layer."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    whole, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(13)))
    x = _x(cfg, 14)
    want, aux = M.moe_dropless(whole, x, cfg, None)
    shared = M._shared(whole, x, cfg)
    total = shared
    for e in range(cfg.moe.num_experts):
        c = _share(cfg, e, 1)
        p, _ = split_tree(M.init_moe(c, jax.random.PRNGKey(13)))
        part, a = M.moe_dropless(p, x, c, None)
        total = total + (part - shared)
        assert int(a["load"][0]) == int(aux["load"][e])
        np.testing.assert_allclose(float(a["balance"]),
                                   float(aux["balance"]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)


@pytest.mark.parametrize("renorm", [True, False])
def test_router_topk_weights(renorm):
    """Without renormalisation the top-k weights are the softmax scores
    themselves (DeepSeek-V2-Lite: norm_topk_prob false, scaling 1)."""
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    cfg = replace(cfg, moe=replace(cfg.moe, norm_topk_prob=renorm))
    p, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(15)))
    x = jax.random.normal(jax.random.PRNGKey(16), (24, cfg.d_model))
    wk, ids, probs = M._router(p, x, cfg.moe)
    picked = np.take_along_axis(np.asarray(probs), np.asarray(ids), -1)
    sums = np.asarray(jnp.sum(wk, -1))
    if renorm:
        np.testing.assert_allclose(sums, 1.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(wk),
                                   picked / picked.sum(-1, keepdims=True),
                                   rtol=1e-5)
    else:
        np.testing.assert_array_equal(np.asarray(wk), picked)
        assert np.all(sums < 1.0)


def _balance_numpy(probs, ids, E, k, alpha):
    """DeepSeek-V2's seq_aux loss, written out per sequence."""
    B, S, _ = probs.shape
    total = 0.0
    for b in range(B):
        counts = np.zeros(E)
        for e in ids[b].reshape(-1):
            counts[e] += 1
        f = counts / (S * k / E)
        total += np.sum(f * probs[b].mean(0))
    return alpha * total / B


@pytest.mark.parametrize("B,S", [(1, 12), (3, 5)])
def test_balance_loss(B, S):
    cfg = get_smoke_config("deepseek-v2-lite-16b")
    me = cfg.moe
    assert me.aux_loss_alpha == 0.001
    p, _ = split_tree(M.init_moe(cfg, jax.random.PRNGKey(17)))
    x2d = jax.random.normal(jax.random.PRNGKey(18), (B * S, cfg.d_model))
    _, ids, probs = M._router(p, x2d, me)
    got = float(M.balance_loss(probs, ids, me, B))
    want = _balance_numpy(np.asarray(probs).reshape(B, S, -1),
                          np.asarray(ids).reshape(B, S, -1), me.num_experts,
                          me.top_k, me.aux_loss_alpha)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    # the term trains the router through the scores
    g = jax.grad(lambda r: M.balance_loss(
        M._router({"router": r}, x2d, me)[2], ids, me, B))(p["router"])
    assert float(jnp.sum(jnp.abs(g))) > 0
