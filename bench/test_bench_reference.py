"""The plain float32 references against the program on small shapes."""
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import ref_dense  # noqa: E402
import ref_rwkv6  # noqa: E402
import ref_train  # noqa: E402
import weights as W  # noqa: E402

DENSE = {"registry": "phi3-mini-3.8b", "reference": "ref_dense",
         "hidden_size": 64, "intermediate_size": 128,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
         "rms_norm_eps": 1e-6}
RWKV = {"registry": "rwkv6-3b", "reference": "ref_rwkv6",
        "hidden_size": 64, "head_size": 32, "intermediate_size": 128,
        "num_hidden_layers": 2, "vocab_size": 256, "layer_norm_epsilon": 1e-6}


def _setup(config, ref, seq=48):
    from repro.config import replace
    from repro.models import init_lm
    mc = replace(harness.program_config(config), dtype="float32")
    shapes = ref.param_shapes(config)
    harness.check_layout(mc, shapes)
    params = init_lm(mc, jax.random.PRNGKey(3))[0]
    rows = W.lm_tokens(2 * (seq + 1), config["vocab_size"], 11).reshape(
        2, seq + 1)
    batch = {"tokens": jnp.asarray(rows[:, :-1]),
             "targets": jnp.asarray(rows[:, 1:])}
    return mc, params, batch


def _agree(mc, params, batch, ref, config, **fw):
    from repro.models import lm_loss
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lambda p: lm_loss(p, batch, mc, **fw))(
            params)
        lr, gr = jax.value_and_grad(lambda p: ref.loss(p, batch, config))(
            params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_dense_reference_matches_program():
    mc, params, batch = _setup(DENSE, ref_dense)
    _agree(mc, params, batch, ref_dense, DENSE, impl="naive")


def test_rwkv6_reference_matches_program_exact_scan():
    # the program's exact per-token recurrence; its chunked path is the
    # one the benchmark measures against this reference on the chip
    mc, params, batch = _setup(RWKV, ref_rwkv6, seq=16)
    _agree(mc, params, batch, ref_rwkv6, RWKV, rec_impl="scan")


def test_control_precision_is_coarser():
    mc, params, batch = _setup(DENSE, ref_dense)
    with jax.default_matmul_precision("highest"):
        hi = float(ref_dense.loss(params, batch, DENSE))
        lo = float(ref_dense.loss(params, batch, DENSE, low=True))
    assert np.isfinite(lo) and abs(lo - hi) / hi > 1e-4


def test_int4_roundtrip_blocks_and_bounds():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 512))
    y = ref_train.int4_roundtrip(x, jax.random.PRNGKey(1))
    blocks = np.asarray(x).reshape(3, 2, 256)
    step = np.abs(blocks).max(-1, keepdims=True) / 7.0
    err = np.abs(np.asarray(y).reshape(3, 2, 256) - blocks)
    assert np.all(err <= step * (1 + 1e-6))
    # a leaf with no whole-block axis is padded along its last axis
    z = ref_train.int4_roundtrip(jnp.ones((5, 100)), jax.random.PRNGKey(2))
    np.testing.assert_allclose(np.asarray(z), 1.0)


def test_round_reference_matches_program_round():
    """One open round at small shapes: the program's merge (int4, error
    feedback, jnp path) and the reference's agree on the merged model to
    the int4 noise, with the same gates."""
    from repro.config import HermesConfig
    from repro.dist.hermes_sync import hermes_pod_state, hermes_round
    shapes = {"a": (4, 512), "b": (300,)}
    p0 = {k: jax.random.normal(jax.random.PRNGKey(i), s)
          for i, (k, s) in enumerate(shapes.items())}
    k1, k2 = jax.random.split(jax.random.PRNGKey(9))
    pods = [jax.tree.map(lambda x, k=k: x + 0.01 * jax.random.normal(
        jax.random.fold_in(k, 1), x.shape), p0) for k in (k1, k2)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *pods)
    hcfg = HermesConfig(alpha=10.0, lam=1, compression="int4",
                        kernel_dispatch="off")
    gup = hermes_pod_state(hcfg, 2)
    # two warm-up losses so the z-score is defined and every gate opens
    gup = {**gup, "queue": gup["queue"].at[:, :2].set(
        jnp.array([[3.0, 2.0], [3.0, 2.0]])),
        "count": jnp.array([2, 2], jnp.int32)}
    losses = jnp.array([1.0, 1.5])
    err0 = jax.tree.map(jnp.zeros_like, stacked)
    out = hermes_round(stacked, gup, losses, p0, jnp.float32(2.0), hcfg,
                       error=err0, rng=jax.random.PRNGKey(3))
    assert np.asarray(out["gates"]).tolist() == [True, True]
    errs = [jax.tree.map(jnp.zeros_like, p0) for _ in range(2)]
    w, new_pods, _, _ = ref_train._merge(
        p0, pods, errs, [True, True], [1.0, 1.5], 2.0,
        jax.random.PRNGKey(4), None, lambda i: jax.devices()[0],
        lambda p: 0.0)
    for name in shapes:
        got = np.asarray(out["w_global"][name]) - np.asarray(p0[name])
        want = np.asarray(w[name]) - np.asarray(p0[name])
        # two draws of int4 noise, each ~0.2 of the delta (rms)
        assert np.linalg.norm(got - want) / np.linalg.norm(want) < 0.4
        assert abs(np.linalg.norm(got) / np.linalg.norm(want) - 1) < 0.05
        np.testing.assert_array_equal(np.asarray(out["pod_params"][name][1]),
                                      np.asarray(out["w_global"][name]))


@pytest.mark.parametrize("seed", [5, 2147483901])
def test_token_stream_copy_is_the_programs(seed):
    from repro.data.synthetic import make_lm_dataset
    for n, vocab in ((4001, 32064), (1500, 256)):
        np.testing.assert_array_equal(W.lm_tokens(n, vocab, seed),
                                      make_lm_dataset(n, vocab, seed=seed))


def test_batch_copies_are_what_the_entries_draw():
    """``train_hermes``'s and ``train_single``'s first batches and the
    gate's held-out batch, drawn as the entries draw them: ``train_hermes``
    takes a step's tokens and its targets from two successive draws."""
    from repro.data.synthetic import make_lm_dataset
    from repro.launch.train import make_batches
    V, B, S, P, seed = 256, 2, 16, 3, 2147483661
    feed, ev = W.hermes_batches(V, seed, pods=P, batch=B, seq=S, steps=3)
    tokens = make_lm_dataset(B * S * 40 * P + B * S + 2, V, seed=seed)
    shards = np.array_split(tokens[:-(B * S + 1)], P)
    its = [make_batches(s, B, S, np.random.default_rng(seed + i))
           for i, s in enumerate(shards)]
    for step in range(3):
        want = {k: np.stack([next(b)[k] for b in its])
                for k in ("tokens", "targets")}
        for k in want:
            np.testing.assert_array_equal(feed[k][step], want[k])
    assert not np.array_equal(feed["tokens"][0, :, :, 1:],
                              feed["targets"][0, :, :, :-1])
    held = next(make_batches(tokens[-(B * S + 1):], B, S,
                             np.random.default_rng(seed)))
    np.testing.assert_array_equal(ev["tokens"], held["tokens"])
    np.testing.assert_array_equal(ev["targets"], held["targets"])
    single = W.single_batches(V, seed, batch=B, seq=S, steps=2)
    it = make_batches(make_lm_dataset(B * S * 40 + 1, V, seed=seed), B, S,
                      np.random.default_rng(seed))
    for step in range(2):
        b = next(it)
        np.testing.assert_array_equal(single["tokens"][step, 0], b["tokens"])
        np.testing.assert_array_equal(single["targets"][step, 0],
                                      b["targets"])


@pytest.mark.parametrize("seed", [3, 2147483901])
def test_dense_init_copy_is_the_programs(seed):
    """The reference's draw of the initial weights equals ``init_lm``'s,
    to the last bit where both run op by op, and to a rounding of the
    last bit under ``jit``."""
    from repro.models import init_lm
    mc = harness.program_config(DENSE)
    prog = init_lm(mc, jax.random.PRNGKey(seed))[0]
    key = jax.random.PRNGKey(seed)
    for mine, tol in ((ref_dense.init_params(DENSE, key), 0.0),
                      (jax.jit(lambda k: ref_dense.init_params(DENSE, k))(
                          key), 1e-6)):
        assert jax.tree.structure(mine) == jax.tree.structure(prog)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(prog)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=0)
