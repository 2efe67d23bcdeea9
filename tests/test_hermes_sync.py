"""Level-B device Hermes vs host Algorithm 2 equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import HermesConfig
from repro.dist.hermes_sync import (
    _gate, hermes_merge, hermes_pod_state, hermes_round,
)


def _pods(key, n, shape=(6, 5)):
    return {"w": jax.random.normal(key, (n,) + shape)}


def test_single_gate_reduces_to_algorithm2():
    """With exactly one gate open, the merge must equal Algorithm 2's
    model-space form: (W1 w_global + W2 w_local) / (W1 + W2)."""
    key = jax.random.PRNGKey(0)
    pods = _pods(key, 3)
    wg = {"w": jax.random.normal(jax.random.PRNGKey(1), (6, 5))}
    gates = jnp.array([False, True, False])
    losses = jnp.array([9.9, 0.8, 9.9])
    L = jnp.float32(1.3)
    new_pods, new_g, _, any_push = hermes_merge(
        pods, gates, losses, wg, L)
    W1, W2 = 1 / 1.3, 1 / 0.8
    want = (W1 * wg["w"] + W2 * pods["w"][1]) / (W1 + W2)
    np.testing.assert_allclose(np.asarray(new_g["w"]), np.asarray(want),
                               atol=1e-5)
    # the pushing pod refreshes; the others keep local params
    np.testing.assert_allclose(np.asarray(new_pods["w"][1]),
                               np.asarray(new_g["w"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_pods["w"][0]),
                               np.asarray(pods["w"][0]), atol=1e-6)
    assert bool(any_push)


def test_no_gate_is_identity():
    key = jax.random.PRNGKey(2)
    pods = _pods(key, 4)
    wg = {"w": jax.random.normal(jax.random.PRNGKey(3), (6, 5))}
    gates = jnp.zeros((4,), bool)
    new_pods, new_g, _, any_push = hermes_merge(
        pods, gates, jnp.ones((4,)), wg, jnp.float32(1.0))
    assert not bool(any_push)
    np.testing.assert_allclose(np.asarray(new_g["w"]), np.asarray(wg["w"]))
    np.testing.assert_allclose(np.asarray(new_pods["w"]),
                               np.asarray(pods["w"]))


def test_round_gates_fire_on_loss_drop():
    # alpha=-1.5: pod 0's +-1-sigma alternation never crosses the gate
    cfg = HermesConfig(alpha=-1.5, window=6, lam=100)
    n = 2
    pods = _pods(jax.random.PRNGKey(4), n)
    gst = hermes_pod_state(cfg, n)
    wg = {"w": jnp.zeros((6, 5))}
    fired = []
    for i in range(10):
        # pod 0: flat losses; pod 1: sudden improvement at i==8
        losses = jnp.array([1.0 + 0.01 * ((-1) ** i),
                            1.0 if i < 8 else 0.2], jnp.float32)
        out = hermes_round(pods, gst, losses, wg, jnp.float32(1.0), cfg)
        gst = out["gup"]
        fired.append(np.asarray(out["gates"]))
    fired = np.stack(fired)
    assert fired[:, 0].sum() == 0          # pod 0 never fires
    assert fired[8:, 1].sum() >= 1         # pod 1 fires on its drop


def test_compressed_merge_close_to_exact():
    cfg = HermesConfig(alpha=-0.1, window=4, lam=2, compression="int8")
    pods = _pods(jax.random.PRNGKey(5), 2)
    wg = {"w": jnp.zeros((6, 5))}
    gates = jnp.array([True, True])
    losses = jnp.array([0.5, 0.5])
    _, g_exact, _, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.0),
                                    compression="none")
    _, g_int8, _, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.0),
                                   compression="int8")
    np.testing.assert_allclose(np.asarray(g_int8["w"]),
                               np.asarray(g_exact["w"]), atol=0.05)


def test_kernel_path_matches_jnp_path():
    pods = _pods(jax.random.PRNGKey(6), 2)
    wg = {"w": jax.random.normal(jax.random.PRNGKey(7), (6, 5))}
    gates = jnp.array([True, False])
    losses = jnp.array([0.7, 9.9])
    _, g1, _, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.1))
    _, g2, _, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.1),
                               use_kernel=True)
    np.testing.assert_allclose(np.asarray(g1["w"]), np.asarray(g2["w"]),
                               atol=1e-5)


def test_fused_compressed_merge_matches_jnp_path():
    """use_kernel + int8 routes the payload through the fused dequant-merge
    kernel; output and error residual must match the decode-then-merge path."""
    pods = {"w": jax.random.normal(jax.random.PRNGKey(8), (3, 40, 17)),
            "b": jax.random.normal(jax.random.PRNGKey(9), (3, 11))}
    wg = {"w": jax.random.normal(jax.random.PRNGKey(10), (40, 17)),
          "b": jnp.zeros((11,))}
    gates = jnp.array([True, False, True])
    losses = jnp.array([0.8, 9.9, 1.2])
    _, g1, e1, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.3),
                                compression="int8")
    _, g2, e2, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.3),
                                compression="int8", use_kernel=True)
    for k in wg:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-5, err_msg=k)
        np.testing.assert_allclose(np.asarray(e1[k]), np.asarray(e2[k]),
                                   atol=1e-7, err_msg=k)


def test_fused_merge_consumes_payloads_directly(monkeypatch):
    """The compressed kernel merge dispatches to ops.dequant_merge with the
    int8 payload — it never routes a reconstructed fp32 tree through the
    loss_weighted_update kernel."""
    from repro.kernels import ops
    calls = {"fused": 0, "recv": 0}
    real = ops.dequant_merge

    def spy_fused(g, q, scales, *a, **kw):
        assert q.dtype == jnp.int8
        calls["fused"] += 1
        return real(g, q, scales, *a, **kw)

    def spy_recv(*a, **kw):
        calls["recv"] += 1
        raise AssertionError("fp32 recv-tree merge used on the fused path")

    monkeypatch.setattr(ops, "dequant_merge", spy_fused)
    monkeypatch.setattr(ops, "loss_weighted_update", spy_recv)
    pods = _pods(jax.random.PRNGKey(11), 2)
    wg = {"w": jnp.zeros((6, 5))}
    hermes_merge(pods, jnp.array([True, True]), jnp.array([0.5, 0.6]),
                 wg, jnp.float32(1.0), compression="int8", use_kernel=True)
    assert calls["fused"] == 1 and calls["recv"] == 0


def test_fused_merge_without_error_feedback_never_decodes(monkeypatch):
    """track_error=False on the fused path must not build any fp32
    reconstruction: the payload is only ever read by the kernel."""
    from repro.dist import wire
    fmt = wire.get_format("int8")
    monkeypatch.setattr(
        type(fmt), "decode",
        lambda *a, **kw: (_ for _ in ()).throw(
            AssertionError("decode called on the no-residual fused path")))
    pods = _pods(jax.random.PRNGKey(15), 2)
    wg = {"w": jnp.zeros((6, 5))}
    _, new_g, new_err, _ = hermes_merge(
        pods, jnp.array([True, True]), jnp.array([0.5, 0.6]), wg,
        jnp.float32(1.0), compression="int8", use_kernel=True,
        track_error=False)
    assert new_err is None
    assert bool(jnp.all(jnp.isfinite(new_g["w"])))


def test_int4_stochastic_merge_close_to_exact():
    pods = _pods(jax.random.PRNGKey(12), 2)
    wg = {"w": jnp.zeros((6, 5))}
    gates = jnp.array([True, True])
    losses = jnp.array([0.5, 0.5])
    _, g_exact, _, _ = hermes_merge(pods, gates, losses, wg,
                                    jnp.float32(1.0), compression="none")
    _, g_int4, _, _ = hermes_merge(pods, gates, losses, wg, jnp.float32(1.0),
                                   compression="int4",
                                   rng=jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(g_int4["w"]),
                               np.asarray(g_exact["w"]), atol=0.2)


def test_round_with_custom_lossless_format():
    """A registered lossless WireFormat must work through hermes_round's
    lax.cond with default error feedback (both branches carry a residual
    tree for every non-'none' format; lossless ones hold exact zeros)."""
    from repro.dist import wire

    class Exact(wire.WireFormat):
        name = "testonly-exact"
        lossy = False

        def encode(self, x, *, rng=None):
            return {"x": x}

        def decode(self, payload, shape, dtype):
            return payload["x"].reshape(shape).astype(dtype)

        def payload_bytes(self, shape):
            return 4 * max(1, int(np.prod(shape)))

    try:
        wire.register(Exact())
        cfg = HermesConfig(alpha=-0.0001, window=3, lam=1,
                           compression="testonly-exact")
        n = 2
        pods = _pods(jax.random.PRNGKey(16), n)
        gst = hermes_pod_state(cfg, n)
        wg = {"w": jnp.zeros((6, 5))}
        error = None
        for i in range(4):
            losses = jnp.array([1.0 / (i + 1), 2.0 / (i + 1)], jnp.float32)
            out = hermes_round(pods, gst, losses, wg, jnp.float32(1.0), cfg,
                               error=error)
            gst, error = out["gup"], out["error"]
            wg = out["w_global"]
        assert float(jnp.abs(error["w"]).max()) == 0.0  # lossless residual
    finally:
        wire._REGISTRY.pop("testonly-exact", None)


def test_closed_round_skips_merge_and_stays_bit_identical(monkeypatch):
    """hermes_round wraps the merge in lax.cond on any_push: a fully closed
    round must return its inputs bit-identically (compressed config included)
    without tracing a push."""
    cfg = HermesConfig(alpha=-3.0, window=4, lam=100, compression="int8")
    n = 3
    pods = _pods(jax.random.PRNGKey(13), n)
    gst = hermes_pod_state(cfg, n)
    wg = {"w": jax.random.normal(jax.random.PRNGKey(14), (6, 5))}
    out = hermes_round(pods, gst, jnp.ones((n,)), wg, jnp.float32(1.0), cfg)
    assert not bool(out["any_push"])
    np.testing.assert_array_equal(np.asarray(out["w_global"]["w"]),
                                  np.asarray(wg["w"]))
    np.testing.assert_array_equal(np.asarray(out["pod_params"]["w"]),
                                  np.asarray(pods["w"]))
    # the error-feedback state starts at zero on closed rounds
    assert float(jnp.abs(out["error"]["w"]).max()) == 0.0


def _cond_around_merge(pods, gup, losses, wg, L, cfg, *, live=None,
                       error=None, rng=None):
    """The round with the whole merge under the cond: ``hermes_merge``
    (refresh and error-feedback select included) in the open branch, the
    inputs passed through the closed one."""
    gates, new_gup, any_push = _gate(gup, losses, cfg, live, rng)
    err_in = error if cfg.error_feedback else None

    def _open(args):
        p, w, e = args
        new_p, new_w, new_e, _ = hermes_merge(
            p, gates, losses, w, L, compression=cfg.compression, error=e,
            rng=rng, track_error=cfg.error_feedback)
        return new_p, new_w, new_e

    def _closed(args):
        p, w, e = args
        if cfg.compression != "none" and cfg.error_feedback and e is None:
            e = jax.tree.map(jnp.zeros_like, p)
        return p, w, e

    new_p, new_w, new_e = jax.lax.cond(any_push, _open, _closed,
                                       (pods, wg, err_in))
    return {"pod_params": new_p, "w_global": new_w, "gup": new_gup,
            "error": new_e, "gates": gates, "any_push": any_push}


# test-round losses after two warm-up rounds at 1.0 and 1.2: 0.5 and 0.6
# open a gate (z far below alpha), 2.0 keeps it shut
_ROUND_KINDS = {
    "all_open": ([0.5, 0.5, 0.6], None),
    "some_open": ([0.5, 2.0, 0.6], None),
    "all_closed": ([2.0, 2.0, 2.0], None),
    "dead_pod": ([0.5, 0.5, 0.6], [True, False, True]),
}


@pytest.mark.parametrize("compression", ["int4", "none"])
@pytest.mark.parametrize("kind", sorted(_ROUND_KINDS))
def test_round_matches_cond_around_merge(kind, compression):
    """Narrowing the round's cond to the merge changes no bit: pods,
    global model, residual, GUP state and gates equal those of the round
    whose cond wraps ``hermes_merge`` whole, jitted both; a fully closed
    round returns its inputs unchanged."""
    cfg = HermesConfig(alpha=-0.5, beta=0.1, lam=1, window=10,
                       compression=compression,
                       error_feedback=compression != "none")
    n = 3
    key = jax.random.PRNGKey(21)
    wg = {"w": jax.random.normal(jax.random.fold_in(key, 0), (8, 512)),
          "b": jax.random.normal(jax.random.fold_in(key, 1), (7,))}
    pods = jax.tree.map(lambda g: g[None] + 0.05 * jax.random.normal(
        jax.random.fold_in(key, 2), (n,) + g.shape), wg)
    error = (jax.tree.map(lambda p: 1e-3 * jax.random.normal(
        jax.random.fold_in(key, 3), p.shape), pods)
        if cfg.error_feedback else None)
    losses, live = _ROUND_KINDS[kind]
    if live is not None:
        # the dead pod's replica has diverged: it must poison nothing
        pods = jax.tree.map(lambda p: p.at[1].set(jnp.nan), pods)
        live = jnp.asarray(live)
    gup = hermes_pod_state(cfg, n)
    for warm in (1.0, 1.2):
        _, gup, _ = _gate(gup, jnp.full((n,), warm, jnp.float32), cfg,
                          None, None)
    args = (pods, gup, jnp.asarray(losses, jnp.float32), wg,
            jnp.float32(1.1))
    rng = jax.random.PRNGKey(5)
    new = jax.jit(lambda p, g, l, w, L, e: hermes_round(
        p, g, l, w, L, cfg, live=live, error=e, rng=rng))(*args, error)
    old = jax.jit(lambda p, g, l, w, L, e: _cond_around_merge(
        p, g, l, w, L, cfg, live=live, error=e, rng=rng))(*args, error)

    want_gates = {"all_open": [1, 1, 1], "some_open": [1, 0, 1],
                  "all_closed": [0, 0, 0], "dead_pod": [1, 0, 1]}[kind]
    np.testing.assert_array_equal(np.asarray(new["gates"]),
                                  np.asarray(want_gates, bool))
    assert set(new) == set(old)
    for k in new:
        assert (jax.tree.structure(new[k]) == jax.tree.structure(old[k])), k
        for a, b in zip(jax.tree.leaves(new[k]), jax.tree.leaves(old[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=k)
    if kind == "all_closed":
        for k, given in (("pod_params", pods), ("w_global", wg),
                         ("error", error)):
            for a, b in zip(jax.tree.leaves(new[k]), jax.tree.leaves(given)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=k)
    else:
        for leaf in jax.tree.leaves(new["w_global"]):
            assert bool(jnp.all(jnp.isfinite(leaf)))


# ---------------------------------------------------------------------------
# Async double-buffered rounds (DESIGN.md §8): dispatch + commit
# ---------------------------------------------------------------------------

def _async_toy(seed=0, n_pods=4, shapes=((8, 16), (16,))):
    key = jax.random.PRNGKey(seed)
    wg = {f"l{i}": jax.random.normal(jax.random.fold_in(key, i), s)
          for i, s in enumerate(shapes)}
    pods = jax.tree.map(
        lambda g: g[None] + 0.01 * jax.random.normal(
            jax.random.fold_in(key, 7), (n_pods,) + g.shape), wg)
    return pods, wg


@pytest.mark.parametrize("mode", ["none", "fp16", "int8", "int4"])
def test_dispatch_commit_bit_identical_to_round(mode):
    """Back-to-back dispatch+commit IS hermes_round executed in halves:
    same rng folds, same merge loop bodies, same cond structure — so with
    no intervening work the split must be bit-identical, per round, for
    every wire format (the anchor the async pipeline's correctness hangs
    on)."""
    from repro.dist.hermes_sync import hermes_commit, hermes_dispatch
    cfg = HermesConfig(alpha=-1.3, beta=0.1, lam=3, window=4,
                       compression=mode,
                       error_feedback=mode in ("int8", "int4"))
    n = 4
    pods, wg = _async_toy(n_pods=n)
    gup = hermes_pod_state(cfg, n)
    err = None
    key = jax.random.PRNGKey(42)
    for r in range(4):
        losses = jnp.asarray([1.0 - 0.1 * r, 1.2, 0.9, 1.1 - 0.2 * r],
                             jnp.float32)
        rng = jax.random.fold_in(key, r)
        sync = hermes_round(pods, gup, losses, wg, jnp.float32(1.0), cfg,
                            error=err, rng=rng)
        dp = hermes_dispatch(pods, gup, losses, wg, jnp.float32(1.0), cfg,
                             error=err, rng=rng)
        cm = hermes_commit(pods, dp["pending"], wg, cfg=cfg)
        np.testing.assert_array_equal(np.asarray(dp["gates"]),
                                      np.asarray(sync["gates"]))
        for a, b in zip(jax.tree.leaves(cm["w_global"]),
                        jax.tree.leaves(sync["w_global"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(cm["pod_params"]),
                        jax.tree.leaves(sync["pod_params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        pods, wg, gup = (sync["pod_params"], sync["w_global"], dp["gup"])
        err = sync.get("error")


def test_async_pipeline_staleness_parity():
    """A pipelined loop with real local compute between dispatch and
    commit (staleness 1) must track the synchronous trajectory within a
    small tolerance, and its dispatch/commit/drain accounting must
    balance."""
    from repro.dist.hermes_sync import hermes_commit, hermes_dispatch
    cfg = HermesConfig(alpha=-1.3, beta=0.1, lam=2, window=4,
                       compression="int4", error_feedback=True)
    n = 4
    key = jax.random.PRNGKey(5)
    target = {"w": jax.random.normal(key, (8, 16))}

    def local_step(pods):
        # one SGD step on the per-pod quadratic 0.5*||p - target||^2
        return jax.tree.map(lambda p, t: p - 0.2 * (p - t[None]),
                            pods, target)

    def losses_of(pods):
        per = jnp.stack([
            jnp.mean((pods["w"][i] - target["w"]) ** 2)
            for i in range(n)])
        return per.astype(jnp.float32), jnp.float32(
            jnp.mean((wg0["w"] - target["w"]) ** 2))

    pods0 = {"w": jax.random.normal(jax.random.fold_in(key, 1),
                                    (n, 8, 16))}
    wg0 = {"w": jax.random.normal(jax.random.fold_in(key, 2), (8, 16))}
    rounds = 25

    def run_sync():
        pods, wg, gup, err = pods0, wg0, hermes_pod_state(cfg, n), None
        opens = 0
        for r in range(rounds):
            pods = local_step(pods)
            losses, L = losses_of(pods)
            out = hermes_round(pods, gup, losses, wg, L, cfg, error=err,
                               rng=jax.random.fold_in(key, 100 + r))
            opens += int(out["any_push"])
            pods, wg, gup, err = (out["pod_params"], out["w_global"],
                                  out["gup"], out["error"])
        return wg, opens

    def run_async():
        pods, wg, gup, err = pods0, wg0, hermes_pod_state(cfg, n), None
        pending = None
        dispatched = committed = 0
        for r in range(rounds):
            pods = local_step(pods)
            losses, L = losses_of(pods)
            if pending is not None:
                cm = hermes_commit(pods, pending, wg, cfg=cfg)
                pods, wg = cm["pod_params"], cm["w_global"]
                committed += int(cm["any_push"])
            dp = hermes_dispatch(pods, gup, losses, wg, L, cfg,
                                 error=err,
                                 rng=jax.random.fold_in(key, 100 + r))
            gup, err, pending = dp["gup"], dp["error"], dp["pending"]
            dispatched += int(dp["any_push"])
        if pending is not None:  # drain: the last in-flight round lands
            cm = hermes_commit(pods, pending, wg, cfg=cfg)
            pods, wg = cm["pod_params"], cm["w_global"]
            committed += int(cm["any_push"])
        return wg, dispatched, committed

    wg_sync, opens = run_sync()
    wg_async, dispatched, committed = run_async()
    assert opens > 0, "schedule never opened a gate; test is vacuous"
    assert dispatched == committed  # every in-flight round lands exactly once
    # Staleness-1 forks the trajectory (gates fire on slightly different
    # losses), so the parity claim is at the objective level: both runs
    # must converge to the same global loss within tolerance.
    loss0 = float(jnp.mean((wg0["w"] - target["w"]) ** 2))
    loss_sync = float(jnp.mean((wg_sync["w"] - target["w"]) ** 2))
    loss_async = float(jnp.mean((wg_async["w"] - target["w"]) ** 2))
    assert loss_sync <= 0.02 * loss0 and loss_async <= 0.02 * loss0, (
        loss0, loss_sync, loss_async)
    assert abs(loss_async - loss_sync) <= 0.02 * loss0, (
        loss0, loss_sync, loss_async)


def test_commit_live_mask_blocks_posthumous_merge():
    """A pod that dies between dispatch and commit must not merge: commit
    under the survivor mask equals a commit whose dispatch-time gates were
    already shut for the dead pod, and the dead pod is never refreshed."""
    from repro.dist.hermes_sync import hermes_commit, hermes_dispatch
    cfg = HermesConfig(alpha=-1.3, beta=0.1, lam=2, window=4,
                       compression="int8", error_feedback=True)
    n = 3
    pods, wg = _async_toy(seed=3, n_pods=n)
    gup = hermes_pod_state(cfg, n)
    # warm the queues so gates can open, then force a known gate pattern
    for r in range(3):
        losses = jnp.asarray([1.0, 1.0, 1.0], jnp.float32) - 0.01 * r
        dp = hermes_dispatch(pods, gup, losses, wg, jnp.float32(1.0), cfg,
                             rng=jax.random.fold_in(jax.random.PRNGKey(0),
                                                    r))
        gup = dp["gup"]
    losses = jnp.asarray([0.2, 0.25, 1.0], jnp.float32)  # pods 0,1 push
    dp = hermes_dispatch(pods, gup, losses, wg, jnp.float32(1.0), cfg,
                         rng=jax.random.PRNGKey(9))
    gates = np.asarray(dp["gates"])
    assert gates[0] and gates[1], gates

    live = jnp.asarray([True, False, True])  # pod 1 died in flight
    masked = hermes_commit(pods, dp["pending"], wg, cfg=cfg, live=live)
    # oracle: the same pending with pod 1's gate shut at dispatch time
    edited = dict(dp["pending"])
    edited["gates"] = dp["pending"]["gates"] & live
    oracle = hermes_commit(pods, edited, wg, cfg=cfg)
    for a, b in zip(jax.tree.leaves(masked["w_global"]),
                    jax.tree.leaves(oracle["w_global"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # dead pod keeps its local params (no posthumous refresh)
    for k in pods:
        np.testing.assert_array_equal(
            np.asarray(masked["pod_params"][k][1]), np.asarray(pods[k][1]))
    # the survivor that pushed still refreshes to the new global
    for k in pods:
        np.testing.assert_array_equal(
            np.asarray(masked["pod_params"][k][0]),
            np.asarray(masked["w_global"][k]))


def test_elastic_shrink_flushes_pending_under_survivor_mask():
    """elastic_shrink on a state carrying an async pending buffer commits
    it first under the survivor mask: survivors' in-flight pushes land,
    the dropped pod's never does, and the resized state carries no
    pending."""
    from repro.dist.hermes_sync import hermes_commit, hermes_dispatch
    from repro.launch.elastic import elastic_shrink
    cfg = HermesConfig(alpha=-1.3, beta=0.1, lam=2, window=4,
                       compression="int8", error_feedback=True,
                       min_live_pods=1)
    n = 3
    pods, wg = _async_toy(seed=11, n_pods=n)
    gup = hermes_pod_state(cfg, n)
    for r in range(3):
        dp = hermes_dispatch(pods, gup,
                             jnp.full((n,), 1.0 - 0.01 * r, jnp.float32),
                             wg, jnp.float32(1.0), cfg,
                             rng=jax.random.fold_in(jax.random.PRNGKey(1),
                                                    r))
        gup = dp["gup"]
    losses = jnp.asarray([0.2, 0.25, 0.3], jnp.float32)  # all push
    dp = hermes_dispatch(pods, gup, losses, wg, jnp.float32(1.0), cfg,
                         rng=jax.random.PRNGKey(2))
    assert np.asarray(dp["gates"]).all()

    keep = [0, 2]  # pod 1 dies with its push in flight
    state = {"pod_params": pods, "gup": dp["gup"], "error": dp["error"],
             "w_global": wg, "pending": dp["pending"]}
    new_state, _ = elastic_shrink(state, keep, None, cfg=cfg)
    assert new_state["pending"] is None
    # oracle: commit under the survivor mask, then take the rows
    live = jnp.asarray([True, False, True])
    cm = hermes_commit(pods, dp["pending"], wg, cfg=cfg, live=live)
    for a, b in zip(jax.tree.leaves(new_state["w_global"]),
                    jax.tree.leaves(cm["w_global"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in pods:
        np.testing.assert_array_equal(
            np.asarray(new_state["pod_params"][k]),
            np.asarray(cm["pod_params"][k][np.asarray(keep)]))
