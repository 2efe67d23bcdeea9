"""The DeepSeek-V2-Lite share's float32 reference against the program on
small shapes: loss and gradients, the initial weights, the expert shares
summing to the uncut layer, and ``correct`` coming out false for planted
faults and the control."""
import json
import os
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402
import ref_dsv2  # noqa: E402
import weights as W  # noqa: E402

CELL = "dsv2lite-single-ep8-4k"
REAL = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json")
                  .read_text())
# the registry share at small depth, width and vocabulary: expert width,
# MLA dims, 64 routed experts (8 held) and top-6 stay as published
SMALL = {**REAL, "name": "tiny-dsv2", "hidden_size": 64,
         "intermediate_size": 96, "num_attention_heads": 4,
         "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 256}


def _setup(config, seq=24, seed=3):
    from repro.config import replace
    from repro.models import init_lm
    mc = replace(harness.program_config(config), dtype="float32")
    harness.check_layout(mc, ref_dsv2.param_shapes(config))
    params = init_lm(mc, jax.random.PRNGKey(seed))[0]
    rows = W.lm_tokens(2 * (seq + 1), config["vocab_size"], 11).reshape(
        2, seq + 1)
    batch = {"tokens": jnp.asarray(rows[:, :-1]),
             "targets": jnp.asarray(rows[:, 1:])}
    return mc, params, batch


def test_published_layout_is_the_programs():
    """At the cell's own sizes the registry share's parameter tree is the
    reference's (shapes only, nothing allocated)."""
    mc = harness.program_config(REAL)
    assert (mc.moe.num_experts, mc.moe.held, mc.moe.top_k) == (64, 8, 6)
    assert (mc.d_ff, mc.moe.expert_ff, mc.first_dense_layers) == (10944,
                                                                   1408, 1)
    harness.check_layout(mc, ref_dsv2.param_shapes(REAL))
    harness.check_runnable(REAL, 4096)


@pytest.mark.parametrize("seed", [3, 2147483901])
def test_init_copy_is_the_programs(seed):
    from repro.models import init_lm
    mc = harness.program_config(SMALL)
    prog = init_lm(mc, jax.random.PRNGKey(seed))[0]
    key = jax.random.PRNGKey(seed)
    for mine, tol in ((ref_dsv2.init_params(SMALL, key), 0.0),
                      (jax.jit(lambda k: ref_dsv2.init_params(SMALL, k))(
                          key), 1e-6)):
        assert jax.tree.structure(mine) == jax.tree.structure(prog)
        for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(prog)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=tol, atol=0)


def test_yarn_frequencies_are_the_programs():
    from repro.models.layers import rope_freqs
    mc = harness.program_config(REAL)
    np.testing.assert_allclose(
        rope_freqs(64, 10000.0, mc.rope_scaling),
        ref_dsv2.yarn_inv_freq(REAL), rtol=1e-6)


@pytest.mark.parametrize("seed", [3, 8])
def test_reference_matches_program(seed):
    """Loss (cross entropy plus the balance terms) and every gradient, in
    float32, through the program's training path (dropless share)."""
    from repro.models import lm_loss
    mc, params, batch = _setup(SMALL, seed=seed)
    with jax.default_matmul_precision("highest"):
        (lp, aux), gp = jax.value_and_grad(
            lambda p: lm_loss(p, batch, mc, with_aux=True),
            has_aux=True)(params)
        lr, gr = jax.value_and_grad(
            lambda p: ref_dsv2.loss(p, batch, SMALL))(params)
    assert float(aux["balance"]) > 0
    assert int(aux["load"].sum()) > 0
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    # the init's queries (fan-in counted as the 4 heads) make attention
    # nearly one-hot, which carries float32 rounding to ~1e-4 of a leaf's
    # largest entry, in every leaf alike: compare each entry to its leaf
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=2e-3,
                                   atol=1e-3 * np.abs(b).max())


def _moe_inputs(seed=5, T=16):
    config = {**SMALL, "n_routed_experts": 64, "first_held_expert": 0}
    params = ref_dsv2.init_params(config, jax.random.PRNGKey(seed))
    ml = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    h = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (2, T, config["hidden_size"]))
    return config, ml, h


def test_shares_sum_to_the_uncut_layer():
    """Eight-way expert parallelism: each chip's part of the layer, with
    the shared experts counted once, adds up to what the uncut layer gives;
    the balance term is every chip's alike."""
    config, ml, h = _moe_inputs()
    with jax.default_matmul_precision("highest"):
        whole, aux = ref_dsv2._moe(ml, h, config, False)
        shared = ref_dsv2._swiglu(
            h.reshape(-1, h.shape[-1]), ml["shared_wi"], ml["shared_wg"],
            ml["shared_wo"], False).reshape(h.shape)
        total = shared
        for j in range(8):
            c = {**config, "n_routed_experts": 8, "first_held_expert": 8 * j}
            part = {**ml, **{k: ml[k][8 * j:8 * j + 8]
                             for k in ("wi", "wg", "wo")}}
            out, a = ref_dsv2._moe(part, h, c, False)
            total = total + (out - shared)
            assert float(a) == pytest.approx(float(aux), rel=1e-6)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def test_program_share_is_the_references():
    """The program's dropless layer holding experts 8-15 gives the
    reference's share of the same weights."""
    from repro.config import replace
    from repro.models import moe as M
    config, ml, h = _moe_inputs()
    share = {**config, "n_routed_experts": 8, "first_held_expert": 8}
    part = {**ml, **{k: ml[k][8:16] for k in ("wi", "wg", "wo")}}
    mc = replace(harness.program_config(SMALL), dtype="float32")
    mc = replace(mc, moe=replace(mc.moe, first_held=8))
    with jax.default_matmul_precision("highest"):
        want, balance = ref_dsv2._moe(part, h, share, False)
        got, aux = M.moe_dropless(part, h, mc, None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(aux["balance"]) == pytest.approx(float(balance), rel=1e-5)


@pytest.mark.parametrize("fault", ref_dsv2.FAULTS)
def test_each_fault_changes_the_reference(fault):
    mc, params, batch = _setup(SMALL)
    with jax.default_matmul_precision("highest"):
        good = float(ref_dsv2.loss(params, batch, SMALL))
        bad = float(ref_dsv2.loss(params, batch, {**SMALL, "fault": fault}))
        lo = float(ref_dsv2.loss(params, batch, SMALL, low=True))
    assert np.isfinite(bad) and bad != good
    assert np.isfinite(lo) and abs(lo - good) / good > 1e-4


# ---------------------------------------------------------------------------
# The cell's check at small sizes
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark, the program and a small copy of the
    cell: the cell's traffic and limits at sequence 32."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", root / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-dsv2.json").write_text(
        json.dumps(SMALL))
    bench["configs"].append({"name": "tiny-dsv2", "source": "test",
                             "file": "bench/configs/tiny-dsv2.json",
                             "reduced": [], "why": "test"})
    t = json.loads((BENCH / "workloads" / "single-b2-4k.json").read_text())
    t.update(seq=32, trace_seconds=1)
    (root / "bench" / "workloads" / "tiny-single-b2-4k.json").write_text(
        json.dumps(t))
    shutil.copy(BENCH / "limits" / f"{CELL}.json",
                root / "bench" / "limits" / "tiny-dsv2.json")
    bench["workloads"].append({"name": "tiny-dsv2", "config": "tiny-dsv2",
                               "traffic": "tiny-single-b2-4k", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _run(root, capsys, seed=2147483659, trace=0):
    import run
    rc = run.main(["--workload", "tiny-dsv2", "--seed", str(seed),
                   "--seconds", "0.2", "--trace", str(trace)],
                  require_chip=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def f32(monkeypatch):
    """The program computes in float32 here: at these widths bfloat16's
    rounding alone outgrows the full-size cell's limits, and in float32
    the sound run holds them with room, so a fault is what breaks them."""
    from repro.config import replace
    real = harness.program_config
    monkeypatch.setattr(harness, "program_config",
                        lambda c: replace(real(c), dtype="float32"))
    return monkeypatch


def test_sound_run_is_correct(root, capsys, f32):
    res = _run(root, capsys)
    assert res["correct"] is True, res["checks"]
    assert res["compiles_in_window"] == 0


def _renormalised(real):
    def router(p, x2d, me):
        wk, ids, probs = real(p, x2d, me)
        return wk / jnp.sum(wk, -1, keepdims=True), ids, probs
    return router


@pytest.mark.parametrize("fault", ["renorm", "no_shared", "no_mscale"])
def test_fault_is_not_correct(root, capsys, f32, fault):
    monkeypatch = f32
    from repro.models import attention as A
    from repro.models import moe as M
    if fault == "renorm":
        monkeypatch.setattr(M, "_router", _renormalised(M._router))
    elif fault == "no_shared":
        monkeypatch.setattr(M, "_shared", lambda p, x, cfg: jnp.zeros_like(x))
    else:
        monkeypatch.setattr(A, "mla_scale", lambda cfg: (
            cfg.resolved_head_dim + cfg.mla.rope_head_dim) ** -0.5)
    assert _run(root, capsys)["correct"] is False


def test_calibration_reads_the_program_as_the_run_does(root):
    """``calibrate_dsv2.program_readings`` takes the readings the harness
    takes of the same steps."""
    import calibrate_dsv2
    import compare
    import spec
    cell = spec.load_cell("tiny-dsv2", root)
    ref = spec.reference_module(cell["config"], root)
    cell.update(model=harness.program_config(cell["config"]),
                shapes=ref.param_shapes(cell["config"]), ref=ref)
    got = calibrate_dsv2.program_readings(
        cell, 5, harness.make_probes(cell["config"], ref))
    want = harness.run(cell, 5, 0.2)["readings"]
    assert compare.numbers(got, want) == {
        "feed_miss": 0.0, "loss_gap": 0.0, "grad_gap": 0.0, "state_gap": 0.0}


def _tiny_cell(root):
    import spec
    cell = spec.load_cell("tiny-dsv2", root)
    cell["model"] = harness.program_config(cell["config"])
    return cell


def test_step_scopes_reach_every_scope_and_reader(root):
    """A fresh compile of the cell's step maps ops to all five scopes, and
    each reader sums its scope's ops per step execution."""
    import spec
    import step_scopes
    ctx = {"cell": _tiny_cell(root)}
    phases = step_scopes.step_op_scopes(ctx)
    assert set(phases.values()) == set(step_scopes.SCOPES)
    # two step executions of 1 us per op, and an op of another executable
    ex = {op: 1e-6 for op in phases}
    ctx["trace"] = {"per_chip": [{"executions": {
        "jit_step_fn": [ex, ex], "jit_other": [{"x": 1.0}]}}]}
    for metric, scope in (("moe_dispatch_ms", "moe/dispatch"),
                          ("moe_experts_ms", "moe/experts"),
                          ("mla_ms", "mla/attend")):
        n = sum(1 for s in phases.values() if s == scope)
        got = spec.metric_reader(metric, root)(ctx)
        assert got == pytest.approx(n * 1e-3)
    ctx["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = spec.metric_reader("moe_experts_roofline", root)(ctx)
    assert share > 0
    # a program without the scopes maps nothing, and the readers say so
    empty = {"cell": ctx["cell"], "trace": ctx["trace"],
             "step_op_scopes": {}, "peaks": ctx["peaks"]}
    for metric in ("moe_dispatch_ms", "moe_experts_ms", "mla_ms",
                   "moe_experts_roofline"):
        assert spec.metric_reader(metric, root)(empty) is None


def test_flops_per_token_hand_count():
    import counts_dsv2
    # d 2048, 16 heads, nope 128 + rope 64, v 128, latent 512, dense 10944,
    # expert 1408, 2 shared, 64 routed (8 held) top-6, vocab 12800,
    # 1 dense + 4 expert layers, sequence 4096 (2048.5 keys on average)
    mla = (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
           + 16 * 192 * 2048.5 + 16 * 128 * 2048.5)
    dense = 3 * 2048 * 10944
    moe = 2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408
    forward = 5 * mla + dense + 4 * moe + 2048 * 12800
    got = counts_dsv2.flops_per_token(REAL, 4096)
    assert got == pytest.approx(6 * forward, rel=1e-12)
    assert got * 8192 == pytest.approx(15.256e12, rel=1e-3)
    work = counts_dsv2.expert_products(REAL, 8192)
    # 6144 held assignments per layer, 3 products, forward and backward
    assert work["flops"] == 4 * 9 * 2 * 6144 * 2048 * 1408
    assert work["bytes"] == 4 * 3 * 3 * 8 * 2048 * 1408 * 2
