"""``correct`` comes out false when the timed path is broken, and for the
control; the harness refuses to run without a TPU.

A run here skips the harness's look for a chip and drives the rest of it
on the CPU at small widths, with the program's own pieces broken
underneath.  The limits are the real cells' limits.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent

TINY = {"name": "tiny-dense", "registry": "phi3-mini-3.8b",
        "reference": "ref_dense", "hidden_size": 64,
        "intermediate_size": 128, "num_attention_heads": 4,
        "num_key_value_heads": 4, "num_hidden_layers": 1, "vocab_size": 256,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
CELLS = {"tiny-hermes": ("hermes-2pod-lam1-jnpwire",
                         "phi3-hermes-2pod-lam1-jnpwire"),
         "tiny-single": ("single-b2", "phi3-single-1l")}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark, the program, and two small cells
    with the traffic and limits of the real ones at small sizes."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(REPO / "src", root / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny-dense.json").write_text(
        json.dumps(TINY))
    bench["configs"].append({"name": "tiny-dense", "source": "test",
                             "file": "bench/configs/tiny-dense.json",
                             "reduced": [], "why": "test"})
    for name, (traffic, real) in CELLS.items():
        t = json.loads((BENCH / "workloads" / f"{traffic}.json").read_text())
        t.update(seq=32, trace_seconds=1)
        (root / "bench" / "workloads" / f"tiny-{traffic}.json").write_text(
            json.dumps(t))
        shutil.copy(BENCH / "limits" / f"{real}.json",
                    root / "bench" / "limits" / f"{name}.json")
        bench["workloads"].append({"name": name, "config": "tiny-dense",
                                   "traffic": f"tiny-{traffic}", "chips": 1,
                                   "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and any("hermes" in w for w in m["workloads"]):
            m["workloads"].append("tiny-hermes")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    sys.path.insert(0, str(root / "bench"))
    return root


def _run(root, cell, capsys, seed=2147483659):
    import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "0.2", "--trace", "0"], require_chip=False, root=root)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _cell(root, name):
    import harness
    import spec
    cell = spec.load_cell(name, root)
    ref = spec.reference_module(cell["config"], root)
    cell.update(model=harness.program_config(cell["config"]),
                shapes=ref.param_shapes(cell["config"]), ref=ref)
    return cell


def test_sound_run_opens_a_gate_in_the_checked_steps(root, capsys):
    import harness
    out = harness.run(_cell(root, "tiny-hermes"), 2147483659, 0.2)
    gates = np.asarray(out["readings"]["gates"])
    assert gates[:3].any()
    # the gate is read on every round of the measured call, one per step
    # at lam 1: the warm-up block's and the window's
    assert out["steps"] >= 8 and out["window_s"] > 0
    assert len(gates) == len(out["readings"]["eval"]) == out["steps"] + 8
    assert out["compiles_in_window"] == 0
    res = _run(root, "tiny-hermes", capsys)
    # the limits are the full-size cell's; at these widths only the exact
    # numbers hold them
    assert res["checks"]["feed_miss"]["value"] == 0
    assert res["checks"]["gate_miss"]["value"] == 0
    assert all(np.isfinite(v["value"]) for v in res["checks"].values())


def test_single_window_is_the_entrys_own(root, capsys):
    """The window is ``train_single`` itself: its log_times bound it, and
    its per-step loss fetch is inside."""
    import harness
    out = harness.run(_cell(root, "tiny-single"), 2147483663, 0.2)
    assert out["steps"] % 16 == 0 and out["steps"] >= 16
    assert out["tokens"] == out["steps"] * 2 * 32
    assert len(out["readings"]["loss"]) == 3
    assert _run(root, "tiny-single", capsys, 2147483663)["correct"] is True


@pytest.mark.parametrize("hermes", [
    {"async_rounds": True},
    {"n_clusters": 2},
    {"participation_rate": 0.5, "admission": "topk"},
], ids=["async", "two-cluster", "participation"])
def test_any_hermes_setting_runs_from_its_traffic_file(root, hermes):
    """A traffic file with any ``HermesConfig`` field drives the entry with
    no edit to the harness; the window and the gate readings come out."""
    import harness
    cell = _cell(root, "tiny-hermes")
    tr = json.loads(json.dumps(cell["traffic"]))
    tr["pods"] = 4
    tr["hermes"].update(hermes)
    cell["traffic"] = tr
    out = harness.run(cell, 2147483667, 0.2)
    assert out["steps"] >= 8 and out["tokens"] > 0
    assert len(out["readings"]["eval"]) == len(out["readings"]["gates"])
    assert np.asarray(out["readings"]["gates"]).shape[1] == 4


def _broken_step(kind):
    import jax
    from repro.launch import train as T
    real = T.make_pod_step

    def make(cfg, optimizer, mesh=None):
        step = real(cfg, optimizer, mesh)
        if kind == "unchanged":
            @jax.jit
            def same(pp, po, b):
                _, _, losses = step(jax.tree.map(lambda x: x + 0, pp),
                                    jax.tree.map(lambda x: x + 0, po), b)
                return pp, po, losses
            return same
        return lambda pp, po, b: (lambda o: (o[0], o[1], o[2] * 1.01))(
            step(pp, po, b))
    return make


def _half_batch_loss(real):
    def loss(params, batch, cfg, *a, **k):
        t = batch["targets"]
        t = t.at[..., t.shape[-1] // 2:].set(-1)
        return real(params, {**batch, "targets": t}, cfg, *a, **k)
    return loss


def _no_exchange(payloads, mesh, *, axis="pod", n_pods=None):
    import jax
    return jax.tree.map(
        lambda a: a.at[1:].set(0) if a.ndim and a.shape[0] == n_pods else a,
        payloads)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange",
                                   "altered_loss"])
def test_hermes_fault_is_not_correct(root, capsys, monkeypatch, fault):
    from repro.dist import hermes_sync
    from repro.launch import train as T
    if fault == "unchanged":
        monkeypatch.setattr(T, "make_pod_step", _broken_step("unchanged"))
    elif fault == "altered_loss":
        monkeypatch.setattr(T, "make_pod_step", _broken_step("altered"))
    elif fault == "half_batch":
        monkeypatch.setattr(T, "lm_loss", _half_batch_loss(T.lm_loss))
    else:
        monkeypatch.setattr(hermes_sync, "gather_payloads", _no_exchange)
    assert _run(root, "tiny-hermes", capsys)["correct"] is False


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_single_fault_is_not_correct(root, capsys, monkeypatch, fault):
    from repro.launch import train as T
    if fault == "unchanged":
        real = T.make_optimizer

        def frozen(cfg, **kw):
            opt = real(cfg, **kw)
            return type(opt)(opt.init, lambda p, g, s: (p, opt.apply(
                p, g, s)[1]), opt.name)
        monkeypatch.setattr(T, "make_optimizer", frozen)
    else:
        monkeypatch.setattr(T, "lm_loss", _half_batch_loss(T.lm_loss))
    assert _run(root, "tiny-single", capsys)["correct"] is False


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(root, cell):
    """The reference in the program's place, computed with float8 matrix
    products: one precision step below the configuration's bfloat16."""
    import compare
    import ref_train
    import spec
    c = spec.load_cell(cell, root)
    ref = spec.reference_module(c["config"], root)
    good = ref_train.readings(ref, c, 2147483661)
    low = ref_train.readings(ref, c, 2147483661, low=True,
                             prog_eval=good.get("eval"))
    nums = compare.numbers(low, good)
    assert not compare.judge(nums, compare.load_limits(root, cell))


def _bench_run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH="src", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "phi3-single-1l", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _bench_run(REPO)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _bench_run(tmp_path)
    assert p.returncode != 0
    assert "correct" not in p.stdout
