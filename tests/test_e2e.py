"""End-to-end drivers: single trainer, Hermes Level-B trainer, server."""

import pytest

from repro.config import HermesConfig, OptimizerConfig, replace
from repro.launch.train import _preset, train_single, train_hermes
from repro.launch.serve import serve


def test_train_single_loss_decreases(tmp_path):
    cfg = _preset("lmtiny")
    out = train_single(cfg, steps=30, batch=4, seq=32,
                       opt_cfg=OptimizerConfig(name="adamw", lr=3e-3),
                       ckpt_dir=str(tmp_path), log_every=1000)
    assert out["final_loss"] < out["first_loss"]


def test_train_restore_resumes(tmp_path):
    cfg = _preset("lmtiny")
    train_single(cfg, steps=10, batch=4, seq=32,
                 opt_cfg=OptimizerConfig(name="adamw", lr=3e-3),
                 ckpt_dir=str(tmp_path), log_every=1000)
    out = train_single(cfg, steps=20, batch=4, seq=32,
                       opt_cfg=OptimizerConfig(name="adamw", lr=3e-3),
                       ckpt_dir=str(tmp_path), restore=True, log_every=1000)
    assert out["final_loss"] < out["first_loss"]


def test_train_hermes_gates_and_converges():
    cfg = _preset("lmtiny")
    out = train_hermes(cfg, steps=40, batch=4, seq=32, pods=2,
                       opt_cfg=OptimizerConfig(name="adamw", lr=3e-3),
                       hcfg=HermesConfig(alpha=-0.8, beta=0.1, lam=4, eta=1.0),
                       log_every=1000)
    assert out["rounds"] > 0
    assert out["merges"] <= out["rounds"]          # the gate filters
    assert out["global_loss"] < 8.0                # moved off init


def test_serve_generates():
    cfg = _preset("lmtiny")
    out = serve(cfg, batch=2, prompt_len=16, gen=8)
    assert out["decode_tok_per_s"] > 0
    assert len(out["generated"][0]) == 8


def test_preset_layers_keeps_published_widths():
    from repro.configs import get_config
    full = get_config("phi3-mini-3.8b")
    cut = _preset("phi3-mini-3.8b", 1)
    assert cut.num_layers == 1
    assert cut == replace(full, num_layers=1)
    assert _preset("phi3-mini-3.8b").d_model < full.d_model  # smoke config


def test_compile_cache_dir_fixed_or_from_env(monkeypatch, tmp_path):
    """Unset, every run in one checkout shares ``<repo>/.jax_cache``; set,
    JAX_COMPILATION_CACHE_DIR is used and nothing is set in code."""
    import jax
    from repro.launch.train import REPO_ROOT, configure_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        first, second = configure_compile_cache(), configure_compile_cache()
        assert first == second == str(REPO_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_make_pod_mesh_refuses_fewer_devices_than_pods():
    import jax
    from repro.launch.mesh import make_pod_mesh
    with pytest.raises(ValueError, match="cannot host"):
        make_pod_mesh(len(jax.devices()) + 1)


_PLACED_SCRIPT = r"""
import json
from repro.config import HermesConfig, OptimizerConfig
from repro.launch.mesh import make_pod_mesh
from repro.launch.train import _preset, train_hermes

kw = dict(steps=6, batch=2, seq=32, pods=4, log_every=10 ** 6,
          opt_cfg=OptimizerConfig(name="adamw", lr=3e-3),
          hcfg=HermesConfig(alpha=-0.5, lam=1, eta=1.0))
placed = train_hermes(_preset("lmtiny"), mesh=make_pod_mesh(4), **kw)
plain = train_hermes(_preset("lmtiny"), **kw)
print(json.dumps({k: placed[k] for k in ("pod_rows", "w_global_replicated",
                                         "gates", "global_loss", "merges")}
                 | {"plain_gates": plain["gates"],
                    "plain_loss": plain["global_loss"]}))
"""


def test_train_hermes_places_one_pod_per_device():
    """Four virtual CPU devices: each holds exactly one pod's rows of every
    pod-stacked tree, w_global is replicated, and the placed run opens the
    same gates as the unplaced one with the same loss."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    repo = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(repo / "src"), env.get("PYTHONPATH", "")) if p)
    r = subprocess.run([sys.executable, "-c", _PLACED_SCRIPT], env=env,
                       cwd=str(repo), capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for name, rows in out["pod_rows"].items():
        assert rows == {str(p): [p] for p in range(4)}, name
    assert out["w_global_replicated"]
    assert out["merges"] >= 1
    assert out["gates"] == out["plain_gates"]
    # same per-pod math; the per-device and the batched programs may only
    # reassociate f32 sums (a few 1e-6 relative after six steps)
    assert abs(out["global_loss"] - out["plain_loss"]) <= (
        1e-4 * abs(out["plain_loss"]))
