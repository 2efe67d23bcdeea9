"""The harness is driven by data: cells, traffic mixes, configurations and
per-layer metric readers are found by name."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import spec  # noqa: E402


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


@pytest.fixture()
def root(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_every_declared_cell_resolves():
    bench = spec.load_benchmark(REPO)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], REPO)
        assert cell["config"]["name"] == w["config"]
        assert spec.reference_module(cell["config"]).param_shapes(
            cell["config"])
        for m in cell["per_layer"]:
            assert callable(spec.metric_reader(m["name"], REPO))
        assert (REPO / "bench" / "limits" / f"{w['name']}.json").is_file()


def test_new_cell_is_picked_up_without_editing_a_file(root):
    before = _digest(root / "bench")
    traffic = json.loads((root / "bench" / "workloads" /
                          "hermes-2pod-lam1-jnpwire.json").read_text())
    traffic["seq"] = 4096
    (root / "bench" / "workloads" / "hermes-2pod-lam1-4k.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "limits" / "phi3-hermes-4k.json").write_text(
        json.dumps({"limits": {"loss_gap": 0.01}}))
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['run']['steps'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "phi3-hermes-4k", "config": "phi3-mini-3.8b",
         "traffic": "hermes-2pod-lam1-4k", "chips": 1, "why": "test"})
    bench["per_layer"].append(
        {"name": "steps_seen", "unit": "steps", "better": "higher",
         "source": "host_clock", "layer": "device", "moves": "tokens_per_s",
         "workloads": ["phi3-hermes-4k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.load_cell("phi3-hermes-4k", root)
    assert cell["traffic"]["seq"] == 4096
    assert "steps_seen" in [m["name"] for m in cell["per_layer"]]
    assert spec.metric_reader("steps_seen", root)(
        {"run": {"steps": 7}}) == 7.0
    # an old cell does not see the new metric
    old = spec.load_cell("phi3-hermes-2pod-lam1-jnpwire", root)
    assert "steps_seen" not in [m["name"] for m in old["per_layer"]]
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_are_refused(root):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell", root)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric", root)
