"""Where a cell's pieces live, found by name.

``BENCHMARK.json`` at the checkout's root lists the cells.  Each cell names
a configuration (``bench/configs/<config>.json``, with its plain reference
``bench/<reference>.py`` beside it) and a traffic mix
(``bench/workloads/<traffic>.json``).  Each per-layer metric is a reader of
its own, ``bench/metrics/<metric>.py``.  Adding a cell, a traffic mix, a
configuration or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class SpecError(ValueError):
    """A cell, configuration, traffic mix or metric that cannot be found."""


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _by_name(entries: List[Dict[str, Any]], name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    known = sorted(e["name"] for e in entries)
    raise SpecError(f"unknown {what} {name!r}; known: {known}")


def load_cell(name: str, root: Path = ROOT) -> Dict[str, Any]:
    """The cell ``name`` with its configuration, traffic and metrics.

    Returns ``{"cell", "config", "traffic", "end_to_end", "per_layer"}``:
    the cell's entry, the configuration file's contents, the traffic file's
    contents, and the metric entries that apply to this cell (an entry
    without ``workloads`` applies to every cell).
    """
    root = Path(root)
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], name, "workload")
    centry = _by_name(bench["configs"], cell["config"], "config")
    config = json.loads((root / centry["file"]).read_text())
    traffic_path = root / "bench" / "workloads" / f"{cell['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"no traffic file {traffic_path}")
    traffic = json.loads(traffic_path.read_text())

    def applies(m):
        return name in m.get("workloads", [name])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)],
            "root": root}


def load_module(path: Path, name: Optional[str] = None):
    """Import the file at ``path`` as a fresh module."""
    spec = importlib.util.spec_from_file_location(
        name or f"bench_{path.stem.replace('-', '_').replace('.', '_')}",
        path)
    if spec is None or spec.loader is None:
        raise SpecError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader {path} for metric {name!r}")
    return load_module(path).read


def reference_module(config: Dict[str, Any], root: Path = ROOT):
    """The plain float32 reference named by a configuration file."""
    path = Path(root) / "bench" / f"{config['reference']}.py"
    if not path.is_file():
        raise SpecError(f"no reference {path}")
    return load_module(path)
