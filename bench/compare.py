"""The numbers that decide ``correct``, from the program's readings and the
reference's, and their limits (``bench/limits/<cell>.json``).

* ``feed_miss``: tokens and targets the program fed in the first steps
  that the reference's copy of its stream does not have (exact, limit 0).
* ``loss_gap``: the largest relative gap of a training-step loss over the
  first steps and pods.
* ``eval_gap``: the largest relative gap of a gate test loss (Hermes).
* ``gate_miss``: gate decisions of the program, over every round of the
  run, that the reference's gate, given the program's test losses, does
  not take (Hermes; exact, limit 0).
* ``grad_gap``: the worst leaf's gap between the program's and the
  reference's norm of the first gradient as the optimizer received it,
  over the larger of the reference's norm of that leaf and of the median
  leaf.
* ``state_gap``: the same for the change of the state the next step keeps
  (parameters; for Hermes also the global model and the error residuals),
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's, which move by round-off alone; a change under a
  hundred-thousandth of the leaf's own initial norm counts as none: the
  program's side draws the initial weights again, which can differ from the
  first draw in the last bit.
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from typing import Any, Dict

import numpy as np

ORDER = ("feed_miss", "loss_gap", "eval_gap", "gate_miss", "grad_gap",
         "state_gap")
QUIET = 1e-3   # leaves with less gradient than this share of the median's


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


ROUNDING = 1e-5   # changes under this share of a leaf's own norm


def _leaf_gaps(prog: Dict[str, list], ref: Dict[str, list], skip=(),
               floor=None):
    """(leaves, |n_p - n_r| / max(n_r, median_r, floor) per leaf and pod).

    ``floor`` maps a leaf to the norm of its initial weights; a change
    under ``ROUNDING`` of it is rounding of the weights themselves."""
    leaves = [p for p in ref if p not in skip]
    r = np.array([ref[p] for p in leaves], np.float64)      # (leaves, pods)
    q = np.array([prog[p] for p in leaves], np.float64)
    med = np.median(r, axis=0, keepdims=True)
    base = np.maximum(r, med)
    if floor is not None:
        base = np.maximum(base, ROUNDING * np.array(
            [[floor[p]] for p in leaves], np.float64))
    gap = np.where(base > 0, np.abs(q - r) / np.where(base > 0, base, 1.0),
                   np.where(q == 0, 0.0, np.inf))
    return leaves, gap


def _worst_leaf(prog: Dict[str, list], ref: Dict[str, list], skip=(),
                floor=None) -> float:
    """max over leaves and pods of |n_p - n_r| / max(n_r, median_r)."""
    return float(np.max(_leaf_gaps(prog, ref, skip, floor)[1]))


def worst(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, str]:
    """Where ``grad_gap`` and ``state_gap`` are worst: leaf, pod, and the
    two norms."""
    out = {}
    groups = {"grad_gap": [("grad", prog["grad"], ref["grad"])],
              "state_gap": [(f"change/{k}", prog["change"][k],
                             ref["change"][k]) for k in ref["change"]]}
    for name, parts in groups.items():
        best = None
        for label, p, r in parts:
            leaves, gap = _leaf_gaps(p, r, floor=ref.get("p0")
                                     if name == "state_gap" else None)
            i, j = np.unravel_index(int(np.argmax(gap)), gap.shape)
            if best is None or gap[i, j] > best[0]:
                best = (gap[i, j], f"{label}/{leaves[i]} pod {j}: program "
                        f"{p[leaves[i]][j]!r} reference {r[leaves[i]][j]!r}")
        out[name] = best[1]
    return out


def _misses(a, b) -> float:
    """Entries of ``a`` that differ from ``b``; inf where the shapes do."""
    a, b = np.asarray(a), np.asarray(b)
    return float(np.sum(a != b)) if a.shape == b.shape else math.inf


def numbers(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    out = {"feed_miss": sum(_misses(prog["feed"][k], ref["feed"][k])
                            for k in ("tokens", "targets")),
           "loss_gap": _rel(prog["loss"], ref["loss"])}
    if "eval" in ref:
        out["eval_gap"] = _rel(prog["eval"][:len(ref["eval"])], ref["eval"])
        out["gate_miss"] = _misses(prog["gates"], ref["gates"])
    out["grad_gap"] = _worst_leaf(prog["grad"], ref["grad"])
    g = np.array([max(v) for v in ref["grad"].values()])
    quiet = {p for p, v in ref["grad"].items()
             if max(v) < QUIET * float(np.median(g))}
    out["state_gap"] = max(_worst_leaf(prog["change"][k], ref["change"][k],
                                       quiet, ref.get("p0"))
                           for k in ref["change"])
    return out


def load_limits(root: Path, cell_name: str) -> Dict[str, float]:
    path = Path(root) / "bench" / "limits" / f"{cell_name}.json"
    return json.loads(path.read_text())["limits"]


def judge(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number is finite and within its limit, and every
    limit has its number."""
    if set(limits) - set(nums):
        return False
    return all(math.isfinite(nums[k]) and nums[k] <= limits[k]
               for k in limits)


def report(nums: Dict[str, float], limits: Dict[str, float]):
    """``{name: {"value", "limit"}}`` of the numbers compared, in a fixed
    order."""
    return {k: {"value": nums.get(k, float("nan")), "limit": limits[k]}
            for k in ORDER if k in limits}
