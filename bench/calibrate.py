#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted faults.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13

For each seed, on the chip and at the cell's own size, the float32
reference follows the cell's first steps; then, each in the program's
place, the reference computed with float8 matrix products (the control),
and the reference with one fault planted: half of the batch's tokens left
out of the loss, and (Hermes) the merge fed the first pod's payload only,
and every gate open whatever its z-score.
Each prints the numbers that decide ``correct``, one JSON line per seed
and kind.  A step that leaves its state unchanged reads 1 on
``grad_gap`` and ``state_gap`` by their definition, and a loss altered by
1% reads 0.01 on ``loss_gap``; neither needs a run.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import jax
    import compare
    import harness
    import ref_train
    import spec
    from repro.launch.train import configure_compile_cache
    configure_compile_cache(ROOT)
    cell = spec.load_cell(args.workload)
    devs = harness.require_tpu(int(cell["cell"]["chips"]))
    ref = spec.reference_module(cell["config"])
    hermes = cell["traffic"]["loop"] == "hermes"
    kinds = [("control", {"low": True}), ("half_batch", {"fault": "half_batch"})]
    if hermes:
        kinds += [("no_exchange", {"fault": "no_exchange"}),
                  ("gate_open", {"fault": "gate_open"})]
    for seed in (int(s) for s in args.seeds.split(",")):
        good = ref_train.readings(ref, cell, seed, devices=devs)
        for name, kw in kinds:
            bad = ref_train.readings(ref, cell, seed, devices=devs,
                                     prog_eval=good.get("eval"), **kw)
            print(json.dumps({"seed": seed, "kind": name,
                              "numbers": compare.numbers(bad, good),
                              "device": devs[0].device_kind}), flush=True)
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
