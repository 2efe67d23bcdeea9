#!/usr/bin/env python3
"""Readings that set the limits of a DeepSeek-V2-Lite cell.

    python3 bench/calibrate_dsv2.py --workload dsv2lite-single-ep8-4k \\
        --seeds 11,12,13 --fault-seeds 11,12 [--faults control,renorm]
        [--out cal.jsonl]

For each of ``--seeds``, on the chip and at the cell's own size, the
program's first steps are read as the benchmark reads them: the cell's
entry (``train_single``) called with the cell's settings for
``check_steps + 1`` steps, its step wrapped by the harness's own reader
(``harness.Watch``); the float32 reference follows the same steps, and the
numbers that decide ``correct`` are printed with the leaf and pod where
``grad_gap`` and ``state_gap`` are worst.  For each of ``--fault-seeds``
the reference's own readings then stand against, in the program's place,
the control (the reference with float8 products), the loss taken over
half of the batch's tokens (``half_batch``), and each fault that
``ref_dsv2.FAULTS`` plants: top-k renormalised, a capacity drop at factor
1.0, the YaRN mscale left out, one held expert's output dropped, the
shared experts left out; ``--faults`` keeps only the kinds it names.  One
JSON line per seed and kind, on standard output and in ``--out``.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def program_readings(cell, seed, probes):
    """The readings ``harness.run`` takes of the first ``check_steps``
    steps, from one call of the entry."""
    import jax
    import numpy as np
    import harness
    import weights as W
    from repro.launch import train as T

    tr = cell["traffic"]
    ocfg = harness.optimizer_config(tr)
    n = int(tr["check_steps"])
    watch = harness.Watch("single", seed=seed, check_steps=n,
                          log_every=int(tr["log_every"]), steps=n + 1,
                          opt_name=ocfg.name, probes=probes)
    with harness.watching(watch):
        T.train_single(cell["model"], steps=n + 1, batch=int(tr["batch"]),
                       seq=int(tr["seq"]), opt_cfg=ocfg,
                       log_every=int(tr["log_every"]), seed=seed)
    r = jax.device_get({"loss": watch.loss, "feed": watch.feed,
                        "grad": watch.grad, "change": watch.state_change})
    paths = W.leaf_paths_of_shapes(cell["shapes"])
    gscale = harness.first_grad(ocfg.name, ocfg.lr, ocfg.beta1)
    feed = [{k: np.asarray(b[k]) for k in ("tokens", "targets")}
            for b in r["feed"]]
    return {"loss": [list(map(float, np.atleast_1d(x)))
                     for x in r["loss"][:n]],
            "feed": {k: np.stack([b[k] for b in feed])
                     for k in ("tokens", "targets")},
            "grad": harness._per_leaf(paths, r["grad"], gscale),
            "change": {k: harness._per_leaf(paths, v)
                       for k, v in r["change"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--faults", default="",
                    help="the fault kinds to read (default: every kind)")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    import gc
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
    cell.update(model=harness.program_config(cell["config"]),
                shapes=ref.param_shapes(cell["config"]), ref=ref)
    harness.check_layout(cell["model"], cell["shapes"])
    probes = harness.make_probes(cell["config"], ref)
    out = open(args.out, "a") if args.out else None

    def emit(seed, kind, nums, where=None):
        line = {"seed": seed, "kind": kind, "numbers": nums,
                "device": devs[0].device_kind}
        if where:
            line["worst"] = where
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    seeds = [int(s) for s in args.seeds.split(",") if s]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    for seed in dict.fromkeys(seeds + fault_seeds):
        good = ref_train.readings(ref, cell, seed, devices=devs)
        if seed in seeds:
            prog = program_readings(cell, seed, probes)
            gc.collect()
            emit(seed, "program", compare.numbers(prog, good),
                 compare.worst(prog, good))
        if seed in fault_seeds:
            kinds = [("control", cell, {"low": True}),
                     ("half_batch", cell, {"fault": "half_batch"})] + [
                (f, {**cell, "config": {**cell["config"], "fault": f}}, {})
                for f in ref.FAULTS]
            for name, c, kw in kinds:
                if args.faults and name not in args.faults.split(","):
                    continue
                bad = ref_train.readings(ref, c, seed, devices=devs, **kw)
                emit(seed, name, compare.numbers(bad, good))
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
