#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and per-layer metric readers are
found by name from ``BENCHMARK.json`` (see ``bench/spec.py``).  The run
calls the program's entry point twice with ``--seed`` (``bench/
harness.py``): a calibration call, which loads or compiles every program
and times a step, and the measured call, whose first steps the check
reads and whose window lasts about ``--seconds``.  With ``--trace 0`` the
result carries the cell's end-to-end metrics; with ``--trace 1`` a shorter
traced window gives the per-layer metrics.  After the window the
program's state is freed and the plain float32 reference follows the same
first steps; the numbers compared, each beside its limit, end standard
error and the result line (key ``checks``).  The last line of standard
output is the result.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the device kind is not in ``peaks.json``.
"""
from __future__ import annotations

import time

T0 = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _finite(x):
    """JSON has no NaN: a non-finite reading is printed as a string."""
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, require_chip: bool = True, root: Path = ROOT) -> int:
    args = parse(argv)
    for p in (str(root / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import spec
    cell = spec.load_cell(args.workload, root)
    tr = cell["traffic"]
    chips = int(cell["cell"]["chips"])

    import jax
    import harness
    if require_chip:
        from repro.launch.train import configure_compile_cache
        configure_compile_cache(root)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = harness.require_tpu(chips) if require_chip \
            else jax.devices()[:chips]
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    peaks = json.loads((BENCH / "peaks.json").read_text())
    kind = devs[0].device_kind
    if require_chip and kind not in peaks:
        print(f"bench: device kind {kind!r} is not in peaks.json",
              file=sys.stderr)
        return 4

    import compare
    import counts
    import ref_train
    import trace_reduce
    ref = spec.reference_module(cell["config"], root)
    mc = harness.program_config(cell["config"])
    shapes = ref.param_shapes(cell["config"])
    harness.check_layout(mc, shapes)
    cell.update(model=mc, shapes=shapes, ref=ref)
    mesh = None
    if tr.get("placed"):
        from repro.launch.mesh import make_pod_mesh
        mesh = make_pod_mesh(int(tr["pods"]), n_clusters=int(
            tr["hermes"].get("n_clusters", 1)))

    seconds = args.seconds
    summary = None
    with tempfile.TemporaryDirectory() as tdir:
        hooks = {}
        if args.trace:
            seconds = min(seconds, float(tr["trace_seconds"]))
            hooks = {"on_window_start": lambda: jax.profiler.start_trace(
                         tdir),
                     "on_window_end": jax.profiler.stop_trace}
        run = harness.run(cell, args.seed, seconds, mesh=mesh, **hooks)
        setup_s = run["t_window_start"] - T0
        stats = [d.memory_stats() or {} for d in devs]
        peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
        if args.trace:
            summary = trace_reduce.summarize(
                trace_reduce.load_events(trace_reduce.find_xplane(tdir)),
                [d.id for d in devs])
    gc.collect()

    prog = run["readings"]
    reads = ref_train.readings(ref, cell, args.seed,
                               prog_eval=prog.get("eval"), devices=devs)
    nums = compare.numbers(prog, reads)
    limits = compare.load_limits(root, args.workload)
    ok = compare.judge(nums, limits)

    tokens_per_s = run["tokens"] / run["window_s"] / chips
    metrics = {}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": ok, "attempted": run["steps"],
              "failed": run["failed"],
              "compiles_in_window": run["compiles_in_window"]}
    if not args.trace:
        values = {"tokens_per_s": tokens_per_s, "setup_s": setup_s}
        if "wire_bytes" in run:
            values["wire_bytes_per_token"] = run["wire_bytes"] / run["tokens"]
        for m in cell["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {"trace": summary, "run": run, "cell": cell,
               "peaks": peaks.get(kind), "chips": chips,
               "tokens_per_s": tokens_per_s, "memory_peak_bytes": peak,
               "flops_per_token": counts.model_flops_per_token(
                   cell["config"], int(tr["seq"]))}
        for m in cell["per_layer"]:
            v = spec.metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        result["breakdown"] = summary["breakdown"]
    result["metrics"] = metrics
    result["device"] = device
    checks = compare.report(nums, limits)
    result["checks"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                        for k, v in checks.items()}
    for k in sorted(set(nums) - set(limits)):
        print(f"reading {k} {nums[k]!r} (not compared)", file=sys.stderr)
    for k, v in compare.worst(prog, reads).items():
        print(f"worst {k}: {v}", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
