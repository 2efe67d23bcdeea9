#!/usr/bin/env python3
"""Trace one cell's window on the chip and print what the program's spans
and scopes show; optionally keep a trimmed copy of the trace.

    python3 bench/record_trace.py --workload <cell> --seed <n> \\
        [--seconds 4] [--out bench/data/<name>.json.gz] [--keep 3]

The run is the traced run of ``bench/run.py`` (set-up, then the measured
call of the program's entry with the profiler on over its window) without
the check.  It prints a JSON line: the clock check and the window's idle
time by host span (``bench/spans.py``), the round's device time by phase
(``bench/scopes.py``) beside ``round_ms``'s reading, and the share of the
round's op time that carries a phase.  ``--out`` writes ``--keep``
consecutive pod steps around a log fetch: their device events in the form
``trace_reduce.load_events`` gives, each op of a round execution with its
``phase``, and the host spans of those steps (``dev`` -1, ``line``
``"host"``).  Exits non-zero without a TPU.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import re
import sys
import tempfile
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def trim(events, phases, keep: int, log_every: int,
         anchor: str = r"pod_step|step_fn", round_pat: str = r"lambda"):
    """``keep`` pod steps centred on the first log step whose neighbours
    were traced: device events inside them (round ops tagged with their
    phase) and the host spans of those steps and of what ran among them."""
    import spans
    host = [e for e in events if e["line"] == "host"]
    dev = [e for e in events if e["line"] != "host"]
    mods = sorted((e for e in dev if e["line"] == "modules"),
                  key=lambda e: e["start"])
    steps = [e for e in mods if re.search(anchor, e["name"])]
    step_spans = [e for e in host if e["name"] == spans.STEP_SPAN]
    paired = list(zip(reversed(steps), reversed(step_spans)))[::-1]
    logs = [j for j, (_, s) in enumerate(paired)
            if (s["step"] + 1) % log_every == 0
            and j >= keep // 2 and j + keep - keep // 2 <= len(paired)]
    if not logs:
        raise ValueError("no log step with traced neighbours")
    kept = paired[logs[0] - keep // 2: logs[0] - keep // 2 + keep]
    lo, hi = kept[0][0]["start"], spans._end(kept[-1][0])
    first, last = kept[0][1]["step"], kept[-1][1]["step"]
    t0, t1 = kept[0][1]["start"], max(spans._end(s) for _, s in kept)
    rounds = [m for m in mods if re.search(round_pat, m["name"])]
    starts = [m["start"] for m in rounds]
    out = []
    for e in dev:
        if e["start"] < lo or spans._end(e) > hi:
            continue
        e = dict(e)
        k = bisect.bisect_right(starts, e["start"])
        if e["line"] == "ops" and k and \
                e["start"] <= spans._end(rounds[k - 1]) and \
                e["name"] in phases:
            e["phase"] = phases[e["name"]]
        out.append(e)
    for e in host:
        if first <= e.get("step", -1) <= last or (
                "step" not in e and t0 <= e["start"] <= t1):
            out.append(e)
    return out


def round_op_seconds(device, round_pat: str):
    """``(executions, {op name: device seconds})`` over the round's
    executions, ops that only hold others and the ends of asynchronous ops
    left out."""
    import trace_reduce
    mods = sorted((e for e in device if e["line"] == "modules"
                   and re.search(round_pat, e["name"])),
                  key=lambda e: e["start"])
    starts = [m["start"] for m in mods]
    out = {}
    for e in device:
        if e["line"] != "ops" or trace_reduce.CONTAINER.search(e["op"]):
            continue
        k = bisect.bisect_right(starts, e["start"])
        if k and e["start"] + e["dur"] <= mods[k - 1]["start"] + \
                mods[k - 1]["dur"]:
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur"] * 1e-9
    return len(mods), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep", type=int, default=3)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import spec
    cell = spec.load_cell(args.workload, ROOT)
    tr = cell["traffic"]

    import jax
    import harness
    import scopes
    import spans
    import trace_reduce
    from repro.launch.train import configure_compile_cache
    configure_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        devs = harness.require_tpu(int(cell["cell"]["chips"]))
    except harness.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return 3
    ref = spec.reference_module(cell["config"], ROOT)
    mc = harness.program_config(cell["config"])
    cell.update(model=mc, shapes=ref.param_shapes(cell["config"]), ref=ref)
    with tempfile.TemporaryDirectory() as tdir:
        run = harness.run(
            cell, args.seed, args.seconds,
            on_window_start=lambda: jax.profiler.start_trace(tdir),
            on_window_end=jax.profiler.stop_trace)
        path = trace_reduce.find_xplane(tdir)
        device = trace_reduce.load_events(path)
        events = device + spans.host_spans(path)
    ids = [d.id for d in devs]
    summary = trace_reduce.summarize(device, ids)
    att = spans.attribute(events, ids)
    phases = scopes.round_op_phases({"cell": cell})
    n_round, round_s = trace_reduce.match(summary["modules"], scopes.ROUND)
    n_traced, by_op = round_op_seconds(device, scopes.ROUND)
    op_s = sum(by_op.values())
    unscoped = sorted(((op, t) for op, t in by_op.items()
                       if op not in phases), key=lambda kv: -kv[1])
    out = {"workload": args.workload, "seed": args.seed,
           "steps": run["steps"], "window_s": run["window_s"],
           "tokens_per_s": run["tokens"] / run["window_s"],
           "busy_s": summary["busy_s"], "trace_window_s": summary["window_s"],
           "spans": {k: v for k, v in att.items() if k != "per_chip"},
           # the same, device events moved as far as the fetches allow
           "idle_ms_per_step_at_slack": spans.attribute(
               events, ids, extra_shift=att["clock_slack_us"] * 1e3)[
               "idle_ms_per_step"] if att["clock_slack_us"] < float("inf")
           else None,
           "round_ms": round_s / n_round * 1e3 if n_round else None,
           "phase_ms": {p: scopes.phase_ms(summary, phases, p)
                        for p in scopes.PHASES},
           "phase_share_of_op_time": (
               1 - sum(t for _, t in unscoped) / op_s if op_s else None),
           "unscoped_ops_ms_per_round": [
               [op, t / n_traced * 1e3] for op, t in unscoped[:12]]}
    print(json.dumps(out), flush=True)
    if args.out:
        kept = trim(events, phases, args.keep, int(tr["log_every"]))
        with gzip.open(args.out, "wt") as f:
            json.dump(kept, f)
        print(json.dumps({"out": args.out, "kept_events": len(kept)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
