"""Benchmark runner — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = simulated or
wall microseconds of the unit being measured; derived = the paper-facing
metric).  ``--fast`` shrinks every run for CI;  ``--only <name>`` selects a
single suite.

Suites:
    table3   — Table III convergence comparison (both datasets)
    comm     — §V-B API-call/byte reduction vs SSP
    straggler— §V-C / Fig. 12 dynamic allocation
    gup      — §V-D / Fig. 13 major-update trace
    alphabeta— §V-E / Fig. 14 sensitivity
    bsp      — Fig. 2/4/5 BSP breakdown
"""
from __future__ import annotations

import argparse
import sys
import time


def _row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_table3(fast: bool) -> None:
    from benchmarks import table3_convergence as T
    datasets = ["mnist"] if fast else ["mnist", "cifar"]
    for ds in datasets:
        rows = T.run(ds, fast=fast)
        for r in rows:
            us = r["sim_time_s"] * 1e6 / max(r["iterations"], 1)
            _row(f"table3/{ds}/{r['framework']}", us,
                 f"acc={r['conv_acc']};simT={r['sim_time_s']}s;"
                 f"iters={r['iterations']};WI={r['wi_avg']};"
                 f"api={r['api_calls']};speedup={r['speedup_vs_bsp']}x")


def bench_comm(fast: bool) -> None:
    from benchmarks import comm_overhead as C
    r = C.run(fast=fast)
    _row("comm/hermes_vs_ssp", 0.0,
         f"api_reduction={r['api_call_reduction']};"
         f"byte_reduction={r['byte_reduction']};"
         f"paper_claim={r['paper_claim_api_reduction']}")


def bench_straggler(fast: bool) -> None:
    from benchmarks import straggler as S
    r = S.run(fast=fast)
    _row("straggler/dynamic_alloc", 0.0,
         f"alloc_events={r['alloc_events']};"
         f"median={r['median_iter_time']}s;"
         f"bsp_straggler_ratio={r['bsp_straggler_ratio']}")


def bench_gup(fast: bool) -> None:
    from benchmarks import gup_trace as G
    r = G.run(fast=fast)
    _row("gup/push_trace", 0.0,
         f"pushes={r['pushes']}/{r['iterations']};"
         f"push_loss={r['mean_loss_at_push']};mean_loss={r['mean_loss']};"
         f"improvements={r.get('pushes_are_improvements')}")


def bench_alphabeta(fast: bool) -> None:
    from benchmarks import alpha_beta_sensitivity as A
    for r in A.run(fast=fast):
        _row(f"alphabeta/a{r['alpha']}_b{r['beta']}", 0.0,
             f"push_rate={r['push_rate']};acc={r['conv_acc']};"
             f"simT={r['sim_time_s']}s")


def bench_bsp(fast: bool) -> None:
    from benchmarks import bsp_breakdown as B
    r = B.run(fast=fast)
    for fam, row in r["families"].items():
        _row(f"bsp_breakdown/{fam}", row["mean_train_s"] * 1e6,
             f"wait={row['mean_wait_s']}s;"
             f"wait_frac={row['wait_fraction']}")


SUITES = {
    "table3": bench_table3,
    "comm": bench_comm,
    "straggler": bench_straggler,
    "gup": bench_gup,
    "alphabeta": bench_alphabeta,
    "bsp": bench_bsp,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    names = [args.only] if args.only else list(SUITES)
    print("name,us_per_call,derived")
    for n in names:
        t0 = time.time()
        try:
            SUITES[n](args.fast)
        except Exception as e:  # keep the suite running
            _row(f"{n}/ERROR", 0.0, f"{type(e).__name__}:{e}")
        print(f"# {n} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
