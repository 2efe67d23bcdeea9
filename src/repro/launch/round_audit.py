"""Round-lowering audit: the packed payload-gather merge, proven two ways.

This is the executable proof tier behind ``tests/test_round_lowering.py``
(DESIGN.md §3/§4): on a small forced-device pod mesh it checks, per wire
format,

1. **Bit-exactness** (``equivalence``): ``hermes_round`` placed on a
   ``(pod, data, model)`` mesh — where the merge ships the *encoded*
   payloads across the pod axis (``dist.wire.gather_payloads``) and merges
   locally — produces **bit-identical** state to the unplaced jnp oracle,
   over a multi-round trajectory that exercises open, closed, and
   mixed-gate rounds, a mid-run ``live``-mask flip, and threaded
   error-feedback residuals.  A gather moves values without changing them,
   so any divergence is a lowering bug (historically: non-partitionable
   threefry splitting the stochastic int4 bits, and asymmetric FMA
   contraction across the two programs).

2. **Lowered-collective pin** (``lowering_pin``): the optimized HLO of the
   full round crosses the pod axis with exactly the billed wire arrays —
   each encoded payload operand gathers **once**, nothing model-sized in
   fp32 crosses for a compressed format, int4 ships <= 0.5625 B/element —
   and the closed round (``live`` baked all-False, ``lax.cond`` folded)
   crosses **nothing**.

3. **Resize cycles** (``resize``): the shrink and grow equivalence
   harnesses (``launch.elastic.drop_pod_equivalence`` /
   ``rejoin_pod_equivalence``), run with the packed int4 wire and the mesh
   threaded into every round, stay bit-identical across a kill -> masked
   round -> shrink -> re-admit cycle.

Run standalone (writes a JSON report the test tier asserts on):

    REPRO_ROUND_AUDIT_DEVICES=8 python -m repro.launch.round_audit \
        --out results/dryrun_opt/round_audit.json
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count="
                      + os.environ.get("REPRO_ROUND_AUDIT_DEVICES", "8"))
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual host devices; never the TPU

import argparse
import json
from typing import Any, Dict, List

import numpy as np
import jax

# Stochastic int4 rounding must draw the SAME bits placed and unplaced;
# the default non-partitionable threefry keys the draw on the sharding.
jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.config import HermesConfig
from repro.dist.compression import payload_bytes
from repro.dist.hermes_sync import (
    hermes_commit, hermes_dispatch, hermes_pod_state, hermes_round,
)
from repro.dist.wire import (
    available_formats, payload_buffer_spec, wire_operand_specs,
)
from repro.analysis import CollectivePlacement, analyze
from repro.launch.mesh import make_pod_mesh

N_PODS = 2


def _cfg(mode: str) -> HermesConfig:
    return HermesConfig(alpha=-0.3, beta=0.1, lam=2, window=4,
                        compression=mode)


def _toy(n: int = N_PODS):
    """One blocked leaf + one short-tail leaf, per-pod distinct."""
    k1, k2, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    pods = {"w": jax.random.normal(k1, (n, 4, 512), jnp.float32),
            "b": jax.random.normal(k2, (n, 7), jnp.float32)}
    wg = {"w": jax.random.normal(kg, (4, 512), jnp.float32),
          "b": jnp.zeros((7,), jnp.float32)}
    return pods, wg


def equivalence(mode: str, mesh, n_rounds: int = 6) -> Dict[str, Any]:
    """Placed (payload-gather) vs unplaced (oracle) multi-round identity."""
    cfg = _cfg(mode)
    rng = jax.random.PRNGKey(42)

    def put(tree, spec):
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, spec)), tree)

    def run(mesh_arg, place):
        pods, wg = _toy()
        gup = hermes_pod_state(cfg, N_PODS)
        if place:
            pods, gup = put(pods, PS("pod")), put(gup, PS("pod"))
            wg = put(wg, PS())
        step = jax.jit(lambda p, g, e, w, losses, lv: hermes_round(
            p, g, losses, w, jnp.float32(1.0), cfg, live=lv, error=e,
            rng=rng, mesh=mesh_arg))
        err, outs = None, []
        live = np.array([True] * N_PODS)
        for r in range(n_rounds):
            # schedule mixes warmup-closed, one-open, and all-open rounds
            losses = np.array([1.0 - 0.1 * r, 1.2 if r < 3 else 0.3],
                              np.float32)
            if r == 4:
                live = np.array([True, False])  # mid-run membership loss
            out = step(pods, gup, err, wg, jnp.asarray(losses),
                       jnp.asarray(live))
            pods, gup, err, wg = (out["pod_params"], out["gup"],
                                  out["error"], out["w_global"])
            outs.append(jax.tree.map(np.asarray, out))
        return outs

    placed = run(mesh, True)
    oracle = run(None, False)
    gates_hist: List[List[bool]] = []
    for x, y in zip(placed, oracle):
        gates_hist.append([bool(g) for g in x["gates"]])
        for u, v in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            np.testing.assert_array_equal(
                u, v, err_msg=f"{mode}: gathered round diverged from the "
                              f"unplaced oracle")
    opens = [any(g) for g in gates_hist]
    return {"bit_identical": True, "rounds": n_rounds,
            "gates": gates_hist,
            "had_closed_round": bool(not all(opens)),
            "had_open_round": bool(any(opens)),
            "had_mixed_round": bool(any(any(g) and not all(g)
                                        for g in gates_hist))}


def lowering_pin(mode: str, mesh) -> Dict[str, Any]:
    """Pin the full round's cross-pod collective schedule in lowered HLO."""
    cfg = _cfg(mode)
    n_dev = int(mesh.devices.size)
    pods, wg = _toy()
    gup = hermes_pod_state(cfg, N_PODS)
    sds = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pods)
    gup_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), gup)
    rep = NamedSharding(mesh, PS())
    rep_tree = jax.tree.map(lambda _: rep, wg)
    losses = jax.ShapeDtypeStruct((N_PODS,), jnp.float32)
    rng = jax.random.PRNGKey(0)

    def open_fn(p, g, pl, w):
        o = hermes_round(p, g, pl, w, jnp.float32(1.0), cfg, rng=rng,
                         mesh=mesh)
        return o["pod_params"], o["w_global"], o["any_push"]

    def closed_fn(p, g, pl, w):
        o = hermes_round(p, g, pl, w, jnp.float32(1.0), cfg,
                         live=jnp.zeros((N_PODS,), bool), rng=rng,
                         mesh=mesh)
        return o["pod_params"], o["w_global"], o["any_push"]

    with mesh:
        shardings = (pod_sh, gup_sh, rep, rep_tree)
        open_hlo = (jax.jit(open_fn, in_shardings=shardings)
                    .lower(sds(pods), sds(gup), losses, sds(wg))
                    .compile().as_text())
        closed_hlo = (jax.jit(closed_fn, in_shardings=shardings)
                      .lower(sds(pods), sds(gup), losses, sds(wg))
                      .compile().as_text())

    # the collective-placement rule carries the old inline asserts: every
    # crossing operand is a billed wire spec (exactly once) or control
    # traffic, the totals match the bill, and the closed round crosses
    # nothing — violations raise AnalysisError (an AssertionError)
    specs = wire_operand_specs(wg, mode, N_PODS)
    billed = payload_bytes(wg, mode)
    rule = CollectivePlacement(specs, n_devices=n_dev, n_pods=N_PODS,
                               billed_bytes=billed)
    analyze(open_hlo, rules=[rule], label=f"lowering_pin[{mode}]")
    cls, recs = rule.classification, rule.records
    rule_c = CollectivePlacement(n_devices=n_dev, n_pods=N_PODS,
                                 expect_none=True)
    analyze(closed_hlo, rules=[rule_c],
            label=f"lowering_pin_closed[{mode}]")
    closed_cross = rule_c.records
    n_elts = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(wg))
    return {
        "billed_bytes_per_pod": int(billed),
        "round_gather_bytes_per_pod": int(cls["payload_bytes"]),
        "round_bytes_per_element": round(cls["payload_bytes"] / n_elts, 6),
        "control_bytes": int(cls["control_bytes"]),
        "cross_pod_collectives": len(recs),
        "payload_gathers": len(specs),
        "unexpected": [],
        "unmatched_specs": [],
        "closed_cross_pod_collectives": len(closed_cross),
    }


def async_pin(mode: str, mesh) -> Dict[str, Any]:
    """Pin the pipelined round's two halves in lowered HLO (DESIGN.md §8).

    * The **dispatch** half carries exactly the billed payload gather —
      each encoded wire operand crosses the pod axis once, inside the
      ``any_push`` cond branch — and lowers to **zero** cross-pod
      collectives when every gate is provably shut (``live`` all-False).
    * The **commit** half lowers to **zero** cross-pod collectives
      unconditionally: the payload it merges was gathered by dispatch, so
      the merge is local.  Since dispatch/commit/pod-step are separate
      executables and only the commit consumes the gather's outputs, this
      is the proof the collective is off the next pod step's critical
      path.
    """
    cfg = _cfg(mode)
    n_dev = int(mesh.devices.size)
    pods, wg = _toy()
    gup = hermes_pod_state(cfg, N_PODS)
    sds = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pods)
    gup_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), gup)
    rep = NamedSharding(mesh, PS())
    rep_tree = jax.tree.map(lambda _: rep, wg)
    losses = jax.ShapeDtypeStruct((N_PODS,), jnp.float32)
    rng = jax.random.PRNGKey(0)

    def dispatch_fn(p, g, pl, w):
        o = hermes_dispatch(p, g, pl, w, jnp.float32(1.0), cfg, rng=rng,
                            mesh=mesh)
        return o["pending"], o["error"], o["any_push"]

    def dispatch_closed(p, g, pl, w):
        o = hermes_dispatch(p, g, pl, w, jnp.float32(1.0), cfg,
                            live=jnp.zeros((N_PODS,), bool), rng=rng,
                            mesh=mesh)
        return o["pending"], o["error"], o["any_push"]

    # the in-flight buffer a commit consumes: gathered payload (replicated
    # over the pod axis, exactly how dispatch's receiver pin leaves it)
    # plus the dispatch-time gates/losses/L scalars
    pending_struct = {
        "payload": payload_buffer_spec(wg, mode, N_PODS),
        "gates": jax.ShapeDtypeStruct((N_PODS,), jnp.bool_),
        "losses": jax.ShapeDtypeStruct((N_PODS,), jnp.float32),
        "L": jax.ShapeDtypeStruct((), jnp.float32),
        "any_push": jax.ShapeDtypeStruct((), jnp.bool_),
    }
    pend_sh = jax.tree.map(lambda _: rep, pending_struct)

    def commit_fn(p, pending, w):
        o = hermes_commit(p, pending, w, cfg=cfg, mesh=mesh)
        return o["pod_params"], o["w_global"], o["any_push"]

    with mesh:
        d_sh = (pod_sh, gup_sh, rep, rep_tree)
        dispatch_hlo = (jax.jit(dispatch_fn, in_shardings=d_sh)
                        .lower(sds(pods), sds(gup), losses, sds(wg))
                        .compile().as_text())
        dclosed_hlo = (jax.jit(dispatch_closed, in_shardings=d_sh)
                       .lower(sds(pods), sds(gup), losses, sds(wg))
                       .compile().as_text())
        commit_hlo = (jax.jit(commit_fn,
                              in_shardings=(pod_sh, pend_sh, rep_tree))
                      .lower(sds(pods), pending_struct, sds(wg))
                      .compile().as_text())

    # analyzer rules replace the old inline asserts: the dispatch ships
    # exactly the billed wire, the closed dispatch and the commit cross
    # the pod axis with nothing
    specs = wire_operand_specs(wg, mode, N_PODS)
    billed = payload_bytes(wg, mode)
    rule = CollectivePlacement(specs, n_devices=n_dev, n_pods=N_PODS,
                               billed_bytes=billed)
    analyze(dispatch_hlo, rules=[rule], label=f"async_pin_dispatch[{mode}]")
    cls, recs = rule.classification, rule.records
    rule_dc = CollectivePlacement(n_devices=n_dev, n_pods=N_PODS,
                                  expect_none=True)
    analyze(dclosed_hlo, rules=[rule_dc],
            label=f"async_pin_dispatch_closed[{mode}]")
    closed_cross = rule_dc.records
    rule_cm = CollectivePlacement(n_devices=n_dev, n_pods=N_PODS,
                                  expect_none=True)
    analyze(commit_hlo, rules=[rule_cm], label=f"async_pin_commit[{mode}]")
    commit_cross = rule_cm.records
    n_elts = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(wg))
    return {
        "dispatch_gather_bytes_per_pod": int(cls["payload_bytes"]),
        "round_bytes_per_element": round(cls["payload_bytes"] / n_elts, 6),
        "dispatch_cross_pod_collectives": len(recs),
        "payload_gathers": len(specs),
        # the gather lowers inside the dispatch program's computations
        # (the any_push cond branch), never in the commit's
        "gather_computations": sorted({r.get("computation", "?")
                                       for r in recs}),
        "dispatch_closed_cross_pod_collectives": len(closed_cross),
        "commit_cross_pod_collectives": len(commit_cross),
    }


def async_parity(mode: str, n_rounds: int = 8, tol: float = 0.05
                 ) -> Dict[str, Any]:
    """Executed staleness-1 parity + drain accounting (unplaced oracle).

    Runs the same deterministic loss schedule through the synchronous
    ``hermes_round`` and the pipelined dispatch/commit loop (commit one
    round late, final drain).  The two trajectories share every gate
    decision; the async one's refreshes land one round later, so the
    final global models agree to a staleness tolerance, not bitwise —
    while the payload *accounting* is exact: every dispatched open round
    is committed exactly once after the drain.
    """
    cfg = _cfg(mode)
    rng0 = jax.random.PRNGKey(42)
    schedule = [np.array([1.0 - 0.08 * r, 1.2 if r < 3 else 0.3],
                         np.float32) for r in range(n_rounds)]

    s_pods, s_wg = _toy()
    a_pods, a_wg = s_pods, s_wg
    s_gup = a_gup = hermes_pod_state(cfg, N_PODS)
    s_err = a_err = None
    pending = None
    dispatched = committed = 0
    sync_opens = []
    for r, losses in enumerate(schedule):
        rng = jax.random.fold_in(rng0, r)
        out = hermes_round(s_pods, s_gup, jnp.asarray(losses), s_wg,
                           jnp.float32(1.0), cfg, error=s_err, rng=rng,
                           use_kernel=False)
        s_pods, s_wg = out["pod_params"], out["w_global"]
        s_gup, s_err = out["gup"], out["error"]
        sync_opens.append(bool(out["any_push"]))
        if pending is not None:
            cm = hermes_commit(a_pods, pending, a_wg, cfg=cfg,
                               use_kernel=False)
            a_pods, a_wg = cm["pod_params"], cm["w_global"]
            committed += int(cm["any_push"])
        dp = hermes_dispatch(a_pods, a_gup, jnp.asarray(losses), a_wg,
                             jnp.float32(1.0), cfg, error=a_err, rng=rng)
        a_gup, a_err, pending = dp["gup"], dp["error"], dp["pending"]
        dispatched += int(dp["any_push"])
    # drain: flush the last in-flight payload
    cm = hermes_commit(a_pods, pending, a_wg, cfg=cfg, use_kernel=False)
    a_pods, a_wg = cm["pod_params"], cm["w_global"]
    committed += int(cm["any_push"])

    # identical gate trajectory (losses are external, GUP state advances
    # identically), refreshes one round late -> tolerance, not bits
    diffs = [float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
             for x, y in zip(jax.tree.leaves(s_wg), jax.tree.leaves(a_wg))]
    max_diff = max(diffs)
    assert dispatched == committed, (dispatched, committed)
    assert dispatched == sum(sync_opens), (dispatched, sync_opens)
    assert max_diff <= tol, (mode, max_diff, tol)
    return {
        "rounds": n_rounds,
        "open_rounds": int(sum(sync_opens)),
        "dispatched": dispatched,
        "committed": committed,
        "drained": True,
        "final_wg_max_abs_diff": max_diff,
        "tolerance": tol,
        "within_tolerance": True,
    }


def resize(mesh) -> Dict[str, Any]:
    """Shrink and grow cycles with the packed int4 wire, mesh threaded."""
    from repro.launch.elastic import (
        drop_pod_equivalence, rejoin_pod_equivalence,
    )
    cfg = HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                       compression="int4", min_live_pods=1,
                       rejoin_cost_rounds=0.5)
    return {
        "drop": drop_pod_equivalence(n_pods=N_PODS, drop=1, cfg=cfg,
                                     mesh=mesh),
        "rejoin": rejoin_pod_equivalence(n_pods=N_PODS, cfg=cfg, mesh=mesh),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="results/dryrun_opt/round_audit.json")
    ap.add_argument("--modes", default=None,
                    help="comma-separated wire formats (default: all)")
    ap.add_argument("--equivalence-modes", default="int4,int8",
                    help="formats to run the executed placed-vs-oracle "
                         "rounds for (lowering pins always cover --modes)")
    ap.add_argument("--pin-only", action="store_true",
                    help="skip the executed equivalence + resize cycles; "
                         "lowering pins only (kernel_bench --wire-bytes "
                         "uses this for the round-level B/element column)")
    ap.add_argument("--async-only", action="store_true",
                    help="audit only the pipelined dispatch/commit round "
                         "(lowering pins + staleness parity); the "
                         "Makefile async-smoke target uses this")
    args = ap.parse_args()

    modes = (args.modes.split(",") if args.modes
             else list(available_formats()))
    eq_modes = args.equivalence_modes.split(",")
    mesh = make_pod_mesh(N_PODS)
    rec: Dict[str, Any] = {
        "devices": int(mesh.devices.size),
        "mesh": list(mesh.devices.shape),
        "n_pods": N_PODS,
        "threefry_partitionable": True,
        "formats": {},
    }
    for mode in modes:
        entry: Dict[str, Any] = {}
        if not args.async_only:
            entry["lowering"] = lowering_pin(mode, mesh)
            if not args.pin_only and mode in eq_modes:
                entry["equivalence"] = equivalence(mode, mesh)
        entry["async"] = async_pin(mode, mesh)
        if not args.pin_only and mode in eq_modes:
            entry["async"]["parity"] = async_parity(mode)
        rec["formats"][mode] = entry
    if not args.pin_only and not args.async_only:
        rec["resize"] = resize(mesh)
    if "int4" in rec["formats"]:
        low = rec["formats"]["int4"].get("lowering")
        if low is not None:
            assert low["round_bytes_per_element"] <= 0.5625, low
        a = rec["formats"]["int4"]["async"]
        assert a["round_bytes_per_element"] <= 0.5625, a
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
