import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual host devices; never the TPU
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Dry-run of the paper's technique itself on the multi-pod mesh:

lower + compile one full Hermes Level-B round (gate -> loss-weighted merge
-> refresh) for a real architecture, with per-pod model replicas sharded on
the leading "pod" axis.  Proves the cross-pod collective schedule of the
gated merge is coherent at (2,16,16), and reports its roofline terms —
including the closed-gate round, whose collective payload is one scalar.

    python -m repro.launch.hermes_dryrun [--arch qwen3-8b]

``--drop-pod`` additionally exercises the elastic-membership path
(DESIGN.md §7), in two parts: (1) it re-lowers the real architecture's
compress step at the survivors' (n_pods-1, data, model) mesh and asserts
it stays collective-free after the shrink; (2) it executes
``launch.elastic.drop_pod_equivalence`` — kill a pod mid-run, masked
round, shrink — on a small stand-in pod mesh (<= 8 devices; executing at
512 virtual devices would be prohibitively slow) and asserts the
surviving pods' ``hermes_round`` outputs are **bit-identical** to a fresh
run at the reduced pod count.  The round math is placement-independent;
the production-mesh *schedule* is what part (1) and the main lowering
audit:

    python -m repro.launch.hermes_dryrun --drop-pod [--arch qwen3-8b]
"""
import argparse
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

# The int4 wire is stochastic (threefry-keyed rounding).  The default
# non-partitionable threefry produces DIFFERENT bits depending on the
# sharding of the array it fills, which would silently break the
# "gathered round == unplaced oracle" bit-identity this audit relies on.
# Partitionable threefry makes the encode placement-invariant.
jax.config.update("jax_threefry_partitionable", True)

from repro.analysis import CollectivePlacement, analyze
from repro.config import HermesConfig
from repro.configs import get_config
from repro.dist.compression import encode_tree, payload_bytes
from repro.dist.wire import wire_operand_specs
from repro.dist.hermes_sync import hermes_pod_state, hermes_round
from repro.launch.mesh import (
    arch_parallel_config, arch_rules, grow_mesh, make_pod_mesh, shrink_mesh,
)
from repro.launch.steps import abstract_init_lm, _shard_tree
from repro.analysis.hlo_parse import parse_hlo_cost


def _compress_audit(mesh, hcfg, abstract_params, base_shardings):
    """Lower the compress step alone on ``mesh``; count its all-gathers.

    The blocked wire layout is computed per shard — no leaf flatten — so
    quantizing the pod-stacked delta must insert *zero* all-gathers (the
    ROADMAP "Sharded compression" item; the elastic path re-checks this at
    the survivors' mesh so a pod drop cannot regress it).
    """
    n_pods = mesh.devices.shape[0]
    pod_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype),
        abstract_params)
    pod_shardings = jax.tree.map(
        lambda sh: NamedSharding(mesh, PS(*(("pod",) + sh.spec))),
        base_shardings)
    global_shardings = jax.tree.map(
        lambda sh: NamedSharding(mesh, sh.spec), base_shardings)

    def compress_fn(pod_p, w_g):
        delta = jax.tree.map(lambda p, g: p - g[None], pod_p, w_g)
        payloads, _, _ = encode_tree(delta, mode=hcfg.compression)
        return payloads

    with mesh:
        cjit = jax.jit(compress_fn,
                       in_shardings=(pod_shardings, global_shardings))
        ccost = parse_hlo_cost(
            cjit.lower(pod_params, abstract_params).compile().as_text())
    n_ag = sum(v for k, v in ccost.collective_counts.items()
               if "all-gather" in k)
    assert n_ag == 0, (
        f"shard-local compress step must not all-gather on "
        f"{tuple(mesh.devices.shape)}, got {ccost.collective_counts}")
    return ccost, n_ag, pod_shardings, global_shardings, pod_params


def _byte_audit(mesh, abstract_params, formats):
    """Billing-vs-wire drift audit (ISSUE 5): per wire format, lower the
    cross-pod *ship* of the encoded push payload — compress the pod-stacked
    fp32 delta, then constrain the payload to pod-replicated, which forces
    XLA to emit an all-gather of exactly the arrays that cross the pod
    axis — and assert the lowered collective's operand bytes equal the
    registry's billed ``payload_bytes``.  Because billing is now *measured*
    from ``encode``'s abstract payload, the only way the two can disagree
    is a layout drift between the per-leaf bill and the stacked wire tree
    (e.g. stacking changing a leaf's blocked axis), which is exactly the
    regression class this catches — for every format at once.

    fp32 leaves, matching the Level-A billing convention (the simulator
    bills fp32 parameter trees; ``NoneFormat`` ships the leaf dtype
    verbatim, so a bf16 audit would legitimately halve its bytes).
    """
    n_pods = mesh.devices.shape[0]
    params32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract_params)
    pod_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype), params32)
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pod_params)
    rep = jax.tree.map(lambda _: NamedSharding(mesh, PS()), params32)
    n_elts = sum(math.prod(s.shape) for s in jax.tree.leaves(params32))
    out = {}
    for name in formats:
        def ship_fn(pod_p, w_g, _name=name):
            delta = jax.tree.map(lambda p, g: p - g[None], pod_p, w_g)
            payloads, _, _ = encode_tree(delta, mode=_name)
            # every pod receives every pusher's payload (the PS-receive
            # view of the merge): replicating over "pod" makes the wire
            # arrays themselves the all-gather operands.  The sender-side
            # constraint + optimization barrier pin the crossing point —
            # without them GSPMD back-propagates the replicated sharding
            # through the elementwise encode and hoists the all-gather
            # onto the *fp32 delta*, silently shipping 2-8x the billed
            # bytes (observed: fp16 shipped fp32 at (2,2,2)); a production
            # wire sender must pin the boundary the same way.
            payloads = jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, PS("pod"))), payloads)
            payloads = jax.lax.optimization_barrier(payloads)
            return jax.tree.map(
                lambda a: jax.lax.with_sharding_constraint(
                    a, NamedSharding(mesh, PS())), payloads)

        with mesh:
            jitted = jax.jit(ship_fn, in_shardings=(pod_sh, rep))
            hlo = jitted.lower(pod_params, params32).compile().as_text()
        cost = parse_hlo_cost(hlo)
        specs = wire_operand_specs(params32, name, n_pods)
        billed = payload_bytes(params32, name)  # per pod == per device here
        # The shared collective-placement rule: every pod-crossing operand
        # must be a billed wire array (fp32 hoists are the named
        # ``fp32-model-crossing`` class) and the matched bytes must equal
        # the bill exactly (``billing-drift``).
        rule = CollectivePlacement(specs, n_devices=int(mesh.devices.size),
                                   n_pods=n_pods, billed_bytes=billed)
        analyze(hlo, rules=[rule], label=f"byte_audit[{name}]")
        cls = rule.classification
        out[name] = {
            "billed_bytes_per_pod": billed,
            "allgather_bytes_per_pod": cls["payload_bytes"],
            "bytes_per_element": round(cls["payload_bytes"] / n_elts, 6),
            "collectives": cost.collective_counts,
        }
    if "int4" in out and "int8" in out:
        # the acceptance bar: nibbles + fp32 block scales, physically half
        # of the int8 payload that PR 2 still shipped for int4
        assert out["int4"]["allgather_bytes_per_pod"] <= 0.5625 * n_elts, \
            out["int4"]
        assert (out["int4"]["allgather_bytes_per_pod"]
                <= 0.53 * out["int8"]["allgather_bytes_per_pod"]), \
            (out["int4"], out["int8"])
    return out


def _round_byte_audit(mesh, hcfg, abstract_params, formats):
    """The round-level half of ``--byte-audit`` (the tentpole acceptance
    gate): lower the **full** ``hermes_round`` — gate, payload gather,
    local merge, refresh, ``lax.cond`` skip — per wire format at this
    mesh, classify every pod-crossing collective operand in the optimized
    HLO, and assert

    * every model-sized cross-pod operand is one of the billed wire
      arrays (``dist.wire.wire_operand_specs``), each crossing exactly
      once — no fp32 merge reduction, no re-gathered decode, no silent
      double ship;
    * the matched operand bytes equal the registry's ``payload_bytes``
      bill (pod-only shardings make this an exact equality, not a bound);
    * int4 ships <= 0.5625 B/element (nibbles + fp32 block scales);
    * the closed round — ``live`` baked all-False, so ``lax.cond`` folds —
      lowers with ZERO cross-pod collectives.

    Remaining cross-pod traffic is the merge's scalar control bookkeeping
    (per-pod ``w2``, ``denom``, ``any_push``), bounded per operand at a
    few bytes and reported, not billed.
    """
    n_pods = mesh.devices.shape[0]
    n_dev = int(mesh.devices.size)
    params32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract_params)
    pod_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype), params32)
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pod_params)
    rep = NamedSharding(mesh, PS())
    rep_tree = jax.tree.map(lambda _: rep, params32)
    losses = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
    n_elts = sum(math.prod(s.shape) for s in jax.tree.leaves(params32))
    rng = jax.random.PRNGKey(0)
    out = {}
    for name in formats:
        cfg_f = dataclasses.replace(hcfg, compression=name)
        gup = hermes_pod_state(cfg_f, n_pods)
        gup_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), gup)
        gup_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), gup)

        def open_fn(pod_p, gs, pl, wg, _cfg=cfg_f):
            o = hermes_round(pod_p, gs, pl, wg, jnp.float32(1.0), _cfg,
                             rng=rng, mesh=mesh)
            return o["pod_params"], o["w_global"], o["any_push"]

        def closed_fn(pod_p, gs, pl, wg, _cfg=cfg_f):
            o = hermes_round(pod_p, gs, pl, wg, jnp.float32(1.0), _cfg,
                             live=jnp.zeros((n_pods,), bool),
                             rng=rng, mesh=mesh)
            return o["pod_params"], o["w_global"], o["any_push"]

        with mesh:
            shardings = (pod_sh, gup_sh, rep, rep_tree)
            hlo = (jax.jit(open_fn, in_shardings=shardings)
                   .lower(pod_params, gup_sds, losses, params32)
                   .compile().as_text())
            closed_hlo = (jax.jit(closed_fn, in_shardings=shardings)
                          .lower(pod_params, gup_sds, losses, params32)
                          .compile().as_text())

        cost = parse_hlo_cost(hlo)
        specs = wire_operand_specs(params32, name, n_pods)
        billed = payload_bytes(params32, name)
        rule = CollectivePlacement(specs, n_devices=n_dev, n_pods=n_pods,
                                   billed_bytes=billed)
        analyze(hlo, rules=[rule], label=f"round_byte_audit[{name}]")
        cls, recs = rule.classification, rule.records
        rule_c = CollectivePlacement(n_devices=n_dev, n_pods=n_pods,
                                     expect_none=True)
        analyze(closed_hlo, rules=[rule_c],
                label=f"round_byte_audit_closed[{name}]")
        closed_cross = rule_c.records
        out[name] = {
            "billed_bytes_per_pod": billed,
            "round_gather_bytes_per_pod": cls["payload_bytes"],
            "round_bytes_per_element": round(cls["payload_bytes"] / n_elts,
                                             6),
            "control_bytes": cls["control_bytes"],
            "cross_pod_collectives": len(recs),
            "closed_cross_pod_collectives": len(closed_cross),
            "collectives": cost.collective_counts,
        }
    if "int4" in out:
        # the acceptance bar, now proven on the FULL round's lowering
        assert (out["int4"]["round_gather_bytes_per_pod"]
                <= 0.5625 * n_elts), out["int4"]
    if "int4" in out and "int8" in out:
        assert (out["int4"]["round_gather_bytes_per_pod"]
                <= 0.53 * out["int8"]["round_gather_bytes_per_pod"]), \
            (out["int4"], out["int8"])
    return out


def _cluster_audit(cmesh, hcfg, abstract_params, formats):
    """The two-tier byte audit (DESIGN.md §10, the ISSUE 9 acceptance
    gate): lower the **full** ``hermes_cluster_round`` per wire format on
    the (cluster, pod, data, model) mesh, split its pod-crossing
    collectives into the fast intra-cluster tier and the slow
    cluster-crossing tier, and assert

    * every intra-cluster model-sized operand is one of the billed
      per-pod wire arrays (``wire_operand_specs``), bytes equal the bill;
    * every **cluster-crossing** model-sized operand is one of the
      re-encoded per-cluster partials (``cluster_wire_operand_specs`` —
      exactly ``n_clusters`` packed payload rows), bytes equal the bill:
      slow-tier traffic scales with ``n_clusters``, not ``n_pods``;
    * the closed round crosses nothing on either tier.
    """
    from repro.dist.hermes_sync import hermes_cluster_round
    from repro.dist.wire import cluster_wire_operand_specs

    n_clusters, ppc = (int(cmesh.devices.shape[0]),
                       int(cmesh.devices.shape[1]))
    n_pods = n_clusters * ppc
    n_dev = int(cmesh.devices.size)
    params32 = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), abstract_params)
    pod_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_pods,) + s.shape, s.dtype), params32)
    rows = PS(("cluster", "pod"))
    pod_sh = jax.tree.map(lambda _: NamedSharding(cmesh, rows), pod_params)
    rep = NamedSharding(cmesh, PS())
    rep_tree = jax.tree.map(lambda _: rep, params32)
    losses = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
    n_elts = sum(math.prod(s.shape) for s in jax.tree.leaves(params32))
    rng = jax.random.PRNGKey(0)
    out = {}
    for name in formats:
        cfg_f = dataclasses.replace(hcfg, compression=name,
                                    n_clusters=n_clusters)
        gup = hermes_pod_state(cfg_f, n_pods)
        gup_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), gup)
        gup_sh = jax.tree.map(lambda _: NamedSharding(cmesh, rows), gup)

        def open_fn(pod_p, gs, pl, wg, _cfg=cfg_f):
            o = hermes_cluster_round(pod_p, gs, pl, wg, jnp.float32(1.0),
                                     cfg=_cfg, rng=rng, mesh=cmesh)
            return o["pod_params"], o["w_global"], o["any_push"]

        def closed_fn(pod_p, gs, pl, wg, _cfg=cfg_f):
            o = hermes_cluster_round(pod_p, gs, pl, wg, jnp.float32(1.0),
                                     cfg=_cfg,
                                     live=jnp.zeros((n_pods,), bool),
                                     rng=rng, mesh=cmesh)
            return o["pod_params"], o["w_global"], o["any_push"]

        with cmesh:
            shardings = (pod_sh, gup_sh, rep, rep_tree)
            hlo = (jax.jit(open_fn, in_shardings=shardings)
                   .lower(pod_params, gup_sds, losses, params32)
                   .compile().as_text())
            closed_hlo = (jax.jit(closed_fn, in_shardings=shardings)
                          .lower(pod_params, gup_sds, losses, params32)
                          .compile().as_text())

        specs = wire_operand_specs(params32, name, n_pods)
        cspecs = cluster_wire_operand_specs(params32, name, n_clusters)
        billed = payload_bytes(params32, name)  # per row == per device
        rule = CollectivePlacement(
            specs, n_devices=n_dev, n_pods=n_pods, billed_bytes=billed,
            n_clusters=n_clusters, cluster_specs=cspecs,
            cluster_billed_bytes=billed)
        analyze(hlo, rules=[rule], label=f"cluster_byte_audit[{name}]")
        icls = rule.classification
        ccls = rule.cluster_classification
        rule_c = CollectivePlacement(n_devices=n_dev, n_pods=n_pods,
                                     expect_none=True)
        analyze(closed_hlo, rules=[rule_c],
                label=f"cluster_byte_audit_closed[{name}]")
        out[name] = {
            "billed_bytes_per_row": billed,
            "bytes_per_element": round(billed / n_elts, 6),
            "intra_gather_bytes_per_pod": icls["payload_bytes"],
            "cluster_gather_bytes_per_device": ccls["payload_bytes"],
            # the scaling claim, as totals: n_clusters packed rows cross
            # the slow tier where a flat round ships n_pods of them
            "slow_tier_total_bytes": ccls["payload_bytes"] * n_clusters,
            "flat_equiv_total_bytes": billed * n_pods,
            "intra_cluster_collectives": len(rule.records)
                                         - len(rule.cluster_records),
            "cluster_crossing_collectives": len(rule.cluster_records),
            "closed_cross_pod_collectives": len(rule_c.records),
        }
        assert out[name]["slow_tier_total_bytes"] < \
            out[name]["flat_equiv_total_bytes"], out[name]
    return out


def _cluster_parity_pin(formats, *, n_pods: int = 4,
                        rounds: int = 6) -> dict:
    """The ``n_clusters=1`` parity pin: a cluster round at one cluster must
    be **bit-identical** to ``hermes_round``, for every wire format, over
    several executed rounds (losses chosen so gates actually open).  The
    implementation delegates verbatim at ``C <= 1``, so this pins the
    delegation against future drift rather than re-proving algebra.
    """
    import numpy as np
    from repro.dist.hermes_sync import (gup_state_jax, hermes_cluster_round,
                                        hermes_round)

    shapes = ((8, 16), (16,))
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, len(shapes) + 1)
    for name in formats:
        hcfg = HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                            compression=name,
                            error_feedback=name in ("int8", "int4"),
                            n_clusters=1)
        wg = [jax.random.normal(ks[i], s, jnp.float32)
              for i, s in enumerate(shapes)]
        pods = [wg[i][None] + 0.01 * jax.random.normal(
                    ks[-1], (n_pods,) + s, jnp.float32)
                for i, s in enumerate(shapes)]
        a = {"pods": pods, "gup": jax.vmap(
                 lambda _: gup_state_jax(hcfg))(jnp.arange(n_pods)),
             "wg": wg, "err": None}
        b = {k: v for k, v in a.items()}
        rng = jax.random.PRNGKey(7)
        for r in range(rounds):
            # descending then spiking losses walk the GUP gate open
            pl = jnp.asarray([1.0 / (r + 1) + 0.1 * i
                              for i in range(n_pods)], jnp.float32)
            L = jnp.asarray(0.5 / (r + 1), jnp.float32)
            ra = hermes_cluster_round(a["pods"], a["gup"], pl, a["wg"], L,
                                      cfg=hcfg, error=a["err"],
                                      rng=jax.random.fold_in(rng, r))
            rb = hermes_round(b["pods"], b["gup"], pl, b["wg"], L, hcfg,
                              error=b["err"], rng=jax.random.fold_in(rng, r))
            a = {"pods": ra["pod_params"], "gup": ra["gup"],
                 "wg": ra["w_global"], "err": ra["error"]}
            b = {"pods": rb["pod_params"], "gup": rb["gup"],
                 "wg": rb["w_global"], "err": rb["error"]}
            for x, y in zip(jax.tree.leaves((ra["pod_params"],
                                             ra["w_global"], ra["gup"])),
                            jax.tree.leaves((rb["pod_params"],
                                             rb["w_global"], rb["gup"]))):
                np.testing.assert_array_equal(
                    np.asarray(x), np.asarray(y),
                    err_msg=f"nc=1 parity drift: format={name} round={r}")
    return {"formats": list(formats), "rounds": rounds,
            "bit_identical": True}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--out", default="results/dryrun_opt/hermes_sync.json")
    ap.add_argument("--drop-pod", action="store_true",
                    help="elastic-membership audit: kill a pod mid-run, "
                         "assert survivor bit-identity and a collective-"
                         "free compress step at the reduced mesh")
    ap.add_argument("--drop-pod-index", type=int, default=1)
    ap.add_argument("--rejoin-pod", action="store_true",
                    help="the grow-path audit: shrink then re-admit a "
                         "pod, assert the incumbents' rounds are bit-"
                         "identical to never having resized, and that "
                         "the compress step on the regrown mesh stays "
                         "collective-free")
    ap.add_argument("--byte-audit", action="store_true",
                    help="billing-vs-wire audit: per wire format, lower "
                         "the cross-pod payload all-gather AND the full "
                         "round and assert the lowered cross-pod operand "
                         "bytes equal the billed payload_bytes (int4 must "
                         "ship <= 0.5625 B/element at round level; the "
                         "closed round must cross nothing)")
    ap.add_argument("--clusters", type=int, default=1,
                    help="with N > 1, additionally audit the two-tier "
                         "round on a (N, 2, data, model) cluster mesh: "
                         "per format, exactly N packed payloads may cross "
                         "the cluster axis per open round; n_clusters=1 "
                         "must stay bit-identical to hermes_round; a "
                         "per-cluster shrink keeps the compress step "
                         "collective-free")
    args = ap.parse_args()

    # (2, 16, 16) at the default 512 forced devices; REPRO_DRYRUN_DEVICES
    # scales the (data, model) grid down so smoke runs stay cheap
    mesh = make_pod_mesh(2)
    n_pods = mesh.devices.shape[0]
    cfg = get_config(args.arch)
    parallel = arch_parallel_config(args.arch)
    rules = arch_rules(cfg, mesh, parallel, multi_pod=False, batch=256)
    # registry default (int4 since ISSUE 5): the headline lowering and the
    # compress audit both exercise the nibble-packed wire path
    hcfg = HermesConfig(alpha=-1.3, beta=0.1, lam=5)

    key = jax.random.PRNGKey(0)
    abstract_params, param_axes = abstract_init_lm(cfg, key)
    abstract_params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16), abstract_params)
    base_shardings = _shard_tree(param_axes, rules)

    # Collective-schedule audit of the compress step alone (ISSUE 2 /
    # ROADMAP "Sharded compression") at the full production mesh.
    ccost, n_ag, pod_shardings, global_shardings, pod_params = \
        _compress_audit(mesh, hcfg, abstract_params, base_shardings)

    gup = hermes_pod_state(hcfg, n_pods)
    rep = NamedSharding(mesh, PS())
    gup_sh = jax.tree.map(lambda _: rep, gup)
    losses = jax.ShapeDtypeStruct((n_pods,), jnp.float32)

    def round_fn(pod_p, gup_state, pod_losses, w_global, L):
        # mesh=mesh: the production merge ships the ENCODED payloads across
        # the pod axis (dist.wire.gather_payloads) and merges locally — the
        # headline lowering below is therefore the packed-gather dataflow,
        # not an implicit fp32 merge reduction
        out = hermes_round(pod_p, gup_state, pod_losses, w_global, L, hcfg,
                           mesh=mesh)
        return out["pod_params"], out["w_global"], out["gup"], out["any_push"]

    with mesh:
        jitted = jax.jit(
            round_fn,
            in_shardings=(pod_shardings, gup_sh, rep, global_shardings, rep),
            out_shardings=(pod_shardings, global_shardings, gup_sh, rep))
        lowered = jitted.lower(
            pod_params, jax.tree.map(lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype), gup), losses, abstract_params,
            jax.ShapeDtypeStruct((), jnp.float32))
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        cost = parse_hlo_cost(compiled.as_text())
        rec = {
            "arch": args.arch, "n_pods": n_pods,
            "devices": int(mesh.devices.size),
            "memory": {k: int(getattr(ma, k)) for k in
                       ("argument_size_in_bytes", "temp_size_in_bytes",
                        "output_size_in_bytes") if hasattr(ma, k)},
            "collective_bytes": cost.collective_bytes,
            "collectives": cost.collective_counts,
            "bytes": cost.bytes,
            "merge_collective_s": cost.collective_bytes / 50e9,
            "compress_collectives": ccost.collective_counts,
            "compress_all_gathers": n_ag,
        }

    if args.drop_pod:
        # lazy import: launch.elastic force-sets XLA flags only under
        # REPRO_ELASTIC_DEVICES, so importing here is safe post-init
        from repro.launch.elastic import drop_pod_equivalence

        drop = args.drop_pod_index % n_pods
        keep = [i for i in range(n_pods) if i != drop]
        small = shrink_mesh(mesh, keep)

        # 1. the lowered compress step stays collective-free at the
        #    survivors' (n_pods-1, data, model) mesh
        small_base = jax.tree.map(
            lambda sh: NamedSharding(small, sh.spec), base_shardings)
        small_cost, small_ag, _, _, _ = _compress_audit(
            small, hcfg, abstract_params, small_base)

        # 2. numeric bit-identity of the surviving pods' rounds, executed
        #    on a small pod mesh (the math is mesh-size independent; the
        #    full-size schedule is what the lowering above audits)
        eq = drop_pod_equivalence(
            n_pods=2, drop=1,
            mesh=make_pod_mesh(2, max_devices=min(jax.device_count(), 8)))
        rec["drop_pod"] = {
            "dropped": drop,
            "survivor_mesh": list(small.devices.shape),
            "survivor_compress_collectives": small_cost.collective_counts,
            "survivor_compress_all_gathers": small_ag,
            "equivalence": eq,
        }

    if args.rejoin_pod:
        from repro.launch.elastic import rejoin_pod_equivalence

        # the grow path resizes the LAST pod row (append == in-place)
        drop = n_pods - 1
        small = shrink_mesh(mesh, list(range(n_pods - 1)))
        regrown = grow_mesh(small, 1)
        # grow_mesh must hand the rejoining pod its own devices back
        assert regrown.devices.shape == mesh.devices.shape, (
            regrown.devices.shape, mesh.devices.shape)
        assert ({d.id for d in regrown.devices.flat}
                == {d.id for d in mesh.devices.flat}), \
            "regrown mesh must reuse the dropped pod's devices"

        # 1. the lowered compress step stays collective-free on the
        #    regrown (n_pods, data, model) mesh — a rejoin cannot regress
        #    the shard-local wire layout
        regrown_base = jax.tree.map(
            lambda sh: NamedSharding(regrown, sh.spec), base_shardings)
        re_cost, re_ag, _, _, _ = _compress_audit(
            regrown, hcfg, abstract_params, regrown_base)

        # 2. numeric bit-identity of the shrink->grow round trip, executed
        #    on a small stand-in pod mesh (the math is mesh-size
        #    independent; the full-size schedule is what part (1) audits)
        eq = rejoin_pod_equivalence(
            n_pods=2,
            mesh=make_pod_mesh(2, max_devices=min(jax.device_count(), 8)))
        rec["rejoin_pod"] = {
            "rejoined": drop,
            "regrown_mesh": list(regrown.devices.shape),
            "regrown_compress_collectives": re_cost.collective_counts,
            "regrown_compress_all_gathers": re_ag,
            "equivalence": eq,
        }

    if args.byte_audit:
        from repro.dist.wire import available_formats, block_axis

        rec["byte_audit"] = _byte_audit(mesh, abstract_params,
                                        available_formats())
        # the round-level half: the FULL round's lowering ships exactly
        # the billed wire bytes across the pod axis, per format, and the
        # closed round crosses nothing at all
        rec["byte_audit_round"] = _round_byte_audit(
            mesh, hcfg, abstract_params, available_formats())

        # Block-axis/shard-rule coupling (ROADMAP): the shape-only blocked
        # axis must coincide with the AxisRules-hinted preference for every
        # leaf of this arch — i.e. no leaf's chosen axis is sharded-but-
        # misaligned, which is what keeps the (audited) compress step
        # collective-free.
        axes_leaves = jax.tree.leaves(
            param_axes, is_leaf=lambda x: isinstance(x, tuple))
        shape_leaves = [s.shape for s in jax.tree.leaves(abstract_params)]
        drift = [
            (shape, axes)
            for shape, axes in zip(shape_leaves, axes_leaves)
            if block_axis(shape) != block_axis(shape, axes=axes, rules=rules)]
        assert not drift, (
            f"{len(drift)} leaves pick a sharded-but-misaligned blocked "
            f"axis: {drift[:3]}")
        rec["block_axis_hint_drift"] = len(drift)

    if args.clusters > 1:
        from repro.dist.wire import available_formats
        from repro.launch.elastic import cluster_resize_cycle_equivalence

        # two pods per cluster on the smallest mesh that exhibits both
        # tiers (2 clusters -> 8 devices under REPRO_DRYRUN_DEVICES=8)
        cmesh = make_pod_mesh(
            2 * args.clusters, n_clusters=args.clusters,
            max_devices=min(jax.device_count(), 4 * args.clusters))
        rec["cluster_audit"] = {
            "mesh": list(cmesh.devices.shape),
            "n_clusters": args.clusters,
            "byte_audit": _cluster_audit(cmesh, hcfg, abstract_params,
                                         available_formats()),
            "parity_nc1": _cluster_parity_pin(available_formats()),
        }

        # per-cluster shrink (DESIGN.md §7/§10): kill the last pod of the
        # last cluster; the flattened cluster-major survivors' mesh must
        # keep the compress step collective-free, and repeated
        # shrink->grow->shrink cycles stay bit-identical to never having
        # resized (the Level-B elastic oracle, per cluster)
        ppc = int(cmesh.devices.shape[1])
        small = shrink_mesh(cmesh, list(range(ppc - 1)),
                            cluster=args.clusters - 1)
        small_base = jax.tree.map(
            lambda sh: NamedSharding(small, sh.spec), base_shardings)
        s_cost, s_ag, _, _, _ = _compress_audit(
            small, hcfg, abstract_params, small_base)
        rec["cluster_audit"]["shrink"] = {
            "survivor_mesh": list(small.devices.shape),
            "survivor_compress_collectives": s_cost.collective_counts,
            "survivor_compress_all_gathers": s_ag,
            "resize_cycles": cluster_resize_cycle_equivalence(
                n_pods=2 * args.clusters, n_clusters=args.clusters),
        }

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
    print(json.dumps(rec, indent=2))


if __name__ == "__main__":
    main()
