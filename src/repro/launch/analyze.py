"""``make lint-hlo``: run the static analyzer over every entry point.

Each entry-point executable is lowered on a small forced-device CPU pod
mesh and checked against the registered invariant rules
(:mod:`repro.analysis`, DESIGN.md §9):

* ``hermes_round`` (open + closed) — the synchronous Level-B round, and
  the executable ``train_hermes`` runs it as (``make_round_jit``), whose
  donated pods, global model and residual must alias in place.
* ``hermes_dispatch`` / ``hermes_commit`` — the async pipelined halves,
  including the commit's donation contract (``make_async_round_jits``).
* ``elastic_shrink`` / ``elastic_grow`` — a *real* 4 -> 3 -> 4 pod resize
  cycle, with the post-resize round lowered on the survivors' and the
  regrown mesh.
* the train step (``launch.steps.build_setup``) — pod-local by
  construction: it may collectivize over (data, model) but must cross
  the pod axis with nothing, and its donated state must alias.

On top of the per-executable HLO rules, the retrace guard scans the
``train_hermes`` round loop source and the Pallas tile lint traces every
wire-path kernel (``kernels.ops.wire_lint_cases``).

``--self-test`` proves the analyzer fails loudly: it rebuilds one known
regression per rule class — the PR 5 fp32 GSPMD hoist, a dropped
``pending`` donation, the PR 4 ``bool(any_push)`` per-round host sync, a
misaligned Pallas BlockSpec — and asserts each raises
:class:`repro.analysis.AnalysisError` with the expected named violation.

Usage:
    REPRO_ANALYZE_DEVICES=8 python -m repro.launch.analyze \
        --self-test --out results/analysis/lint_hlo.json
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count="
                      + os.environ.get("REPRO_ANALYZE_DEVICES", "8"))
os.environ["JAX_PLATFORMS"] = "cpu"  # virtual host devices; never the TPU

import argparse
import json
from typing import Any, Dict, List, Optional

import jax

# placed/unplaced bit-identity for stochastic int4 (same as the training
# entry points; the lowerings here must match what production compiles)
jax.config.update("jax_threefry_partitionable", True)

import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as PS

from repro.analysis import (
    AnalysisError, CollectivePlacement, DonationAliasing, PallasTileLint,
    Report, RetraceGuard, analyze, donated_param_numbers,
)
from repro.config import (
    HermesConfig, OptimizerConfig, ParallelConfig, ShapeConfig,
)
from repro.configs import get_smoke_config
from repro.dist.compression import payload_bytes
from repro.dist.hermes_sync import (
    hermes_commit, hermes_dispatch, hermes_pod_state, hermes_round,
)
from repro.dist.wire import payload_buffer_spec, wire_operand_specs
from repro.launch.elastic import elastic_grow, elastic_shrink
from repro.launch.mesh import arch_rules, make_pod_mesh
from repro.launch.steps import build_setup
from repro.launch.train import (
    make_async_round_jits, make_round_jit, train_hermes,
)

Tree = Any

N_PODS = 2          # round/dispatch/commit/train targets
ELASTIC_PODS = 4    # shrink 4 -> 3 keeps real cross-pod gathers at 8 dev


def _cfg(mode: Optional[str] = None) -> HermesConfig:
    kw = {} if mode is None else {"compression": mode}
    return HermesConfig(alpha=-0.3, beta=0.1, lam=2, window=4, **kw)


def _toy(n: int = N_PODS):
    """One blocked leaf + one short-tail leaf (round_audit's toy tree)."""
    k1, k2, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    pods = {"w": jax.random.normal(k1, (n, 4, 512), jnp.float32),
            "b": jax.random.normal(k2, (n, 7), jnp.float32)}
    wg = {"w": jax.random.normal(kg, (4, 512), jnp.float32),
          "b": jnp.zeros((7,), jnp.float32)}
    return pods, wg


def _sds(tree: Tree) -> Tree:
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _placed(tree: Tree, shardings: Tree) -> Tree:
    """Abstract args carrying their shardings, so a production jit lowers
    with its own donation and output contract."""
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        _sds(tree), shardings)


def _round_shardings(mesh, pods, gup, wg):
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pods)
    gup_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), gup)
    rep = NamedSharding(mesh, PS())
    rep_tree = jax.tree.map(lambda _: rep, wg)
    return pod_sh, gup_sh, rep, rep_tree


def _lower_round(mesh, cfg, n_pods, *, closed: bool = False):
    """Lower the synchronous round on ``mesh``; returns (lowered, fn,
    example_args) so the HLO and the AST/arg rules see the same thing."""
    pods, wg = _toy(n_pods)
    gup = hermes_pod_state(cfg, n_pods)
    pod_sh, gup_sh, rep, rep_tree = _round_shardings(mesh, pods, gup, wg)
    losses = jax.ShapeDtypeStruct((n_pods,), jnp.float32)
    rng = jax.random.PRNGKey(0)
    live = jnp.zeros((n_pods,), bool) if closed else None

    def round_fn(p, g, pl, w):
        o = hermes_round(p, g, pl, w, jnp.float32(1.0), cfg, live=live,
                         rng=rng, mesh=mesh)
        return o["pod_params"], o["w_global"], o["any_push"]

    args = (_sds(pods), _sds(gup), losses, _sds(wg))
    with mesh:
        lowered = jax.jit(
            round_fn, in_shardings=(pod_sh, gup_sh, rep, rep_tree)
        ).lower(*args)
    return lowered, round_fn, args


def _placement_rule(mesh, wg, mode, n_pods) -> CollectivePlacement:
    return CollectivePlacement(
        wire_operand_specs(wg, mode, n_pods),
        n_devices=int(mesh.devices.size), n_pods=n_pods,
        billed_bytes=payload_bytes(
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.float32), wg), mode))


# ---------------------------------------------------------------------------
# Entry-point targets
# ---------------------------------------------------------------------------

def check_hermes_round(mode: Optional[str] = None) -> List[Report]:
    """Open round ships exactly the billed wire; closed round ships nothing."""
    cfg = _cfg(mode)
    mesh = make_pod_mesh(N_PODS)
    _, wg = _toy()
    lowered, fn, args = _lower_round(mesh, cfg, N_PODS)
    rep_open = analyze(
        lowered,
        rules=[_placement_rule(mesh, wg, cfg.compression, N_PODS),
               RetraceGuard(scan_source=False)],
        fn=fn, example_args=args,
        label=f"hermes_round[{cfg.compression}]")
    closed, fn_c, args_c = _lower_round(mesh, cfg, N_PODS, closed=True)
    rep_closed = analyze(
        closed,
        rules=[CollectivePlacement(n_devices=int(mesh.devices.size),
                                   n_pods=N_PODS, expect_none=True)],
        fn=fn_c, example_args=args_c,
        label=f"hermes_round_closed[{cfg.compression}]")
    return [rep_open, rep_closed]


def check_round_jit(mode: Optional[str] = None) -> List[Report]:
    """The round as ``train_hermes`` jits it (``make_round_jit``): it ships
    the billed wire, and its donated pods, global model and error residual
    (a tree exactly when the wire is lossy with error feedback) each alias
    their output in the ``input_output_alias`` header."""
    cfg = _cfg(mode)
    mesh = make_pod_mesh(N_PODS)
    pods, wg = _toy()
    gup = hermes_pod_state(cfg, N_PODS)
    pod_sh, gup_sh, rep, rep_tree = _round_shardings(mesh, pods, gup, wg)
    has_error = cfg.compression != "none" and cfg.error_feedback
    args = (_placed(pods, pod_sh), _placed(gup, gup_sh),
            jax.ShapeDtypeStruct((N_PODS,), jnp.float32, sharding=rep),
            _placed(wg, rep_tree),
            jax.ShapeDtypeStruct((), jnp.float32, sharding=rep),
            _placed(pods, pod_sh) if has_error else None,
            jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    with mesh:
        lowered = make_round_jit(cfg, mesh).lower(*args)
    donated = donated_param_numbers(args, (0, 3, 5))
    names = {0: "pod_params", 3: "w_global"}
    if has_error:
        names[5] = "error"
    return [analyze(
        lowered,
        rules=[_placement_rule(mesh, wg, cfg.compression, N_PODS),
               DonationAliasing({name: range(*donated[k])
                                 for k, name in names.items()})],
        label=f"hermes_round_jit[{cfg.compression}]")]


def check_async_halves(mode: Optional[str] = None) -> List[Report]:
    """Dispatch carries the gather; commit is collective-free and its
    donations (pod_params + pending) hold in the alias header."""
    cfg = _cfg(mode)
    mesh = make_pod_mesh(N_PODS)
    pods, wg = _toy()
    gup = hermes_pod_state(cfg, N_PODS)
    pod_sh, gup_sh, rep, rep_tree = _round_shardings(mesh, pods, gup, wg)
    losses = jax.ShapeDtypeStruct((N_PODS,), jnp.float32)
    rng = jax.random.PRNGKey(0)

    def dispatch_fn(p, g, pl, w):
        o = hermes_dispatch(p, g, pl, w, jnp.float32(1.0), cfg, rng=rng,
                            mesh=mesh)
        return o["pending"], o["error"], o["any_push"]

    d_args = (_sds(pods), _sds(gup), losses, _sds(wg))
    with mesh:
        d_lowered = jax.jit(
            dispatch_fn, in_shardings=(pod_sh, gup_sh, rep, rep_tree)
        ).lower(*d_args)
    rep_dispatch = analyze(
        d_lowered,
        rules=[_placement_rule(mesh, wg, cfg.compression, N_PODS),
               RetraceGuard(scan_source=False)],
        fn=dispatch_fn, example_args=d_args,
        label=f"hermes_dispatch[{cfg.compression}]")

    # the commit half, exactly as train_hermes builds it (one definition:
    # make_async_round_jits) — donated pod_params/pending, zero collectives
    pending = {
        "payload": payload_buffer_spec(wg, cfg.compression, N_PODS),
        "gates": jax.ShapeDtypeStruct((N_PODS,), jnp.bool_),
        "losses": jax.ShapeDtypeStruct((N_PODS,), jnp.float32),
        "L": jax.ShapeDtypeStruct((), jnp.float32),
        "any_push": jax.ShapeDtypeStruct((), jnp.bool_),
    }
    _, commit_jit = make_async_round_jits(cfg, mesh)
    # lower the PRODUCTION commit executable (donation contract included)
    c_args = (_placed(pods, pod_sh),
              _placed(pending, jax.tree.map(lambda _: rep, pending)),
              _placed(wg, rep_tree))
    with mesh:
        c_lowered = commit_jit.lower(*c_args)
    donated = donated_param_numbers(c_args, (0, 1))
    pp_lo, pp_hi = donated[0]
    pd_lo, pd_hi = donated[1]
    rep_commit = analyze(
        c_lowered,
        rules=[CollectivePlacement(n_devices=int(mesh.devices.size),
                                   n_pods=N_PODS, expect_none=True),
               DonationAliasing(
                   {"pod_params": range(pp_lo, pp_hi),
                    "pending": range(pd_lo, pd_hi)},
                   # the encoded int8 payload/scale leaves have no
                   # shape-matching output to alias into (they are freed,
                   # not aliased); the bool any_push round-trips
                   min_aliased={"pending": 1})],
        label=f"hermes_commit[{cfg.compression}]")
    return [rep_dispatch, rep_commit]


def check_admission(mode: Optional[str] = None) -> List[Report]:
    """Participation admission must not change the wire (DESIGN.md §11).

    Lowers the round and the dispatch half with ``participation_rate``
    0.5 under both admission policies against the UNCHANGED
    ``wire_operand_specs`` placement rule: admission thins which open
    gates ship — ``any_push`` frequency — but the cross-pod collective's
    operand multiset (shapes, dtypes, billed bytes) is pinned to the
    same registry entry as the ungated round.  A deferred pod's payload
    rows are the same exact zeros as a closed pod's, so no new operand
    may appear and none may grow."""
    reports: List[Report] = []
    mesh = make_pod_mesh(N_PODS)
    _, wg = _toy()
    losses = jax.ShapeDtypeStruct((N_PODS,), jnp.float32)
    rng = jax.random.PRNGKey(0)
    for admission in ("topk", "prob"):
        kw = {} if mode is None else {"compression": mode}
        cfg = HermesConfig(alpha=-0.3, beta=0.1, lam=2, window=4,
                           participation_rate=0.5, admission=admission,
                           **kw)
        lowered, fn, args = _lower_round(mesh, cfg, N_PODS)
        reports.append(analyze(
            lowered,
            rules=[_placement_rule(mesh, wg, cfg.compression, N_PODS),
                   RetraceGuard(scan_source=False)],
            fn=fn, example_args=args,
            label=f"hermes_round[{cfg.compression},prate=0.5,"
                  f"{admission}]"))
        pods, _ = _toy()
        gup = hermes_pod_state(cfg, N_PODS)
        pod_sh, gup_sh, rep, rep_tree = _round_shardings(mesh, pods, gup,
                                                         wg)

        def dispatch_fn(p, g, pl, w, cfg=cfg):
            o = hermes_dispatch(p, g, pl, w, jnp.float32(1.0), cfg,
                                rng=rng, mesh=mesh)
            return o["pending"], o["error"], o["any_push"]

        d_args = (_sds(pods), _sds(gup), losses, _sds(wg))
        with mesh:
            d_lowered = jax.jit(
                dispatch_fn, in_shardings=(pod_sh, gup_sh, rep, rep_tree)
            ).lower(*d_args)
        reports.append(analyze(
            d_lowered,
            rules=[_placement_rule(mesh, wg, cfg.compression, N_PODS),
                   RetraceGuard(scan_source=False)],
            fn=dispatch_fn, example_args=d_args,
            label=f"hermes_dispatch[{cfg.compression},prate=0.5,"
                  f"{admission}]"))
    return reports


def check_elastic(mode: Optional[str] = None) -> List[Report]:
    """Post-resize rounds: shrink 4 -> 3, grow 3 -> 4, re-lower the round
    on the survivors' and the regrown mesh — the wire bill tracks the new
    pod count and nothing else crosses."""
    cfg = _cfg(mode)
    mesh = make_pod_mesh(ELASTIC_PODS)
    pods, wg = _toy(ELASTIC_PODS)
    gup = hermes_pod_state(cfg, ELASTIC_PODS)
    pod_spec = jax.tree.map(lambda _: PS("pod"), pods)
    state = {"pod_params": pods, "gup": gup, "error": None,
             "w_global": wg, "pending": None}
    specs = {"pod_params": pod_spec,
             "gup": jax.tree.map(lambda _: PS("pod"), gup)}

    keep = [0, 1, 3]
    shrunk, small_mesh = elastic_shrink(state, keep, mesh, cfg=cfg,
                                        specs=specs)
    assert small_mesh is not None and small_mesh.devices.shape[0] == 3
    lowered_s, fn_s, args_s = _lower_round(small_mesh, cfg, len(keep))
    rep_shrink = analyze(
        lowered_s,
        rules=[_placement_rule(small_mesh, wg, cfg.compression, len(keep))],
        fn=fn_s, example_args=args_s,
        label=f"elastic_shrink_round[{cfg.compression}]")

    grown, big_mesh = elastic_grow(shrunk, small_mesh, cfg=cfg, specs=specs)
    assert big_mesh is not None
    n_after = int(big_mesh.devices.shape[0])
    assert n_after == ELASTIC_PODS, (n_after, ELASTIC_PODS)
    lowered_g, fn_g, args_g = _lower_round(big_mesh, cfg, n_after)
    rep_grow = analyze(
        lowered_g,
        rules=[_placement_rule(big_mesh, wg, cfg.compression, n_after)],
        fn=fn_g, example_args=args_g,
        label=f"elastic_grow_round[{cfg.compression}]")
    return [rep_shrink, rep_grow]


def check_train_step(arch: str = "qwen3-8b") -> List[Report]:
    """The Level-B local train step, lowered per-pod.

    Hermes pods train *locally*: the production step runs on one pod's
    own (data, model) submesh, so its executable structurally cannot
    address another pod's devices and ``expect_none`` (measured against
    the full fleet's pod boundaries) must hold.  Lowering the same setup
    on the full (pod, data, model) mesh instead is a real regression the
    rule catches: with the pod axis idle, XLA's partitioner freely
    routes backward-pass resharding/partial-sum collectives *across*
    pods (observed at (2, 2, 2): model-sized f32 all-reduces with
    replica groups pairing pods) — silent cross-pod traffic on every
    step.  The donated train state must fully alias in place.
    """
    pod_mesh = make_pod_mesh(N_PODS)
    from jax.sharding import Mesh
    sub = Mesh(pod_mesh.devices[0], ("data", "model"))
    cfg = get_smoke_config(arch)
    parallel = ParallelConfig()
    batch = 8
    rules = arch_rules(cfg, sub, parallel, batch=batch)
    shape = ShapeConfig("analyze_smoke", 32, batch, "train")
    opt = OptimizerConfig(name="adamw", lr=1e-3)
    with sub:
        setup = build_setup("train", cfg, shape, rules, parallel, opt,
                            impl="auto")
        lowered = jax.jit(setup.step_fn, in_shardings=setup.in_shardings,
                          out_shardings=setup.out_shardings,
                          donate_argnums=(0,)).lower(*setup.abstract_args)
    lo, hi = donated_param_numbers(setup.abstract_args, (0,))[0]
    report = analyze(
        lowered,
        rules=[CollectivePlacement(n_devices=int(pod_mesh.devices.size),
                                   n_pods=N_PODS, expect_none=True),
               DonationAliasing({"train_state": range(lo, hi)})],
        label=f"train_step[{arch}]")
    return [report]


def check_round_loop_source() -> List[Report]:
    """AST pass over the production round loop: every device->host read
    goes through the single allow-listed fetcher."""
    report = analyze(
        None, rules=[RetraceGuard(allow=("_host_fetch",), check_args=False)],
        fn=train_hermes, example_args=(), label="train_hermes[source]")
    return [report]


def check_kernels() -> List[Report]:
    """Tile lint over every wire-path Pallas kernel + the pack constants."""
    from repro.kernels.ops import wire_lint_cases
    out = []
    for label, fn, args in wire_lint_cases():
        out.append(analyze(None, rules=[PallasTileLint()], fn=fn,
                           example_args=args, label=f"kernel[{label}]"))
    out.append(analyze(None, rules=[PallasTileLint(check_constants=True)],
                       label="kernel[pack-constants]"))
    return out


# ---------------------------------------------------------------------------
# Self-test: prove each rule class fails loudly on a known regression
# ---------------------------------------------------------------------------

def _expect_violation(label: str, cls: str, thunk) -> Dict[str, Any]:
    try:
        thunk()
    except AnalysisError as e:
        classes = {v.cls for v in e.violations}
        assert cls in classes, (
            f"{label}: expected violation class {cls!r}, got {classes}")
        return {"fixture": label, "expected_class": cls, "raised": True,
                "classes": sorted(classes)}
    raise AssertionError(
        f"{label}: analyzer passed a fixture built to violate {cls!r}")


def selftest_fp32_hoist() -> Dict[str, Any]:
    """Re-create the PR 5 regression: a wire sender with a receiver-only
    sharding constraint (no sender pin, no optimization barrier) lets
    GSPMD hoist the all-gather onto the fp32 delta."""
    from repro.dist.compression import encode_tree
    mode = "fp16"
    mesh = make_pod_mesh(N_PODS)
    pods, wg = _toy()
    pod_sh = jax.tree.map(lambda _: NamedSharding(mesh, PS("pod")), pods)
    rep_tree = jax.tree.map(lambda _: NamedSharding(mesh, PS()), wg)

    def hoisted_ship(pod_p, w_g):
        delta = jax.tree.map(lambda p, g: p - g[None], pod_p, w_g)
        payloads, _, _ = encode_tree(delta, mode=mode)
        # BUG (deliberate): receiver-side constraint only — the sender pin
        # + optimization_barrier that production wire code uses are gone
        return jax.tree.map(
            lambda a: jax.lax.with_sharding_constraint(
                a, NamedSharding(mesh, PS())), payloads)

    with mesh:
        lowered = jax.jit(hoisted_ship, in_shardings=(pod_sh, rep_tree)
                          ).lower(_sds(pods), _sds(wg))
    return _expect_violation(
        "fp32-hoist", "fp32-model-crossing",
        lambda: analyze(lowered,
                        rules=[_placement_rule(mesh, wg, mode, N_PODS)],
                        label="selftest[fp32-hoist]"))


def selftest_dropped_donation() -> Dict[str, Any]:
    """A commit jitted WITHOUT donate_argnums: the pod_params aliases
    disappear from the module header and the rule names the drop."""
    cfg = _cfg()
    mesh = make_pod_mesh(N_PODS)
    pods, wg = _toy()
    pod_sh, _, rep, rep_tree = _round_shardings(
        mesh, pods, hermes_pod_state(cfg, N_PODS), wg)
    pending = {
        "payload": payload_buffer_spec(wg, cfg.compression, N_PODS),
        "gates": jax.ShapeDtypeStruct((N_PODS,), jnp.bool_),
        "losses": jax.ShapeDtypeStruct((N_PODS,), jnp.float32),
        "L": jax.ShapeDtypeStruct((), jnp.float32),
        "any_push": jax.ShapeDtypeStruct((), jnp.bool_),
    }
    pend_sh = jax.tree.map(lambda _: rep, pending)

    def commit_fn(p, pending, w):
        o = hermes_commit(p, pending, w, cfg=cfg, mesh=mesh)
        return o["pod_params"], o["w_global"], o["any_push"]

    c_args = (_sds(pods), pending, _sds(wg))
    with mesh:
        lowered = jax.jit(  # BUG (deliberate): donate_argnums dropped
            commit_fn, in_shardings=(pod_sh, pend_sh, rep_tree)
        ).lower(*c_args)
    lo, hi = donated_param_numbers(c_args, (0,))[0]
    return _expect_violation(
        "dropped-donation", "dropped-donation",
        lambda: analyze(lowered,
                        rules=[DonationAliasing(
                            {"pod_params": range(lo, hi)})],
                        label="selftest[dropped-donation]"))


def selftest_host_sync_loop() -> Dict[str, Any]:
    """The PR 4 bug shape: ``bool(any_push)`` once per round, plus a
    weak-typed python-float argument churning the jit cache."""

    def bad_round_loop(state, steps):  # pragma: no cover - traced by AST
        for i in range(steps):
            state, any_push = step(state)          # noqa: F821
            if bool(any_push):                     # per-round host sync
                log(i)                             # noqa: F821
        return state

    def run_scan():
        analyze(None, rules=[RetraceGuard(check_args=False)],
                fn=bad_round_loop, label="selftest[host-sync]")

    scan = _expect_violation("host-sync-in-loop", "host-sync-in-loop",
                             run_scan)
    weak = _expect_violation(
        "weak-type-arg", "weak-type-arg",
        lambda: analyze(None,
                        rules=[RetraceGuard(scan_source=False)],
                        fn=None, example_args=(1.0,),
                        label="selftest[weak-arg]"))
    return {"fixture": "retrace", "parts": [scan, weak],
            "expected_class": "host-sync-in-loop", "raised": True}


def selftest_bad_tiles() -> Dict[str, Any]:
    """A pallas_call whose BlockSpec neither divides the array nor meets
    the dtype minimum tile."""
    from jax.experimental import pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def bad(x):
        # BUG (deliberate): 100 does not divide 250 and is not a lane
        # multiple of 128
        return pl.pallas_call(
            copy_kernel,
            grid=(64 // 8, 3),
            in_specs=[pl.BlockSpec((8, 100), lambda i, j: (i, j))],
            out_specs=pl.BlockSpec((8, 100), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((64, 250), jnp.float32),
            interpret=True)(x)

    args = (jax.ShapeDtypeStruct((64, 250), jnp.float32),)
    return _expect_violation(
        "bad-tiles", "tile-misaligned",
        lambda: analyze(None, rules=[PallasTileLint()], fn=bad,
                        example_args=args, label="selftest[bad-tiles]"))


def run_selftests() -> List[Dict[str, Any]]:
    return [selftest_fp32_hoist(), selftest_dropped_donation(),
            selftest_host_sync_loop(), selftest_bad_tiles()]


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default=None,
                    help="wire format for the round targets "
                         "(default: HermesConfig default)")
    ap.add_argument("--self-test", action="store_true",
                    help="also run the violating fixtures (each must "
                         "fail with its named violation class)")
    ap.add_argument("--out", default=None, help="write a JSON report")
    args = ap.parse_args()

    reports: List[Report] = []
    reports += check_hermes_round(args.mode)
    reports += check_round_jit(args.mode)
    reports += check_async_halves(args.mode)
    reports += check_admission(args.mode)
    reports += check_elastic(args.mode)
    reports += check_train_step()
    reports += check_round_loop_source()
    reports += check_kernels()
    for r in reports:
        print(f"  ok {r.label} ({', '.join(r.rules)})")

    record: Dict[str, Any] = {
        "devices": int(jax.device_count()),
        "targets": [r.to_json() for r in reports],
        "ok": all(r.ok for r in reports),
    }
    if args.self_test:
        fixtures = run_selftests()
        record["self_test"] = fixtures
        for f in fixtures:
            print(f"  ok self-test {f['fixture']} raised "
                  f"{f['expected_class']}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=2)
        print(f"wrote {args.out}")
    print(f"analyzed {len(reports)} executables: all clean")


if __name__ == "__main__":
    main()
