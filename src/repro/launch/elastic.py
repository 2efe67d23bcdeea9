"""Elastic membership: survive node/pod loss and resume on a smaller mesh.

Two resize paths live here (DESIGN.md §7):

* **Checkpoint restart** (``run_demo``): checkpoint -> "node failure" ->
  restore onto a smaller (data, model) mesh with re-sharded state and a
  re-balanced batch allocation.  This is the coarse path — any state
  survives anything, at the cost of a full restore.

* **In-flight pod shrink** (``elastic_shrink`` + ``drop_pod_equivalence``):
  the Level-B Hermes state is *pod-stacked* (leading ``(n_pods,)`` axis on
  pod_params, GUP ring buffers, and error-feedback residuals), so losing a
  pod is an index migration, not a restart: drop the dead rows from every
  stacked tree (``shrink_pod_tree``), rebuild the mesh from the surviving
  pods' devices (``launch.mesh.shrink_mesh``), device_put the survivors
  onto it, and re-split the data shards via ``core.allocator.reallocate``
  (``survivor_allocations``).  Between failure detection and the shrink,
  ``hermes_round(live=...)`` masks the dead pod out of gates/wire/merge,
  so the two representations are bit-identical for the survivors —
  ``drop_pod_equivalence`` asserts exactly that, and
  ``launch/hermes_dryrun.py --drop-pod`` runs it at the production mesh.

* **In-flight pod grow** (``elastic_grow`` + ``rejoin_pod_equivalence``):
  the inverse — a recovered pod is re-admitted by appending one row to
  every pod-stacked tree (``grow_pod_tree``: pod_params seeded from
  ``w_global``, fresh GUP ring buffers, zeroed error residuals),
  regrowing the mesh onto the rejoining pod's own devices
  (``launch.mesh.grow_mesh``), and re-splitting the data with the
  newcomer seeded at the median observed iteration time
  (``rejoin_allocations``).  The re-admission *policy*
  (``core.allocator.should_readmit``, ``HermesConfig.rejoin_cost_rounds``)
  gates the whole thing: the recompile + re-shard stall only pays off
  when enough rounds remain to amortize it.  Because the newcomer's
  empty loss queue keeps its gate provably shut while it warms up, the
  join is invisible to the incumbents — ``rejoin_pod_equivalence``
  asserts grow-after-shrink is bit-identical for them to never having
  resized at all, and ``launch/hermes_dryrun.py --rejoin-pod`` runs that
  proof plus a collective-free compress audit on the regrown mesh.

Run the demos under 8 virtual devices:

    REPRO_ELASTIC_DEVICES=8 python -m repro.launch.elastic
"""
import os
if os.environ.get("REPRO_ELASTIC_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_ELASTIC_DEVICES"])
    os.environ["JAX_PLATFORMS"] = "cpu"  # virtual devices; never the TPU

import json
import tempfile
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.config import (
    HermesConfig, ShapeConfig, OptimizerConfig, ParallelConfig,
)
from repro.configs import get_smoke_config
from repro.checkpoint import Checkpointer
from repro.core.allocator import (
    Allocation, dual_binary_search, reallocate, rejoin_gain_rounds,
    should_readmit,
)
from repro.core.gup import gup_state_jax
from repro.dist.hermes_sync import (
    hermes_cluster_commit, hermes_cluster_round, hermes_grow_pod_state,
    hermes_pod_state, hermes_round,
)
from repro.launch.mesh import (
    arch_rules, grow_mesh, make_pod_mesh, shrink_mesh,
)
from repro.launch.steps import build_setup

Tree = Any


# ---------------------------------------------------------------------------
# Pod-stacked state migration
# ---------------------------------------------------------------------------

def shrink_pod_tree(tree: Tree, keep: Sequence[int]) -> Tree:
    """Drop dead pods from a pod-stacked pytree: every leaf keeps only the
    ``keep`` rows of its leading (n_pods,) axis, in ``keep`` order.

    This is the whole GUP-state migration: ring buffers, alpha/n_iter
    counters, error-feedback residuals, and the model replicas themselves
    all carry their pod identity in axis 0, so surviving state moves by
    index and nothing is re-derived.

    ``keep`` is validated against the leading axis before the take:
    ``jnp.take``'s default clamp mode would otherwise turn an out-of-range
    or stale pod index into a silently *duplicated* survivor row — a
    corrupted membership table must fail loudly, not fork a replica.
    """
    if tree is None:
        return None
    keep = [int(k) for k in keep]
    leaves = jax.tree.leaves(tree)
    if leaves:
        n_pods = leaves[0].shape[0]
        bad = [k for k in keep if not 0 <= k < n_pods]
        if bad:
            raise ValueError(
                f"pod indices {bad} out of range for leading axis "
                f"{n_pods} (stale membership table?)")
    if len(set(keep)) != len(keep):
        raise ValueError(f"duplicate pod indices in keep={keep}: a "
                         f"survivor row must not be forked")
    idx = jnp.asarray(keep, jnp.int32)
    return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), tree)


# state keys the resize paths treat as pod-stacked (leading n_pods axis)
POD_STACKED_KEYS = ("pod_params", "gup", "error")


def flush_pending(state: Dict[str, Any], *,
                  cfg: Optional[HermesConfig] = None,
                  live: Optional[Sequence[bool]] = None,
                  mesh: Optional[Mesh] = None,
                  n_clusters: Optional[int] = None,
                  cluster_sizes: Optional[Sequence[int]] = None
                  ) -> Dict[str, Any]:
    """Commit an async in-flight payload before a membership resize.

    The async pipelined loop (DESIGN.md §8) carries a ``pending`` buffer —
    a dispatched-but-unmerged round — whose arrays are sized to the
    *current* pod count; a resize would orphan it, and naively merging it
    afterwards would let a dead pod's in-flight push land posthumously.
    The rule is: **flush first, under the survivor mask**.
    The commit re-masks the dispatch-time gates with the current
    membership, so a dropped pod's payload row gets merge weight zero and
    no refresh — its push never merges — while the survivors' in-flight
    contributions land exactly as a synchronous round would have merged
    them.  A two-tier buffer (``cluster_payload``, DESIGN.md §10) commits
    through :func:`repro.dist.hermes_sync.hermes_cluster_commit`, whose
    cluster-granular re-mask drops the *whole cluster* of any dead gated
    pod — an aggregated partial cannot shed one member — and a flat
    buffer takes the single-tier commit verbatim (the dispatcher
    self-selects on the pending keys).

    Returns ``state`` with the commit applied to ``pod_params`` /
    ``w_global`` and ``pending`` cleared (``None``); a state with no
    pending buffer passes through untouched.  Both resize entry points
    (``elastic_shrink`` / ``elastic_grow``) call this themselves, so
    production code only needs it directly for a flush *without* a
    resize (e.g. draining before a checkpoint).
    """
    pending = state.get("pending")
    if pending is None:
        return state
    cfg = cfg or HermesConfig()
    lv = None if live is None else jnp.asarray(np.asarray(live, bool))
    cm = hermes_cluster_commit(state["pod_params"], pending,
                               state["w_global"], cfg=cfg,
                               n_clusters=n_clusters,
                               cluster_sizes=cluster_sizes,
                               live=lv, mesh=mesh)
    return {**state, "pod_params": cm["pod_params"],
            "w_global": cm["w_global"], "pending": None}


def _reshard(tree: Tree, spec_tree: Optional[Tree],
             mesh: Optional[Mesh]) -> Tree:
    """device_put a pytree onto ``mesh`` using a PartitionSpec pytree
    (``None`` replicates every leaf); no-op without a tree or a mesh."""
    if tree is None or mesh is None:
        return tree
    if spec_tree is None:
        sh = NamedSharding(mesh, PS())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
    return jax.tree.map(
        lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp)),
        tree, spec_tree)


def elastic_shrink(state: Dict[str, Any], keep: Sequence[int],
                   mesh: Optional[Mesh], *,
                   cfg: Optional[HermesConfig] = None,
                   specs: Optional[Dict[str, Any]] = None,
                   cluster: Optional[int] = None
                   ) -> Tuple[Dict[str, Any], Optional[Mesh]]:
    """Resize the Level-B Hermes state from ``n_pods`` to ``len(keep)``.

    ``state`` holds the pod-stacked trees (any of ``POD_STACKED_KEYS``;
    ``None`` entries pass through) plus optionally unstacked globals under
    other keys (moved as-is).  With a ``mesh``, every output is re-sharded
    onto the survivors' mesh (``shrink_mesh``) using the PartitionSpec
    pytrees in ``specs`` (absent keys replicate); ``mesh=None`` skips
    placement entirely (single-device / host use).  Refuses to shrink
    below ``cfg.min_live_pods``.

    On a two-tier (cluster, pod, ...) mesh the failure domain is
    cluster-local: pass ``cluster=c`` to assert every dropped pod lives
    in cluster ``c`` (``keep`` stays GLOBAL pod rows), and the mesh
    shrinks via ``launch.mesh.shrink_mesh(..., cluster=c)`` — only that
    cluster's rows move, every other cluster's devices stay put.  The
    result is a *flat* pod mesh (the cluster grid is no longer uniform);
    rounds run single-tier — or unplaced with explicit uneven
    ``cluster_sizes`` — until a grow rebalances the grid.

    An async ``pending`` buffer in ``state`` is flushed first under the
    survivor mask (:func:`flush_pending`): the dropped pods' in-flight
    pushes are masked out of the late merge — never applied posthumously
    — and the survivors' land before their rows migrate.  Returns
    ``(new_state, survivors_mesh)``.
    """
    cfg = cfg or HermesConfig()
    keep = list(keep)
    if len(keep) < cfg.min_live_pods:
        raise ValueError(
            f"shrinking to {len(keep)} pods violates min_live_pods="
            f"{cfg.min_live_pods}")
    if state.get("pending") is not None:
        n_pods = jax.tree.leaves(state["pod_params"])[0].shape[0]
        live = np.zeros((n_pods,), bool)
        live[np.asarray(keep, int)] = True
        state = flush_pending(state, cfg=cfg, live=live, mesh=mesh)
    if mesh is None:
        new_mesh = None
    elif cluster is not None and "cluster" in mesh.axis_names:
        n_c = mesh.devices.shape[list(mesh.axis_names).index("cluster")]
        ppc = mesh.devices.shape[list(mesh.axis_names).index("pod")]
        assert 0 <= cluster < n_c, (cluster, n_c)
        lo, hi = cluster * ppc, (cluster + 1) * ppc
        outside = [k for k in range(n_c * ppc)
                   if not lo <= k < hi and k not in keep]
        if outside:
            raise ValueError(
                f"cluster={cluster} shrink but pods {outside} outside "
                f"that cluster are also dropped; the failure domain "
                f"must stay cluster-local")
        local = sorted(k - lo for k in keep if lo <= k < hi)
        new_mesh = shrink_mesh(mesh, local, cluster=cluster)
    else:
        new_mesh = shrink_mesh(mesh, keep)
    out: Dict[str, Any] = {}
    for k, v in state.items():
        v = shrink_pod_tree(v, keep) if k in POD_STACKED_KEYS else v
        out[k] = _reshard(v, (specs or {}).get(k), new_mesh)
    return out, new_mesh


def grow_pod_tree(tree: Tree, new_row: Tree, n_new: int = 1) -> Tree:
    """Append ``n_new`` copies of an unstacked ``new_row`` pytree to every
    leaf's leading (n_pods,) axis — the inverse of ``shrink_pod_tree``.

    This is the whole join-side state migration: the newcomer's model
    replica is ``w_global`` (it starts exactly where a refreshing pod
    would), its GUP row is fresh (empty ring buffer — the gate cannot
    open until the loss queue warms, see
    ``dist.hermes_sync.hermes_grow_pod_state``), and its error-feedback
    residual is zero (it has dropped nothing yet).
    """
    if tree is None:
        return None
    return jax.tree.map(
        lambda x, r: jnp.concatenate(
            [x, jnp.broadcast_to(r[None], (n_new,) + x.shape[1:])
                .astype(x.dtype)], axis=0),
        tree, new_row)


def elastic_grow(state: Dict[str, Any], mesh: Optional[Mesh], *,
                 cfg: Optional[HermesConfig] = None,
                 specs: Optional[Dict[str, Any]] = None,
                 remaining_rounds: Optional[float] = None,
                 n_clusters: Optional[int] = None
                 ) -> Tuple[Dict[str, Any], Optional[Mesh]]:
    """Re-admit one pod: resize the Level-B Hermes state from ``n_pods``
    to ``n_pods + 1``, the inverse of ``elastic_shrink``.

    Every pod-stacked tree gains one appended row: ``pod_params`` seeded
    from ``state["w_global"]``, ``gup`` a fresh ring buffer
    (``hermes_grow_pod_state``), ``error`` exact zeros.  With a ``mesh``,
    outputs are re-sharded onto ``launch.mesh.grow_mesh``'s regrown
    (pod, data, model) mesh — the rejoining pod's own devices fill the new
    row, so no surviving buffer moves.  ``specs`` follows the
    ``elastic_shrink`` convention (PartitionSpec pytrees per key; absent
    keys replicate; ``mesh=None`` skips placement).

    ``remaining_rounds`` gates the whole thing through the re-admission
    policy (``core.allocator.should_readmit``): a rejoin pays a recompile
    + re-shard stall worth ``cfg.rejoin_cost_rounds`` rounds, so when too
    little work remains to amortize it the grow refuses — pass ``None``
    to bypass the policy (caller already decided).

    An async ``pending`` buffer is flushed first (:func:`flush_pending`,
    all incumbents live — they all dispatched it): its arrays are sized
    to the pre-grow pod count, and committing before the append keeps the
    newcomer out of a merge it never dispatched into.

    ``n_clusters`` restores the two-tier grid after a cluster-local
    shrink: the regrown mesh (which appends the newcomer's devices at
    the END, i.e. the last row of the last cluster) is regrouped to a
    (cluster, pod, ...) mesh when the new pod count divides evenly —
    the round trip shrink(last cluster) -> grow(n_clusters=C) is exact
    (``launch.mesh.grow_mesh``).  Returns ``(new_state, regrown_mesh)``.
    """
    cfg = cfg or HermesConfig()
    if state.get("pending") is not None:
        state = flush_pending(state, cfg=cfg, mesh=mesh)
    w_global = state["w_global"]
    n_pods = jax.tree.leaves(state["pod_params"])[0].shape[0]
    if remaining_rounds is not None and not should_readmit(
            remaining_rounds, n_pods, cfg):
        raise ValueError(
            f"re-admission denied: expected gain "
            f"{rejoin_gain_rounds(n_pods, remaining_rounds):.2f} rounds "
            f"does not amortize rejoin_cost_rounds={cfg.rejoin_cost_rounds}")
    new_mesh = (grow_mesh(mesh, 1, n_clusters=n_clusters)
                if mesh is not None else None)

    # the newcomer's row per pod-stacked key; a key added to
    # POD_STACKED_KEYS without a seeding rule here must fail loudly, not
    # pass through with a mismatched row count
    new_row = {
        "pod_params": lambda: w_global,
        "gup": None,  # handled by hermes_grow_pod_state (fresh state)
        "error": lambda: jax.tree.map(jnp.zeros_like, w_global),
    }
    out: Dict[str, Any] = {}
    for k, v in state.items():
        if v is not None and k in POD_STACKED_KEYS:
            v = (hermes_grow_pod_state(v, cfg) if k == "gup"
                 else grow_pod_tree(v, new_row[k]()))
        out[k] = _reshard(v, (specs or {}).get(k), new_mesh)
    return out, new_mesh


def rejoin_allocations(times: Dict[str, float],
                       allocs: Dict[str, Allocation],
                       newcomer: str, cfg: HermesConfig, *,
                       n_train: int,
                       mem_limit_dss: Optional[Dict[str, int]] = None
                       ) -> Dict[str, Allocation]:
    """Re-split the data shards after a membership *grow*.

    The newcomer has no fresh iteration-time observation (it just came
    back), so it enters the allocator's sweep seeded at the **median**
    observed time — the cluster's own definition of "typical" — with a
    median-sized starting allocation.  One ``reallocate`` round then
    re-sizes any member the IQR sweep flags against the new, larger
    membership.  Returns a full allocation map covering everyone.
    """
    assert times, "rejoin with no surviving observations"
    med_t = float(np.median(list(times.values())))
    med_dss = int(np.median([a.dss for a in allocs.values()]))
    med_mbs = int(np.median([a.mbs for a in allocs.values()]))
    times = {**times, newcomer: med_t}
    allocs = {**allocs, newcomer: Allocation(med_dss, med_mbs)}
    dss_hi = max(64, n_train // max(1, len(times)))
    new = reallocate(times, allocs, cfg, dss_domain=(32, dss_hi),
                     mem_limit_dss=dict(mem_limit_dss or {}))
    return {**allocs, **new}


def survivor_allocations(times: Dict[str, float],
                         allocs: Dict[str, Allocation],
                         dead: Sequence[str], cfg: HermesConfig, *,
                         n_train: int,
                         mem_limit_dss: Optional[Dict[str, int]] = None
                         ) -> Dict[str, Allocation]:
    """Re-split the data shards for the survivors of a membership change.

    Dead members are dropped from the observation set *before* the IQR
    sweep (a stale entry would otherwise keep skewing the fences and keep
    billing transfers to a node that will never run again — the Level-A
    bug this PR fixes), then ``core.allocator.reallocate`` re-sizes the
    survivors toward the new cluster median.  Returns a full allocation
    map covering every survivor (resized or carried over) and no dead one.
    """
    dead_set = set(dead)
    live_times = {k: v for k, v in times.items() if k not in dead_set}
    live_allocs = {k: v for k, v in allocs.items() if k not in dead_set}
    dss_hi = max(64, n_train // max(1, len(live_times)))
    new = reallocate(live_times, live_allocs, cfg,
                     dss_domain=(32, dss_hi),
                     mem_limit_dss={k: v for k, v in
                                    (mem_limit_dss or {}).items()
                                    if k not in dead_set})
    return {**live_allocs, **new}


# ---------------------------------------------------------------------------
# Drop-pod equivalence harness (shared with launch/hermes_dryrun.py)
# ---------------------------------------------------------------------------

def _toy_pod_state(n_pods: int, cfg: HermesConfig, seed: int = 0
                   ) -> Tuple[Tree, Tree, Tree]:
    """Per-pod-distinct toy replicas: one blocked leaf, one padded leaf."""
    k1, k2, kg = jax.random.split(jax.random.PRNGKey(seed), 3)
    pod_params = {
        "w": jax.random.normal(k1, (n_pods, 4, 512), jnp.float32),
        "b": jax.random.normal(k2, (n_pods, 7), jnp.float32),
    }
    w_global = {"w": jax.random.normal(kg, (4, 512), jnp.float32),
                "b": jnp.zeros((7,), jnp.float32)}
    return pod_params, w_global, hermes_pod_state(cfg, n_pods)


def _demo_losses(n_pods: int, r: int) -> np.ndarray:
    """Deterministic per-pod loss schedule with sharp per-pod drops so the
    z-score gates open on different rounds for different pods."""
    base = 1.0 + 0.05 * np.cos(np.arange(n_pods) + r)
    drop = (np.arange(n_pods) + 3 == r % 7).astype(np.float64) * 0.8
    return (base - drop).astype(np.float32)


def drop_pod_equivalence(*, n_pods: int = 2, drop: int = 1,
                         rounds_before: int = 4, rounds_after: int = 4,
                         mesh: Optional[Mesh] = None,
                         cfg: Optional[HermesConfig] = None,
                         seed: int = 0) -> Dict[str, Any]:
    """Kill pod ``drop`` mid-run; prove the survivors never notice.

    Path A (what production does): run ``rounds_before`` full-membership
    rounds, poison the dead pod with NaNs, run one masked round
    (``live[drop] = False``), ``elastic_shrink`` to the survivors' mesh,
    then ``rounds_after`` rounds at the reduced pod count.

    Path B (the oracle): shrink *at the moment of death* and run the same
    rounds at the smaller size from the start.

    Every surviving tensor — pod_params, w_global, GUP ring buffers, and
    the error-feedback residual — must match **bit-identically** between
    the two paths, which is exactly the claim that a masked round zeroes
    the dead pod out of gates, wire payloads, and merge weights.

    ``mesh=None`` auto-builds a (pod, data, model) mesh when enough
    devices exist, else runs unplaced on the default device (the math is
    placement-independent; tier-1 exercises this path on one CPU device).
    """
    cfg = cfg or HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                              compression="int8")
    assert 0 <= drop < n_pods and n_pods >= 2
    keep = [i for i in range(n_pods) if i != drop]
    if mesh is None and jax.device_count() >= n_pods:
        mesh = make_pod_mesh(n_pods)
    pod_spec = PS("pod")

    def put(tree, m, spec):
        if m is None:
            return tree
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(m, spec)), tree)

    def pod_specs(tree):
        return jax.tree.map(lambda _: pod_spec, tree)

    def rounds(pods, gup, err, wg, n, start, *, live=None, m=None):
        # placement rides on the committed inputs; `m` is the CURRENT
        # (possibly resized) mesh of those inputs, threaded into
        # hermes_round so the merge ships encoded payloads explicitly
        # across its pod axis (m=None: unplaced oracle math, identical
        # bits — dist.wire.gather_payloads is a value-preserving ship)
        step = jax.jit(
            lambda p, g, e, w, losses, lv: hermes_round(
                p, g, losses, w, jnp.float32(1.0), cfg, live=lv, error=e,
                mesh=m))
        np_ = jax.tree.leaves(pods)[0].shape[0]
        lv = (np.ones((np_,), bool) if live is None
              else np.asarray(live, bool))
        for r in range(start, start + n):
            full = _demo_losses(n_pods, r)
            losses = full if np_ == n_pods else full[np.asarray(keep)]
            losses = np.where(lv, losses, np.nan)  # dead pods go dark
            out = step(pods, gup, err, wg, jnp.asarray(losses),
                       jnp.asarray(lv))
            pods, gup, err, wg = (out["pod_params"], out["gup"],
                                  out["error"], out["w_global"])
        return pods, gup, err, wg

    # common prefix: full membership
    pods0, wg0, gup0 = _toy_pod_state(n_pods, cfg, seed)
    pods = put(pods0, mesh, pod_spec)
    gup = put(gup0, mesh, pod_spec)
    wg = put(wg0, mesh, PS())
    pods, gup, err, wg = rounds(pods, gup, None, wg, rounds_before, 0,
                                m=mesh)
    snap = {"pods": jax.tree.map(np.asarray, pods),
            "gup": jax.tree.map(np.asarray, gup),
            "err": jax.tree.map(np.asarray, err),
            "wg": jax.tree.map(np.asarray, wg)}

    # path A: pod `drop` dies (NaN replica), one masked round, then shrink
    live = np.ones((n_pods,), bool)
    live[drop] = False
    dead_pods = jax.tree.map(lambda x: x.at[drop].set(jnp.nan), pods)
    a_pods, a_gup, a_err, a_wg = rounds(
        dead_pods, gup, err, wg, 1, rounds_before, live=live, m=mesh)
    a_state, a_mesh = elastic_shrink(
        {"pod_params": a_pods, "gup": a_gup, "error": a_err,
         "w_global": a_wg},
        keep, mesh, cfg=cfg,
        specs={"pod_params": pod_specs(a_pods), "gup": pod_specs(a_gup),
               "error": pod_specs(a_err)})
    a_pods, a_gup, a_err, a_wg = rounds(
        a_state["pod_params"], a_state["gup"], a_state["error"],
        a_state["w_global"], rounds_after, rounds_before + 1, m=a_mesh)

    # path B: shrink at the moment of death, replay the same rounds small
    b_state, b_mesh = elastic_shrink(
        {"pod_params": jax.tree.map(jnp.asarray, snap["pods"]),
         "gup": jax.tree.map(jnp.asarray, snap["gup"]),
         "error": jax.tree.map(jnp.asarray, snap["err"]),
         "w_global": jax.tree.map(jnp.asarray, snap["wg"])},
        keep, mesh, cfg=cfg,
        specs={"pod_params": pod_specs(snap["pods"]),
               "gup": pod_specs(snap["gup"]),
               "error": pod_specs(snap["err"])})
    b_pods, b_gup, b_err, b_wg = rounds(
        b_state["pod_params"], b_state["gup"], b_state["error"],
        b_state["w_global"], 1 + rounds_after, rounds_before, m=b_mesh)

    def check(name, a, b):
        for x, y in zip(jax.tree.leaves(jax.tree.map(np.asarray, a)),
                        jax.tree.leaves(jax.tree.map(np.asarray, b))):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{name}: surviving state diverged after "
                              f"the pod drop")

    check("pod_params", a_pods, b_pods)
    check("gup", a_gup, b_gup)
    check("error", a_err, b_err)
    check("w_global", a_wg, b_wg)
    return {
        "n_pods": n_pods, "dropped": drop, "survivors": keep,
        "mesh": list(mesh.devices.shape) if mesh is not None else None,
        "survivor_mesh": (list(a_mesh.devices.shape)
                          if a_mesh is not None else None),
        "rounds": rounds_before + 1 + rounds_after,
        "compression": cfg.compression,
        "bit_identical": True,
    }


def rejoin_pod_equivalence(*, n_pods: int = 2, rounds_before: int = 3,
                           rounds_shrunk: int = 3, rounds_after: int = 4,
                           mesh: Optional[Mesh] = None,
                           cfg: Optional[HermesConfig] = None,
                           seed: int = 0) -> Dict[str, Any]:
    """Kill the last pod mid-run, shrink, then re-admit a pod; prove the
    incumbents never notice either resize.

    Path A (what production does): ``rounds_before`` full-membership
    rounds, poison the last pod with NaNs, one masked round
    (``live[-1] = False``), ``elastic_shrink`` to the survivors' mesh,
    ``rounds_shrunk`` rounds at ``n_pods - 1``, then ``elastic_grow`` —
    append a fresh row (pod_params = ``w_global``, empty GUP queue, zero
    error residual) on the regrown mesh, gated by the re-admission policy
    — and ``rounds_after`` rounds back at ``n_pods``.

    Path B (the oracle — *never resized*): identical rounds on a state
    that keeps all ``n_pods`` rows throughout: the dead stretch runs
    live-masked, and at the rejoin boundary the dead row is re-seeded in
    place with exactly the newcomer's state.  Every tensor — pod_params,
    w_global, GUP ring buffers, error residuals — must match
    **bit-identically**, which combines the PR-3 shrink invariant (masked
    == reduced) with the grow half: a newcomer seeded at ``w_global``
    whose empty loss queue keeps its gate shut is indistinguishable from
    never having left.

    Path C (the survivors-must-not-move check): the shrunk run simply
    continues at ``n_pods - 1`` with no grow.  For the first
    ``min(2, rounds_after)`` post-join rounds the newcomer's gate
    *provably* cannot open (fewer than two losses in its queue), so the
    incumbents' state in path A must be bit-identical to path C's — the
    join must not move the survivors' trajectories.  This cross-pod-count
    check runs only unsharded (``mesh=None``): two differently-shaped
    lowered programs may reassociate the fp32 merge reduction, so under a
    mesh the matched-shape path-B oracle carries the proof.

    The dropped pod is the last row so path A's appended row occupies the
    same index as path B's re-seeded one: fp32 merge accumulation order
    is identical, and "bit-identical" means exactly that.
    """
    cfg = cfg or HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                              compression="int8", rejoin_cost_rounds=0.5)
    assert n_pods >= 2
    drop = n_pods - 1
    keep = list(range(n_pods - 1))
    if mesh is None and jax.device_count() >= n_pods:
        mesh = make_pod_mesh(n_pods)
    pod_spec = PS("pod")

    def put(tree, m, spec):
        if m is None:
            return tree
        return jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(m, spec)), tree)

    def pod_specs(tree):
        return jax.tree.map(lambda _: pod_spec, tree)

    def rounds(pods, gup, err, wg, n, start, *, live=None, m=None):
        # rows 0..k-1 always map to pods 0..k-1 (the resized pod is last),
        # so the demo loss schedule stays aligned across every membership;
        # `m` is the current mesh of the inputs (see drop_pod_equivalence)
        step = jax.jit(
            lambda p, g, e, w, losses, lv: hermes_round(
                p, g, losses, w, jnp.float32(1.0), cfg, live=lv, error=e,
                mesh=m))
        np_ = jax.tree.leaves(pods)[0].shape[0]
        lv = (np.ones((np_,), bool) if live is None
              else np.asarray(live, bool))
        for r in range(start, start + n):
            losses = _demo_losses(n_pods, r)[:np_]
            losses = np.where(lv, losses, np.nan)  # dead pods go dark
            out = step(pods, gup, err, wg, jnp.asarray(losses),
                       jnp.asarray(lv))
            pods, gup, err, wg = (out["pod_params"], out["gup"],
                                  out["error"], out["w_global"])
        return pods, gup, err, wg

    # common prefix: full membership, then the masked death round
    pods0, wg0, gup0 = _toy_pod_state(n_pods, cfg, seed)
    pods = put(pods0, mesh, pod_spec)
    gup = put(gup0, mesh, pod_spec)
    wg = put(wg0, mesh, PS())
    pods, gup, err, wg = rounds(pods, gup, None, wg, rounds_before, 0,
                                m=mesh)
    live = np.ones((n_pods,), bool)
    live[drop] = False
    pods = jax.tree.map(lambda x: x.at[drop].set(jnp.nan), pods)
    pods, gup, err, wg = rounds(pods, gup, err, wg, 1, rounds_before,
                                live=live, m=mesh)
    snap = {k: jax.tree.map(np.asarray, v)
            for k, v in (("pods", pods), ("gup", gup), ("err", err),
                         ("wg", wg))}

    # path A: shrink -> shrunk rounds -> grow (policy-gated) -> rounds
    a_state, a_mesh = elastic_shrink(
        {"pod_params": pods, "gup": gup, "error": err, "w_global": wg},
        keep, mesh, cfg=cfg,
        specs={"pod_params": pod_specs(pods), "gup": pod_specs(gup),
               "error": pod_specs(err)})
    a_pods, a_gup, a_err, a_wg = rounds(
        a_state["pod_params"], a_state["gup"], a_state["error"],
        a_state["w_global"], rounds_shrunk, rounds_before + 1, m=a_mesh)
    gain = rejoin_gain_rounds(n_pods - 1, float(rounds_after))
    g_state, g_mesh = elastic_grow(
        {"pod_params": a_pods, "gup": a_gup, "error": a_err,
         "w_global": a_wg},
        a_mesh, cfg=cfg, remaining_rounds=float(rounds_after),
        specs={"pod_params": pod_specs(a_pods), "gup": pod_specs(a_gup),
               "error": pod_specs(a_err)})
    warm = min(2, rounds_after)
    start_after = rounds_before + 1 + rounds_shrunk
    a_pods, a_gup, a_err, a_wg = rounds(
        g_state["pod_params"], g_state["gup"], g_state["error"],
        g_state["w_global"], warm, start_after, m=g_mesh)
    a_warm = {"pods": jax.tree.map(np.asarray, a_pods),
              "wg": jax.tree.map(np.asarray, a_wg)}
    a_pods, a_gup, a_err, a_wg = rounds(
        a_pods, a_gup, a_err, a_wg, rounds_after - warm,
        start_after + warm, m=g_mesh)

    # path B: never resize — masked rounds, then re-seed the row in place
    # (replayed on the original full mesh so both paths run identically
    # sharded programs: fp32 reduction grouping is part of "bit-identical")
    b_pods = put(jax.tree.map(jnp.asarray, snap["pods"]), mesh, pod_spec)
    b_gup = put(jax.tree.map(jnp.asarray, snap["gup"]), mesh, pod_spec)
    b_err = put(jax.tree.map(jnp.asarray, snap["err"]), mesh, pod_spec)
    b_wg = put(jax.tree.map(jnp.asarray, snap["wg"]), mesh, PS())
    b_pods, b_gup, b_err, b_wg = rounds(
        b_pods, b_gup, b_err, b_wg, rounds_shrunk, rounds_before + 1,
        live=live, m=mesh)
    fresh = gup_state_jax(cfg)
    b_pods = jax.tree.map(
        lambda x, g: x.at[drop].set(g.astype(x.dtype)), b_pods, b_wg)
    b_gup = jax.tree.map(
        lambda x, f: x.at[drop].set(f.astype(x.dtype)), b_gup, fresh)
    b_err = jax.tree.map(lambda x: x.at[drop].set(0.0), b_err)
    b_pods, b_gup, b_err, b_wg = rounds(
        b_pods, b_gup, b_err, b_wg, rounds_after, start_after, m=mesh)

    # path C: no grow — the incumbents' oracle for the warm-up rounds
    # (only consulted unsharded; see the warmup_checked note below)
    if mesh is None:
        c_pods, c_gup, c_err, c_wg = rounds(
            a_state["pod_params"], a_state["gup"], a_state["error"],
            a_state["w_global"], rounds_shrunk + warm, rounds_before + 1)

    def check(name, a, b):
        for x, y in zip(jax.tree.leaves(jax.tree.map(np.asarray, a)),
                        jax.tree.leaves(jax.tree.map(np.asarray, b))):
            np.testing.assert_array_equal(
                x, y, err_msg=f"{name}: state diverged across the "
                              f"shrink->grow round trip")

    check("pod_params", a_pods, b_pods)
    check("gup", a_gup, b_gup)
    check("error", a_err, b_err)
    check("w_global", a_wg, b_wg)
    # The join never moved the incumbents (newcomer gate shut while warm):
    # exact only unsharded — two *different-shape* lowered programs (an
    # n-row merge with a zero-weight row vs the (n-1)-row merge) may
    # reassociate the fp32 reduction differently under a mesh, so on
    # sharded runs the matched-shape oracle (path B) carries the proof.
    warmup_checked = mesh is None
    if warmup_checked:
        check("warmup w_global", a_warm["wg"], c_wg)
        check("warmup survivors",
              {k: v[:n_pods - 1] for k, v in a_warm["pods"].items()},
              c_pods)
    return {
        "n_pods": n_pods, "rejoined": drop, "incumbents": keep,
        "mesh": list(mesh.devices.shape) if mesh is not None else None,
        "shrunk_mesh": (list(a_mesh.devices.shape)
                        if a_mesh is not None else None),
        "regrown_mesh": (list(g_mesh.devices.shape)
                         if g_mesh is not None else None),
        "rounds": rounds_before + 1 + rounds_shrunk + rounds_after,
        "compression": cfg.compression,
        "readmission": {"admitted": True, "gain_rounds": gain,
                        "rejoin_cost_rounds": cfg.rejoin_cost_rounds},
        "bit_identical": True,
        "warmup_checked": warmup_checked,
    }


def cluster_resize_cycle_equivalence(*, n_pods: int = 4, n_clusters: int = 2,
                                     cycles: int = 3, rounds_full: int = 2,
                                     rounds_shrunk: int = 2,
                                     cfg: Optional[HermesConfig] = None,
                                     seed: int = 0) -> Dict[str, Any]:
    """Repeated cluster-local shrink->grow->shrink cycles leave no scar.

    The two-tier analogue of ``rejoin_pod_equivalence``, iterated: in
    every cycle the LAST pod of the LAST cluster dies (one masked
    two-tier round), the state shrinks (``elastic_shrink``), runs
    ``rounds_shrunk`` rounds with the degraded uneven cluster split
    (``cluster_sizes=[ppc, ..., ppc-1]``), grows back
    (``elastic_grow``) and resumes the balanced ``n_clusters`` grid —
    at least three full cycles, so a scar left by cycle k (a stale GUP
    row, a mis-seeded residual, an off-by-one cluster index) compounds
    and must surface by cycle k+1.

    Path B, the oracle, never resizes: it runs every round at ``n_pods``
    rows with the dead stretch live-masked, and re-seeds the dead row in
    place at each grow boundary (pod_params = ``w_global``, fresh GUP
    queue, zero error) — exactly the newcomer ``elastic_grow`` appends.
    Every tensor must match **bit-identically** across all cycles, which
    is the per-cluster membership claim of DESIGN.md §10: a masked
    member costs its cluster an exact ``+0.0`` partial term, so the
    degraded uneven split and the masked balanced split ship the same
    cluster payloads.

    Runs unplaced (the uneven ``cluster_sizes`` stretch is host-side by
    design; ``launch/hermes_dryrun.py --clusters`` carries the placed
    per-cluster shrink proof).
    """
    cfg = cfg or HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                              compression="int8", min_live_pods=1,
                              rejoin_cost_rounds=0.0,
                              n_clusters=n_clusters)
    assert n_pods % n_clusters == 0 and n_pods // n_clusters >= 1
    assert cycles >= 3, "fewer cycles cannot catch compounding scars"
    ppc = n_pods // n_clusters
    drop = n_pods - 1          # last pod of the last cluster
    keep = list(range(n_pods - 1))
    sizes_shrunk = [ppc] * (n_clusters - 1) + [ppc - 1]
    if sizes_shrunk[-1] == 0:
        sizes_shrunk = sizes_shrunk[:-1]

    def rounds(pods, gup, err, wg, n, start, *, live=None, sizes=None):
        step = jax.jit(
            lambda p, g, e, w, losses, lv: hermes_cluster_round(
                p, g, losses, w, jnp.float32(1.0), cfg, live=lv, error=e,
                n_clusters=(None if sizes is not None else n_clusters),
                cluster_sizes=sizes),
            static_argnames=())
        np_ = jax.tree.leaves(pods)[0].shape[0]
        lv = (np.ones((np_,), bool) if live is None
              else np.asarray(live, bool))
        for r in range(start, start + n):
            losses = _demo_losses(n_pods, r)[:np_]
            losses = np.where(lv, losses, np.nan)
            out = step(pods, gup, err, wg, jnp.asarray(losses),
                       jnp.asarray(lv))
            pods, gup, err, wg = (out["pod_params"], out["gup"],
                                  out["error"], out["w_global"])
        return pods, gup, err, wg

    pods0, wg0, gup0 = _toy_pod_state(n_pods, cfg, seed)
    a = {"pods": pods0, "gup": gup0, "err": None, "wg": wg0}
    b = {k: v for k, v in a.items()}
    live_mask = np.ones((n_pods,), bool)
    live_mask[drop] = False
    fresh = gup_state_jax(cfg)
    r0 = 0
    for cyc in range(cycles):
        # full-membership balanced rounds
        a["pods"], a["gup"], a["err"], a["wg"] = rounds(
            a["pods"], a["gup"], a["err"], a["wg"], rounds_full, r0)
        b["pods"], b["gup"], b["err"], b["wg"] = rounds(
            b["pods"], b["gup"], b["err"], b["wg"], rounds_full, r0)
        r0 += rounds_full
        # death: poison + one masked balanced round, both paths
        for s in (a, b):
            s["pods"] = jax.tree.map(lambda x: x.at[drop].set(jnp.nan),
                                     s["pods"])
            s["pods"], s["gup"], s["err"], s["wg"] = rounds(
                s["pods"], s["gup"], s["err"], s["wg"], 1, r0,
                live=live_mask)
        r0 += 1
        # path A shrinks to the uneven split; path B stays masked
        st, _ = elastic_shrink(
            {"pod_params": a["pods"], "gup": a["gup"], "error": a["err"],
             "w_global": a["wg"]}, keep, None, cfg=cfg)
        a = {"pods": st["pod_params"], "gup": st["gup"],
             "err": st["error"], "wg": st["w_global"]}
        a["pods"], a["gup"], a["err"], a["wg"] = rounds(
            a["pods"], a["gup"], a["err"], a["wg"], rounds_shrunk, r0,
            sizes=sizes_shrunk)
        b["pods"], b["gup"], b["err"], b["wg"] = rounds(
            b["pods"], b["gup"], b["err"], b["wg"], rounds_shrunk, r0,
            live=live_mask)
        r0 += rounds_shrunk
        # grow back to the balanced grid; oracle re-seeds the row in place
        st, _ = elastic_grow(
            {"pod_params": a["pods"], "gup": a["gup"], "error": a["err"],
             "w_global": a["wg"]}, None, cfg=cfg)
        a = {"pods": st["pod_params"], "gup": st["gup"],
             "err": st["error"], "wg": st["w_global"]}
        b["pods"] = jax.tree.map(
            lambda x, g: x.at[drop].set(g.astype(x.dtype)),
            b["pods"], b["wg"])
        b["gup"] = jax.tree.map(
            lambda x, f: x.at[drop].set(f.astype(x.dtype)),
            b["gup"], fresh)
        b["err"] = jax.tree.map(lambda x: x.at[drop].set(0.0), b["err"])
        for name in ("pods", "gup", "err", "wg"):
            for x, y in zip(jax.tree.leaves(jax.tree.map(np.asarray,
                                                         a[name])),
                            jax.tree.leaves(jax.tree.map(np.asarray,
                                                         b[name]))):
                np.testing.assert_array_equal(
                    x, y, err_msg=f"cycle {cyc}, {name}: resize cycle "
                                  f"left a scar vs the never-resized "
                                  f"oracle")
    return {
        "n_pods": n_pods, "n_clusters": n_clusters, "cycles": cycles,
        "rounds": r0, "compression": cfg.compression,
        "shrunk_cluster_sizes": sizes_shrunk,
        "bit_identical": True,
    }


def run_hermes_cluster_resize_demo(n_pods: int = 4, n_clusters: int = 2,
                                   seed: int = 0) -> Dict[str, Any]:
    """Three shrink->grow->shrink cycles on the two-tier round, checked
    bit-exactly against the never-resized masked oracle per cycle."""
    return cluster_resize_cycle_equivalence(
        n_pods=n_pods, n_clusters=n_clusters, cycles=3, seed=seed)


def run_hermes_rejoin_demo(n_pods: int = 4, seed: int = 0) -> Dict[str, Any]:
    """The in-flight pod-join demo: shrink->grow equivalence, policy
    decisions, and the newcomer's data re-split."""
    cfg = HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                       compression="int8", min_live_pods=1,
                       rejoin_cost_rounds=0.5)
    n_pods = max(2, min(n_pods, jax.device_count()))
    out = rejoin_pod_equivalence(n_pods=n_pods, cfg=cfg, seed=seed)
    # the allocator folds the newcomer in at the median observed time
    times = {f"pod{i}": 1.0 + 0.4 * i for i in range(n_pods - 1)}
    allocs = {f"pod{i}": Allocation(256, 16) for i in range(n_pods - 1)}
    new = rejoin_allocations(times, allocs, f"pod{n_pods - 1}", cfg,
                             n_train=4096)
    assert f"pod{n_pods - 1}" in new
    out["realloc"] = {k: {"dss": a.dss, "mbs": a.mbs}
                      for k, a in sorted(new.items())}
    # the policy half: plenty of work left -> admit; nearly done -> deny
    out["policy"] = {
        "admit_100_rounds_left": should_readmit(100.0, n_pods - 1, cfg),
        "deny_0p5_rounds_left": not should_readmit(0.5, n_pods - 1, cfg),
    }
    assert out["policy"]["admit_100_rounds_left"]
    assert out["policy"]["deny_0p5_rounds_left"]
    return out


def run_hermes_shrink_demo(n_pods: int = 4, drop: int = 1,
                           seed: int = 0) -> Dict[str, Any]:
    """The in-flight pod-shrink demo: drop-pod equivalence + data re-split."""
    cfg = HermesConfig(alpha=-0.5, beta=0.1, lam=2, window=4,
                       compression="int8", min_live_pods=1)
    n_pods = max(2, min(n_pods, jax.device_count()))
    drop = min(drop, n_pods - 1)
    out = drop_pod_equivalence(n_pods=n_pods, drop=drop, cfg=cfg, seed=seed)
    # the allocator re-splits the surviving members' data shards
    times = {f"pod{i}": 1.0 + 0.4 * i for i in range(n_pods)}
    allocs = {f"pod{i}": Allocation(256, 16) for i in range(n_pods)}
    new = survivor_allocations(times, allocs, [f"pod{drop}"], cfg,
                               n_train=4096)
    assert f"pod{drop}" not in new
    out["realloc"] = {k: {"dss": a.dss, "mbs": a.mbs}
                      for k, a in sorted(new.items())}
    return out


# ---------------------------------------------------------------------------
# Checkpoint-restart demo (the original coarse path)
# ---------------------------------------------------------------------------

def run_demo(arch: str = "qwen3-8b", steps_before: int = 5,
             steps_after: int = 5, seed: int = 0) -> dict:
    cfg = get_smoke_config(arch)
    parallel = ParallelConfig()
    opt = OptimizerConfig(name="adamw", lr=1e-3)
    ndev = jax.device_count()
    assert ndev >= 4, "need >=4 devices (set REPRO_ELASTIC_DEVICES=8)"
    batch = 16

    def make(mesh_shape):
        mesh = jax.make_mesh(mesh_shape, ("data", "model"))
        rules = arch_rules(cfg, mesh, parallel, batch=batch)
        shape = ShapeConfig("t", 32, batch, "train")
        setup = build_setup("train", cfg, shape, rules, parallel, opt,
                            impl="auto")
        step = jax.jit(setup.step_fn, in_shardings=setup.in_shardings,
                       out_shardings=setup.out_shardings)
        return mesh, rules, setup, step

    def batch_for(rng):
        t = rng.integers(0, cfg.vocab_size, (batch, 32))
        return {"tokens": jnp.asarray(t, jnp.int32),
                "targets": jnp.asarray(t, jnp.int32)}

    rng = np.random.default_rng(seed)
    out = {}
    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir, async_write=False)

        # phase 1: full mesh
        mesh, rules, setup, step = make((ndev // 4, 4))
        with mesh:
            state = jax.jit(setup.meta["init_state"],
                            out_shardings=setup.state_sharding)(
                                jax.random.PRNGKey(seed))
            losses = []
            for _ in range(steps_before):
                state, loss = step(state, batch_for(rng))
                losses.append(float(loss))
        ck.save(state, steps_before)
        out["phase1_losses"] = losses
        out["phase1_mesh"] = list(mesh.devices.shape)

        # phase 2: "half the nodes died" -> smaller mesh, re-shard state
        mesh2, rules2, setup2, step2 = make((max(1, ndev // 8), 4))
        with mesh2:
            template = jax.eval_shape(setup2.meta["init_state"],
                                      jax.random.PRNGKey(seed))
            restored, at_step = ck.restore(
                template, shardings=setup2.state_sharding)
            losses2 = []
            for _ in range(steps_after):
                restored, loss = step2(restored, batch_for(rng))
                losses2.append(float(loss))
        out["phase2_losses"] = losses2
        out["phase2_mesh"] = list(mesh2.devices.shape)
        out["resumed_from_step"] = at_step

        # allocator re-balances per-node work for the smaller cluster
        a = dual_binary_search(k=0.02, t_target=1.0,
                               dss_domain=(32, 4096))
        out["realloc"] = {"dss": a.dss, "mbs": a.mbs}
        out["loss_continuous"] = losses2[0] < losses[0]
    return out


if __name__ == "__main__":
    print(json.dumps({"hermes_shrink": run_hermes_shrink_demo(),
                      "hermes_rejoin": run_hermes_rejoin_demo(),
                      "hermes_cluster_resize": run_hermes_cluster_resize_demo(),
                      "checkpoint_restart": run_demo()}, indent=2))
