"""Production meshes + per-architecture axis rules.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state.  Axis rules are derived
per architecture: a logical axis maps to the "model" mesh axis only when the
corresponding dimension is divisible by the axis size (e.g. 56 query heads
do not 16-way shard -> head sharding disabled for llava, the flat projection
output is sharded instead and GSPMD falls back to an all-gather at the
reshape; see DESIGN.md and the §Perf head-padding hillclimb).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from repro.config import ModelConfig, ParallelConfig
from repro.dist.sharding import AxisRules, make_rules


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def pod_mesh_shape(ndev: int, n_pods: int) -> Tuple[int, int, int]:
    """Largest square-ish (pods, data, model) shape for ``ndev`` devices.

    Per pod, the model axis is the largest power of two whose square fits
    the per-pod device count; at 512 devices and 2 pods this is exactly the
    (2, 16, 16) production mesh.  Raises when fewer than one device per pod
    is available.
    """
    per_pod = ndev // n_pods
    assert per_pod >= 1, f"{ndev} devices cannot host {n_pods} pods"
    model = 1
    while (model * 2) ** 2 <= per_pod:
        model *= 2
    return (n_pods, per_pod // model, model)


def make_pod_mesh(n_pods: int, *, n_clusters: int = 1,
                  max_devices: int = 0) -> Mesh:
    """A (pod, data, model) mesh — or, with ``n_clusters > 1``, the
    two-tier (cluster, pod, data, model) mesh — over the first available
    devices.

    Unlike ``jax.make_mesh`` this takes a device *subset*, so an elastic
    run can stand up a smaller mesh than the full fleet (the survivors of
    a pod loss).  ``max_devices`` caps the device count (0 = all).

    ``n_pods`` is always the TOTAL pod count; with clusters it must split
    evenly (``n_pods % n_clusters == 0``) and the leading "cluster" axis
    is the slow tier (DESIGN.md §10): devices are laid out cluster-major,
    so cluster ``c`` owns the contiguous id block
    ``[c*ndev/C, (c+1)*ndev/C)`` — which is what lets the analysis tier
    classifier split pod-crossing from cluster-crossing collectives by
    device-id divisor alone.
    """
    devs = jax.devices()
    if max_devices:
        devs = devs[:max_devices]
    if len(devs) < n_pods:
        raise ValueError(f"{len(devs)} devices cannot host {n_pods} pods "
                         f"(one device per pod at least)")
    if n_clusters <= 1:
        shape = pod_mesh_shape(len(devs), n_pods)
        n = shape[0] * shape[1] * shape[2]
        return Mesh(np.asarray(devs[:n], dtype=object).reshape(shape),
                    ("pod", "data", "model"))
    assert n_pods % n_clusters == 0, (
        f"{n_pods} pods do not split into {n_clusters} equal clusters")
    shape = cluster_mesh_shape(len(devs), n_clusters, n_pods // n_clusters)
    n = int(np.prod(shape))
    return Mesh(np.asarray(devs[:n], dtype=object).reshape(shape),
                ("cluster", "pod", "data", "model"))


def cluster_mesh_shape(ndev: int, n_clusters: int,
                       pods_per_cluster: int) -> Tuple[int, int, int, int]:
    """(cluster, pod, data, model) shape: the device fleet splits evenly
    into ``n_clusters`` contiguous blocks, each hosting its own
    ``pod_mesh_shape`` grid.  8 devices, 2 clusters, 2 pods/cluster ->
    (2, 2, 2, 1)."""
    per_cluster = ndev // n_clusters
    assert per_cluster >= pods_per_cluster >= 1, (
        f"{ndev} devices cannot host {n_clusters} clusters of "
        f"{pods_per_cluster} pods")
    return (n_clusters,) + pod_mesh_shape(per_cluster, pods_per_cluster)


def flatten_cluster_mesh(mesh: Mesh) -> Mesh:
    """Merge the (cluster, pod) tiers into one flat "pod" axis.

    Devices are kept verbatim in cluster-major order — flat pod row
    ``c * pods_per_cluster + p`` is exactly cluster ``c``'s pod ``p`` —
    so no buffer moves and the flat round's row order matches the
    two-tier round's ``(C, ppc)`` reshape.  A mesh already flat passes
    through unchanged.
    """
    if mesh.axis_names[0] != "cluster":
        return mesh
    d = mesh.devices
    return Mesh(d.reshape((d.shape[0] * d.shape[1],) + d.shape[2:]),
                mesh.axis_names[1:])


def regroup_mesh(mesh: Mesh, n_clusters: int) -> Mesh:
    """Inverse of :func:`flatten_cluster_mesh`: reshape a flat
    (pod, data, model) mesh into (cluster, pod, data, model).

    Requires the pod count to split evenly; rows are grouped
    cluster-major (pods ``[c*ppc, (c+1)*ppc)`` form cluster ``c``), so a
    flat mesh produced by a per-cluster shrink + end-append grow round
    trip regains exactly its original device layout.
    """
    if n_clusters <= 1:
        return mesh
    assert mesh.axis_names[0] == "pod", mesh.axis_names
    n_pods = mesh.devices.shape[0]
    assert n_pods % n_clusters == 0, (
        f"{n_pods} pods do not regroup into {n_clusters} clusters")
    d = mesh.devices
    return Mesh(d.reshape((n_clusters, n_pods // n_clusters) + d.shape[1:]),
                ("cluster",) + mesh.axis_names)


def shrink_mesh(mesh: Mesh, keep_pods: Sequence[int], *,
                cluster: Optional[int] = None) -> Mesh:
    """The survivors' mesh: same per-pod (data, model) grid, fewer pods.

    ``keep_pods`` indexes the leading "pod" axis of ``mesh.devices``; the
    selected pods' devices are reused verbatim so no live buffers have to
    leave their device — only the dead pod's rows are dropped.

    On a two-tier (cluster, pod, data, model) mesh, pass ``cluster=c``
    and ``keep_pods`` indexes pods *within* cluster ``c`` — the death
    resizes only its own cluster.  Because one short cluster breaks the
    rectangular (cluster, pod) grid, the result is the **flattened**
    (pod, data, model) mesh in cluster-major order with only cluster
    ``c``'s dead rows removed: every other cluster's device assignment
    is untouched, and the round degrades to the flat single-tier merge
    until a grow rebalances the grid (:func:`regroup_mesh` restores it).
    """
    keep = list(keep_pods)
    assert keep, "cannot shrink a mesh to zero pods"
    if mesh.axis_names[0] == "cluster":
        assert cluster is not None, (
            "shrinking a cluster mesh needs cluster=<idx> (keep_pods "
            "indexes pods within that cluster)")
        n_c, ppc = mesh.devices.shape[:2]
        assert 0 <= cluster < n_c, (cluster, n_c)
        flat_keep = [c * ppc + p
                     for c in range(n_c)
                     for p in (keep if c == cluster else range(ppc))]
        return shrink_mesh(flatten_cluster_mesh(mesh), flat_keep)
    assert mesh.axis_names[0] == "pod", mesh.axis_names
    assert cluster is None, "cluster= only applies to a cluster mesh"
    return Mesh(mesh.devices[np.asarray(keep)], mesh.axis_names)


def grow_mesh(mesh: Mesh, n_new: int = 1, *,
              new_devices: Optional[Sequence] = None,
              n_clusters: Optional[int] = None) -> Mesh:
    """The regrown mesh: same per-pod (data, model) grid, more pods.

    Inverse of ``shrink_mesh``: ``n_new`` pod rows are appended to the
    leading "pod" axis.  By default the rows are filled with the first
    free devices — present in ``jax.devices()`` but absent from ``mesh``
    — which after a shrink are exactly the dropped pod's devices, so a
    rejoining pod gets its own hardware back and no surviving pod's
    buffers have to move.  Pass ``new_devices`` to pin the rows
    explicitly (a genuinely new pod's devices).

    ``n_clusters`` restores the two-tier grid after a per-cluster shrink:
    once the append rebalances the pod count, the flat mesh is regrouped
    into (cluster, pod, data, model) via :func:`regroup_mesh`.  The
    appended rows land at the END of the flat cluster-major order, so
    this round-trips exactly when the dead pod was the last row of the
    last cluster (the convention the elastic equivalence harnesses use);
    any other death site still grows fine flat, but the caller then owns
    the row->cluster permutation.
    """
    if mesh.axis_names[0] == "cluster":
        mesh = flatten_cluster_mesh(mesh)
    assert mesh.axis_names[0] == "pod", mesh.axis_names
    assert n_new >= 1, n_new
    per_pod_shape = mesh.devices.shape[1:]
    need = n_new * int(np.prod(per_pod_shape))
    if new_devices is None:
        in_use = {d.id for d in mesh.devices.flat}
        pool = [d for d in jax.devices() if d.id not in in_use]
    else:
        pool = list(new_devices)
    if len(pool) < need:
        raise ValueError(
            f"growing by {n_new} pod(s) needs {need} free devices, "
            f"have {len(pool)}")
    rows = np.asarray(pool[:need], dtype=object).reshape(
        (n_new,) + per_pod_shape)
    grown = Mesh(np.concatenate([mesh.devices, rows], axis=0),
                 mesh.axis_names)
    if n_clusters is not None and n_clusters > 1:
        return regroup_mesh(grown, n_clusters)
    return grown


def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def arch_parallel_config(arch: str, optimized: bool = False) -> ParallelConfig:
    """Parallelism policy per assigned architecture.

    ``optimized=True`` applies the §Perf hillclimb results: gradient
    accumulation for the HBM-heaviest archs (activation temporaries shrink
    by 1/microbatch at a small collective-traffic cost).
    """
    fsdp = arch in ("grok-1-314b", "granite-34b", "llava-next-34b")
    mb = 1
    if optimized:
        mb = {"grok-1-314b": 4, "llava-next-34b": 2, "granite-34b": 2,
              "deepseek-v2-lite-16b": 2, "recurrentgemma-2b": 4}.get(arch, 1)
    return ParallelConfig(fsdp=fsdp, microbatch=mb)


def arch_rules(cfg: ModelConfig, mesh: Optional[Mesh], parallel: ParallelConfig,
               *, multi_pod: bool = False, decode: bool = False,
               batch: int = 0, tp_pad_heads: bool = False) -> AxisRules:
    """Divisibility-aware logical->mesh rules for one (arch, mesh, mode)."""
    tp = mesh_axis_size(mesh, "model") if mesh is not None else 16
    dp = mesh_axis_size(mesh, "data") if mesh is not None else 16
    pods = mesh_axis_size(mesh, "pod") if (mesh is not None and multi_pod) else 1
    clusters = (mesh_axis_size(mesh, "cluster")
                if (mesh is not None and multi_pod) else 1)

    def div(n: int) -> bool:
        return n > 0 and n % tp == 0

    extra: Dict[str, object] = {}
    # heads shard only when divisible; tp_pad_heads pads ACTIVATION heads
    # (per KV group, function-preserving) so act_heads can shard even when
    # the parameter head dim cannot
    extra["heads"] = "model" if div(cfg.num_heads) else None
    extra["act_heads"] = ("model" if (div(cfg.num_heads) or tp_pad_heads)
                          else None)
    extra["kv_heads"] = "model" if div(cfg.num_kv_heads) else None
    extra["act_kv"] = "model" if div(cfg.num_kv_heads) else None
    extra["vocab"] = "model" if div(cfg.vocab_size) else None
    extra["act_vocab"] = "model" if div(cfg.vocab_size) else None
    extra["ff"] = "model" if div(cfg.d_ff) else None
    extra["act_ff"] = "model" if div(cfg.d_ff) else None
    if cfg.recurrent is not None:
        w = cfg.recurrent.lru_width or cfg.d_model
        extra["lru"] = "model" if div(w) else None
    if cfg.moe is not None:
        if parallel.expert_parallel and div(cfg.moe.num_experts):
            extra["expert"] = "model"
            extra["expert_ff"] = None
        else:
            # too few experts for EP -> TP inside each expert
            extra["expert"] = None
            extra["expert_ff"] = "model" if div(cfg.moe.expert_ff) else None

    # batch sharding: drop mesh axes that don't divide the global batch;
    # the replica tiers claim first (cluster outermost, then pod), data last
    batch_axes = []
    if multi_pod and clusters > 1 and batch % clusters == 0:
        batch_axes.append("cluster")
    rep = clusters if "cluster" in batch_axes else 1
    if multi_pod and pods > 1 and (batch // rep) % pods == 0:
        batch_axes.append("pod")
        rep *= pods
    eff = batch // rep
    if batch % (rep * dp) == 0 and eff >= dp:
        batch_axes.append("data")
    extra["batch"] = tuple(batch_axes) if batch_axes else None
    extra["moe_group"] = extra["batch"]

    # decode caches: shard the cache sequence dim over "model" when the KV
    # heads can't shard (MQA) — bounds per-device cache memory
    if decode:
        extra["cache_seq"] = "model" if not div(cfg.num_kv_heads) else None
        extra["seq"] = None  # single-token activations: no SP
    else:
        extra["cache_seq"] = None

    if parallel.fsdp:
        # with batch not sharding "data" (tiny serve batches), FSDP over an
        # idle data axis is still valid (pure weight sharding)
        extra.setdefault("embed", "data")
        extra.setdefault("qkv", "data")

    rules = make_rules(mesh, fsdp=parallel.fsdp,
                       sequence_parallel=parallel.sequence_parallel and not decode,
                       multi_pod=multi_pod, extra=extra)
    return rules
