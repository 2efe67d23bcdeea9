"""End-to-end training driver.

Two modes:

* ``single``  — standard data-parallel training of one model replica with
  prefetching input pipeline, checkpointing, and optional restore.
* ``hermes``  — the paper's technique at LM scale (Level B): N pod replicas
  train locally on disjoint shards; every round each pod's eval loss feeds
  HermesGUP; gate-opening pods merge into the global model via loss-based
  SGD (the device-resident generalization in dist/hermes_sync.py) and
  refresh.  Communication (the merge collective) only carries compressed
  payloads on rounds where a gate opens.

CPU-scale presets keep the tests fast; ``--preset <arch> --layers N`` runs
a registry architecture at its published widths, cut only in depth.
``--placed`` puts each pod on its own devices (``launch.mesh.make_pod_mesh``).

Usage:
    python -m repro.launch.train --preset lm100m --steps 300
    python -m repro.launch.train --preset lm100m --hermes --pods 4 --steps 300
    python -m repro.launch.train --preset lm100m --hermes --pods 4 \
        --clusters 2 --steps 300   # two-tier: intra-cluster merge, one
                                   # packed payload per cluster crosses
    python -m repro.launch.train --preset phi3-mini-3.8b --layers 1 \
        --hermes --pods 4 --placed --batch 1 --seq 2048
"""
from __future__ import annotations

import argparse
import json
import os
import time
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.config import (
    ModelConfig,
    HermesConfig,
    OptimizerConfig,
    FAMILY_DENSE,
    replace,
)
from repro.configs import get_config, get_smoke_config
from repro.checkpoint import Checkpointer
from repro.data.synthetic import make_lm_dataset
from repro.dist.hermes_sync import (
    hermes_cluster_commit, hermes_cluster_dispatch, hermes_cluster_round,
    hermes_pod_state,
)
from repro.launch.mesh import make_pod_mesh
from repro.models import init_lm, lm_loss
from repro.optim import make_optimizer
from repro.utils import tracing
from repro.utils.tracing import span

Tree = Any

REPO_ROOT = Path(__file__).resolve().parents[3]

PRESETS: Dict[str, ModelConfig] = {}

# The single choke point for device->host reads in the Hermes round loop.
# Everything the loop *must* know on the host goes through here, and only
# at log intervals or after the loop — never per round, so the dispatch
# queue stays full (tests/test_perf_opts.py counts these calls).
_host_fetch = jax.device_get


def configure_compile_cache(root=REPO_ROOT) -> str:
    """Keep JAX's persistent compilation cache at one fixed directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache is ``<root>/.jax_cache``, the
    same path for every run in one checkout (the path is part of the cache
    key).  Entry points call this at start-up, never at import.  Returns
    the directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pod_shardings(mesh):
    """``(pod-stacked, replicated)`` shardings on ``mesh``.

    A pod-stacked tree's leading axis splits over the pod tier(s) — the
    ``(cluster, pod)`` pair on a two-tier mesh — so each pod's rows live
    on that pod's own devices; the global model is replicated.
    ``(None, None)`` when unplaced.
    """
    if mesh is None:
        return None, None
    lead = ("cluster", "pod") if mesh.axis_names[0] == "cluster" else "pod"
    return (NamedSharding(mesh, PartitionSpec(lead)),
            NamedSharding(mesh, PartitionSpec()))


def pod_rows(tree: Tree) -> Dict[int, List[int]]:
    """Device id -> the pod rows of a pod-stacked ``tree`` that device
    holds (union over leaves of each shard's leading-axis slice).  Reads
    shard metadata only; nothing moves to the host."""
    rows: Dict[int, set] = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            rows.setdefault(shard.device.id, set()).update(
                range(leaf.shape[0])[shard.index[0]])
    return {d: sorted(r) for d, r in sorted(rows.items())}


def make_pod_step(cfg: ModelConfig, optimizer, mesh=None):
    """The jitted pod-stacked local step ``(pod_params, pod_opt, batches)
    -> (pod_params, pod_opt, losses)``: one vmapped value-and-grad +
    optimizer update per pod.  The params/opt state are donated (consumed
    in place, halving the peak for the largest arrays).  With a ``mesh``
    every output stays pod-sharded, so each pod's step runs on its own
    devices and lowers with no cross-pod collective."""
    def pod_step(pod_params, pod_opt, batches):
        def one(params, opt, batch):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(p, batch, cfg))(params)
            p, o = optimizer.apply(params, grads, opt)
            return p, o, loss
        return jax.vmap(one)(pod_params, pod_opt, batches)

    pod_sh, _ = pod_shardings(mesh)
    placed = {} if pod_sh is None else {"out_shardings": pod_sh}
    return jax.jit(pod_step, donate_argnums=(0, 1), **placed)


def make_round_jit(hcfg: HermesConfig, mesh=None):
    """The synchronous round as one executable:
    ``(pod_params, gup, pod_losses, w_global, L, error, rng) -> dict``
    (``hermes_cluster_round``'s).  The pod params, the global model and
    the error residual are donated: the round returns their successors and
    the loop never reads the old ones, so each aliases its output in place
    (``launch/analyze.py: check_round_jit``).  The round's ``lax.cond``
    carries only the global model and the residual; the refresh and the
    error-feedback select are masked selects after it, written into the
    donated pod and residual buffers.  The residual is a tree exactly when
    the wire is lossy with error feedback, else None.  With a ``mesh`` the
    pod-stacked outputs stay pod-sharded and the global model
    replicated."""
    pod_sh, rep = pod_shardings(mesh)
    has_error = hcfg.compression != "none" and hcfg.error_feedback
    placed = {}
    if mesh is not None:
        placed["out_shardings"] = {
            "pod_params": pod_sh, "w_global": rep, "gup": pod_sh,
            "error": pod_sh if has_error else None,
            "gates": rep, "any_push": rep}
    return jax.jit(
        lambda pod_params, gup, pod_losses, w_global, L, error, rng:
        hermes_cluster_round(pod_params, gup, pod_losses, w_global, L,
                             cfg=hcfg, error=error, rng=rng, mesh=mesh),
        donate_argnums=(0, 3, 5), **placed)


def make_async_round_jits(hcfg: HermesConfig, mesh=None):
    """The async round's two jitted halves: ``(dispatch_jit, commit_jit)``.

    Separate executables are the overlap mechanism (DESIGN.md §8): the
    gather's outputs feed only ``commit_jit``, so the runtime's async
    dispatch runs the collective while the pod step executes.  The
    stacked ``pod_params`` and the pending buffer are donated into the
    commit (``donate_argnums=(0, 1)``) — both are consumed exactly once.
    The pod params alias the merged outputs in place (the model-sized
    win, pinned by the donation-aliasing rule); the pending wire arrays
    have no shape-matching output to alias but are freed the moment the
    late merge reads them.  Module-level so the donation contract is one
    definition shared by ``train_hermes``, the static analyzer
    (``launch/analyze.py``), and the pinned donation test.

    Routes through the two-tier entry points (DESIGN.md §10): with
    ``hcfg.n_clusters > 1`` the dispatch gathers intra-cluster and ships
    only the re-encoded per-cluster partials across the cluster axis;
    at one cluster both delegate verbatim to ``hermes_dispatch`` /
    ``hermes_commit``, so the flat donation/aliasing contract is
    unchanged.
    """
    commit_jit = jax.jit(
        lambda pod_params, pending, w_global: hermes_cluster_commit(
            pod_params, pending, w_global, cfg=hcfg, mesh=mesh),
        donate_argnums=(0, 1))
    dispatch_jit = jax.jit(
        lambda pod_params, gup, pod_losses, w_global, L, error, rng:
        hermes_cluster_dispatch(pod_params, gup, pod_losses, w_global, L,
                                hcfg, error=error, rng=rng, mesh=mesh))
    return dispatch_jit, commit_jit


def _preset(name: str, layers: int = 0) -> ModelConfig:
    """A CPU preset (``lm100m``, ``lmtiny``), or a registry arch: its smoke
    config by default, with ``layers > 0`` its published config with only
    ``num_layers`` replaced (every width as published)."""
    if layers:
        cfg = replace(get_config(name), num_layers=layers)
        cfg.validate()
        return cfg
    if name == "lm100m":
        return ModelConfig(
            name="lm100m", family=FAMILY_DENSE, num_layers=12, d_model=768,
            num_heads=12, num_kv_heads=4, d_ff=2048, vocab_size=32000,
            qk_norm=True, remat=False, dtype="float32")
    if name == "lmtiny":
        return ModelConfig(
            name="lmtiny", family=FAMILY_DENSE, num_layers=2, d_model=64,
            num_heads=4, num_kv_heads=2, d_ff=128, vocab_size=512,
            remat=False, dtype="float32")
    return get_smoke_config(name)


def make_batches(tokens: np.ndarray, batch: int, seq: int, rng,
                 skip: int = 0) -> Any:
    n = (len(tokens) - 1) // seq
    # fast-forward the index stream without materializing skipped batches
    for _ in range(skip):
        rng.integers(0, n, batch)
    while True:
        idx = rng.integers(0, n, batch)
        x = np.stack([tokens[i * seq:(i + 1) * seq] for i in idx])
        y = np.stack([tokens[i * seq + 1:(i + 1) * seq + 1] for i in idx])
        yield {"tokens": jnp.asarray(x), "targets": jnp.asarray(y)}


def single_step_fn(cfg: ModelConfig, optimizer):
    """``train_single``'s step, before jit: ``(state, batch) -> (state,
    loss)``.  An expert model's state also carries ``"expert_load"``, the
    assignments each expert layer's held experts received in the step,
    (expert layers, held) int32, so the log line reads it at its fetch."""
    def step_fn(state, batch):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lm_loss(p, batch, cfg, with_aux=True),
            has_aux=True)(state["params"])
        p, o = optimizer.apply(state["params"], grads, state["opt"])
        new = {"params": p, "opt": o, "step": state["step"] + 1}
        if aux is not None:
            new["expert_load"] = aux["load"]
        return new, loss
    return step_fn


def single_state(cfg: ModelConfig, optimizer, params) -> Dict:
    """``train_single``'s initial state for ``params``."""
    state = {"params": params, "opt": optimizer.init(params),
             "step": jnp.int32(0)}
    if cfg.moe is not None:
        state["expert_load"] = jnp.zeros(
            (cfg.num_layers - cfg.first_dense_layers, cfg.moe.held),
            jnp.int32)
    return state


def train_single(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
                 opt_cfg: OptimizerConfig, ckpt_dir: Optional[str] = None,
                 restore: bool = False, log_every: int = 20,
                 seed: int = 0) -> Dict:
    tracing.install_gc_span()
    rng = np.random.default_rng(seed)
    tokens = make_lm_dataset(batch * seq * 40 + 1, cfg.vocab_size, seed=seed)
    optimizer = make_optimizer(opt_cfg)
    params, _ = init_lm(cfg, jax.random.PRNGKey(seed))
    state = single_state(cfg, optimizer, params)
    start_step = 0
    ck = Checkpointer(ckpt_dir) if ckpt_dir else None
    if ck and restore:
        try:
            state, start_step = ck.restore(state)
            print(f"restored from step {start_step}")
        except FileNotFoundError:
            pass
    # resume the data stream, don't replay already-consumed batches
    batches = make_batches(tokens, batch, seq, rng,
                           skip=min(start_step, steps))

    # the old state is dead the moment the step returns the new one, so
    # donate it: peak memory stays one state + transients, and the
    # donation-aliasing rule (repro.analysis) can pin the alias header
    step_fn = partial(jax.jit, donate_argnums=(0,))(
        single_step_fn(cfg, optimizer))

    losses = []
    load = ""
    log_times = []  # (step, wall s since loop start), taken after a sync
    t0 = time.time()
    for i in range(start_step, steps):
        log = (i + 1) % log_every == 0
        with span(tracing.BATCH, step=i):
            batch_i = next(batches)
        with span(tracing.STEP, step=i):
            state, loss = step_fn(state, batch_i)
        with span(tracing.FETCH, step=i):
            if log and "expert_load" in state:
                loss, counts = jax.device_get((loss, state["expert_load"]))
                load = (f" expert load max/mean "
                        f"{counts.max() / max(counts.mean(), 1e-9):.3f}")
            losses.append(float(loss))
            if log:
                jax.block_until_ready(state)
        if log:
            with span(tracing.LOG, step=i):
                log_times.append((i + 1, time.time() - t0))
                print(f"step {i+1:5d} loss "
                      f"{np.mean(losses[-log_every:]):.4f} "
                      f"({(i + 1 - start_step) / (time.time() - t0):.2f} "
                      f"it/s){load}", flush=True)
        if ck and (i + 1) % 100 == 0:
            with span(tracing.CKPT, step=i):
                ck.save(state, i + 1)
    if ck:
        with span(tracing.CKPT, step=steps):
            ck.save(state, steps)
            ck.wait()
    # a restore at/after `steps` runs zero iterations; report nan, don't crash
    return {"final_loss": float(np.mean(losses[-10:])) if losses
            else float("nan"),
            "first_loss": losses[0] if losses else float("nan"),
            "last_loss": losses[-1] if losses else float("nan"),
            "steps": steps, "log_times": log_times}


def train_hermes(cfg: ModelConfig, *, steps: int, batch: int, seq: int,
                 pods: int, opt_cfg: OptimizerConfig, hcfg: HermesConfig,
                 ckpt_dir: Optional[str] = None, log_every: int = 20,
                 seed: int = 0, mesh=None) -> Dict:
    """Level-B Hermes: pod-stacked local training + gated merges.

    ``mesh`` (a ``(pod, data, model)`` — or, with ``hcfg.n_clusters > 1``,
    a ``(cluster, pod, data, model)`` — ``jax.sharding.Mesh``, optional)
    is threaded into every round: with a mesh the merge ships
    the *encoded* push payloads explicitly across the pod axis and merges
    locally (``dist.hermes_sync.hermes_merge``); ``mesh=None`` runs the
    same math unplaced (single-host demo default) — bit-identical, by the
    round-lowering test tier.  Placed runs with stochastic int4 need
    ``jax_threefry_partitionable=True`` for that bit-identity (set by the
    launch entry points, not here).

    With ``hcfg.async_rounds`` the loop pipelines the two-phase protocol
    (DESIGN.md §8): at each boundary it first *commits* the previous
    round's in-flight payload (merge + staleness-1 refresh — zero
    collectives), then *dispatches* this round's gates/encode/gather and
    immediately returns to local steps.  Dispatch, commit, and the pod
    step are separate jitted programs and the pending payload is only
    read by the commit, so the runtime overlaps the gather with the next
    ``lam`` pod steps.  The stacked pod params and the pending buffer
    are donated into the commit (``make_async_round_jits``; both are
    consumed exactly once), and a final drain commit flushes the
    last in-flight payload after the loop so every dispatched round
    merges exactly once.

    With a ``mesh``, every pod-stacked tree (params, optimizer state, GUP
    state, error residuals, batches) is placed pod-sharded
    (:func:`pod_shardings`) and the global model replicated, so each pod
    trains on its own devices; the result then also carries ``pod_rows``
    (per tree, which pod rows each device holds) and
    ``w_global_replicated``.  ``log_times`` lists ``(step, wall seconds)``
    at each log line, taken after the log fetch has waited on the step;
    ``gates`` holds each round's per-pod gate row (0/1), and ``history``
    each round's ``(step, mean pod loss, open gates)``.
    """
    tracing.install_gc_span()
    rng = np.random.default_rng(seed)
    tokens = make_lm_dataset(batch * seq * 40 * pods + batch * seq + 2,
                             cfg.vocab_size, seed=seed)
    # held-out eval split from the SAME stream (same Markov transitions)
    eval_tokens = tokens[-(batch * seq + 1):]
    shards = np.array_split(tokens[:-(batch * seq + 1)], pods)
    batch_iters = [make_batches(s, batch, seq, np.random.default_rng(seed + i))
                   for i, s in enumerate(shards)]
    eval_batch = next(make_batches(eval_tokens, min(batch, 8), seq,
                                   np.random.default_rng(seed)))

    optimizer = make_optimizer(opt_cfg)
    pod_sh, rep_sh = pod_shardings(mesh)

    def place(tree, sharding):
        return tree if sharding is None else jax.device_put(tree, sharding)

    params0, _ = init_lm(cfg, jax.random.PRNGKey(seed))
    pod_params = place(jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (pods,) + x.shape).copy(),
        params0), pod_sh)
    pod_opt = place(jax.vmap(optimizer.init)(pod_params), pod_sh)
    w_global = place(params0, rep_sh)
    L_global = jnp.float32(1e9)
    gup = place(hermes_pod_state(hcfg, pods), pod_sh)
    # a lossy wire with error feedback carries a residual from the first
    # round on: start it at zeros (what the first round would create) so
    # the round compiles once, for one tree structure
    error = None
    if hcfg.compression != "none" and hcfg.error_feedback:
        error = place(jax.tree.map(jnp.zeros_like, pod_params), pod_sh)

    pod_step = make_pod_step(cfg, optimizer, mesh)

    @jax.jit
    def pod_eval(pod_params):
        return jax.vmap(lambda p: lm_loss(p, eval_batch, cfg))(pod_params)

    @jax.jit
    def eval_global(params):
        return lm_loss(params, eval_batch, cfg)

    @jax.jit
    def eval_if_push(any_push, params, L_prev):
        # re-evaluate the global loss only on merge rounds, entirely on
        # device: the old `bool(any_push)` here forced a host sync every
        # round, stalling dispatch on the hot path
        return jax.lax.cond(any_push,
                            lambda: lm_loss(params, eval_batch, cfg),
                            lambda: L_prev)

    async_rounds = bool(getattr(hcfg, "async_rounds", False))
    if async_rounds:
        dispatch_jit, commit_jit = make_async_round_jits(hcfg, mesh)
    else:
        round_jit = make_round_jit(hcfg, mesh)

    def _commit_pending(pod_params, w_global, L_global, pending, counters,
                        step):
        merges_dev, committed_dev = counters
        cm = commit_jit(pod_params, pending, w_global)
        pod_params, w_global = cm["pod_params"], cm["w_global"]
        with span(tracing.EVAL, step=step):
            L_global = eval_if_push(cm["any_push"], w_global, L_global)
        bump = cm["any_push"].astype(jnp.int32)
        return pod_params, w_global, L_global, (merges_dev + bump,
                                                committed_dev + bump)

    rounds = 0
    merges_dev = jnp.int32(0)      # device-side counter; fetched at logs
    dispatched_dev = jnp.int32(0)  # async accounting: opens shipped…
    committed_dev = jnp.int32(0)   # …and opens merged (equal after drain)
    pending = None                 # the in-flight round (async only)
    log_times = []                 # (step, wall s since loop start)
    t0 = time.time()
    history_dev = []               # (step, device mean loss, device gates)
    for i in range(steps):
        with span(tracing.BATCH, step=i):
            stacked = place({k: jnp.stack([next(b)[k] for b in batch_iters])
                             for k in ("tokens", "targets")}, pod_sh)
        with span(tracing.STEP, step=i):
            pod_params, pod_opt, losses = pod_step(pod_params, pod_opt,
                                                   stacked)
        if (i + 1) % hcfg.lam == 0 or i == 0:
            rounds += 1
            with span(tracing.EVAL, step=i):
                pod_losses = pod_eval(pod_params)
            with span(tracing.ROUND, step=i):
                rng_i = jax.random.fold_in(jax.random.PRNGKey(seed), i)
                if async_rounds:
                    # commit round k-1's in-flight payload first (its
                    # gather overlapped the lam steps just taken), then
                    # dispatch round k against the freshly merged global
                    # and return to compute without waiting on the new
                    # gather
                    if pending is not None:
                        (pod_params, w_global, L_global,
                         (merges_dev, committed_dev)) = _commit_pending(
                            pod_params, w_global, L_global, pending,
                            (merges_dev, committed_dev), i)
                    dp = dispatch_jit(pod_params, gup, pod_losses, w_global,
                                      L_global, error, rng_i)
                    gup, error, pending = (dp["gup"], dp["error"],
                                           dp["pending"])
                    dispatched_dev = (dispatched_dev
                                      + dp["any_push"].astype(jnp.int32))
                    history_dev.append((i + 1, jnp.mean(pod_losses),
                                        dp["gates"]))
                else:
                    out = round_jit(pod_params, gup, pod_losses, w_global,
                                    L_global, error, rng_i)
                    pod_params, w_global = out["pod_params"], out["w_global"]
                    gup, error = out["gup"], out["error"]
                    with span(tracing.EVAL, step=i):
                        L_global = eval_if_push(out["any_push"], w_global,
                                                L_global)
                    merges_dev = (merges_dev
                                  + out["any_push"].astype(jnp.int32))
                    history_dev.append((i + 1, jnp.mean(pod_losses),
                                        out["gates"]))
        if (i + 1) % log_every == 0:
            with span(tracing.FETCH, step=i):
                pod_l, gl_l, m = _host_fetch((jnp.mean(losses), L_global,
                                              merges_dev))
            with span(tracing.LOG, step=i):
                log_times.append((i + 1, time.time() - t0))
                print(f"step {i+1:5d} pod-loss {float(pod_l):.4f} "
                      f"global-L {float(gl_l):.4f} merges={int(m)}/{rounds}",
                      flush=True)
    # drain: the last dispatched payload has no following boundary, so
    # flush it here — every open round merges exactly once
    if pending is not None:
        with span(tracing.ROUND, step=steps):
            (pod_params, w_global, L_global,
             (merges_dev, committed_dev)) = _commit_pending(
                pod_params, w_global, L_global, pending,
                (merges_dev, committed_dev), steps)
        pending = None
    # one bulk transfer: stack the per-round scalars on device first so
    # the final fetch is two arrays, not thousands of tiny copies
    hist_steps = [s for s, _, _ in history_dev]
    hist_loss = (jnp.stack([l for _, l, _ in history_dev])
                 if history_dev else jnp.zeros((0,)))
    hist_gates = (jnp.stack([g for _, _, g in history_dev])
                  if history_dev else jnp.zeros((0, pods), bool))
    gl, pl, merges, dispatched, committed, hist_loss, hist_gates = \
        _host_fetch((eval_global(w_global), pod_eval(pod_params), merges_dev,
                     dispatched_dev, committed_dev, hist_loss, hist_gates))
    gl, merges = float(gl), int(merges)
    pl = [float(x) for x in pl]
    history = [(s, float(l), int(g))
               for s, l, g in zip(hist_steps, hist_loss, hist_gates.sum(1))]
    out = {"global_loss": gl, "merges": merges, "rounds": rounds,
           "pod_losses": pl, "best_pod_loss": min(pl),
           "history": history, "steps": steps,
           "comm_fraction": merges / max(rounds, 1),
           "async_rounds": async_rounds,
           "dispatched": int(dispatched), "committed": int(committed),
           "drained": pending is None, "log_times": log_times,
           "gates": hist_gates.astype(int).tolist()}
    if mesh is not None:
        out["pod_rows"] = {"pod_params": pod_rows(pod_params),
                           "pod_opt": pod_rows(pod_opt),
                           "error": None if error is None else pod_rows(error)}
        out["w_global_replicated"] = all(
            shard.data.shape == leaf.shape
            for leaf in jax.tree.leaves(w_global)
            for shard in leaf.addressable_shards)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="lmtiny")
    ap.add_argument("--layers", type=int, default=0,
                    help="run a registry --preset at its published widths "
                         "cut to this many layers (0 = its smoke config)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--hermes", action="store_true")
    ap.add_argument("--pods", type=int, default=4)
    ap.add_argument("--placed", action="store_true",
                    help="place each pod on its own devices (a pod mesh over "
                         "jax.devices(); fails when there are fewer devices "
                         "than pods)")
    ap.add_argument("--clusters", type=int, default=1,
                    help="two-tier Hermes (DESIGN.md §10): group the pods "
                         "into N latency clusters; the gated merge runs "
                         "intra-cluster and only each cluster's merged, "
                         "re-encoded payload crosses the slow tier "
                         "(--pods must divide evenly; 1 = flat round)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--alpha", type=float, default=-1.3)
    ap.add_argument("--beta", type=float, default=0.1)
    ap.add_argument("--lam", type=int, default=5)
    ap.add_argument("--compression", default=None,
                    help="wire format for the push payloads (any registered "
                         "name; default = HermesConfig default)")
    ap.add_argument("--async-rounds", action="store_true",
                    help="pipeline the rounds: dispatch the packed payload "
                         "gather and keep training, merge it one round late "
                         "(staleness-1; DESIGN.md §8)")
    ap.add_argument("--participation-rate", type=float, default=1.0,
                    help="admission budget on top of the z-gate (DESIGN.md "
                         "§11): at most max(1, floor(rate * n_open)) of the "
                         "open gates ship per round, the rest defer behind "
                         "error feedback; 1.0 = admission statically off "
                         "(bit-identical lowering)")
    ap.add_argument("--admission", default="topk", choices=("topk", "prob"),
                    help="how the budget picks shippers: 'topk' by the "
                         "Algorithm-2 merge weight 1/loss, 'prob' i.i.d. "
                         "Bernoulli thinning")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--restore", action="store_true")
    args = ap.parse_args()

    configure_compile_cache()
    cfg = _preset(args.preset, args.layers)
    opt = OptimizerConfig(name="adamw", lr=args.lr)
    if args.hermes:
        kw = {} if args.compression is None else {
            "compression": args.compression}
        hcfg = HermesConfig(alpha=args.alpha, beta=args.beta, lam=args.lam,
                            eta=1.0, async_rounds=args.async_rounds,
                            n_clusters=args.clusters,
                            participation_rate=args.participation_rate,
                            admission=args.admission, **kw)
        hcfg.validate()
        if args.clusters > 1 and args.pods % args.clusters:
            ap.error(f"--pods {args.pods} must split evenly into "
                     f"--clusters {args.clusters}")
        mesh = (make_pod_mesh(args.pods, n_clusters=args.clusters)
                if args.placed else None)
        out = train_hermes(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, pods=args.pods, opt_cfg=opt,
                           hcfg=hcfg, ckpt_dir=args.ckpt, mesh=mesh)
        out["compression"] = hcfg.compression
    else:
        out = train_single(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, opt_cfg=opt, ckpt_dir=args.ckpt,
                           restore=args.restore)
    print(json.dumps({k: v for k, v in out.items() if k != "history"},
                     indent=2))


if __name__ == "__main__":
    main()
