"""Device-resident Hermes round: gate, loss-weighted merge, refresh.

This is the Level-B generalization (DESIGN.md §hermes_sync) of the paper's
host-side loop: ``core/gup.py`` (Algorithm 1 z-score gate) and
``core/loss_sgd.py`` (Algorithm 2 loss-weighted merge) re-expressed as one
pure-jnp program over *pod-stacked* pytrees, so a whole synchronization
round jits into a single SPMD step on the (pod, data, model) mesh.

It relies on the model-merge identity (tests/test_loss_sgd.py): because
every pod's parameters are an affine function of its gradient-sum,
Algorithm 2's gradient-space merge equals the model-space form

    w_global' = (W1 * w_global + sum_i W2_i * w_i) / (W1 + sum_i W2_i)

with W1 = 1/L(global), W2_i = 1/loss_i, the sum over gate-open pods.  With
exactly one gate open this is literally Eq. 5-6; with none it is the
identity (closed rounds ship one scalar, no model bytes).

Gate-open pods *refresh*: they restart local training from the new global
model, exactly as a paper worker does after a push+pull.

Compression goes through the :mod:`repro.dist.wire` registry.  The merge
consumes the encoded *payloads* — on the fused-kernel path a format's
``fused_merge`` hook merges them straight into the global leaf without
ever materializing a dequantized fp32 delta tree: int8 rides the Pallas
dequant-merge kernel over ``(q, scales)``, int4 the packed variant over
``(q_packed, scales)`` whose nibble unpack is fused into the tile loop, so
the half-width wire payload is also the only thing the merge ever reads
from HBM.  The jnp path decodes per leaf and is the oracle the kernels are
pinned against.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.config import HermesConfig
from repro.core.gup import gup_gate_jax, gup_state_jax
from repro.dist.compression import (
    decode_tree, encode_tree, gather_payloads, get_format, pin_gathered,
)
from repro.dist.wire import (
    gather_payloads_tiered, payload_buffer_spec, pin_tier,
    resolve_kernel_dispatch,
)

Tree = Any

_EPS = 1e-12  # loss -> weight guard; matches core/loss_sgd.py


def hermes_pod_state(cfg: HermesConfig, n_pods: int) -> Tree:
    """Pod-stacked device GUP state: every leaf gains a leading (n_pods,)."""
    base = gup_state_jax(cfg)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_pods,) + x.shape), base)


def hermes_grow_pod_state(gup_state: Tree, cfg: HermesConfig,
                          n_new: int = 1) -> Tree:
    """Append ``n_new`` fresh rows to a pod-stacked GUP state (the grow
    path's mirror of ``hermes_pod_state``): empty ring buffer, zeroed
    count/n_iter, alpha back at ``cfg.alpha``.

    A fresh row's loss queue holds fewer than two valid entries for its
    first two rounds, so its z-score is +inf and its gate *provably*
    cannot open — a rejoined pod contributes exact zeros to the wire and
    the merge while it warms up, which is what makes the grow path
    invisible to the incumbent pods (``launch/elastic.py:
    rejoin_pod_equivalence``)."""
    fresh = gup_state_jax(cfg)
    return jax.tree.map(
        lambda x, f: jnp.concatenate(
            [x, jnp.broadcast_to(f[None], (n_new,) + f.shape).astype(x.dtype)],
            axis=0),
        gup_state, fresh)


def _pod_mask(gates: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Reshape (n,) gates to broadcast against a (n, ...) stacked leaf."""
    return gates.reshape(gates.shape + (1,) * (leaf.ndim - 1))


def admit_gates(gates: jnp.ndarray, losses: jnp.ndarray, cfg: HermesConfig,
                rng=None) -> jnp.ndarray:
    """Participation-rate admission on top of the z-score gate (DESIGN.md
    §11): keep at most ``max(1, floor(participation_rate * n_open))`` of
    the OPEN gates; the rest are deferred to a later round.

    At ``participation_rate >= 1.0`` this returns ``gates`` itself — no
    ops are traced, so every round family lowers bit-identically to the
    pre-admission gate by construction (the same static-delegation
    pattern as the ``n_clusters=1`` cluster paths).

    ``admission="topk"`` ranks the open pods by their Algorithm-2 merge
    weight ``w2 = 1/loss`` (stable sort, index tie-break) so the budget
    ships the pushes the merge weights most; ``"prob"`` thins the open
    gates i.i.d. Bernoulli(prate) and needs ``rng`` (folded, so the
    encode stream is untouched).  Both only ever *clear* gate bits:
    admitted ⊆ open, a closed gate can never be admitted, and the wire
    payload of a deferred pod is the same exact zeros as a closed one —
    admission changes ``any_push`` frequency, never the wire-operand
    multiset (``launch/analyze.py::check_admission``).  Error feedback /
    local accumulation make the deferral lossless in the telescoped sum:
    a deferred pod's delta stays anchored to its last refresh, so its
    next admitted push carries everything the deferrals withheld.
    """
    prate = float(getattr(cfg, "participation_rate", 1.0))
    if prate >= 1.0:
        return gates
    mode = getattr(cfg, "admission", "topk")
    gates = gates.astype(bool)
    n_open = jnp.sum(gates.astype(jnp.int32))
    if mode == "prob":
        if rng is None:
            raise ValueError(
                "admission='prob' with participation_rate < 1 needs an rng")
        u = jax.random.uniform(jax.random.fold_in(rng, 0xAD317),
                               gates.shape, jnp.float32)
        return gates & (u < prate)
    # topk by merge weight; closed gates rank below every open one (-inf)
    w2 = jnp.where(gates,
                   1.0 / jnp.maximum(losses.astype(jnp.float32), _EPS),
                   -jnp.inf)
    order = jnp.argsort(-w2, stable=True)
    rank = jnp.zeros(gates.shape, jnp.int32).at[order].set(
        jnp.arange(gates.shape[0], dtype=jnp.int32))
    k = jnp.maximum(jnp.int32(1),
                    jnp.floor(prate * n_open.astype(jnp.float32))
                    .astype(jnp.int32))
    k = jnp.where(n_open > 0, k, jnp.int32(0))
    return gates & (rank < k)


def _merge_leaf_jnp(g, pods, w1, w2, denom, any_push):
    """(w1*g + sum_i w2_i*pods_i)/denom, falling back to g on closed rounds.

    Mirrors ``kernels.ref.loss_weighted_update_ref`` / the fused Pallas
    kernel operation-for-operation so both paths agree to fp32 rounding.

    The accumulation is an unrolled elementwise loop over the static pod
    count rather than a ``tensordot`` contraction: a dot's contraction
    dimension is fair game for GSPMD to re-split across the pod mesh axis,
    which would ship a model-sized fp32 all-reduce right after the packed
    payload gather — exactly the traffic the gather exists to avoid.
    Elementwise adds have no contraction to split, so the merge stays
    local to wherever the gathered operands already live.

    The accumulation runs in a ``lax.fori_loop`` rather than an unrolled
    Python loop: a while-loop body is compiled as its own computation, so
    XLA makes the *same* fusion and FMA-contraction choices for it in the
    gathered and oracle programs — an unrolled multiply-add chain sits in
    whatever fusion surrounds it, and a product that contracts to an FMA
    on one side but not the other costs one ulp of bit-identity.
    (``optimization_barrier`` does not help: XLA's CPU pipeline expands
    barriers away before fusion.)  Same per-element arithmetic as
    ``kernels.ref.loss_weighted_update_ref``.
    """
    gf = g.astype(jnp.float32)

    def _body(i, acc):
        pod = jax.lax.dynamic_index_in_dim(pods, i, 0, keepdims=False)
        return acc + w2[i] * pod.astype(jnp.float32)

    acc = jax.lax.fori_loop(0, pods.shape[0], _body, w1 * gf)
    merged = acc / denom
    return jnp.where(any_push, merged, gf).astype(g.dtype)


def _merge_sliced(w_global, payloads, delta, fmt, w1, w2, denom, any_push,
                  n_pods):
    """Receiver-side merge over *gathered payload rows*, one pod at a time.

    Decodes pod ``i``'s row of the gathered payload and folds it straight
    into the accumulator, so no pod-stacked fp32 tree is ever
    materialized.  Two properties hang on that:

    * **Wire bytes** — every intermediate is per-leaf shaped (no leading
      pod dimension), so GSPMD has nothing it can re-split over the pod
      mesh axis; the nibble-packed payload all-gather stays the only
      model-sized cross-pod traffic.
    * **Bit-identity** — the gathered and unplaced (oracle) programs run
      the *same* op graph downstream of the payload arrays, so XLA makes
      the same fusion/FMA-contraction choices in both and the merge is
      placement-invariant bit-for-bit.  (An ``optimization_barrier``
      around the stacked decode does **not** achieve this: XLA's CPU
      emitter contracts multiply-adds across barriers.)

    Blocked formats tile the rightmost block-divisible axis, so decoding
    a single pod row of the payload is exactly the row of the stacked
    decode.  The one exception is a leaf whose blocked axis *is* the pod
    stacking itself (e.g. stacked scalars): its payload rows are not
    per-pod, so it takes the stacked decode and is sliced afterwards.

    The decode-and-accumulate runs inside a ``lax.fori_loop`` for the
    same reason as :func:`_merge_leaf_jnp`: the loop body is its own XLA
    computation, compiled (and FMA-contracted) identically in the
    gathered and oracle programs.
    """
    g_leaves, treedef = jax.tree.flatten(w_global)
    p_leaves = treedef.flatten_up_to(payloads)
    d_leaves = treedef.flatten_up_to(delta)
    out = []
    for g, p, dl in zip(g_leaves, p_leaves, d_leaves):
        sliceable = all(getattr(a, "ndim", 0) >= 1
                        and int(a.shape[0]) == n_pods
                        for a in jax.tree.leaves(p))
        gf = g.astype(jnp.float32)
        if sliceable:
            def _body(i, acc, p=p, dl=dl, g=g):
                p_i = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, i, 0, keepdims=False), p)
                r = fmt.decode(p_i, tuple(dl.shape[1:]), dl.dtype)
                return acc + w2[i] * (g + r).astype(jnp.float32)
        else:
            def _body(i, acc, p=p, dl=dl, g=g):
                r = fmt.decode(p, dl.shape, dl.dtype)
                r_i = jax.lax.dynamic_index_in_dim(r, i, 0, keepdims=False)
                return acc + w2[i] * (g + r_i).astype(jnp.float32)
        acc = jax.lax.fori_loop(0, n_pods, _body, w1 * gf)
        merged = acc / denom
        out.append(jnp.where(any_push, merged, gf).astype(g.dtype))
    return jax.tree.unflatten(treedef, out)


def _merge_recv(w_global, recv, w1, w2, denom, any_push, use_kernel,
                mesh=None):
    """The reconstructed-tree merge (uncompressed or decode-fallback path).
    On a multi-device ``mesh`` the kernel runs per device on the gathered
    rows (``dist.wire.run_on_mesh``)."""
    if use_kernel:
        from jax.sharding import PartitionSpec
        from repro.dist.wire import run_on_mesh
        from repro.kernels import ops
        merge = run_on_mesh(ops.loss_weighted_update, mesh,
                            (PartitionSpec(),) * 6, PartitionSpec())
        return jax.tree.map(
            lambda g, p: merge(g, p, w1, w2, denom, any_push),
            w_global, recv)
    return jax.tree.map(
        lambda g, p: _merge_leaf_jnp(g, p, w1, w2, denom, any_push),
        w_global, recv)


def hermes_merge(pod_params: Tree, gates: jnp.ndarray, losses: jnp.ndarray,
                 w_global: Tree, L: jnp.ndarray, *,
                 live: Optional[jnp.ndarray] = None,
                 compression: str = "none", error: Optional[Tree] = None,
                 use_kernel: bool = False, rng=None,
                 track_error: bool = True,
                 mesh=None, pod_axis: str = "pod"
                 ) -> Tuple[Tree, Tree, Optional[Tree], jnp.ndarray]:
    """One gated loss-weighted merge over pod-stacked parameters.

    Args:
      pod_params: pytree whose leaves are (n_pods, ...) stacked local models.
      gates:      (n_pods,) bool — which pods push this round.
      losses:     (n_pods,) fp32 eval losses (the paper's L_temp per pod).
      live:       optional (n_pods,) bool membership mask.  Dead pods are
        zeroed out of the gates — and therefore out of every wire payload,
        merge weight, and refresh — through the same ``_gate_zero``
        machinery that protects against diverged replicas, so a dead pod's
        nonfinite leaves cannot poison the global model.  Restricted to the
        live rows, a masked merge is bit-identical to the same merge run at
        the smaller pod count (``tests/test_elastic_membership.py``).
      w_global:   unstacked global-model pytree.
      L:          scalar eval loss of the current global model.
      compression: wire-format name from the :mod:`repro.dist.wire`
        registry for the push deltas (each pushing pod transmits
        ``w_i - w_global``).
      error:      per-pod error-feedback residual tree (same structure as
        ``pod_params``) from the previous round, or None.
      use_kernel: route the merge through the Pallas kernels — the fused
        dequant-merge kernel when the format has a ``fused_merge`` hook
        (the compressed payload flows through the merge directly), else the
        fp32 loss-weighted-update kernel (identical math).  The wire
        format's own encode/decode kernels (the int4 nibble pack) follow
        the same flag.
      rng:        PRNG key for stochastic formats (int4); fold per round.
      track_error: compute and return the error-feedback residual.  With
        ``track_error=False`` on the fused-kernel path the payloads are
        never decoded at all — no reconstructed fp32 delta tree exists,
        even outside jit — and ``new_error`` is None.
      mesh:       optional ``jax.sharding.Mesh`` carrying a ``pod_axis``
        axis.  With a mesh, the merge ships the *encoded payloads*
        explicitly across the pod axis (``dist.wire.gather_payloads``:
        send-side ``PS(pod, U, ...)`` pin + optimization barrier +
        receive-side ``PS(None, U, ...)``), then merges **locally** from
        the gathered wire arrays — so the physical cross-pod collective
        is the nibble-packed ``(q_packed, scales)`` payload, never an
        implicit fp32 all-reduce that GSPMD would otherwise lower for the
        merge reduction.  ``mesh=None`` (the default) is the same math
        with an identity ship and is the bit-exactness oracle: a gather
        moves values without changing them, so gathered and unplaced
        merges agree bit-for-bit (``tests/test_round_lowering.py``).
      pod_axis:   mesh-axis name of the pod stacking (default ``"pod"``).

    Returns ``(new_pod_params, new_w_global, new_error, any_push)``.
    Closed-gate pods keep their local parameters and their pending error;
    on a fully closed round the global model is returned bit-identical.
    """
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    n_pods = int(gates.shape[0])
    any_push = jnp.any(gates)
    w1 = 1.0 / jnp.maximum(jnp.asarray(L, jnp.float32), _EPS)
    w2 = jnp.where(gates,
                   1.0 / jnp.maximum(losses.astype(jnp.float32), _EPS), 0.0)
    denom = w1 + jnp.sum(w2)

    # What the PS actually receives: gate-open pods ship (w_i - w_global),
    # compressed, with their accumulated error folded in (error feedback).
    # Closed pods transmit nothing — they are zero-masked out of every wire
    # and merge term so a diverged (nonfinite) local replica cannot poison
    # the global model through its 0-weight contribution (0 * nan = nan).
    def _gate_zero(leaf):
        return jnp.where(_pod_mask(gates, leaf), leaf, jnp.zeros_like(leaf))

    if compression != "none":
        fmt = get_format(compression, use_kernel=use_kernel, mesh=mesh,
                         axis=pod_axis)
        fused = use_kernel and fmt.fused_merge is not None
        delta = jax.tree.map(
            lambda p, g: _gate_zero(p - g[None]), pod_params, w_global)
        err_in = (None if error is None
                  else jax.tree.map(_gate_zero, error))
        # Sender-side: encode, and keep the residual local — error
        # feedback is each pod's private bookkeeping of what its own wire
        # dropped, so it never crosses the pod axis.  The decode-side
        # reconstruction is only built when the residual consumes it.
        payloads, _, residual = encode_tree(
            delta, fmt, error=err_in, rng=rng, with_residual=track_error)
        if not track_error:
            new_error = None
        elif error is None:
            new_error = jax.tree.map(_gate_zero, residual)
        else:
            new_error = jax.tree.map(
                lambda r, e: jnp.where(_pod_mask(gates, r), r, e),
                residual, error)
        # The ship: the encoded wire arrays are what cross the pod axis.
        payloads = gather_payloads(payloads, mesh, axis=pod_axis,
                                   n_pods=n_pods)
        if fused:
            # Gathered payloads flow through the merge: the fused kernel
            # dequantizes (q, scales) inside its VMEM pass.  A leaf whose
            # blocked axis is the pod axis itself (stacked scalars) has no
            # per-pod block layout, so it falls back to the decoded form.
            from repro.dist.wire import block_axis
            g_leaves, treedef = jax.tree.flatten(w_global)
            p_leaves = treedef.flatten_up_to(payloads)
            d_leaves = treedef.flatten_up_to(delta)

            def _fallback(g, p, dl):
                r = fmt.decode(p, dl.shape, dl.dtype)
                pods = pin_gathered(g[None] + r, mesh, axis=pod_axis,
                                    n_pods=n_pods)
                return _merge_leaf_jnp(g, pods, w1, w2, denom, any_push)

            merged = [
                fmt.fused_merge(g, p, w2, denom, any_push)
                if block_axis((n_pods,) + tuple(g.shape)) >= 1
                else _fallback(g, p, dl)
                for g, p, dl in zip(g_leaves, p_leaves, d_leaves)]
            new_global = jax.tree.unflatten(treedef, merged)
        elif use_kernel:
            # Kernel merge wants the stacked reconstruction; pin it
            # pod-replicated so GSPMD cannot re-shard the decode.
            rec = decode_tree(payloads, delta, fmt)
            rec = pin_gathered(rec, mesh, axis=pod_axis, n_pods=n_pods)
            recv = jax.tree.map(lambda g, d: g[None] + d, w_global, rec)
            new_global = _merge_recv(w_global, recv, w1, w2, denom,
                                     any_push, use_kernel, mesh)
        else:
            # Receiver-side: decode the *gathered* payloads row by row
            # and merge locally (see _merge_sliced for why slicewise).
            new_global = _merge_sliced(w_global, payloads, delta, fmt,
                                       w1, w2, denom, any_push, n_pods)
    else:
        # Uncompressed wire: the gate-zeroed replicas themselves are the
        # payload; they cross the pod axis the same explicit way.
        recv = jax.tree.map(_gate_zero, pod_params)
        recv = gather_payloads(recv, mesh, axis=pod_axis, n_pods=n_pods)
        new_error = error if track_error else None
        new_global = _merge_recv(w_global, recv, w1, w2, denom,
                                 any_push, use_kernel, mesh)

    # refresh: pushing pods restart from the merged global model
    new_pods = jax.tree.map(
        lambda p, g: jnp.where(_pod_mask(gates, p), g[None], p),
        pod_params, new_global)
    return new_pods, new_global, new_error, any_push


def hermes_round(pod_params: Tree, gup_state: Tree, pod_losses: jnp.ndarray,
                 w_global: Tree, L: jnp.ndarray, cfg: HermesConfig, *,
                 live: Optional[jnp.ndarray] = None,
                 error: Optional[Tree] = None,
                 use_kernel: Optional[bool] = None,
                 rng=None, mesh=None,
                 pod_axis: str = "pod") -> Dict[str, Any]:
    """One full Level-B round: per-pod Algorithm-1 gates, then the merge.

    The gate is the vmapped device twin of ``core.gup.gup_update`` (same
    z-score, alpha decay, and ring-buffer bookkeeping), so a Level-B run
    opens its gates on exactly the rounds the Level-A host simulator would.

    ``live`` is the elastic-membership mask (DESIGN.md §7): a dead pod's
    gate is forced shut, so it contributes nothing to the wire, the merge,
    or ``any_push`` — even when its replica or loss has gone nonfinite —
    and the returned ``gates`` reflect the masked values.  The per-pod GUP
    states still advance independently (they are vmapped), so a survivor's
    gate trajectory is unchanged by dead peers; the host resize path
    (``launch/elastic.py``) later drops the dead rows from every
    pod-stacked tree (shrink) or appends fresh ones seeded from
    ``w_global`` (grow — the newcomer's empty loss queue keeps its gate
    shut while it warms up, so incumbents never see the join).

    The merge is wrapped in ``jax.lax.cond`` on ``any_push``: the gate
    reduction is one scalar, and a fully closed round takes the identity
    branch — it never pays the merge collective's latency, and its output
    is bit-identical to the inputs (the ROADMAP "Gate/merge overlap" item).

    ``use_kernel=None`` resolves the kernel-vs-jnp dispatch from
    ``cfg.kernel_dispatch`` and the ``REPRO_WIRE_KERNEL`` env var
    (``dist.wire.resolve_kernel_dispatch``).

    ``mesh``/``pod_axis`` turn on the explicit payload-gather ship inside
    the merge (see :func:`hermes_merge`): the open branch's only
    cross-pod collective becomes the all-gather of the encoded wire
    arrays, and the ``hermes_dryrun --byte-audit`` round-level audit pins
    its lowered operand bytes to the registry bill.  Unplaced
    (``mesh=None``) rounds are the bit-exact oracle for gathered ones.

    Returns a dict: pod_params, w_global, gup, error, gates, any_push.
    """
    if use_kernel is None:
        use_kernel = resolve_kernel_dispatch(
            getattr(cfg, "kernel_dispatch", "auto"))
    gates, new_gup = jax.vmap(
        lambda s, x: gup_gate_jax(s, x, cfg))(gup_state, pod_losses)
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    # participation budget AFTER the gate+live mask and BEFORE any_push /
    # wire / merge / refresh: a deferred pod behaves exactly like a closed
    # one downstream (the per-pod GUP bookkeeping above already advanced
    # on the RAW gate decision — deferral is a transport policy, not a
    # gate override).  At participation_rate=1.0 this is `gates` itself.
    gates = admit_gates(gates, pod_losses, cfg, rng=rng)
    any_push = jnp.any(gates)
    err_in = error if cfg.error_feedback else None
    # hermes_merge tracks a residual for every non-"none" format (lossless
    # ones just carry exact zeros), so the closed branch must mirror that
    # exactly or lax.cond's output trees diverge.
    compressed = cfg.compression != "none"

    def _open(args):
        pods, wg, err = args
        new_pods, new_global, new_error, _ = hermes_merge(
            pods, gates, pod_losses, wg, L,
            compression=cfg.compression, error=err,
            use_kernel=use_kernel, rng=rng,
            track_error=cfg.error_feedback,
            mesh=mesh, pod_axis=pod_axis)
        return new_pods, new_global, new_error

    def _closed(args):
        pods, wg, err = args
        # A compressed error-tracking round with no residual yet starts one
        # at zero so both cond branches return the same pytree structure.
        if compressed and cfg.error_feedback and err is None:
            err = jax.tree.map(jnp.zeros_like, pods)
        return pods, wg, err

    new_pods, new_global, new_error = jax.lax.cond(
        any_push, _open, _closed, (pod_params, w_global, err_in))
    return {
        "pod_params": new_pods,
        "w_global": new_global,
        "gup": new_gup,
        "error": new_error,
        "gates": gates,
        "any_push": any_push,
    }


# ---------------------------------------------------------------------------
# Async double-buffered rounds: dispatch / commit halves (DESIGN.md §8)
# ---------------------------------------------------------------------------
#
# ``hermes_round`` is a barrier: every pod stalls on the payload gather
# before any of them takes another local step.  The pipelined protocol
# splits the round at exactly that collective:
#
#   dispatch(k):  gate -> encode -> *start* the payload gather; return an
#                 in-flight ``pending`` buffer and keep training.
#   commit(k):    one round later, merge the gathered round-k payload into
#                 w_global locally (zero collectives) and refresh the pods
#                 that pushed at round k.
#
# Between dispatch(k) and commit(k) no other commit runs, so the commit
# sees ``w_global`` exactly as dispatch encoded deltas against it — the
# merge arithmetic is the *synchronous* round-k merge, executed late.  The
# only semantic difference from sync is the refresh landing one round of
# local steps later (staleness-1); the local progress a pushing pod made in
# between is discarded by the refresh and its quantization residue stays in
# that pod's private error-feedback residual, so the bias still telescopes.
#
# The overlap itself comes from dispatch, commit, and the pod step being
# *separate* jitted programs: the gather's outputs feed only the commit
# executable, never the pod step, so the runtime's async dispatch runs the
# collective concurrently with the next lam local steps.  The round audit
# (``launch/round_audit.py``) pins this shape in the lowered HLO: the
# dispatch half carries exactly the billed payload gather (once, inside the
# ``any_push`` cond), and the commit half lowers with zero cross-pod
# collectives — the gather is provably off the pod step's critical path.


def hermes_dispatch(pod_params: Tree, gup_state: Tree,
                    pod_losses: jnp.ndarray, w_global: Tree, L: jnp.ndarray,
                    cfg: HermesConfig, *,
                    live: Optional[jnp.ndarray] = None,
                    error: Optional[Tree] = None,
                    rng=None, mesh=None,
                    pod_axis: str = "pod") -> Dict[str, Any]:
    """The dispatch half of a pipelined round: gate, encode, start the ship.

    Runs the same vmapped Algorithm-1 gates as :func:`hermes_round` (same
    ``live`` masking — a dead pod's gate is forced shut so it never makes
    it into the wire), then under ``lax.cond(any_push)`` encodes the
    gate-zeroed deltas with error feedback and starts the payload gather.
    A fully closed round takes the zeros branch: the pending buffer is a
    zero payload of the identical :func:`repro.dist.wire.payload_buffer_spec`
    structure (its gates row is all-False, so the matching commit is the
    identity) and no cross-pod collective lowers at all.

    The sender-side error residual updates *here*, at encode time — it is
    the pod's private bookkeeping of what this round's wire dropped and
    does not wait for the commit.

    Returns a dict:

    * ``gup``/``error``/``gates``/``any_push`` — as in ``hermes_round``.
    * ``pending`` — the in-flight round: ``{"payload", "gates", "losses",
      "L", "any_push"}``.  Thread it, unread, through the next ``lam``
      local steps and hand it to :func:`hermes_commit`; resizes must flush
      it first (``launch/elastic.py``).
    """
    gates, new_gup = jax.vmap(
        lambda s, x: gup_gate_jax(s, x, cfg))(gup_state, pod_losses)
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    # participation budget (see hermes_round / admit_gates): the pending
    # buffer carries the ADMITTED gates, so the matching commit merges
    # and refreshes exactly the pods whose payload actually shipped.
    gates = admit_gates(gates, pod_losses, cfg, rng=rng)
    n_pods = int(gates.shape[0])
    any_push = jnp.any(gates)
    compressed = cfg.compression != "none"
    track_error = cfg.error_feedback
    err_in = error if track_error else None
    fmt = get_format(cfg.compression, use_kernel=resolve_kernel_dispatch(
        getattr(cfg, "kernel_dispatch", "auto")), mesh=mesh, axis=pod_axis)

    def _gate_zero(leaf):
        return jnp.where(_pod_mask(gates, leaf), leaf, jnp.zeros_like(leaf))

    if compressed:
        def _open(args):
            pods, wg, err = args
            delta = jax.tree.map(
                lambda p, g: _gate_zero(p - g[None]), pods, wg)
            e_in = None if err is None else jax.tree.map(_gate_zero, err)
            payloads, _, residual = encode_tree(
                delta, fmt, error=e_in, rng=rng, with_residual=track_error)
            if not track_error:
                new_error = None
            elif err is None:
                new_error = jax.tree.map(_gate_zero, residual)
            else:
                new_error = jax.tree.map(
                    lambda r, e: jnp.where(_pod_mask(gates, r), r, e),
                    residual, err)
            shipped = gather_payloads(payloads, mesh, axis=pod_axis,
                                      n_pods=n_pods)
            return shipped, new_error

        def _closed(args):
            pods, wg, err = args
            if track_error and err is None:
                err = jax.tree.map(jnp.zeros_like, pods)
            spec = payload_buffer_spec(wg, cfg.compression, n_pods)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
            zeros = pin_gathered(zeros, mesh, axis=pod_axis, n_pods=n_pods)
            return zeros, err

        payload, new_error = jax.lax.cond(
            any_push, _open, _closed, (pod_params, w_global, err_in))
    else:
        # Uncompressed wire: the gate-zeroed replicas themselves are the
        # payload values, shipped in the format's payload-dict structure
        # so the pending buffer always matches payload_buffer_spec; the
        # error residual passes through unchanged (a lossless wire drops
        # nothing).
        def _open(pods):
            recv = jax.tree.map(_gate_zero, pods)
            payloads, _, _ = encode_tree(recv, fmt, with_residual=False)
            return gather_payloads(payloads, mesh, axis=pod_axis,
                                   n_pods=n_pods)

        def _closed(pods):
            spec = payload_buffer_spec(w_global, cfg.compression, n_pods)
            zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
            return pin_gathered(zeros, mesh, axis=pod_axis, n_pods=n_pods)

        payload = jax.lax.cond(any_push, _open, _closed, pod_params)
        new_error = err_in

    pending = {
        "payload": payload,
        "gates": gates,
        "losses": pod_losses.astype(jnp.float32),
        "L": jnp.asarray(L, jnp.float32),
        "any_push": any_push,
    }
    return {
        "gup": new_gup,
        "error": new_error,
        "gates": gates,
        "any_push": any_push,
        "pending": pending,
    }


def hermes_commit(pod_params: Tree, pending: Dict[str, Any], w_global: Tree,
                  *, cfg: HermesConfig,
                  live: Optional[jnp.ndarray] = None,
                  use_kernel: Optional[bool] = None,
                  mesh=None, pod_axis: str = "pod") -> Dict[str, Any]:
    """The commit half: merge an in-flight payload, one round late.

    Re-derives the Algorithm-2 weights from the *dispatch-time* losses
    carried in ``pending`` (so the merge is arithmetically the synchronous
    round the payload was encoded for), merges the gathered payload rows
    into ``w_global`` via the same sliced/fused/kernel machinery as
    :func:`hermes_merge`, and refreshes the pods whose gates were open at
    dispatch.  Lowers with **zero** cross-pod collectives: the payload was
    already gathered by the dispatch half, so the merge is local wherever
    the rows landed.

    ``live`` re-masks the dispatch-time gates with the *current*
    membership: a pod that died (or was dropped) after dispatching gets
    merge weight zero and no refresh, so its in-flight push never merges
    posthumously — this is the elastic flush rule (``launch/elastic.py``
    commits a pending buffer under the survivor mask before any resize).

    Returns ``{"pod_params", "w_global", "gates", "any_push"}`` where
    ``gates``/``any_push`` reflect the live re-mask (``any_push`` False
    means the commit was the identity).
    """
    if use_kernel is None:
        use_kernel = resolve_kernel_dispatch(
            getattr(cfg, "kernel_dispatch", "auto"))
    gates = pending["gates"].astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    losses = pending["losses"].astype(jnp.float32)
    L = pending["L"]
    n_pods = int(gates.shape[0])
    any_push = jnp.any(gates)
    w1 = 1.0 / jnp.maximum(jnp.asarray(L, jnp.float32), _EPS)
    w2 = jnp.where(gates,
                   1.0 / jnp.maximum(losses, _EPS), 0.0)
    denom = w1 + jnp.sum(w2)
    payload = pending["payload"]
    compressed = cfg.compression != "none"

    def _open(args):
        pods, wg = args
        if compressed:
            fmt = get_format(cfg.compression, use_kernel=use_kernel,
                             mesh=mesh, axis=pod_axis)
            fused = use_kernel and fmt.fused_merge is not None
            # The merge machinery only reads shapes/dtypes from the delta
            # tree; the values stayed on the sender.  (A dead-at-commit
            # pod's payload row was encoded while it was still finite, and
            # its w2 is zero, so the row contributes an exact 0.)
            delta_t = jax.tree.map(
                lambda g: jax.ShapeDtypeStruct((n_pods,) + tuple(g.shape),
                                               g.dtype), wg)
            if fused:
                from repro.dist.wire import block_axis
                g_leaves, treedef = jax.tree.flatten(wg)
                p_leaves = treedef.flatten_up_to(payload)
                d_leaves = treedef.flatten_up_to(delta_t)

                def _fallback(g, p, dl):
                    r = fmt.decode(p, dl.shape, dl.dtype)
                    stacked = pin_gathered(g[None] + r, mesh, axis=pod_axis,
                                           n_pods=n_pods)
                    return _merge_leaf_jnp(g, stacked, w1, w2, denom,
                                           any_push)

                merged = [
                    fmt.fused_merge(g, p, w2, denom, any_push)
                    if block_axis((n_pods,) + tuple(g.shape)) >= 1
                    else _fallback(g, p, dl)
                    for g, p, dl in zip(g_leaves, p_leaves, d_leaves)]
                new_global = jax.tree.unflatten(treedef, merged)
            elif use_kernel:
                rec = decode_tree(payload, delta_t, fmt)
                rec = pin_gathered(rec, mesh, axis=pod_axis, n_pods=n_pods)
                recv = jax.tree.map(lambda g, d: g[None] + d, wg, rec)
                new_global = _merge_recv(wg, recv, w1, w2, denom,
                                         any_push, use_kernel, mesh)
            else:
                new_global = _merge_sliced(wg, payload, delta_t, fmt,
                                           w1, w2, denom, any_push, n_pods)
        else:
            # Uncompressed pending payload rows are the replicas themselves,
            # shipped in the lossless format's payload-dict structure (so
            # the buffer matches payload_buffer_spec); decoding is identity.
            rep_t = jax.tree.map(
                lambda g: jax.ShapeDtypeStruct((n_pods,) + tuple(g.shape),
                                               g.dtype), wg)
            recv = decode_tree(payload, rep_t, cfg.compression)
            new_global = _merge_recv(wg, recv, w1, w2, denom,
                                     any_push, use_kernel, mesh)
        new_pods = jax.tree.map(
            lambda p, g: jnp.where(_pod_mask(gates, p), g[None], p),
            pods, new_global)
        return new_pods, new_global

    def _closed(args):
        return args

    new_pods, new_global = jax.lax.cond(
        any_push, _open, _closed, (pod_params, w_global))
    return {
        "pod_params": new_pods,
        "w_global": new_global,
        "gates": gates,
        "any_push": any_push,
    }


# ---------------------------------------------------------------------------
# Two-tier rounds: intra-cluster merge, cluster-crossing ship (DESIGN.md §10)
# ---------------------------------------------------------------------------
#
# The flat round's one collective gathers every pod's payload globally, so
# the slow tier carries ``n_pods`` model-sized arrays per open round.  The
# two-tier round splits the merge along the algebraic identity
#
#     merged = (w1*g + sum_i w2_i*(g + r_i)) / denom
#            =  g + (sum_c R_c) / denom,      R_c = sum_{i in c} w2_i * r_i
#
# (exact because denom = w1 + sum_i w2_i): each cluster reduces its own
# members' weighted decoded deltas to ONE model-shaped partial R_c on fast
# intra-cluster links (``gather_payloads_tiered`` keeps the payload rows
# cluster-sharded), re-encodes the stacked partials, and only that
# ``(n_clusters,)``-row payload crosses the slow cluster axis — slow-tier
# model-sized bytes scale with ``n_clusters``, not ``n_pods``.
#
# Two deliberate deviations from the flat round, both pinned by tests:
#
# * ``n_clusters=1`` does not run this path at all — every entry point
#   DELEGATES verbatim to its flat twin, so the parity oracle is
#   bit-identity by construction (the ISSUE 9 acceptance gate).
# * The cluster-tier re-encode carries NO error feedback: the requantize
#   noise of a lossy wire is zero-mean for the stochastic formats and one
#   extra quantization deep for the rest, and threading a per-cluster
#   residual through elastic resizes would couple every cluster's state.
#   Pod-tier error feedback is untouched (it updates at the sender's
#   encode, exactly as in ``hermes_merge``).
#
# The per-cluster partials are jnp-only (``lax.fori_loop`` accumulation,
# same bit-identity argument as ``_merge_leaf_jnp``); the fused/Pallas
# kernels keep serving the flat path that ``n_clusters=1`` lowers to.


def resolve_n_clusters(cfg: HermesConfig, n_clusters: Optional[int] = None,
                       cluster_sizes: Optional[Sequence[int]] = None) -> int:
    """Effective cluster count: explicit sizes > explicit count > config."""
    if cluster_sizes is not None:
        return len(cluster_sizes)
    if n_clusters is not None:
        return int(n_clusters)
    return int(getattr(cfg, "n_clusters", 1) or 1)


def _cluster_index(n_pods: int, n_clusters: int,
                   cluster_sizes: Optional[Sequence[int]] = None
                   ) -> np.ndarray:
    """Static pod-row -> cluster-id map, cluster-major (matching the
    ``launch.mesh.make_pod_mesh`` device layout)."""
    if cluster_sizes is None:
        assert n_pods % n_clusters == 0, (n_pods, n_clusters)
        return np.repeat(np.arange(n_clusters), n_pods // n_clusters)
    sizes = [int(s) for s in cluster_sizes]
    assert sum(sizes) == n_pods, (sizes, n_pods)
    assert all(s >= 1 for s in sizes), sizes
    return np.repeat(np.arange(len(sizes)), sizes)


def _cluster_partials(w_global: Tree, payloads: Tree, delta: Tree, fmt,
                      w2: jnp.ndarray, n_pods: int, n_clusters: int,
                      cluster_sizes: Optional[Sequence[int]] = None) -> Tree:
    """Per-cluster weighted partial sums ``R_c = sum_{i in c} w2_i * r_i``
    over gathered payload rows, stacked on a leading ``(n_clusters,)``.

    The balanced path reshapes each payload row axis ``(n_pods,) ->
    (C, ppc)`` and runs one ``lax.fori_loop`` over the within-cluster
    index, decoding all clusters' i-th members at once (a batched decode
    is valid because the blocked wire layout tiles a trailing axis for
    every sliceable leaf, independent of the leading row count).  After
    the tiered gather the row axis is cluster-sharded, so the reshape,
    the axis-1 indexing, and the accumulate are all cluster-local — no
    decoded fp32 ever crosses a cluster boundary.

    A leaf whose payload is not row-stacked (blocked axis == the pod
    stacking itself, e.g. stacked scalars) decodes whole and is reduced
    from the reconstruction — same fallback as ``_merge_sliced``.

    ``cluster_sizes`` (uneven clusters, the degraded post-shrink state —
    unplaced only) runs the SAME loop body over a zero-weight-padded
    ``(C, max_size)`` member grid: a padding slot replays row 0's payload
    at weight exactly ``0.0``, contributing a ``±0.0`` term — bit-for-bit
    what a live-masked member contributes on the balanced grid, which is
    how the resize-cycle oracle stays exact (the structurally different
    per-cluster loop this replaced cost a ulp of parity to differing
    fusion).  Accumulation in fp32, like every merge path here.
    """
    C = int(n_clusters)
    g_leaves, treedef = jax.tree.flatten(w_global)
    p_leaves = treedef.flatten_up_to(payloads)
    d_leaves = treedef.flatten_up_to(delta)
    out = []
    if cluster_sizes is None:
        ppc = n_pods // C
        w2r = w2.astype(jnp.float32).reshape((C, ppc))
        # balanced grid: the member grid is a local reshape (this is the
        # placed path — the rows are already cluster-sharded)
        regroup = lambda a: a.reshape((C, ppc) + tuple(a.shape[1:]))
    else:
        sizes = [int(s) for s in cluster_sizes]
        ppc = max(sizes)
        idx = np.zeros((C, ppc), np.int64)
        wm = np.zeros((C, ppc), np.float32)
        s0 = 0
        for c, s in enumerate(sizes):
            idx[c, :s] = np.arange(s0, s0 + s)
            wm[c, :s] = 1.0
            s0 += s
        flat_idx = jnp.asarray(idx.reshape(-1))
        w2r = (jnp.take(w2.astype(jnp.float32), flat_idx, axis=0)
               .reshape((C, ppc)) * jnp.asarray(wm))
        regroup = lambda a: (jnp.take(a, flat_idx, axis=0)
                             .reshape((C, ppc) + tuple(a.shape[1:])))
    for g, p, dl in zip(g_leaves, p_leaves, d_leaves):
        sliceable = all(getattr(a, "ndim", 0) >= 1
                        and int(a.shape[0]) == n_pods
                        for a in jax.tree.leaves(p))
        rest = tuple(dl.shape[1:])
        wshape = (C,) + (1,) * len(rest)
        if sliceable:
            pr = jax.tree.map(regroup, p)

            def _body(i, acc, pr=pr, rest=rest, dl=dl, wshape=wshape):
                p_i = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, i, 1, keepdims=False), pr)
                r = fmt.decode(p_i, (C,) + rest, dl.dtype)
                w = jax.lax.dynamic_index_in_dim(
                    w2r, i, 1, keepdims=False).reshape(wshape)
                return acc + w * r.astype(jnp.float32)
        else:
            r_full = fmt.decode(p, dl.shape, dl.dtype)
            rr = regroup(r_full)

            def _body(i, acc, rr=rr, wshape=wshape):
                r = jax.lax.dynamic_index_in_dim(rr, i, 1, keepdims=False)
                w = jax.lax.dynamic_index_in_dim(
                    w2r, i, 1, keepdims=False).reshape(wshape)
                return acc + w * r.astype(jnp.float32)
        acc = jax.lax.fori_loop(
            0, ppc, _body, jnp.zeros((C,) + rest, jnp.float32))
        out.append(acc)
    return jax.tree.unflatten(treedef, out)


def _merge_cluster(w_global: Tree, cpayloads: Tree, stacked_t: Tree, fmt,
                   denom, any_push, n_clusters: int) -> Tree:
    """Fold the gathered per-cluster partials into the global model:
    ``merged = g + (sum_c decode(R'_c)) / denom``.

    ``stacked_t`` carries the ``(n_clusters,) + leaf`` shapes/dtypes the
    cluster payload was encoded against (values never needed).
    Row-indexed decode per ``lax.fori_loop`` step, so every intermediate
    is leaf-shaped and the accumulate stays local wherever the gathered
    payload landed — same placement/bit-identity argument as
    ``_merge_sliced``.  There is deliberately no per-cluster weighting
    here: the commit-time cluster-drop mask zeroes dropped clusters'
    *payload rows* instead (:func:`_mask_cluster_rows`), so the merge
    graph is one and the same in the sync round and in the commit half —
    an in-loop multiplier, even by an exact ``1.0``, shifts XLA's fusion
    enough to cost a ulp of parity.
    """
    C = int(n_clusters)
    g_leaves, treedef = jax.tree.flatten(w_global)
    p_leaves = treedef.flatten_up_to(cpayloads)
    s_leaves = treedef.flatten_up_to(stacked_t)
    out = []
    for g, p, st in zip(g_leaves, p_leaves, s_leaves):
        sliceable = all(getattr(a, "ndim", 0) >= 1
                        and int(a.shape[0]) == C
                        for a in jax.tree.leaves(p))
        gf = g.astype(jnp.float32)
        rest = tuple(st.shape[1:])
        if sliceable:
            def _body(c, acc, p=p, rest=rest, st=st):
                p_c = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, c, 0, keepdims=False), p)
                r = fmt.decode(p_c, rest, st.dtype).astype(jnp.float32)
                return acc + r
        else:
            def _body(c, acc, p=p, st=st):
                rr = fmt.decode(p, st.shape, st.dtype)
                r = jax.lax.dynamic_index_in_dim(
                    rr, c, 0, keepdims=False).astype(jnp.float32)
                return acc + r
        acc = jax.lax.fori_loop(0, C, _body,
                                jnp.zeros(tuple(g.shape), jnp.float32))
        merged = gf + acc / denom
        out.append(jnp.where(any_push, merged, gf).astype(g.dtype))
    return jax.tree.unflatten(treedef, out)


def _mask_cluster_rows(cpayloads: Tree, keep_c: jnp.ndarray,
                       n_clusters: int) -> Tree:
    """Zero dropped clusters' rows of a gathered cluster payload.

    Every wire array of the cluster payload is ``(n_clusters,)``-leading
    by construction (it encodes a ``(n_clusters,) + leaf`` stack), and
    every format decodes an all-zero row to exact zeros — zeroed scales
    null int4/int8 rows, zeroed values null "none"/fp16 rows — so a
    masked row contributes an exact ``+0.0`` to the merge accumulate.
    Masking the operand instead of weighting inside the merge loop keeps
    :func:`_merge_cluster` a single graph for both the sync and the
    commit half (see its docstring).
    """
    C = int(n_clusters)

    def _mask(a):
        assert getattr(a, "ndim", 0) >= 1 and int(a.shape[0]) == C, (
            "cluster payload arrays are (n_clusters,)-leading by "
            "construction", getattr(a, "shape", None), C)
        m = keep_c.reshape((C,) + (1,) * (a.ndim - 1))
        return jnp.where(m, a, jnp.zeros_like(a))

    return jax.tree.map(_mask, cpayloads)


def hermes_cluster_merge(pod_params: Tree, gates: jnp.ndarray,
                         losses: jnp.ndarray, w_global: Tree, L: jnp.ndarray,
                         *, n_clusters: int,
                         cluster_sizes: Optional[Sequence[int]] = None,
                         live: Optional[jnp.ndarray] = None,
                         compression: str = "none",
                         error: Optional[Tree] = None, rng=None,
                         track_error: bool = True, use_kernel: bool = False,
                         mesh=None, pod_axis: str = "pod",
                         cluster_axis: str = "cluster"
                         ) -> Tuple[Tree, Tree, Optional[Tree], jnp.ndarray]:
    """The two-tier gated loss-weighted merge (see the section comment).

    Sender side is identical to :func:`hermes_merge`: gate-zeroed deltas,
    pod-tier encode, pod-private error feedback.  The ship then happens
    twice: the member payloads cross only the fast ``pod_axis``
    (:func:`repro.dist.wire.gather_payloads_tiered` keeps them
    cluster-sharded), each cluster reduces them to one weighted partial,
    and the re-encoded ``(n_clusters,)``-stacked partials are the only
    model-sized arrays crossing the slow ``cluster_axis``.  ``w1``, the
    per-pod weights, and ``denom`` are computed from replicated
    gates/losses, so the scalar bookkeeping needs no collective.

    ``cluster_sizes`` supports uneven clusters (the post-shrink degraded
    state) on the unplaced path only — a placed run flattens to the
    single-tier round until the grid rebalances (``launch/elastic.py``).
    Lossy formats requantize at the cluster tier WITHOUT error feedback
    (deliberate; zero-mean for stochastic formats — DESIGN.md §10).
    ``use_kernel`` pins only the wire format's encode/decode kernels (the
    int4 nibble pack), and only unplaced: the tiered payloads are sharded
    over two mesh tiers, for which no per-device kernel wrapper exists
    yet, so a placed two-tier round packs with the exact jnp twin.  The
    partial sums are jnp.

    Returns ``(new_pod_params, new_w_global, new_error, any_push)``.
    """
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    n_pods = int(gates.shape[0])
    C = int(n_clusters)
    assert C >= 1, C
    if cluster_sizes is not None:
        assert mesh is None, (
            "uneven cluster_sizes run unplaced; a placed run uses the "
            "flat round until the cluster grid rebalances")
    _cluster_index(n_pods, C, cluster_sizes)  # validates the split
    any_push = jnp.any(gates)
    w1 = 1.0 / jnp.maximum(jnp.asarray(L, jnp.float32), _EPS)
    w2 = jnp.where(gates,
                   1.0 / jnp.maximum(losses.astype(jnp.float32), _EPS), 0.0)
    denom = w1 + jnp.sum(w2)

    def _gate_zero(leaf):
        return jnp.where(_pod_mask(gates, leaf), leaf, jnp.zeros_like(leaf))

    fmt = get_format(compression, use_kernel=use_kernel and mesh is None)
    delta = jax.tree.map(
        lambda p, g: _gate_zero(p - g[None]), pod_params, w_global)
    if compression != "none":
        err_in = None if error is None else jax.tree.map(_gate_zero, error)
        payloads, _, residual = encode_tree(
            delta, fmt, error=err_in, rng=rng, with_residual=track_error)
        if not track_error:
            new_error = None
        elif error is None:
            new_error = jax.tree.map(_gate_zero, residual)
        else:
            new_error = jax.tree.map(
                lambda r, e: jnp.where(_pod_mask(gates, r), r, e),
                residual, error)
    else:
        # Lossless wire: unlike the flat merge (which ships gate-zeroed
        # replicas), the two-tier path ships the DELTA uniformly for all
        # formats — the partial-sum identity needs r_i, not w_i — and a
        # lossless wire drops nothing, so the residual passes through.
        payloads, _, _ = encode_tree(delta, fmt, with_residual=False)
        new_error = error if track_error else None

    # Fast tier: every cluster gathers its own members' payload rows.
    payloads = gather_payloads_tiered(payloads, mesh, axis=pod_axis,
                                      keep=cluster_axis, n_rows=n_pods)
    partials = _cluster_partials(w_global, payloads, delta, fmt, w2,
                                 n_pods, C, cluster_sizes)
    # Stacked (C,)+leaf partials in the leaf dtype, cluster-sharded, ready
    # for the slow-tier re-encode (a fully closed cluster's partial is
    # exact zeros, which every format encodes/decodes to exact zeros).
    # The barrier keeps the accumulate's arithmetic independent of what
    # consumes the re-encoded payload, so the sync round and the
    # dispatch/commit split produce bit-identical cluster payloads.
    partials = jax.tree.map(
        lambda a, g: a.astype(g.dtype), partials, w_global)
    partials = jax.lax.optimization_barrier(partials)
    partials = pin_tier(partials, mesh, lead=cluster_axis, n_rows=C)
    crng = None if rng is None else jax.random.fold_in(rng, 0x5C1)
    cpayloads, _, _ = encode_tree(partials, fmt, rng=crng,
                                  with_residual=False)
    # Barrier the wire bits too: in the dispatch/commit split the payload
    # is a cond output (a natural fusion boundary); pinning it here keeps
    # the sync round's encode arithmetic identical to dispatch's.
    cpayloads = jax.lax.optimization_barrier(cpayloads)
    # Slow tier: ONE payload per cluster crosses the cluster axis.
    cpayloads = gather_payloads(cpayloads, mesh, axis=cluster_axis,
                                n_pods=C)
    stacked_t = jax.tree.map(
        lambda g: jax.ShapeDtypeStruct((C,) + tuple(g.shape), g.dtype),
        w_global)
    new_global = _merge_cluster(w_global, cpayloads, stacked_t, fmt,
                                denom, any_push, C)
    new_pods = jax.tree.map(
        lambda p, g: jnp.where(_pod_mask(gates, p), g[None], p),
        pod_params, new_global)
    return new_pods, new_global, new_error, any_push


def hermes_cluster_round(pod_params: Tree, gup_state: Tree,
                         pod_losses: jnp.ndarray, w_global: Tree,
                         L: jnp.ndarray, cfg: HermesConfig, *,
                         n_clusters: Optional[int] = None,
                         cluster_sizes: Optional[Sequence[int]] = None,
                         live: Optional[jnp.ndarray] = None,
                         error: Optional[Tree] = None,
                         use_kernel: Optional[bool] = None,
                         rng=None, mesh=None, pod_axis: str = "pod",
                         cluster_axis: str = "cluster") -> Dict[str, Any]:
    """One full two-tier Level-B round: :func:`hermes_round` with the
    merge replaced by :func:`hermes_cluster_merge`.

    The cluster count resolves ``cluster_sizes`` > ``n_clusters`` >
    ``cfg.n_clusters``; at an effective count of 1 this function is
    *literally* :func:`hermes_round` — the flat twin is called verbatim,
    so the ``n_clusters=1`` parity pin is bit-identity by construction.
    ``use_kernel`` (``None``: ``cfg.kernel_dispatch``) picks the merge
    kernels on the flat path; the two-tier partials are jnp-only, so
    there it pins only the wire format's pack/unpack.  Returns the same
    dict as ``hermes_round``.
    """
    C = resolve_n_clusters(cfg, n_clusters, cluster_sizes)
    if use_kernel is None:
        use_kernel = resolve_kernel_dispatch(
            getattr(cfg, "kernel_dispatch", "auto"))
    if C <= 1:
        return hermes_round(pod_params, gup_state, pod_losses, w_global, L,
                            cfg, live=live, error=error,
                            use_kernel=use_kernel, rng=rng, mesh=mesh,
                            pod_axis=pod_axis)
    gates, new_gup = jax.vmap(
        lambda s, x: gup_gate_jax(s, x, cfg))(gup_state, pod_losses)
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    # same admission point as the flat round (the C<=1 delegation above
    # already applied it through hermes_round)
    gates = admit_gates(gates, pod_losses, cfg, rng=rng)
    any_push = jnp.any(gates)
    err_in = error if cfg.error_feedback else None
    compressed = cfg.compression != "none"

    def _open(args):
        pods, wg, err = args
        new_pods, new_global, new_error, _ = hermes_cluster_merge(
            pods, gates, pod_losses, wg, L, n_clusters=C,
            cluster_sizes=cluster_sizes, compression=cfg.compression,
            error=err, rng=rng, track_error=cfg.error_feedback,
            use_kernel=use_kernel, mesh=mesh, pod_axis=pod_axis,
            cluster_axis=cluster_axis)
        return new_pods, new_global, new_error

    def _closed(args):
        pods, wg, err = args
        if compressed and cfg.error_feedback and err is None:
            err = jax.tree.map(jnp.zeros_like, pods)
        return pods, wg, err

    new_pods, new_global, new_error = jax.lax.cond(
        any_push, _open, _closed, (pod_params, w_global, err_in))
    return {
        "pod_params": new_pods,
        "w_global": new_global,
        "gup": new_gup,
        "error": new_error,
        "gates": gates,
        "any_push": any_push,
    }


def hermes_cluster_dispatch(pod_params: Tree, gup_state: Tree,
                            pod_losses: jnp.ndarray, w_global: Tree,
                            L: jnp.ndarray, cfg: HermesConfig, *,
                            n_clusters: Optional[int] = None,
                            cluster_sizes: Optional[Sequence[int]] = None,
                            live: Optional[jnp.ndarray] = None,
                            error: Optional[Tree] = None,
                            rng=None, mesh=None, pod_axis: str = "pod",
                            cluster_axis: str = "cluster") -> Dict[str, Any]:
    """The dispatch half of a pipelined two-tier round.

    The async ``pending`` buffer splits per tier at the collective that
    matters: the fast intra-cluster gather AND the per-cluster partial
    reduction retire *inside* dispatch (they ride the fast links, so
    hiding them buys nothing), while the slow cluster-axis gather of the
    re-encoded partials is what stays in flight — ``pending`` carries a
    ``cluster_payload`` of ``(n_clusters,)``-row wire arrays instead of
    the flat half's ``(n_pods,)``-row ``payload``.  Only the slow tier is
    double-buffered, which is exactly the tier whose latency the overlap
    exists to hide.

    Delegates verbatim to :func:`hermes_dispatch` at an effective cluster
    count of 1.  A closed round's pending buffer is a zero cluster-tier
    payload (``payload_buffer_spec(w_global, mode, n_clusters)``); the
    sender-side error residual updates here, at encode time, exactly as
    in the flat dispatch.  Returns the ``hermes_dispatch`` dict shape
    with the tiered ``pending``.
    """
    C = resolve_n_clusters(cfg, n_clusters, cluster_sizes)
    if C <= 1:
        return hermes_dispatch(pod_params, gup_state, pod_losses, w_global,
                               L, cfg, live=live, error=error, rng=rng,
                               mesh=mesh, pod_axis=pod_axis)
    gates, new_gup = jax.vmap(
        lambda s, x: gup_gate_jax(s, x, cfg))(gup_state, pod_losses)
    gates = gates.astype(bool)
    if live is not None:
        gates = gates & live.astype(bool)
    # same admission point as the flat dispatch (the C<=1 delegation
    # above already applied it through hermes_dispatch)
    gates = admit_gates(gates, pod_losses, cfg, rng=rng)
    n_pods = int(gates.shape[0])
    if cluster_sizes is not None:
        assert mesh is None, (
            "uneven cluster_sizes run unplaced; a placed run uses the "
            "flat dispatch until the cluster grid rebalances")
    _cluster_index(n_pods, C, cluster_sizes)
    any_push = jnp.any(gates)
    compressed = cfg.compression != "none"
    track_error = cfg.error_feedback
    err_in = error if track_error else None
    w2 = jnp.where(gates,
                   1.0 / jnp.maximum(pod_losses.astype(jnp.float32), _EPS),
                   0.0)
    # wire kernels only unplaced, as in hermes_cluster_merge
    fmt = get_format(cfg.compression, use_kernel=resolve_kernel_dispatch(
        getattr(cfg, "kernel_dispatch", "auto")) and mesh is None)

    def _gate_zero(leaf):
        return jnp.where(_pod_mask(gates, leaf), leaf, jnp.zeros_like(leaf))

    def _open(args):
        pods, wg, err = args
        delta = jax.tree.map(
            lambda p, g: _gate_zero(p - g[None]), pods, wg)
        if compressed:
            e_in = None if err is None else jax.tree.map(_gate_zero, err)
            payloads, _, residual = encode_tree(
                delta, fmt, error=e_in, rng=rng, with_residual=track_error)
            if not track_error:
                new_error = None
            elif err is None:
                new_error = jax.tree.map(_gate_zero, residual)
            else:
                new_error = jax.tree.map(
                    lambda r, e: jnp.where(_pod_mask(gates, r), r, e),
                    residual, err)
        else:
            payloads, _, _ = encode_tree(delta, fmt, with_residual=False)
            new_error = err
        shipped = gather_payloads_tiered(payloads, mesh, axis=pod_axis,
                                         keep=cluster_axis, n_rows=n_pods)
        partials = _cluster_partials(wg, shipped, delta, fmt, w2,
                                     n_pods, C, cluster_sizes)
        # Same barrier as the sync merge: pins the partials' arithmetic
        # against downstream fusion so both halves ship identical bits.
        partials = jax.tree.map(lambda a, g: a.astype(g.dtype), partials, wg)
        partials = jax.lax.optimization_barrier(partials)
        partials = pin_tier(partials, mesh, lead=cluster_axis, n_rows=C)
        crng = None if rng is None else jax.random.fold_in(rng, 0x5C1)
        cpayloads, _, _ = encode_tree(partials, fmt, rng=crng,
                                      with_residual=False)
        cpayloads = jax.lax.optimization_barrier(cpayloads)
        cpayloads = gather_payloads(cpayloads, mesh, axis=cluster_axis,
                                    n_pods=C)
        return cpayloads, new_error

    def _closed(args):
        pods, wg, err = args
        if compressed and track_error and err is None:
            err = jax.tree.map(jnp.zeros_like, pods)
        spec = payload_buffer_spec(wg, cfg.compression, C)
        zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
        zeros = pin_gathered(zeros, mesh, axis=cluster_axis, n_pods=C)
        return zeros, err

    payload, new_error = jax.lax.cond(
        any_push, _open, _closed, (pod_params, w_global, err_in))
    pending = {
        "cluster_payload": payload,
        "gates": gates,
        "losses": pod_losses.astype(jnp.float32),
        "L": jnp.asarray(L, jnp.float32),
        "any_push": any_push,
    }
    return {
        "gup": new_gup,
        "error": new_error,
        "gates": gates,
        "any_push": any_push,
        "pending": pending,
    }


def hermes_cluster_commit(pod_params: Tree, pending: Dict[str, Any],
                          w_global: Tree, *, cfg: HermesConfig,
                          n_clusters: Optional[int] = None,
                          cluster_sizes: Optional[Sequence[int]] = None,
                          live: Optional[jnp.ndarray] = None,
                          mesh=None, pod_axis: str = "pod",
                          cluster_axis: str = "cluster") -> Dict[str, Any]:
    """The commit half of a pipelined two-tier round: fold an in-flight
    ``cluster_payload`` into the global model, one round late, with zero
    collectives.

    A flat pending buffer (no ``"cluster_payload"`` key — e.g. one
    dispatched by the delegating ``n_clusters=1`` path) commits through
    :func:`hermes_commit` verbatim.

    ``live`` re-masks at **cluster granularity**: a cluster partial is an
    inseparable weighted sum of its members' pushes, so if any pod whose
    gate was open at dispatch has since died, its whole cluster's partial
    is dropped (its payload rows are zeroed, an exact ``+0.0`` in the
    merge) and every w2 the dropped partial carried leaves the
    denominator — no posthumous merge, the same flush rule as the flat
    commit, enforced at the granularity the wire actually shipped.
    Survivors in a dropped cluster do not refresh (their push never
    merged), so the returned ``gates`` clear their rows too; a pod that
    died *ungated* costs its cluster nothing (its w2 was already zero at
    dispatch).

    Returns ``{"pod_params", "w_global", "gates", "any_push"}``.
    """
    if "cluster_payload" not in pending:
        return hermes_commit(pod_params, pending, w_global, cfg=cfg,
                             live=live, mesh=mesh, pod_axis=pod_axis)
    gates_d = pending["gates"].astype(bool)
    n_pods = int(gates_d.shape[0])
    C = resolve_n_clusters(cfg, n_clusters, cluster_sizes)
    cidx = jnp.asarray(_cluster_index(n_pods, C, cluster_sizes))
    lv = (jnp.ones((n_pods,), bool) if live is None
          else live.astype(bool))
    dead_gated = gates_d & ~lv
    dropped = jax.ops.segment_max(dead_gated.astype(jnp.int32), cidx,
                                  num_segments=C)
    keep_c = dropped == 0
    keep_pod = keep_c[cidx]
    gates = gates_d & lv & keep_pod
    losses = pending["losses"].astype(jnp.float32)
    L = pending["L"]
    any_push = jnp.any(gates)
    w1 = 1.0 / jnp.maximum(jnp.asarray(L, jnp.float32), _EPS)
    w2 = jnp.where(gates_d & keep_pod,
                   1.0 / jnp.maximum(losses, _EPS), 0.0)
    denom = w1 + jnp.sum(w2)
    payload = _mask_cluster_rows(pending["cluster_payload"], keep_c, C)
    fmt = get_format(cfg.compression, use_kernel=resolve_kernel_dispatch(
        getattr(cfg, "kernel_dispatch", "auto")) and mesh is None)
    stacked_t = jax.tree.map(
        lambda g: jax.ShapeDtypeStruct((C,) + tuple(g.shape), g.dtype),
        w_global)

    def _open(args):
        pods, wg = args
        new_global = _merge_cluster(wg, payload, stacked_t, fmt, denom,
                                    any_push, C)
        new_pods = jax.tree.map(
            lambda p, g: jnp.where(_pod_mask(gates, p), g[None], p),
            pods, new_global)
        return new_pods, new_global

    def _closed(args):
        return args

    new_pods, new_global = jax.lax.cond(
        any_push, _open, _closed, (pod_params, w_global))
    return {
        "pod_params": new_pods,
        "w_global": new_global,
        "gates": gates,
        "any_push": any_push,
    }
