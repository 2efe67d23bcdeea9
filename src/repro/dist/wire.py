"""Pluggable wire-format registry for the Hermes push payloads.

Replaces the old ``"none"|"fp16"|"int8"`` string-switch (DESIGN.md
§compression): every format is an object that owns its whole wire contract —

* ``encode(leaf) -> payload``    dict of arrays that cross the pod axis,
* ``decode(payload, shape, dtype)``  the receiver-side reconstruction,
* ``payload_bytes(shape)``       wire bytes billed for one leaf — **measured**
  by abstractly evaluating ``encode`` and summing the payload arrays'
  ``nbytes``, so the bill and the physical collective can never drift
  apart (the ``hermes_dryrun --byte-audit`` lowers the cross-pod
  all-gather and asserts its operand bytes equal this number),
* ``fused_merge`` (optional)     a hook that merges the *compressed* payload
  straight into the global model through the Pallas dequant-merge kernel,
  so the merge never round-trips a dequantized fp32 delta tree.

Sub-byte formats are physically sub-byte: ``int4`` ships ``q_packed`` —
two nibbles per int8 byte, paired within each 256-element block
(``kernels/pack.py``) — so the lowered collective moves half the bytes of
the int8 path, not just half the billed bytes.

Blocked formats are **shard-local**: the absmax blocks tile exactly one
axis (``block_axis`` — the rightmost whole-block axis) and every other axis
is untouched, so a pod/data/model-sharded leaf quantizes without any
resharding (the old layout flattened each leaf, which forced an all-gather
before quantization at the multi-pod mesh — ROADMAP "Sharded compression").
Block boundaries align with shard boundaries whenever the per-shard slice
of the blocked axis is a multiple of ``BLOCK``.

New formats register themselves::

    class MyFormat(WireFormat):
        name = "my4bit"
        ...
    register(MyFormat())

after which ``HermesConfig(compression="my4bit")`` validates and the whole
pipeline (Level-A billing, Level-B merge, benchmarks) picks it up.
"""
from __future__ import annotations

import copy
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.utils.tracing import GATHER, scope

Payload = Dict[str, jnp.ndarray]

BLOCK = 256  # absmax block along the last axis; kernels/quantize.py agrees


# ---------------------------------------------------------------------------
# Kernel dispatch policy
# ---------------------------------------------------------------------------

def resolve_kernel_dispatch(policy: str = "auto") -> bool:
    """Should quantize/pack/merge route through the Pallas kernels?

    Priority: ``REPRO_WIRE_KERNEL`` env var (``1/on`` forces the kernel
    path — interpret mode off-TPU — ``0/off`` forces jnp) > the config
    policy (``"on"`` / ``"off"``) > backend probe (``"auto"``: kernels on
    TPU, jnp twins elsewhere).  Lives here (not ``dist.compression``) so
    the wire formats themselves can consult it — the int4 nibble pack has
    a Pallas kernel and a jnp fallback; ``dist.compression`` re-exports.
    """
    if policy not in ("auto", "on", "off"):
        raise ValueError(
            f"kernel_dispatch policy {policy!r} (want auto|on|off)")
    env = os.environ.get("REPRO_WIRE_KERNEL", "").strip().lower()
    if env in ("1", "on", "true", "yes"):
        return True
    if env in ("0", "off", "false", "no"):
        return False
    if policy == "on":
        return True
    if policy == "off":
        return False
    return jax.default_backend() == "tpu"


def run_on_mesh(fn, mesh, in_specs, out_specs):
    """``fn`` run by every device of ``mesh`` on its own block (a
    ``shard_map`` over all mesh axes); ``fn`` itself off a mesh or on one
    device.  A Pallas TPU kernel cannot be partitioned automatically, so
    inside a multi-device program each kernel call goes through here:
    sender-side kernels with ``PartitionSpec(pod_axis)`` (each pod packs
    its own rows), receiver-side merges with ``PartitionSpec()`` (every
    device merges the gathered payload, as the replicated program
    would)."""
    if mesh is None or mesh.devices.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _norm_shape(shape) -> Tuple[int, ...]:
    """Scalars are treated as one-element vectors throughout."""
    s = tuple(int(x) for x in shape)
    return s if s else (1,)


def _numel(shape) -> int:
    return int(math.prod(_norm_shape(shape)))


def _shard_factor(rule, mesh) -> int:
    """Devices the rule splits one axis over (1 when unsharded/mesh-free)."""
    if rule is None or mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    members = (rule,) if isinstance(rule, str) else tuple(rule)
    f = 1
    for m in members:
        f *= sizes.get(m, 1)
    return f


def block_axis(shape, *, axes: Optional[Sequence[Optional[str]]] = None,
               rules=None) -> int:
    """Which axis the absmax blocks tile for a leaf of ``shape``.

    The rightmost axis whose size is a whole number of blocks, else the
    last axis (zero-padded to blocks).  Whole-block axes keep the layout
    shard-local whenever the per-shard slice is also a multiple of
    ``BLOCK`` — e.g. a 151936-vocab logits dim sharded 16-way can never
    align with 256-blocks, but its 4096 embed axis can, so the blocks tile
    embed and the compress step stays collective-free (the
    ``hermes_dryrun`` assertion).  Deterministic in the shape alone, so
    encode and decode never need side-channel metadata.

    ``axes``/``rules`` are an optional **advisory** sharding hint (ROADMAP
    "Block-axis/shard-rule coupling"): ``axes`` names the leaf's logical
    axes (the ``param_axes`` twin) and ``rules`` is a mesh-bound
    ``dist.sharding.AxisRules``.  With the hint, the rightmost
    block-divisible axis whose *per-shard slice* is still block-divisible
    (unsharded axes trivially qualify) is preferred over a
    sharded-but-misaligned one; when no divisible axis aligns, the choice
    falls back to the shape-only rule.  Encode/decode always use the
    shape-only path — the hint exists for placement planning and for the
    dryrun audit that asserts no assigned architecture's layout actually
    diverges from it (if one ever does, the shard-local guarantee is lost
    and the collective-free assertion fails loudly).
    """
    s = _norm_shape(shape)
    if axes is not None and rules is not None:
        axs = list(axes) + [None] * (len(s) - len(axes))
        for ax in range(len(s) - 1, -1, -1):
            if s[ax] % BLOCK != 0:
                continue
            f = _shard_factor(rules.rules.get(axs[ax]) if axs[ax] else None,
                              rules.mesh)
            if s[ax] % f == 0 and (s[ax] // f) % BLOCK == 0:
                return ax
    for ax in range(len(s) - 1, -1, -1):
        if s[ax] % BLOCK == 0:
            return ax
    return len(s) - 1


class WireFormat:
    """One wire format.  Subclass, set ``name``, implement the contract."""

    name: str = "?"
    lossy: bool = True
    stochastic: bool = False  # True -> ``encode`` consumes an rng key
    # kernel dispatch pinned by ``with_kernels`` (None: resolve per call)
    use_kernel: Optional[bool] = None
    kernel_mesh = None      # with a mesh, kernels run per device
    kernel_axis: str = "pod"

    def encode(self, x: jnp.ndarray, *, rng=None) -> Payload:
        raise NotImplementedError

    def decode(self, payload: Payload, shape, dtype) -> jnp.ndarray:
        raise NotImplementedError

    def _encode_hinted(self, x: jnp.ndarray, *, ax: Optional[int] = None,
                       rng=None) -> Payload:
        """Billing twin of ``encode`` with the blocked axis forced to
        ``ax`` (``None`` = the format's own shape-only choice).  The base
        implementation ignores the hint — formats without a blocked layout
        bill the same bytes whatever the placement — so plain
        ``encode(self, x, *, rng=None)`` subclasses stay valid.  Blocked
        formats override it so a ``block_axis`` AxisRules hint changes the
        *measured* payload, not just the planned one.
        """
        return self.encode(x, rng=rng)

    def payload_bytes(self, shape, *, axes=None, rules=None) -> int:
        """Wire bytes for one leaf of ``shape``: the **measured** size of
        what ``encode`` emits (``sum(arr.nbytes)`` over the payload via
        ``jax.eval_shape`` — block padding included), not a parallel
        billing formula.  Level-A billing, the benchmarks, and the dryrun
        byte audit all read this, so whatever the lowered collective
        physically ships is by construction what gets billed.  Formats
        whose true wire cost differs from their jax payload (e.g. an
        entropy-coded format) may still override.

        ``axes``/``rules`` are the optional ``block_axis`` sharding hint.
        The memo is keyed on ``(shape, resolved blocked axis)`` — not the
        shape alone — so a hint that moves the blocked axis re-measures
        instead of returning the stale shape-only bill (two placements of
        the same shape may legitimately bill different payloads).
        """
        s = _norm_shape(shape)
        ax = block_axis(s, axes=axes, rules=rules)
        # per-instance memo: encode is pure in (shape, blocked axis), so
        # one abstract evaluation per (format, shape, axis) is enough
        cache = self.__dict__.setdefault("_measured_bytes", {})
        key = (s, ax)
        got = cache.get(key)
        if got is None:
            p = jax.eval_shape(
                lambda x: self._encode_hinted(
                    x, ax=ax, rng=jax.random.PRNGKey(0) if self.stochastic
                    else None),
                jax.ShapeDtypeStruct(s, jnp.float32))
            got = int(sum(math.prod(a.shape) * a.dtype.itemsize
                          for a in jax.tree.leaves(p)))
            cache[key] = got
        return got

    # Optional fused-merge hook: merge the payload of a pod-stacked delta
    # leaf directly into the global leaf ``g`` without materializing the
    # dequantized delta.  ``None`` means the merge falls back to
    # decode + loss_weighted_update.
    fused_merge = None

    def with_kernels(self, use_kernel: Optional[bool], mesh=None,
                     axis: str = "pod") -> "WireFormat":
        """This format with its kernel dispatch pinned to ``use_kernel``
        (``None`` keeps ``resolve_kernel_dispatch()``), for a round placed
        on ``mesh`` with pods stacked along ``axis``: on a multi-device
        mesh every kernel call runs per device (:func:`run_on_mesh`)."""
        if use_kernel is None and mesh is None:
            return self
        pinned = copy.copy(self)
        pinned.use_kernel = None if use_kernel is None else bool(use_kernel)
        pinned.kernel_mesh, pinned.kernel_axis = mesh, axis
        return pinned

    def _kernels(self) -> bool:
        return (resolve_kernel_dispatch() if self.use_kernel is None
                else self.use_kernel)

    def _per_pod(self, fn):
        """``fn`` (one array in, one out, pod-stacked) run sender-side:
        each device on its own pods' rows."""
        from jax.sharding import PartitionSpec
        spec = PartitionSpec(self.kernel_axis)
        return run_on_mesh(fn, self.kernel_mesh, spec, spec)

    def _replicated(self, fn, n_args: int):
        """``fn`` run receiver-side on gathered (replicated) operands."""
        from jax.sharding import PartitionSpec
        return run_on_mesh(fn, self.kernel_mesh,
                           (PartitionSpec(),) * n_args, PartitionSpec())


# ---------------------------------------------------------------------------
# Built-in formats
# ---------------------------------------------------------------------------

class NoneFormat(WireFormat):
    """fp32 leaves verbatim: 4 bytes/element."""

    name = "none"
    lossy = False

    def encode(self, x, *, rng=None):
        return {"x": x}

    def decode(self, payload, shape, dtype):
        return payload["x"].reshape(shape).astype(dtype)


class Fp16Format(WireFormat):
    """Half-precision cast (the paper's §IV-D format): 2 bytes/element."""

    name = "fp16"

    def encode(self, x, *, rng=None):
        return {"h": x.astype(jnp.float16)}

    def decode(self, payload, shape, dtype):
        return payload["h"].reshape(shape).astype(dtype)


def _pad_axis(x: jnp.ndarray, ax: int, to: int) -> jnp.ndarray:
    """Zero-pad axis ``ax`` of ``x`` up to length ``to`` (no-op if equal)."""
    if x.shape[ax] == to:
        return x
    widths = [(0, 0)] * x.ndim
    widths[ax] = (0, to - x.shape[ax])
    return jnp.pad(x, widths)


class BlockedIntFormat(WireFormat):
    """Shared machinery of the blocked integer formats (int8, int4).

    Wire layout per leaf: with ``ax = block_axis(shape)``, ``d = shape[ax]``
    and ``nb = ceil(d/BLOCK)``:

        q:      shape with axis ax -> d    int8 (one per *real* element)
        scales: shape with axis ax -> nb   fp32 (per-block absmax/qmax)

    Every other axis is preserved verbatim (shard-local — no leaf flatten).
    ``q`` holds the quantized values in [-qmax, qmax]; the zero padding the
    block reduce needs internally is **trimmed off the wire** (it carries
    no information — the receiver re-pads locally), so the measured
    payload is exactly one byte per element plus the scales, whatever the
    leaf shape.  Sub-byte subclasses repack ``q`` into a genuinely
    narrower wire payload (``Int4Format`` ships two nibbles per byte) so
    the physical collective — and therefore the measured bill — is
    sub-byte too.
    """

    bits: int = 8
    qmax: int = 127

    def _round(self, y: jnp.ndarray, rng) -> jnp.ndarray:
        return jnp.round(y)

    def _quantize(self, x, rng, ax: Optional[int] = None):
        """Whole-block quantization: (q_padded, scales, s, ax, d, nb).

        ``ax=None`` resolves the blocked axis from the shape alone (the
        encode/decode contract); billing passes the hint-resolved axis so
        the measured payload tracks the planned placement.
        """
        s = _norm_shape(x.shape)
        if ax is None:
            ax = block_axis(s)
        d = s[ax]
        nb = -(-d // BLOCK)
        xb = _pad_axis(x.reshape(s).astype(jnp.float32), ax, nb * BLOCK)
        xb = xb.reshape(s[:ax] + (nb, BLOCK) + s[ax + 1:])
        scale = jnp.max(jnp.abs(xb), axis=ax + 1, keepdims=True) \
            / float(self.qmax)
        scale = jnp.maximum(scale, 1e-12)
        q = jnp.clip(self._round(xb / scale, rng),
                     -float(self.qmax), float(self.qmax))
        return (q.astype(jnp.int8).reshape(
                    s[:ax] + (nb * BLOCK,) + s[ax + 1:]),
                scale.astype(jnp.float32).reshape(
                    s[:ax] + (nb,) + s[ax + 1:]),
                s, ax, d, nb)

    def encode(self, x, *, rng=None):
        return self._encode_hinted(x, rng=rng)

    def _encode_hinted(self, x, *, ax=None, rng=None):
        q, scale, s, ax, d, nb = self._quantize(x, rng, ax)
        idx = (slice(None),) * ax + (slice(0, d),)
        return {"q": q[idx], "scales": scale}

    def decode(self, payload, shape, dtype):
        q, sc = payload["q"], payload["scales"]
        s = _norm_shape(shape)
        ax = block_axis(s)
        d = s[ax]
        nb = sc.shape[ax]
        q = _pad_axis(q, ax, nb * BLOCK)  # re-grow the trimmed wire array
        xb = q.reshape(s[:ax] + (nb, BLOCK) + s[ax + 1:]).astype(jnp.float32) \
            * jnp.expand_dims(sc, ax + 1)
        flat = xb.reshape(s[:ax] + (nb * BLOCK,) + s[ax + 1:])
        idx = (slice(None),) * ax + (slice(0, d),)
        return flat[idx].reshape(shape).astype(dtype)

    def fused_merge(self, g, payload, w2, denom, any_push):
        # ax mirrors what encode() chose for the stacked delta leaf, whose
        # shape is exactly (n_pods,) + g.shape.
        from repro.kernels import ops
        n_pods = payload["q"].shape[0]
        ax = block_axis((n_pods,) + tuple(g.shape))
        merge = self._replicated(
            lambda *a: ops.dequant_merge(*a, axis=ax), 6)
        return merge(g, payload["q"], payload["scales"], w2, denom,
                     any_push)


class Int8Format(BlockedIntFormat):
    """Blockwise int8 absmax (round-to-nearest): 1 byte/element + scales."""

    name = "int8"
    bits, qmax = 8, 127


class Int4Format(BlockedIntFormat):
    """Blockwise int4, **stochastic rounding**, **nibble-packed** payload.

    ``q = floor(x/scale + u)``, ``u ~ U[0, 1)`` — unbiased in expectation
    (E[q·scale] = x inside the representable range), so quantization noise
    averages out across rounds instead of drifting; the error-feedback
    residual one level up (``compress_tree``) absorbs what is left.  Pass a
    fresh ``rng`` per round; with ``rng=None`` the rounding falls back to a
    fixed key (deterministic, still bounded-error, no longer unbiased
    across rounds).

    The wire payload is ``q_packed``: two nibbles per int8 byte, paired
    *within one quantization block* so the pack is exactly as shard-local
    as the blocks themselves.  Whole 256-blocks use the
    ``kernels/pack.py`` kernel layout (packed byte ``k`` of a block =
    element ``k`` low nibble, element ``k + 128`` high); a leaf's final
    partial block of ``rem`` elements pairs ``(k, k + ceil(rem/2))``
    instead (``kernels/ref.py:pack_tail_ref``), so even a short blocked
    axis ships ~0.5 B/element — the blocked axis carries
    ``(d//256)*128 + ceil((d%256)/2)`` wire bytes, which is what
    ``payload_bytes`` now measures.  Pack/unpack dispatch follows the
    same policy as the merge kernels: the round resolves
    ``HermesConfig.kernel_dispatch`` once and pins it with
    :meth:`with_kernels` (unpinned, ``resolve_kernel_dispatch()`` decides:
    ``REPRO_WIRE_KERNEL`` > backend probe), with exact jnp twins on the
    fallback path.  Pack and unpack run sender-side, on each pod's own
    rows; the fused merge consumes ``q_packed`` directly
    (``ops.dequant_merge_packed``), so the unpacked int8 tree never lands
    in HBM either.
    """

    name = "int4"
    bits, qmax = 4, 7
    stochastic = True

    HALF = BLOCK // 2  # packed bytes per whole block

    def _round(self, y, rng):
        if rng is None:
            rng = jax.random.PRNGKey(0)
        return jnp.floor(y + jax.random.uniform(rng, y.shape))

    @classmethod
    def packed_len(cls, d: int) -> int:
        """Packed wire bytes along a blocked axis of ``d`` elements."""
        return (d // BLOCK) * cls.HALF + (d % BLOCK + 1) // 2

    def encode(self, x, *, rng=None):
        return self._encode_hinted(x, rng=rng)

    def _encode_hinted(self, x, *, ax=None, rng=None):
        from repro.kernels import ref
        q, scale, s, ax, d, nb = self._quantize(x, rng, ax)
        nf = d // BLOCK                      # whole blocks
        rem = d % BLOCK
        parts = []
        if nf:
            head = jax.lax.slice_in_dim(q, 0, nf * BLOCK, axis=ax)
            if self._kernels():
                from repro.kernels import ops
                parts.append(self._per_pod(
                    lambda h: ops.pack_int4(h, axis=ax))(head))
            else:
                parts.append(ref.pack_nibbles_ref(head, axis=ax, block=BLOCK))
        if rem:
            tail = jax.lax.slice_in_dim(q, nf * BLOCK, d, axis=ax)
            parts.append(ref.pack_tail_ref(tail, axis=ax))
        packed = parts[0] if len(parts) == 1 else jnp.concatenate(parts, ax)
        return {"q_packed": packed, "scales": scale}

    def unpack_payload(self, payload: Payload, shape) -> jnp.ndarray:
        """Wire ``q_packed`` -> the trimmed int8 ``q`` (one per element)."""
        from repro.kernels import ref
        s = _norm_shape(shape)
        ax = block_axis(s)
        d = s[ax]
        nf = d // BLOCK
        rem = d % BLOCK
        packed = payload["q_packed"]
        parts = []
        if nf:
            head = jax.lax.slice_in_dim(packed, 0, nf * self.HALF, axis=ax)
            if self._kernels():
                from repro.kernels import ops
                parts.append(self._per_pod(
                    lambda h: ops.unpack_int4(h, axis=ax))(head))
            else:
                parts.append(ref.unpack_nibbles_ref(head, axis=ax,
                                                    block=BLOCK))
        if rem:
            tail = jax.lax.slice_in_dim(packed, nf * self.HALF,
                                        packed.shape[ax], axis=ax)
            parts.append(ref.unpack_tail_ref(tail, rem, axis=ax))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, ax)

    def decode(self, payload, shape, dtype):
        q = self.unpack_payload(payload, shape)
        return super().decode({"q": q, "scales": payload["scales"]},
                              shape, dtype)

    def fused_merge(self, g, payload, w2, denom, any_push):
        from repro.kernels import ops
        n_pods = payload["q_packed"].shape[0]
        ax = block_axis((n_pods,) + tuple(g.shape))
        merge = self._replicated(
            lambda *a: ops.dequant_merge_packed(*a, axis=ax), 6)
        return merge(g, payload["q_packed"], payload["scales"], w2, denom,
                     any_push)


# ---------------------------------------------------------------------------
# The in-flight round: what a dispatched-but-uncommitted payload looks like
# ---------------------------------------------------------------------------

def payload_buffer_spec(tree: Any, mode: str, n_pods: int) -> Any:
    """Abstract spec of one round's in-flight payload buffer.

    For an unstacked parameter ``tree``, return a pytree of
    ``jax.ShapeDtypeStruct`` mirroring what ``encode_tree`` emits for the
    ``(n_pods,)``-stacked delta: one payload dict per leaf, with every
    wire array's post-gather shape and dtype.  This is the double buffer
    the async pipelined round threads between its dispatch half (producer
    — the gather of exactly these arrays is started) and its commit half
    (consumer — the merge reads them one round later): the dispatch
    ``lax.cond``'s closed branch materializes zeros of this spec so open
    and closed rounds return one structure, and the audit asserts the
    gathered operands of the dispatch lowering match these specs.

    Shapes come from ``jax.eval_shape`` of the format's own ``encode`` —
    the same measurement ``payload_bytes`` bills — so the pending buffer
    can never drift from the physical wire.
    """
    fmt = get_format(mode)
    leaves, treedef = jax.tree.flatten(tree)
    stacked = [jax.ShapeDtypeStruct((int(n_pods),) + _norm_shape(x.shape),
                                    jnp.float32) for x in leaves]
    rng = jax.random.PRNGKey(0)

    def _enc(xs):
        return [fmt.encode(
                    x, rng=(jax.random.fold_in(rng, i)
                            if fmt.stochastic else None))
                for i, x in enumerate(xs)]

    payloads = jax.eval_shape(_enc, stacked)
    return jax.tree.unflatten(treedef, payloads)


# ---------------------------------------------------------------------------
# The cross-pod ship: explicit payload gather
# ---------------------------------------------------------------------------

@scope(GATHER)
def gather_payloads(payloads: Any, mesh, *, axis: str = "pod",
                    n_pods: Optional[int] = None) -> Any:
    """Ship an encoded payload tree across the ``axis`` mesh axis.

    This is the production cross-pod collective: every array whose leading
    dimension is the pod-stacking axis is pinned to ``PS(axis, U, U, ...)``
    on the send side, passed through ``jax.lax.optimization_barrier``, and
    re-pinned to ``PS(None, U, U, ...)`` on the receive side — so XLA must
    lower exactly one all-gather *of the wire arrays themselves* over the
    pod axis.  The barrier + double constraint is the idiom the dryrun
    byte audit proved out: without it GSPMD back-propagates the replicated
    sharding through the elementwise encode and hoists the all-gather onto
    the fp32 delta, silently shipping 2-8x the billed bytes.  Non-pod
    dimensions stay ``UNCONSTRAINED`` on both sides, so intra-pod
    data/model sharding is preserved through the ship (no resharding, no
    memory blow-up) and the local merge that follows reads gathered
    payloads in its own layout.

    Identity when ``mesh`` is ``None``, when ``axis`` is not a mesh axis,
    or when the pod axis has size 1 — the unplaced call is therefore the
    bit-exactness oracle for the gathered one (a gather moves values, it
    never changes them).  Arrays whose leading dimension is *not* the pod
    stacking (``n_pods``) — e.g. the scales of a leaf whose blocked axis
    is the pod axis itself — are passed through unpinned and left to
    GSPMD; such leaves take the decode fallback in the merge anyway.
    """
    if mesh is None:
        return payloads
    names = tuple(getattr(mesh, "axis_names", ()))
    if axis not in names:
        return payloads
    size = int(dict(zip(names, mesh.devices.shape)).get(axis, 1))
    if size <= 1:
        return payloads
    from jax.sharding import NamedSharding, PartitionSpec

    U = PartitionSpec.UNCONSTRAINED

    def _pinnable(a) -> bool:
        if getattr(a, "ndim", 0) < 1:
            return False
        lead = int(a.shape[0])
        if n_pods is not None and lead != int(n_pods):
            return False
        return lead % size == 0

    def _pin(a, spec0):
        if not _pinnable(a):
            return a
        spec = PartitionSpec(spec0, *([U] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    sent = jax.tree.map(lambda a: _pin(a, axis), payloads)
    sent = jax.lax.optimization_barrier(sent)
    return jax.tree.map(lambda a: _pin(a, None), sent)


def pin_gathered(tree: Any, mesh, *, axis: str = "pod",
                 n_pods: Optional[int] = None) -> Any:
    """Re-assert the receiver-side constraint on values *derived from* a
    gathered payload tree (the ``PS(None, U, ...)`` half of
    :func:`gather_payloads`, without the send pin or the barrier).

    Sharding constraints do not flow through arbitrary downstream ops:
    after the payload all-gather, GSPMD is free to decide that the decode
    of each pod's slice is cheaper *re-sharded* over the pod axis — each
    pod dequantizes its own row — which then forces a model-sized fp32
    collective-permute/all-reduce to recombine the merge terms.  Pinning
    the decoded (pod-stacked, post-gather) tree pod-replicated keeps the
    dequant-and-accumulate local, so the packed wire arrays stay the only
    model-sized traffic crossing ``axis``.  Identity under the same
    conditions as :func:`gather_payloads`.
    """
    if mesh is None:
        return tree
    names = tuple(getattr(mesh, "axis_names", ()))
    if axis not in names:
        return tree
    size = int(dict(zip(names, mesh.devices.shape)).get(axis, 1))
    if size <= 1:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec

    U = PartitionSpec.UNCONSTRAINED

    def _pin(a):
        if getattr(a, "ndim", 0) < 1:
            return a
        lead = int(a.shape[0])
        if n_pods is not None and lead != int(n_pods):
            return a
        if lead % size != 0:
            return a
        spec = PartitionSpec(None, *([U] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    return jax.tree.map(_pin, tree)


@scope(GATHER)
def gather_payloads_tiered(payloads: Any, mesh, *, axis: str = "pod",
                           keep: str = "cluster",
                           n_rows: Optional[int] = None) -> Any:
    """The intra-cluster half of the two-tier ship (DESIGN.md §10): gather
    a row-stacked payload tree across the fast ``axis`` tier while KEEPING
    it sharded over the slow ``keep`` tier.

    Same pin + ``optimization_barrier`` + re-pin idiom as
    :func:`gather_payloads`, with tiered specs: the send side is
    ``PS((keep, axis), U, ...)`` (every pod holds its own row slice of the
    cluster-major stacking), the receive side ``PS(keep, U, ...)`` — each
    cluster ends up holding ALL of its own members' rows, replicated
    across its pods, while never seeing another cluster's.  XLA therefore
    lowers the gather with replica groups confined to single clusters:
    intra-cluster traffic only, which is exactly what the tiered byte
    audit classifies.

    Falls back to the flat :func:`gather_payloads` when ``keep`` is not a
    mesh axis (a flat pod mesh has no slow tier); identity when ``mesh``
    is ``None``.  ``n_rows`` guards which arrays count as row-stacked,
    like ``n_pods`` in :func:`gather_payloads`.
    """
    if mesh is None:
        return payloads
    names = tuple(getattr(mesh, "axis_names", ()))
    if keep not in names:
        return gather_payloads(payloads, mesh, axis=axis, n_pods=n_rows)
    sizes = dict(zip(names, mesh.devices.shape))
    total = int(sizes.get(keep, 1)) * int(sizes.get(axis, 1))
    from jax.sharding import NamedSharding, PartitionSpec

    U = PartitionSpec.UNCONSTRAINED
    send0 = (keep, axis) if axis in names else (keep,)

    def _pin(a, spec0):
        if getattr(a, "ndim", 0) < 1:
            return a
        lead = int(a.shape[0])
        if n_rows is not None and lead != int(n_rows):
            return a
        if lead % max(1, total) != 0:
            return a
        spec = PartitionSpec(spec0, *([U] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    sent = jax.tree.map(lambda a: _pin(a, send0), payloads)
    sent = jax.lax.optimization_barrier(sent)
    return jax.tree.map(lambda a: _pin(a, keep), sent)


def pin_tier(tree: Any, mesh, *, lead, n_rows: Optional[int] = None) -> Any:
    """Re-assert a leading-axis constraint on values derived from a tiered
    gather — :func:`pin_gathered` generalized to an arbitrary leading
    spec.

    ``lead`` is the PartitionSpec entry for the row axis: an axis name
    (``"cluster"``: keep the rows cluster-sharded so the per-cluster
    partial sums stay local), a tuple of names, or ``None`` (fully
    replicated, the classic receive pin).  Trailing dims stay
    ``UNCONSTRAINED``.  Arrays whose leading dim is not ``n_rows`` (when
    given) or does not divide the named axes' total size pass through
    unpinned; identity without a mesh or when any named axis is absent.
    """
    if mesh is None:
        return tree
    names = tuple(getattr(mesh, "axis_names", ()))
    members = (() if lead is None else
               ((lead,) if isinstance(lead, str) else tuple(lead)))
    if any(m not in names for m in members):
        return tree
    sizes = dict(zip(names, mesh.devices.shape))
    total = 1
    for m in members:
        total *= int(sizes.get(m, 1))
    spec0 = (None if not members else
             (members[0] if len(members) == 1 else members))
    from jax.sharding import NamedSharding, PartitionSpec

    U = PartitionSpec.UNCONSTRAINED

    def _pin(a):
        if getattr(a, "ndim", 0) < 1:
            return a
        lead_n = int(a.shape[0])
        if n_rows is not None and lead_n != int(n_rows):
            return a
        if lead_n % max(1, total) != 0:
            return a
        spec = PartitionSpec(spec0, *([U] * (a.ndim - 1)))
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec))

    return jax.tree.map(_pin, tree)


# ---------------------------------------------------------------------------
# Round-level wire audit: what SHOULD cross the pod axis, and did it
# ---------------------------------------------------------------------------

# jnp dtype name -> HLO shape-string dtype (the subset wire arrays use)
_HLO_DTYPE = {"float32": "f32", "float16": "f16", "bfloat16": "bf16",
              "int8": "s8", "uint8": "u8", "int32": "s32", "uint32": "u32",
              "bool": "pred", "float64": "f64", "int4": "s4", "uint4": "u4"}


def wire_operand_specs(tree: Any, mode: str, n_pods: int
                       ) -> List[Tuple[str, Tuple[int, ...], int]]:
    """The expected per-device all-gather operands of one round's ship.

    For an unstacked abstract parameter ``tree``, return one
    ``(hlo_dtype, dims, bytes)`` entry per wire array that a pod-sharded
    (``PS("pod")``-only) round must gather across the pod axis: each
    encoded payload array of the ``(n_pods,) + leaf`` stacked tree, as the
    single-pod row shard ``(1,) + rest`` a sender device holds.  ``none``
    ships the stacked leaves themselves.  Shapes come from
    ``jax.eval_shape`` of the format's own ``encode`` — the same
    measurement ``payload_bytes`` bills — so matching the lowered
    collective operands against these specs *is* the billing-vs-wire
    equality proof at round level.
    """
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((int(n_pods),) + tuple(s.shape),
                                       s.dtype), tree)
    if mode == "none":
        payload_leaves = jax.tree.leaves(stacked)
    else:
        fmt = get_format(mode)

        def _enc(t):
            leaves = jax.tree.leaves(t)
            rng = jax.random.PRNGKey(0)
            return [fmt.encode(
                        leaf,
                        rng=(jax.random.fold_in(rng, i)
                             if fmt.stochastic else None))
                    for i, leaf in enumerate(leaves)]

        payload_leaves = jax.tree.leaves(jax.eval_shape(_enc, stacked))
    specs = []
    for a in payload_leaves:
        if a.ndim < 1 or int(a.shape[0]) != int(n_pods):
            continue  # not pod-stacked: never pinned, never gathered
        dims = (1,) + tuple(int(d) for d in a.shape[1:])
        nbytes = int(a.dtype.itemsize)
        for d in dims:
            nbytes *= d
        specs.append((_HLO_DTYPE.get(a.dtype.name, a.dtype.name),
                      dims, nbytes))
    return specs


def cluster_wire_operand_specs(tree: Any, mode: str, n_clusters: int
                               ) -> List[Tuple[str, Tuple[int, ...], int]]:
    """The expected **slow-tier** operands of one two-tier round: the
    re-encoded per-cluster partial sums.

    The two-tier merge (DESIGN.md §10) reduces each cluster's gated
    weighted deltas to ONE model-shaped partial, stacks the partials on a
    leading ``(n_clusters,)`` axis, re-encodes, and ships only that across
    the cluster axis — so the cluster-crossing operand set is exactly
    :func:`wire_operand_specs` of the same tree with ``n_clusters`` rows:
    per-device dims ``(1,) + rest`` of the encode of the
    ``(n_clusters,) + leaf`` stacked tree.  Slow-tier model-sized bytes
    therefore scale with ``n_clusters``, not ``n_pods`` — the byte-scaling
    claim the tiered audit asserts.
    """
    return wire_operand_specs(tree, mode, n_clusters)


def classify_round_collectives(records: List[Dict], specs,
                               *, control_bytes: Optional[int] = None,
                               n_pods: int = 2,
                               n_devices: Optional[int] = None,
                               n_clusters: Optional[int] = None,
                               cluster_records: Optional[List[Dict]] = None,
                               cluster_specs=None) -> Dict[str, Any]:
    """Match a lowered round's cross-pod collective operands against the
    expected wire specs (:func:`wire_operand_specs`).

    Compatibility alias: the classification (and the control-traffic
    allowance constant) moved to :mod:`repro.analysis.collectives`, where
    the ``collective-placement`` rule reuses it.  Imported lazily so the
    wire registry keeps zero analyzer dependencies at import time.

    With ``n_clusters`` (two-tier rounds), ``records`` must already be the
    pod-crossing set and ``cluster_records`` the cluster-crossing subset
    (``repro.analysis.hlo_parse.cross_pod_collectives`` with the two
    divisors); the intra-cluster remainder is classified against ``specs``
    (the fast tier) and ``cluster_records`` against ``cluster_specs``
    (:func:`cluster_wire_operand_specs`), returned under a ``"cluster"``
    key.  ``n_devices`` is accepted for signature symmetry with the rule.
    """
    from repro.analysis.collectives import classify_collectives
    del n_devices
    if n_clusters is None or cluster_records is None:
        return classify_collectives(records, specs,
                                    control_bytes=control_bytes,
                                    n_pods=n_pods)
    cluster_ids = {id(r) for r in cluster_records}
    intra = [r for r in records if id(r) not in cluster_ids]
    out = classify_collectives(intra, specs, control_bytes=control_bytes,
                               n_pods=n_pods)
    out["cluster"] = classify_collectives(
        cluster_records, list(cluster_specs or ()),
        control_bytes=control_bytes, n_pods=n_pods)
    return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, WireFormat] = {}


def register(fmt: WireFormat, *, overwrite: bool = False) -> WireFormat:
    """Add ``fmt`` to the registry (``overwrite=True`` to replace)."""
    if not overwrite and fmt.name in _REGISTRY:
        raise ValueError(f"wire format {fmt.name!r} already registered")
    _REGISTRY[fmt.name] = fmt
    return fmt


def get_format(name: str, *, use_kernel: Optional[bool] = None, mesh=None,
               axis: str = "pod") -> WireFormat:
    """The registered format ``name``, its kernel dispatch pinned by
    :meth:`WireFormat.with_kernels` when ``use_kernel`` or ``mesh`` is
    given."""
    try:
        fmt = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown compression mode {name!r} "
                         f"(want one of {available_formats()})") from None
    return fmt.with_kernels(use_kernel, mesh, axis)


def available_formats() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


register(NoneFormat())
register(Fp16Format())
register(Int8Format())
register(Int4Format())
