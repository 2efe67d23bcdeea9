"""Tree-level push-payload compression with error feedback (DESIGN.md §compression).

The Hermes merge collective only fires on gate-open rounds, but when it
fires the payload is a whole model delta — compressing it is the second
half of the paper's communication story (§IV-D uses fp16; blocked int8 and
int4+stochastic-rounding are our beyond-paper upgrades).

The per-leaf wire contract lives in the :mod:`repro.dist.wire` registry
(``WireFormat``: encode / decode / payload_bytes / optional fused-merge
hook); this module provides the pytree-level operations on top of it:

* :func:`encode_tree` / :func:`compress_tree` — encode a payload tree with
  an *error-feedback* residual: the caller keeps ``error`` (what the wire
  dropped last round) and adds it back into the next payload, making the
  compression bias telescope to zero over rounds instead of accumulating
  (Karimireddy et al., 2019).
* :func:`payload_bytes` — the single per-leaf billing function the
  simulator and benchmarks use.

Kernel-vs-jnp dispatch policy lives in
:func:`repro.dist.wire.resolve_kernel_dispatch` (one source of truth —
import it from there), overridable via ``HermesConfig.kernel_dispatch``
or the ``REPRO_WIRE_KERNEL`` env var so CPU CI can exercise the Pallas
kernel path in interpret mode.

Blocked formats are shard-local (blocks tile the last axis only; leading
axes — including the pod axis of a stacked delta — are untouched), so the
compress step inserts no collectives on a sharded mesh.  The flat
``quantize_int8`` / ``dequantize_int8`` pair below keeps the original
whole-array layout of ``kernels/quantize.py`` for callers that want it.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.dist.wire import (  # noqa: F401  (re-exported API)
    BLOCK, WireFormat, available_formats, gather_payloads, get_format,
    pin_gathered, register,
)
from repro.dist.wire import resolve_kernel_dispatch as _resolve_dispatch

Tree = Any


def _use_kernel() -> bool:
    return _resolve_dispatch()


# ---------------------------------------------------------------------------
# Flat int8 layout (kernels/quantize.py compatible)
# ---------------------------------------------------------------------------

def quantize_int8(x: jnp.ndarray, *, block: int = BLOCK
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: any shape -> (q: (nblocks, block) int8, scales: (nblocks, 1) f32).

    Blockwise absmax over the *flattened* array: scale = max|x_block| / 127,
    q = round(x / scale).  Same wire format as ``kernels.quantize``
    (which pads the row count up to its grid multiple — both dequantize via
    flat[:n]).  Prefer the shard-local tree API for sharded payloads.
    """
    if _use_kernel():
        from repro.kernels import ops
        return ops.quantize_int8(x, block=block)
    from repro.kernels import ref
    return ref.quantize_int8_ref(x, block=block)


def dequantize_int8(q: jnp.ndarray, scales: jnp.ndarray, shape
                    ) -> jnp.ndarray:
    """Inverse of :func:`quantize_int8`; trailing block padding discarded."""
    if _use_kernel():
        from repro.kernels import ops
        return ops.dequantize_int8(q, scales, tuple(shape))
    from repro.kernels import ref
    return ref.dequantize_int8_ref(q, scales, shape)


# ---------------------------------------------------------------------------
# Tree-level encode / error feedback
# ---------------------------------------------------------------------------

def _format(mode: Union[str, WireFormat]) -> WireFormat:
    return mode if isinstance(mode, WireFormat) else get_format(mode)


def encode_tree(tree: Tree, mode: Union[str, WireFormat] = "int8",
                error: Optional[Tree] = None, rng=None,
                with_residual: bool = True
                ) -> Tuple[Tree, Optional[Tree], Optional[Tree]]:
    """Encode a payload tree; returns ``(payloads, reconstructed, new_error)``.

        eff           = tree + error          (error defaults to zeros)
        payloads      = encode(eff)           per leaf, shard-local
        reconstructed = decode(payloads)      what the receiver sees
        new_error     = eff - reconstructed   (exact, in the leaf dtype)

    ``payloads`` mirrors ``tree``'s structure with one payload dict per
    leaf (recover the leaves with ``treedef.flatten_up_to``).  ``rng`` seeds
    stochastic formats (int4); each leaf gets an independent fold.

    ``with_residual=False`` skips the decode entirely and returns
    ``(payloads, None, None)`` — the fused-merge path uses this when no
    error-feedback state is tracked, so no reconstructed fp32 tree is ever
    built, even eagerly.

    ``mode`` is a registered format name, or a format the round pinned
    with ``get_format(name, use_kernel=..., mesh=...)``.
    """
    fmt = _format(mode)
    eff = tree if error is None else jax.tree.map(jnp.add, tree, error)
    leaves, treedef = jax.tree.flatten(eff)
    if fmt.stochastic and rng is None:
        rng = jax.random.PRNGKey(0)
    payloads, rec, err = [], [], []
    for i, leaf in enumerate(leaves):
        key = jax.random.fold_in(rng, i) if fmt.stochastic else None
        p = fmt.encode(leaf, rng=key)
        payloads.append(p)
        if with_residual:
            r = fmt.decode(p, leaf.shape, leaf.dtype)
            rec.append(r)
            err.append(leaf - r)
    if not with_residual:
        return jax.tree.unflatten(treedef, payloads), None, None
    return (jax.tree.unflatten(treedef, payloads),
            jax.tree.unflatten(treedef, rec),
            jax.tree.unflatten(treedef, err))


def decode_tree(payloads: Tree, template: Tree,
                mode: Union[str, WireFormat] = "int8") -> Tree:
    """Decode a payload tree back into ``template``'s structure/shapes.

    ``payloads`` is the per-leaf payload-dict tree :func:`encode_tree`
    emits (possibly after :func:`gather_payloads` shipped it across the
    pod axis); ``template`` supplies each leaf's shape and dtype.  The
    receiver side of the wire: decoding *gathered* payloads is
    value-identical to decoding them before the gather, which is what
    keeps the unplaced merge the bit-exactness oracle for the
    payload-gather one.  ``mode`` as in :func:`encode_tree`.
    """
    fmt = _format(mode)
    leaves, treedef = jax.tree.flatten(template)
    p_leaves = treedef.flatten_up_to(payloads)
    return jax.tree.unflatten(
        treedef, [fmt.decode(p, leaf.shape, leaf.dtype)
                  for p, leaf in zip(p_leaves, leaves)])


def compress_tree(tree: Tree, mode: str = "int8",
                  error: Optional[Tree] = None, rng=None) -> Tuple[Tree, Tree]:
    """Compress-decompress a payload tree with error feedback.

    Returns ``(reconstructed, new_error)`` where ``reconstructed`` is what
    crosses the wire after a round trip and ``new_error`` is the residual
    the sender must fold into its *next* payload.
    """
    _, rec, err = encode_tree(tree, mode, error=error, rng=rng)
    return rec, err


# ---------------------------------------------------------------------------
# Billing
# ---------------------------------------------------------------------------

def payload_bytes(tree: Tree, mode: str = "int8", *,
                  param_axes: Optional[Tree] = None, rules=None) -> int:
    """Wire bytes for one push of ``tree`` under ``mode``.

    *Measured*, per leaf, from the format's own encoded payload
    (``WireFormat.payload_bytes``: abstract-eval of ``encode``, summed
    ``nbytes``): int8 is 1 B/element + one fp32 scale per 256-block, int4
    the nibble-packed ~0.5 B/element + scales, fp16/none 2/4 B/element.
    Leaf dtypes are ignored — the wire format, not the in-memory dtype,
    is billed; ``hermes_dryrun --byte-audit`` proves the lowered
    collective ships exactly these bytes.

    ``param_axes``/``rules`` forward the ``block_axis`` sharding hint per
    leaf (``param_axes`` mirrors ``tree`` with one logical-axes tuple per
    leaf); the per-format memo is keyed on the hint-resolved blocked axis,
    so a placement change re-measures instead of returning a stale bill.
    Formats that override ``payload_bytes`` without hint support are only
    reachable on the hint-free path.
    """
    fmt = get_format(mode)
    leaves = jax.tree.leaves(tree)
    if param_axes is None:
        return sum(fmt.payload_bytes(leaf.shape) for leaf in leaves)
    axes_leaves = jax.tree.leaves(
        param_axes, is_leaf=lambda x: isinstance(x, tuple))
    return sum(fmt.payload_bytes(leaf.shape, axes=axes, rules=rules)
               for leaf, axes in zip(leaves, axes_leaves))
