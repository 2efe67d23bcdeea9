"""Pallas tile lint: static BlockSpec-vs-shape and dtype checks.

The wire kernels (``kernels/quantize.py``, ``kernels/pack.py``,
``kernels/dequant_merge.py``, ``kernels/loss_weighted_update.py``) encode
hard layout contracts — int8 tiles are (32, 128), nibble packing pairs a
256-element block with a 128-byte packed row, the fused merge accumulates
in fp32.  All of them are visible *statically*: a traced ``pallas_call``
eqn carries its ``grid_mapping`` (one ``BlockMapping`` per operand, with
the block shape and the full array shape/dtype) and the kernel body
jaxpr.  This rule walks them without executing anything.

Named violation classes:

* ``tile-misaligned`` — a grid-tiled dimension's block size does not
  evenly divide the array dimension (the kernel would read/write a
  partial tile XLA has to mask every invocation).
* ``tile-below-minimum`` — a tiled trailing dim below the dtype's minimum
  TPU tile: lane (last dim) a multiple of 128, sublane (second-to-last)
  >= 8 (f32) / 16 (bf16,f16) / 32 (int8,uint8,fp8).  Dimensions mapped at
  the full array extent are unblocked and exempt (e.g. the merge's
  per-pod scalar rows).
* ``low-precision-accumulate`` — an add/sub/dot inside the kernel body
  produces f16/bf16: accumulation must run in fp32 (the merge prologue
  contract).
* ``pack-pairing-drift`` — the nibble-pack constants disagree across
  ``kernels/pack.py``, ``kernels/dequant_merge.py`` and the
  ``dist.wire`` int4 format (HALF must stay BLOCK // 2 everywhere, or
  packed payload layouts silently diverge from the bill).
"""
from __future__ import annotations

from typing import Any, List

import jax

from repro.analysis.core import Rule, Target, Violation, register_rule

# minimum (sublane) tile per dtype; the lane (last-dim) minimum is always
# 128 (see the Pallas/TPU tiling table)
MIN_SUBLANE = {
    "float32": 8, "int32": 8, "uint32": 8,
    "bfloat16": 16, "float16": 16,
    "int8": 32, "uint8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32,
}
LANE = 128
LOW_PRECISION = ("float16", "bfloat16")
_ACCUM_PRIMS = ("add", "sub", "dot_general", "cumsum", "reduce_sum")


def iter_pallas_eqns(jaxpr) -> List[Any]:
    """All pallas_call eqns reachable from ``jaxpr`` (descends into
    call/cond/scan sub-jaxprs)."""
    out = []
    seen = set()

    def walk(jp):
        if id(jp) in seen:
            return
        seen.add(id(jp))
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn)
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)

    walk(jaxpr)
    return out


def _sub_jaxprs(param: Any):
    from jax.extend.core import Jaxpr, ClosedJaxpr
    if isinstance(param, ClosedJaxpr):
        yield param.jaxpr
    elif isinstance(param, Jaxpr):
        yield param
    elif isinstance(param, (tuple, list)):
        for p in param:
            yield from _sub_jaxprs(p)


def _blocked_size(b: Any, full: int) -> int:
    """One BlockSpec entry's block size: an int, or (jax >= 0.9) a
    ``Blocked(block_size=...)`` object.  None / squeezed entries mean the
    dim is not blocked, so the block spans the whole array dim."""
    if isinstance(b, int):
        return int(b)
    if type(b).__name__ == "Blocked":
        return int(b.block_size)
    return full


def _block_mappings(eqn) -> List[Any]:
    gm = eqn.params.get("grid_mapping")
    return list(getattr(gm, "block_mappings", ()) or ())


@register_rule
class PallasTileLint(Rule):
    """Trace ``target.fn(*target.example_args)`` and lint every
    ``pallas_call`` it contains; with no ``fn``, check only the static
    pack-pairing constants.  ``check_constants`` toggles the latter."""

    name = "pallas-tile"

    def __init__(self, *, check_constants: bool = False,
                 min_sublane=None):
        self.check_constants = check_constants
        self.min_sublane = dict(min_sublane or MIN_SUBLANE)

    # -- BlockSpec / dtype checks ------------------------------------------
    def _lint_mapping(self, label: str, bm) -> List[Violation]:
        out: List[Violation] = []
        # jax >= 0.9 names the operand aval ``array_aval``
        sd = (getattr(bm, "array_shape_dtype", None)
              or getattr(bm, "array_aval", None))
        if sd is None:
            return out
        ashape = tuple(int(d) for d in sd.shape)
        dtype = str(sd.dtype)
        raw = tuple(getattr(bm, "block_shape", ()) or ())
        bshape = tuple(_blocked_size(b, ashape[i])
                       for i, b in enumerate(raw)) if raw else ashape
        if len(bshape) != len(ashape):
            return out
        tiled = [i for i in range(len(ashape)) if bshape[i] != ashape[i]]
        for i in tiled:
            if bshape[i] <= 0 or ashape[i] % bshape[i] != 0:
                out.append(self.violation(
                    "tile-misaligned",
                    f"{label}: block dim {i} = {bshape[i]} does not tile "
                    f"array dim {ashape[i]} ({dtype}{list(ashape)} vs "
                    f"block {list(bshape)})",
                    operand=label, dim=i, block=list(bshape),
                    array=list(ashape), dtype=dtype))
        nd = len(ashape)
        if nd >= 1 and (nd - 1) in tiled and bshape[-1] % LANE != 0:
            out.append(self.violation(
                "tile-below-minimum",
                f"{label}: tiled lane dim {bshape[-1]} is not a multiple "
                f"of {LANE} ({dtype} block {list(bshape)})",
                operand=label, block=list(bshape), dtype=dtype))
        min_sub = self.min_sublane.get(dtype)
        if (nd >= 2 and (nd - 2) in tiled and min_sub
                and bshape[-2] % min_sub != 0):
            out.append(self.violation(
                "tile-below-minimum",
                f"{label}: tiled sublane dim {bshape[-2]} is below/off the "
                f"{dtype} minimum tile ({min_sub}, {LANE})",
                operand=label, block=list(bshape), dtype=dtype,
                min_sublane=min_sub))
        return out

    def _lint_kernel_body(self, label: str, eqn) -> List[Violation]:
        out: List[Violation] = []
        body = eqn.params.get("jaxpr")
        if body is None:
            return out
        for sub in _sub_jaxprs(body):
            stack = [sub]
            seen = set()
            while stack:
                jp = stack.pop()
                if id(jp) in seen:
                    continue
                seen.add(id(jp))
                for e in jp.eqns:
                    for v in e.params.values():
                        stack.extend(_sub_jaxprs(v))
                    if e.primitive.name not in _ACCUM_PRIMS:
                        continue
                    for ov in e.outvars:
                        dt = str(getattr(getattr(ov, "aval", None),
                                         "dtype", ""))
                        if dt in LOW_PRECISION:
                            out.append(self.violation(
                                "low-precision-accumulate",
                                f"{label}: kernel body {e.primitive.name} "
                                f"produces {dt}; accumulate in fp32 and "
                                f"cast on the way out",
                                operand=label, primitive=e.primitive.name,
                                dtype=dt))
        return out

    # -- static constants (nibble-pack pairing) ----------------------------
    def _lint_constants(self) -> List[Violation]:
        from repro.dist import wire
        from repro.kernels import dequant_merge as dqm
        from repro.kernels import pack as pk
        from repro.kernels import quantize as qz

        out: List[Violation] = []
        blocks = {"dist.wire": wire.BLOCK, "kernels.pack": pk.BLOCK,
                  "kernels.dequant_merge": dqm.BLOCK,
                  "kernels.quantize": qz.BLOCK}
        if len(set(blocks.values())) != 1:
            out.append(self.violation(
                "pack-pairing-drift",
                f"quantization BLOCK constants diverged: {blocks}",
                blocks=blocks))
        halves = {"kernels.pack": pk.HALF,
                  "kernels.dequant_merge": dqm.HALF,
                  "dist.wire.Int4Format": wire.Int4Format.HALF}
        want = wire.BLOCK // 2
        bad = {k: v for k, v in halves.items() if v != want}
        if bad:
            out.append(self.violation(
                "pack-pairing-drift",
                f"nibble-pack HALF must be BLOCK//2 = {want} everywhere, "
                f"got {bad}", halves=halves, expected=want))
        if pk.LANE != LANE or dqm.LANE != LANE:
            out.append(self.violation(
                "pack-pairing-drift",
                f"kernel LANE constants drifted from {LANE}: "
                f"pack={pk.LANE} dequant_merge={dqm.LANE}",
                pack=pk.LANE, dequant_merge=dqm.LANE))
        return out

    def check(self, target: Target) -> List[Violation]:
        out: List[Violation] = []
        if target.fn is not None:
            closed = jax.make_jaxpr(target.fn)(*target.example_args)
            eqns = iter_pallas_eqns(closed.jaxpr)
            for k, eqn in enumerate(eqns):
                label = f"{target.label}#pallas_call[{k}]"
                for bm in _block_mappings(eqn):
                    olabel = f"{label}:{getattr(bm, 'origin', '?')}"
                    out.extend(self._lint_mapping(olabel, bm))
                out.extend(self._lint_kernel_body(label, eqn))
        if self.check_constants:
            out.extend(self._lint_constants())
        return out
