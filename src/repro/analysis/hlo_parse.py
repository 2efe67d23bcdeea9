"""Mini HLO parser/cost model — the parser core of :mod:`repro.analysis`.

``compiled.cost_analysis()`` counts each while body ONCE (verified
empirically), which silently drops ~L x the FLOPs of scan-over-layers
models.  This parser walks the optimized post-SPMD HLO text instead:

* dot/convolution FLOPs from operand/result shapes,
* HBM bytes per top-level op (operands + results — post-fusion, each fusion
  reads inputs and writes outputs through HBM once, which is exactly the
  memory-roofline quantity),
* collective bytes (all-gather / all-reduce / reduce-scatter / all-to-all /
  collective-permute) from operand sizes, with one structured record per
  collective op (the static-analysis rules classify cross-pod traffic from
  these),
* while ops multiply their body+condition cost by ``known_trip_count``
  (emitted by XLA in backend_config),
* ``input_output_alias`` donation entries from the module header
  (:func:`parse_input_output_aliases`).

Shapes in the partitioned module are per-device shard shapes, so every
number is per-device — matching the roofline denominators (per-chip peak
FLOP/s, HBM and ICI bandwidth).

Moved here from ``repro.roofline.hlo_parse`` (which remains as a
compatibility shim) so the roofline reports and the analyzer rules share
one parser.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0,
    "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# a collective-permute's ``source_target_pairs`` read as one two-device
# group per pair: the pair crosses pods exactly when the group does
_RG_LITERAL_RE = re.compile(
    r"(?:replica_groups|source_target_pairs)="
    r"\{(\{[\d,]*\}(?:,\{[\d,]*\})*)\}")
_RG_IOTA_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?.+?\)?)\s+([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_TRIP_RE = re.compile(r"known_trip_count\D*(\d+)")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TF_COMP_RE = re.compile(r"(?:true_computation|false_computation)"
                         r"=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}\s*:\s*\((\d+)\s*,\s*\{([\d,\s]*)\}"
    r"(?:\s*,\s*(may-alias|must-alias))?\)")

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _loop_read(operand_bytes: int, result_bytes: int, trips: int) -> float:
    """Charge for reading one operand inside a `trips`-iteration loop body:
    operands much larger than the result are stacked buffers sliced per
    iteration (the loop reads the buffer once in total)."""
    if result_bytes > 0 and operand_bytes > 8 * result_bytes and trips > 1:
        return operand_bytes / trips
    return float(operand_bytes)


def parse_replica_groups(attrs: str) -> Optional[List[List[int]]]:
    """Decode a collective's ``replica_groups`` attribute into device-id
    groups.  A collective-permute's ``source_target_pairs={{0,1},{1,0}}``
    reads as one group per pair.  Handles both emitted forms: the literal
    ``{{0,4},{1,5}}`` and
    the iota ``[4,2]<=[2,4]T(1,0)`` (reshape an arange to the ``<=[dims]``
    shape, transpose by the ``T`` permutation, flatten row-major, split
    into the ``[groups, group_size]`` rows).  Degenerate iota dims — size-1
    axes, 1-D group shapes, or a zero anywhere — resolve without crashing:
    a zero-sized product or group yields no parsable groups.  Returns None
    when the op carries no parsable groups, including the bare
    ``replica_groups={}`` form (XLA's "one group of all replicas"); callers
    must treat None conservatively, as a crossing."""
    m = _RG_LITERAL_RE.search(attrs)
    if m:
        return [[int(x) for x in grp.split(",") if x]
                for grp in re.findall(r"\{([\d,]*)\}", m.group(1))]
    m = _RG_IOTA_RE.search(attrs)
    if m:
        gshape = [int(x) for x in m.group(1).split(",")]
        dims = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(3).split(",")] if m.group(3)
                else list(range(len(dims))))
        n = 1
        for d in dims:
            n *= d
        # degenerate iota: a zero-sized device product or group row would
        # otherwise make the range() step below 0 — treat as unparsable
        k = gshape[-1] if gshape else n
        if n <= 0 or k <= 0:
            return None
        # row-major transpose without numpy: flat index -> multi-index in
        # `dims`, permuted, re-linearized in the permuted shape
        pdims = [dims[p] for p in perm]
        flat = [0] * n
        for src in range(n):
            idx, rem = [], src
            for d in reversed(dims):
                idx.append(rem % d)
                rem //= d
            idx = idx[::-1]
            dst, stride = 0, 1
            for ax in reversed(range(len(pdims))):
                dst += idx[perm[ax]] * stride
                stride *= pdims[ax]
            flat[dst] = src
        return [flat[i:i + k] for i in range(0, n, k)]
    return None


def groups_cross_pods(groups: Optional[List[List[int]]],
                      devices_per_pod: int) -> bool:
    """True when any replica group spans more than one pod (device ids are
    pod-major on ``make_pod_mesh`` meshes: pod = id // devices_per_pod).
    Unparsable groups (None) count as crossing — the audit must stay
    conservative."""
    if groups is None:
        return True
    dpp = max(1, devices_per_pod)
    return any(len({d // dpp for d in g}) > 1 for g in groups)


def cross_pod_collectives(cost: "HloCost", n_devices: int, n_pods: int
                          ) -> List[Dict]:
    """The collective records whose replica groups span pod boundaries."""
    dpp = max(1, n_devices // max(1, n_pods))
    return [r for r in cost.collective_ops
            if groups_cross_pods(r.get("replica_groups"), dpp)]


def shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


def parse_input_output_aliases(hlo_text: str) -> List[Dict]:
    """Donation entries from the HloModule header's ``input_output_alias``.

    Compiled modules record each honored donation as
    ``{output_index}: (param_number, {param_index}, may-alias)`` inside
    ``input_output_alias={ ... }``.  Returns one dict per entry:
    ``{"output_index", "param_number", "param_index", "kind"}`` (index
    tuples; ``kind`` is ``may-alias``/``must-alias``).  A donation that XLA
    silently dropped (shape mismatch, ``donate_argnums`` drift) simply has
    no entry — which is exactly what the donation-aliasing rule checks.
    """
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    i = start + len("input_output_alias={")
    depth, j = 1, i
    while j < len(hlo_text) and depth > 0:
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
        j += 1
    body = hlo_text[i:j - 1]
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(body):
        out_idx = tuple(int(x) for x in m.group(1).split(",") if x.strip())
        par_idx = tuple(int(x) for x in m.group(3).split(",") if x.strip())
        out.append({"output_index": out_idx,
                    "param_number": int(m.group(2)),
                    "param_index": par_idx,
                    "kind": m.group(4) or "may-alias"})
    return out


@dataclasses.dataclass
class HloCost:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_counts: Dict[str, int] = dataclasses.field(default_factory=dict)
    collective_bytes_by_kind: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    dot_flops: float = 0.0
    conv_flops: float = 0.0
    bytes_by_op: Dict[str, float] = dataclasses.field(default_factory=dict)
    # one record per collective op: kind, the defining var name, per-operand
    # (dtype, dims, bytes) specs, total operand bytes, and the parsed
    # replica groups (None when the op carries none) — the collective-
    # placement rule classifies cross-pod traffic from these
    collective_ops: List[Dict] = dataclasses.field(default_factory=list)

    def charge(self, op: str, b: float):
        self.bytes += b
        self.bytes_by_op[op] = self.bytes_by_op.get(op, 0.0) + b

    def add(self, other: "HloCost", times: float = 1.0):
        self.flops += other.flops * times
        self.bytes += other.bytes * times
        self.collective_bytes += other.collective_bytes * times
        self.dot_flops += other.dot_flops * times
        self.conv_flops += other.conv_flops * times
        for k, v in other.collective_counts.items():
            self.collective_counts[k] = self.collective_counts.get(k, 0) + \
                int(v * times)
        for k, v in other.collective_bytes_by_kind.items():
            self.collective_bytes_by_kind[k] = \
                self.collective_bytes_by_kind.get(k, 0.0) + v * times
        for k, v in other.bytes_by_op.items():
            self.bytes_by_op[k] = self.bytes_by_op.get(k, 0.0) + v * times
        self.collective_ops.extend(
            other.collective_ops * max(1, int(times)))


def _dot_flops(result_type: str, operand_types: List[str], attrs: str) -> float:
    out_dims = shape_dims(result_type)
    out_n = 1
    for d in out_dims:
        out_n *= d
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", attrs)
    lhs_dims = shape_dims(operand_types[0]) if operand_types else []
    contract = 1
    if m and m.group(1):
        for idx in m.group(1).split(","):
            i = int(idx)
            if i < len(lhs_dims):
                contract *= lhs_dims[i]
    return 2.0 * out_n * contract


def _conv_flops(result_type: str, operand_types: List[str], attrs: str) -> float:
    # FLOPs = 2 * prod(output spatial+batch+features) * (kernel spatial * Cin)
    out_dims = shape_dims(result_type)
    out_n = 1
    for d in out_dims:
        out_n *= d
    if len(operand_types) < 2:
        return 0.0
    k_dims = shape_dims(operand_types[1])
    if len(k_dims) < 2:
        return 0.0
    kn = 1
    for d in k_dims[:-1]:  # all but output-feature dim (approximation)
        kn *= d
    return 2.0 * out_n * kn


def split_computations(hlo_text: str
                       ) -> Tuple[Dict[str, List[str]], Optional[str]]:
    """``({computation name: its instruction lines}, ENTRY's name)``."""
    computations: Dict[str, List[str]] = {}
    entry_name = None
    cur: Optional[str] = None
    for line in hlo_text.splitlines():
        stripped = line.rstrip()
        if cur is None:
            m = _COMP_RE.match(stripped)
            if m and "{" in stripped:
                cur = m.group(1)
                computations[cur] = []
                if stripped.startswith("ENTRY"):
                    entry_name = cur
        else:
            if stripped.strip() == "}":
                cur = None
            else:
                computations[cur].append(stripped)
    return computations, entry_name


def parse_hlo_cost(hlo_text: str, entry: Optional[str] = None) -> HloCost:
    """Compute the per-device cost of the ENTRY computation."""
    computations, entry_name = split_computations(hlo_text)
    if entry is not None:
        entry_name = entry
    if entry_name is None:
        # fall back: biggest computation
        entry_name = max(computations, key=lambda k: len(computations[k]))

    memo: Dict[str, HloCost] = {}

    def comp_cost(name: str, top_level: bool, in_loop: bool = False,
                  trips: int = 1) -> HloCost:
        key = f"{name}|{top_level}|{in_loop}|{trips}"
        if key in memo:
            return memo[key]
        cost = HloCost()
        for line in computations.get(name, []):
            m = _OP_RE.match(line)
            if not m:
                continue
            var_name, result_type, op, rest = m.groups()
            # operands: the parenthesized list before ), attrs
            depth, i = 1, 0
            while i < len(rest) and depth > 0:
                if rest[i] == "(":
                    depth += 1
                elif rest[i] == ")":
                    depth -= 1
                i += 1
            operand_str = rest[:i - 1]
            attrs = rest[i:]
            op_b = shape_bytes(result_type)

            if op == "dot":
                # operand types unknown from the call line; resolve via the
                # defining line's result type (symbol table below)
                opnds = _OPERAND_RE.findall(operand_str)
                types = [symtab.get(name, {}).get(o, "") for o in opnds]
                f = _dot_flops(result_type, types, attrs)
                cost.flops += f
                cost.dot_flops += f
                if top_level:
                    cost.charge("dot", op_b + sum(shape_bytes(t) for t in types))
            elif op == "convolution":
                opnds = _OPERAND_RE.findall(operand_str)
                types = [symtab.get(name, {}).get(o, "") for o in opnds]
                f = _conv_flops(result_type, types, attrs)
                cost.flops += f
                cost.conv_flops += f
                if top_level:
                    cost.charge("convolution", op_b + sum(shape_bytes(t) for t in types))
            elif op == "fusion":
                called = _CALLS_RE.search(attrs or rest)
                if called and called.group(1) in computations:
                    inner = comp_cost(called.group(1), False)
                    cost.flops += inner.flops
                    cost.dot_flops += inner.dot_flops
                    cost.conv_flops += inner.conv_flops
                    cost.collective_bytes += inner.collective_bytes
                    for k, v in inner.collective_counts.items():
                        cost.collective_counts[k] = \
                            cost.collective_counts.get(k, 0) + v
                    for k, v in inner.collective_bytes_by_kind.items():
                        cost.collective_bytes_by_kind[k] = \
                            cost.collective_bytes_by_kind.get(k, 0.0) + v
                    # collectives fused into a computation must keep their
                    # structured records, or a gather two cond levels deep
                    # (cond branch -> fusion -> collective) silently drops
                    # out of the cross-pod audit
                    cost.collective_ops.extend(inner.collective_ops)
                opnds = _OPERAND_RE.findall(operand_str)
                types = [symtab.get(name, {}).get(o, "") for o in opnds]
                ob = [shape_bytes(t) for t in types]
                if in_loop and op_b in ob and op_b > 0:
                    # in-place accumulator pattern (scan ys-stacking /
                    # carry update): XLA aliases the result with the
                    # equal-sized operand; real per-iteration traffic is
                    # the update slice, approximated by the largest
                    # non-aliased operand.
                    rest_b = list(ob)
                    rest_b.remove(op_b)
                    rest_b = [_loop_read(b, op_b, trips) for b in rest_b]
                    upd = max(rest_b) if rest_b else 0
                    cost.charge("fusion", sum(rest_b) + min(op_b, 2 * upd))
                elif in_loop:
                    # stacked-input reads: an operand much larger than the
                    # result is a per-iteration dynamic-slice of a loop
                    # invariant/carried buffer -> the WHOLE buffer is read
                    # once across the loop, i.e. bytes/trips per iteration.
                    charged = sum(_loop_read(b, op_b, trips) for b in ob)
                    cost.charge("fusion", op_b + charged)
                else:
                    cost.charge("fusion", op_b + sum(ob))
            elif op == "dynamic-update-slice":
                opnds = _OPERAND_RE.findall(operand_str)
                types = [symtab.get(name, {}).get(o, "") for o in opnds]
                upd = shape_bytes(types[1]) if len(types) > 1 else op_b
                if in_loop:
                    cost.charge("dynamic-update-slice", 2 * upd)
                else:
                    cost.charge("dynamic-update-slice", op_b + upd)
            elif op == "dynamic-slice":
                cost.charge("dynamic-slice", 2 * op_b)
            elif op == "while":
                body = _CALLS_RE.search(rest)
                cond = _COND_RE.search(rest)
                trip_m = _TRIP_RE.search(rest)
                loop_trips = int(trip_m.group(1)) if trip_m else 1
                inner = HloCost()
                if body and body.group(1) in computations:
                    inner.add(comp_cost(body.group(1), True, in_loop=True,
                                        trips=loop_trips))
                if cond and cond.group(1) in computations:
                    inner.add(comp_cost(cond.group(1), True, in_loop=True,
                                        trips=loop_trips))
                cost.add(inner, times=loop_trips)
            elif op in ("call", "custom-call", "conditional"):
                called_names = _CALLS_RE.findall(rest)
                # lax.cond lowers to `conditional(...),
                # branch_computations={%a, %b}` (or true_/false_computation
                # on two-way conds) — the gated merge's collectives live in
                # those branches, so missing them undercounts every
                # open-round collective.  branch_computations={} (an empty
                # or fully-pruned conditional) contributes nothing.
                bm = _BRANCHES_RE.search(rest)
                if bm:
                    called_names += [c.strip().lstrip("%")
                                     for c in bm.group(1).split(",")
                                     if c.strip()]
                called_names += _TF_COMP_RE.findall(rest)
                for called in called_names:
                    if called in computations:
                        cost.add(comp_cost(called, top_level, in_loop, trips))
            elif (any(op.startswith(c) for c in COLLECTIVES)
                  and not op.endswith("-done")):
                # async pairs lower as `all-gather-start` + `all-gather-done`
                # over the SAME buffer; counting both would double every
                # async collective's bytes and records, so only the -start
                # (or the sync form) is charged
                kind = next(c for c in COLLECTIVES if op.startswith(c))
                opnds = _OPERAND_RE.findall(operand_str)
                types = [symtab.get(name, {}).get(o, "") for o in opnds]
                b = sum(shape_bytes(t) for t in types if t)
                if b == 0:
                    b = op_b  # fall back to result size
                operands = []
                for t in types:
                    for sm in _SHAPE_RE.finditer(t):
                        dt, dims = sm.group(1), sm.group(2)
                        if dt not in DTYPE_BYTES:
                            continue
                        dl = [int(d) for d in dims.split(",")] if dims else []
                        nb = DTYPE_BYTES[dt]
                        for d in dl:
                            nb *= d
                        operands.append({"dtype": dt, "dims": dl,
                                         "bytes": nb})
                cost.collective_ops.append({
                    "kind": kind, "name": var_name,
                    # which HLO computation the collective lowered inside:
                    # the async round audit uses this to show the payload
                    # gather lives in the dispatch half's cond branch, not
                    # in any program the next pod step waits on
                    "computation": name,
                    "operands": operands, "operand_bytes": int(b),
                    "replica_groups": parse_replica_groups(attrs or rest),
                })
                cost.collective_bytes += b
                cost.collective_counts[kind] = \
                    cost.collective_counts.get(kind, 0) + 1
                cost.collective_bytes_by_kind[kind] = \
                    cost.collective_bytes_by_kind.get(kind, 0.0) + b
                cost.charge(kind, op_b + b)
            elif op in ("tuple", "get-tuple-element", "parameter", "constant",
                        "bitcast", "after-all", "partition-id", "replica-id"):
                pass
            else:
                # generic top-level op: charge HBM traffic (includes the
                # -done halves of async collective pairs, which read/write
                # the already-counted buffer)
                if top_level and not op.endswith("-done"):
                    opnds = _OPERAND_RE.findall(operand_str)
                    types = [symtab.get(name, {}).get(o, "") for o in opnds]
                    cost.charge(op, op_b + sum(shape_bytes(t) for t in types))
        memo[key] = cost
        return cost

    # --- symbol tables: per computation, op name -> result type -----------
    symtab: Dict[str, Dict[str, str]] = {}
    for cname, lines in computations.items():
        table: Dict[str, str] = {}
        for line in lines:
            m = _OP_RE.match(line)
            if m:
                table[m.group(1)] = m.group(2)
        symtab[cname] = table

    return comp_cost(entry_name, True)
