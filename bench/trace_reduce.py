"""Reduce a profiler trace of the window to device times.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
``load_events`` keeps the device planes' events in a plain form:
``{"dev", "line", "name", "start", "dur"}`` (nanoseconds), where ``line``
is ``"modules"`` for executables (XLA Modules), ``"ops"`` for the
operations inside them (XLA Ops) and ``"async"`` for the spans of
asynchronous ones (Async XLA Ops); an op also carries its ``op`` code,
and its ``name`` is its HLO instruction name.  ``summarize`` reduces those events,
per chip and then averaged over the chips:

* the steady window: from the start of the first to the end of the last
  executable whose name matches ``anchor`` (the pod step), never set-up;
* busy time: the union of op intervals inside the window (asynchronous
  spans not counted);
* per-executable device time and count;
* per-op (kernel) device time and count, by name;
* collective exposure: the part of collective ops' intervals, async
  spans included, that no compute op covers on that chip (ops that only
  hold others, ``while`` and ``conditional``, and the ``-start``/``-done``
  ends of asynchronous ops are not compute);
* per execution of each executable inside the window, its ops' device
  time by name (``per_chip[i]["executions"]``);
* the breakdown: the ops that took most time, named
  ``<executable>/<op>`` (containers left out), and the longest idle gaps,
  named by the executables on either side.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, List, Sequence

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"send|recv)")
# ops that only hold other ops, or mark an asynchronous op's ends
CONTAINER = re.compile(r"^(while|conditional|call)$|-(start|done)$")
LINES = {"XLA Modules": "modules", "XLA Ops": "ops",
         "Async XLA Ops": "async"}
# an XLA op event is named by its HLO text: "%name = <type> opcode(...)"
HLO = re.compile(r"^%(\S+) = ")
OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_name(text: str):
    """(instruction name, opcode) of an XLA op event's HLO text."""
    m = HLO.match(text)
    if not m:
        return text, text
    code = OPCODE.search(text, m.end() - 1)
    return m.group(1), code.group(1) if code else ""


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    return found[-1]


def load_events(path: str) -> List[Dict[str, Any]]:
    """Device events of an ``.xplane.pb`` file, or of a ``.json.gz`` file
    holding such a list (a trimmed recorded trace)."""
    if path.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        m = re.match(r"/device:TPU:(\d+)", plane.name)
        if not m:
            continue
        dev = int(m.group(1))
        for line in plane.lines:
            kind = LINES.get(line.name)
            if kind is None:
                continue
            for ev in line.events:
                e = {"dev": dev, "line": kind, "name": ev.name,
                     "start": float(ev.start_ns),
                     "dur": float(ev.duration_ns)}
                if kind != "modules":
                    e["name"], e["op"] = op_name(ev.name)
                out.append(e)
    return out


def union(intervals) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _subtract(a, b) -> float:
    """Length of union ``a`` not covered by union ``b`` (both sorted)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _clip(ev, lo, hi):
    s, e = max(ev["start"], lo), min(ev["start"] + ev["dur"], hi)
    return (s, e) if e > s else None


def summarize(events: Sequence[Dict[str, Any]], devs: Sequence[int], *,
              anchor: str = "pod_step|step_fn") -> Dict[str, Any]:
    pat = re.compile(anchor)
    per_dev = []
    for d in devs:
        evs = [e for e in events if e["dev"] == d]
        mods = sorted((e for e in evs if e["line"] == "modules"),
                      key=lambda e: e["start"])
        anchors = [e for e in mods if pat.search(e["name"])]
        if not anchors:
            continue
        lo = anchors[0]["start"]
        hi = max(e["start"] + e["dur"] for e in anchors)
        ops = [e for e in evs if e["line"] == "ops"]
        op_iv = [iv for iv in (_clip(e, lo, hi) for e in ops) if iv]
        busy = union(op_iv)
        coll = union(iv for e in evs if e["line"] != "modules"
                     and COLLECTIVE.search(e.get("op", ""))
                     for iv in [_clip(e, lo, hi)] if iv)
        comp = union(iv for e in ops
                     if not COLLECTIVE.search(e.get("op", ""))
                     and not CONTAINER.search(e.get("op", ""))
                     for iv in [_clip(e, lo, hi)] if iv)
        modules: Dict[str, List[float]] = {}
        for e in mods:
            if _clip(e, lo, hi):
                t = modules.setdefault(e["name"], [0, 0.0])
                t[0] += 1
                t[1] += e["dur"] * 1e-9
        kernels: Dict[str, List[float]] = {}
        for e in ops:
            if _clip(e, lo, hi):
                t = kernels.setdefault(e["name"], [0, 0.0])
                t[0] += 1
                t[1] += e["dur"] * 1e-9
        inside = [e for e in mods if _clip(e, lo, hi)]
        starts = [m["start"] for m in inside]
        gaps = []
        for a, b in zip(busy, busy[1:]):
            j = bisect.bisect_right(starts, a[1])
            before = inside[j - 1]["name"] if j else "?"
            k = bisect.bisect_left(starts, b[0])
            after = inside[k]["name"] if k < len(inside) else "?"
            gaps.append((f"after {before} before {after}",
                         (b[0] - a[1]) * 1e-9))
        executions: Dict[str, List[Dict[str, float]]] = {}
        op_starts = [e["start"] for e in ops]
        order = sorted(range(len(ops)), key=lambda i: op_starts[i])
        sorted_ops = [ops[i] for i in order]
        sorted_starts = [op_starts[i] for i in order]
        for m in inside:
            if m["start"] < lo or m["start"] + m["dur"] > hi:
                continue
            a = bisect.bisect_left(sorted_starts, m["start"])
            b = bisect.bisect_right(sorted_starts, m["start"] + m["dur"])
            ex: Dict[str, float] = {}
            for e in sorted_ops[a:b]:
                ex[e["name"]] = ex.get(e["name"], 0.0) + e["dur"] * 1e-9
            executions.setdefault(m["name"], []).append(ex)
        per_dev.append({"window_s": (hi - lo) * 1e-9,
                        "busy_s": _length(busy) * 1e-9,
                        "exposed_s": _subtract(coll, comp) * 1e-9,
                        "modules": modules, "kernels": kernels,
                        "gaps": gaps, "executions": executions})
    if not per_dev:
        raise ValueError("no device events of the anchor executable")
    n = len(per_dev)

    def mean_table(key):
        names = {k for p in per_dev for k in p[key]}
        return {k: [sum(p[key].get(k, [0, 0.0])[0] for p in per_dev) / n,
                    sum(p[key].get(k, [0, 0.0])[1] for p in per_dev) / n]
                for k in names}

    kernels = mean_table("kernels")
    containers = {e["name"] for e in events if e["line"] == "ops"
                  and CONTAINER.search(e.get("op", ""))}
    gaps = sorted((g for p in per_dev for g in p["gaps"]),
                  key=lambda g: -g[1])[:10]
    by_op: Dict[str, float] = {}
    for p in per_dev:
        for mod, execs in p["executions"].items():
            for ex in execs:
                for op, t in ex.items():
                    if op not in containers:
                        key = f"{mod}/{op}"
                        by_op[key] = by_op.get(key, 0.0) + t / n
    top = sorted(([k, v] for k, v in by_op.items()),
                 key=lambda kv: -kv[1])[:10]
    return {"window_s": sum(p["window_s"] for p in per_dev) / n,
            "busy_s": sum(p["busy_s"] for p in per_dev) / n,
            "exposed_s": sum(p["exposed_s"] for p in per_dev) / n,
            "modules": mean_table("modules"), "kernels": kernels,
            "chips": n, "per_chip": per_dev,
            "breakdown": {"device_ops": top,
                          "idle_gaps": [list(g) for g in gaps]}}


def match(table: Dict[str, List[float]], pattern: str):
    """(count, seconds) summed over the names that match ``pattern``."""
    pat = re.compile(pattern)
    hits = [v for k, v in table.items() if pat.search(k)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def kernel_in_rounds(summary, round_pat: str, kernel_pat: str):
    """Per chip: (round executions that ran a kernel matching
    ``kernel_pat``, that kernel's device seconds in them)."""
    rp, kp = re.compile(round_pat), re.compile(kernel_pat)
    out = []
    for chip in summary["per_chip"]:
        n, t = 0, 0.0
        for name, execs in chip["executions"].items():
            if not rp.search(name):
                continue
            for ex in execs:
                s = sum(v for k, v in ex.items() if kp.search(k))
                if s > 0:
                    n, t = n + 1, t + s
        out.append((n, t))
    return out


def roofline_share(ctx, round_pat: str, kernel_pat: str, work: str,
                   per_chip_share: bool):
    """% of the roofline a wire kernel reaches, averaged over the chips:
    bytes per open round (``ctx["wire_bytes"][work]``, a chip's share of
    them when each chip works on its own pods' rows) over the HBM peak,
    against the kernel's device time per open round.  None when the trace
    holds no open round."""
    per = kernel_in_rounds(ctx["trace"], round_pat, kernel_pat)
    nbytes = ctx["wire_bytes"][work]
    if per_chip_share:
        nbytes /= ctx["chips"]
    shares = [n * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / t * 100.0
              for n, t in per if n and t > 0]
    return sum(shares) / len(shares) if shares else None
