"""The program's host spans against the device trace: clock check, idle
time by span, and idle gaps named by the span they fell in.

The program opens ``jax.profiler.TraceAnnotation`` spans around each phase
of its training loops (``train/batch``, ``train/step``, ``train/eval``,
``train/round``, ``train/fetch``, ``train/log``, ``train/ckpt``, each with
a ``step``) and around each garbage collection (``host/gc``).  They land on
the profiler's ``/host:CPU`` plane.  ``host_spans`` reads them as events of
the form ``trace_reduce.load_events`` gives, with ``dev`` -1 and ``line``
``"host"``, so one list can hold a trimmed trace's device and host events.

The host and the device planes do not share a clock to the millisecond: on
a TPU v5e the device's events start up to about a millisecond before the
host span that dispatched them.  ``attribute`` therefore pairs the window's
pod-step executions with the ``train/step`` spans (from the last one back:
the profiler starts inside the first step's span), takes the worst lead of
an execution over its span (``clock_skew_us``) and shifts the device's
events by it, the least shift under which no execution starts before its
dispatch.  The fetch spans bound the shift from above: a fetch cannot end
before the device work dispatched ahead of it; ``clock_slack_us`` is how
much further the shift could go, so the error of a label's idle time is at
most that much per gap.

Each idle interval of the steady window (as ``trace_reduce.summarize``
bounds it: first to last pod step, idle = not covered by any op) is cut at
the span boundaries and each piece is labelled with the innermost span
that covers it on the host, or ``-`` where none does, so the idle time
summed over the labels is the window's idle time.
"""
from __future__ import annotations

import bisect
import re
from typing import Any, Dict, List, Sequence

import trace_reduce

SPAN = re.compile(r"^(train|host)/[a-z]+$")
STEP_SPAN, FETCH_SPAN = "train/step", "train/fetch"
NONE = "-"


def host_spans(path: str) -> List[Dict[str, Any]]:
    """The program's spans on the host plane of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if not SPAN.match(ev.name):
                    continue
                e = {"dev": -1, "line": "host", "name": ev.name,
                     "start": float(ev.start_ns),
                     "dur": float(ev.duration_ns)}
                step = dict(ev.stats).get("step")
                if step is not None:
                    e["step"] = int(step)
                out.append(e)
    return sorted(out, key=lambda e: e["start"])


def _end(e):
    return e["start"] + e["dur"]


def clock_lead(anchors, host) -> float:
    """Worst lead (ns) of a pod-step execution's start over the start of
    its ``train/step`` span, pairing both from the last one back;
    ``-inf`` when there is nothing to pair."""
    steps = [e for e in host if e["name"] == STEP_SPAN]
    pairs = list(zip(reversed(anchors), reversed(steps)))
    return max((s["start"] - a["start"] for a, s in pairs),
               default=float("-inf"))


def clock_slack(mods, host, shift: float) -> float:
    """Least room (ns), over the fetch spans, between a fetch's end and the
    end of the last execution that starts before it, device times shifted
    by ``shift``; ``inf`` without a fetch."""
    starts = [m["start"] + shift for m in mods]
    out = float("inf")
    for f in host:
        if f["name"] != FETCH_SPAN:
            continue
        k = bisect.bisect_left(starts, _end(f))
        if k:
            out = min(out, _end(f) - (_end(mods[k - 1]) + shift))
    return out


def _label_pieces(lo: float, hi: float, host) -> List[tuple]:
    """``[(label, length)]`` covering ``[lo, hi]``: at each instant, the
    covering span that started last (the innermost), else ``-``."""
    live = [e for e in host if e["start"] < hi and _end(e) > lo]
    cuts = sorted({lo, hi} | {t for e in live for t in (e["start"], _end(e))
                              if lo < t < hi})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        cover = [e for e in live if e["start"] <= mid < _end(e)]
        label = max(cover, key=lambda e: (e["start"], -e["dur"]))["name"] \
            if cover else NONE
        out.append((label, b - a))
    return out


def attribute(events: Sequence[Dict[str, Any]], devs: Sequence[int], *,
              anchor: str = "pod_step|step_fn",
              extra_shift: float = 0.0) -> Dict[str, Any]:
    """Per chip and averaged: the clock check, the window's idle seconds by
    label and per pod step, and the ten longest idle gaps named
    ``<label> | after A before B`` (A and B the executables around it).
    ``extra_shift`` (ns) moves the device's events further than the clock
    check does, up to ``clock_slack_us``, to bound what the shift decides."""
    pat = re.compile(anchor)
    host = [e for e in events if e["line"] == "host"]
    per_chip = []
    for d in devs:
        evs = [e for e in events if e["dev"] == d]
        mods = sorted((e for e in evs if e["line"] == "modules"),
                      key=lambda e: e["start"])
        anchors = [e for e in mods if pat.search(e["name"])]
        if not anchors:
            continue
        lead = clock_lead(anchors, host)
        shift = max(lead, 0.0) + extra_shift   # 0 with no span to pair
        lo = anchors[0]["start"]
        hi = max(_end(e) for e in anchors)
        busy = trace_reduce.union(
            iv for e in evs if e["line"] == "ops"
            for iv in [trace_reduce._clip(e, lo, hi)] if iv)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        inside = [m for m in mods if trace_reduce._clip(m, lo, hi)]
        starts = [m["start"] for m in inside]
        idle: Dict[str, float] = {}
        gaps = []
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            pieces = _label_pieces(a + shift, b + shift, host)
            for label, t in pieces:
                idle[label] = idle.get(label, 0.0) + t * 1e-9
            j = bisect.bisect_right(starts, a)
            k = bisect.bisect_left(starts, b)
            before = inside[j - 1]["name"] if j else "?"
            after = inside[k]["name"] if k < len(inside) else "?"
            top = max(pieces, key=lambda p: p[1])[0]
            gaps.append((f"{top} | after {before} before {after}",
                         (b - a) * 1e-9))
        n = sum(1 for e in anchors if e["start"] + e["dur"] <= hi)
        per_chip.append({
            "clock_skew_us": lead * 1e-3 if lead > float("-inf") else None,
            "clock_slack_us": clock_slack(mods, host, shift) * 1e-3,
            "window_s": (hi - lo) * 1e-9, "steps": n,
            "idle_s": idle, "gaps": gaps})
    if not per_chip:
        raise ValueError("no device events of the anchor executable")
    m = len(per_chip)
    labels = {k for p in per_chip for k in p["idle_s"]}
    idle = {k: sum(p["idle_s"].get(k, 0.0) for p in per_chip) / m
            for k in labels}
    steps = sum(p["steps"] for p in per_chip) / m
    skews = [p["clock_skew_us"] for p in per_chip
             if p["clock_skew_us"] is not None]
    return {"clock_skew_us": max(skews) if skews else None,
            "clock_slack_us": min(p["clock_slack_us"] for p in per_chip),
            "idle_s": idle,
            "idle_ms_per_step": {k: v / steps * 1e3 for k, v in idle.items()},
            "idle_gaps": [list(g) for g in sorted(
                (g for p in per_chip for g in p["gaps"]),
                key=lambda g: -g[1])[:10]],
            "per_chip": per_chip}
