"""Device time of the Hermes round's phases, from the program's scopes.

The program wraps each phase of the round in a ``jax.named_scope``
(``hermes/gate``, ``hermes/encode``, ``hermes/gather``, ``hermes/merge``,
``hermes/refresh``), which only sets the ``op_name`` metadata of the ops
traced inside.  The profiler's op events carry the HLO instruction name and
not that metadata, so the round is compiled once more, here, with the
persistent compilation cache off (its key ignores metadata, so a cached
executable may carry another checkout's), and each instruction of the
compiled text is mapped to the innermost phase in its ``op_name``; a fusion
without one takes the phase most of its fused instructions carry, and ops
that only hold others (``while``, ``conditional``, ``call``) take none.
The compile is deterministic, so its instruction names are those of the
traced executable.

``phase_ms`` sums, per round execution in the traced window, the device
time of the ops of one phase, averaged over the executions and the chips.
A program without the scopes maps no instruction, and every phase reads
``None``.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Optional

PHASES = ("gate", "encode", "gather", "merge", "refresh")
HOLDERS = ("while", "conditional", "call")
ROUND = r"lambda"       # make_round_jit's executable, as round_ms reads it
_PHASE = re.compile(r"hermes/(" + "|".join(PHASES) + r")(?![A-Za-z0-9_])")
_COMP = re.compile(r"^(?:ENTRY )?%(\S+) ")
_INSTR = re.compile(r"^\s+(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%([^\s,]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _phase(op_name: Optional[str]) -> Optional[str]:
    found = _PHASE.findall(op_name or "")
    return found[-1] if found else None


def op_phases(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> phase, over every computation of a compiled
    module's text; instructions of no phase are left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, list] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name = m.group(1)
        if m.group(2) in HOLDERS:
            continue
        op = _OP_NAME.search(line)
        own[name] = _phase(op.group(1) if op else None)
        members.setdefault(comp, []).append(name)
        if m.group(2) == "fusion":
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    out = {}
    for name, phase in own.items():
        if phase is None and name in calls:
            votes = Counter(own[i] for i in members.get(calls[name], ())
                            if own[i] is not None)
            phase = votes.most_common(1)[0][0] if votes else None
        if phase is not None:
            out[name] = phase
    return out


def _round_hlo(cell) -> Optional[str]:
    """The compiled text of the cell's round executable, compiled with the
    persistent cache off; None for a cell with no synchronous unplaced
    round."""
    tr = cell["traffic"]
    hermes = tr.get("hermes", {})
    if (tr.get("loop") != "hermes" or tr.get("placed")
            or hermes.get("async_rounds")):
        return None
    import jax
    import jax.numpy as jnp
    import harness
    from repro.dist.hermes_sync import hermes_pod_state
    from repro.launch.train import make_round_jit
    from repro.models import init_lm

    P = int(tr["pods"])
    hcfg = harness.hermes_config(tr)
    params = jax.eval_shape(lambda k: init_lm(cell["model"], k)[0],
                            jax.random.PRNGKey(0))
    stacked = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((P,) + a.shape, a.dtype), params)
    lossy = hcfg.compression != "none" and hcfg.error_feedback
    args = (stacked, jax.eval_shape(lambda: hermes_pod_state(hcfg, P)),
            jax.ShapeDtypeStruct((P,), jnp.float32), params,
            jax.ShapeDtypeStruct((), jnp.float32),
            stacked if lossy else None,
            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    from jax.experimental.compilation_cache import compilation_cache as cc
    # the cache decides once per process whether it is on: reset it around
    # the switch, both ways
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return make_round_jit(hcfg).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def round_op_phases(ctx) -> Dict[str, str]:
    """The round's instruction -> phase map, kept in ``ctx`` so the phase
    readers of one run compile once."""
    if "round_op_phases" not in ctx:
        text = _round_hlo(ctx["cell"])
        ctx["round_op_phases"] = op_phases(text) if text else {}
    return ctx["round_op_phases"]


def phase_ms(summary, phases: Dict[str, str], phase: str,
             round_pat: str = ROUND) -> Optional[float]:
    """Device ms per round execution of the ops of ``phase``, averaged over
    the chips; None when no op of the round carries the phase."""
    pat = re.compile(round_pat)
    per_chip, found = [], False
    for chip in summary["per_chip"]:
        n, t = 0, 0.0
        for mod, execs in chip["executions"].items():
            if not pat.search(mod):
                continue
            for ex in execs:
                n += 1
                for op, s in ex.items():
                    if phases.get(op) == phase:
                        t += s
                        found = True
        if n:
            per_chip.append(t / n * 1e3)
    return sum(per_chip) / len(per_chip) if found else None
