"""Device time of the training step's expert and latent-attention scopes.

The program wraps the expert layer's phases and MLA in ``jax.named_scope``
(``moe/route``, ``moe/dispatch``, ``moe/experts``, ``moe/shared``,
``mla/attend``; ``repro.utils.tracing``), which only sets the ``op_name``
metadata of the ops traced inside, forward and backward.  As for the
Hermes round (``bench/scopes.py``), the profiler's op events carry the HLO
instruction name and not that metadata, so ``train_single``'s step is
compiled once more here, at the cell's shapes, with the persistent
compilation cache off, and each instruction of the compiled text is mapped
to the innermost scope in its ``op_name``; a fusion without one takes the
scope most of its fused instructions carry, and ops that only hold others
take none.  XLA puts its own ragged-dot kernels and their group metadata
(``ragged-dot-*``) in the place of ``jax.lax.ragged_dot``, which the
program calls under ``moe/experts`` alone, and drops their ``op_name``:
those instructions are ``moe/experts``'.  ``scopes.phase_ms`` then sums
each scope's op time per step execution.  A program without the scopes maps no instruction, and every
reader returns ``None``.
"""
from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Optional

import scopes

STEP = r"step_fn"       # train_single's executable, as pod_step_ms reads it
SCOPES = ("moe/route", "moe/dispatch", "moe/experts", "moe/shared",
          "mla/attend")
_SCOPE = re.compile(r"(" + "|".join(SCOPES) + r")(?![A-Za-z0-9_])")
_RAGGED = re.compile(r"ragged-dot")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> scope, over every computation of a compiled
    module's text; instructions of no scope are left out."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    members: Dict[str, list] = {}
    comp = None
    for line in hlo_text.splitlines():
        m = scopes._COMP.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = scopes._INSTR.match(line)
        if not m or comp is None or m.group(2) in scopes.HOLDERS:
            continue
        name = m.group(1)
        op = scopes._OP_NAME.search(line)
        found = _SCOPE.findall(op.group(1) if op else "")
        own[name] = (found[-1] if found else
                     "moe/experts" if _RAGGED.match(name) else None)
        members.setdefault(comp, []).append(name)
        if m.group(2) == "fusion":
            c = scopes._CALLS.search(line)
            if c:
                calls[name] = c.group(1)
    out = {}
    for name, scope in own.items():
        if scope is None and name in calls:
            votes = Counter(own[i] for i in members.get(calls[name], ())
                            if own[i] is not None)
            scope = votes.most_common(1)[0][0] if votes else None
        if scope is not None:
            out[name] = scope
    return out


def _step_hlo(cell) -> Optional[str]:
    """The compiled text of the cell's ``train_single`` step, compiled with
    the persistent cache off; None for a cell of another loop."""
    tr = cell["traffic"]
    if tr.get("loop") != "single":
        return None
    import jax
    import jax.numpy as jnp
    import harness
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.launch.train import single_state, single_step_fn
    from repro.models import init_lm
    from repro.optim import make_optimizer

    cfg = cell["model"]
    opt = make_optimizer(harness.optimizer_config(tr))
    state = jax.eval_shape(lambda k: single_state(cfg, opt, init_lm(cfg, k)[0]),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((int(tr["batch"]), int(tr["seq"])),
                                     jnp.int32) for k in ("tokens", "targets")}
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return jax.jit(single_step_fn(cfg, opt), donate_argnums=(0,)).lower(
            state, batch).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def step_op_scopes(ctx) -> Dict[str, str]:
    """The step's instruction -> scope map, kept in ``ctx`` so the readers
    of one run compile once."""
    if "step_op_scopes" not in ctx:
        try:
            text = _step_hlo(ctx["cell"])
        except ImportError:     # a program without ``single_step_fn``
            text = None
        ctx["step_op_scopes"] = op_scopes(text) if text else {}
    return ctx["step_op_scopes"]


def scope_ms(ctx, scope: str) -> Optional[float]:
    """Device ms per step execution of the ops of ``scope``."""
    return scopes.phase_ms(ctx["trace"], step_op_scopes(ctx), scope, STEP)
