"""Host spans of the training loops and device scopes of the Hermes round.

* ``train_single`` and ``train_hermes`` (sync and async) run under the JAX
  profiler at ``lmtiny`` size: every ``train/*`` span lands on the host
  plane with the right ``step``, once per step, per round or per log;
* collections land as ``host/gc`` spans, and the hook installs once;
* the compiled sync, async and two-tier rounds carry each ``hermes/*``
  scope in their op metadata (the gather's scope, a placed round's only,
  is checked in ``test_chip_compile.py``).
"""
from __future__ import annotations

import gc
import glob
import os
from collections import Counter

import pytest

import jax
import jax.numpy as jnp

from repro.config import HermesConfig, OptimizerConfig
from repro.dist.hermes_sync import hermes_pod_state
from repro.launch.train import (
    _preset, make_async_round_jits, make_round_jit, train_hermes,
    train_single,
)
from repro.utils import tracing


def _host_spans(trace_dir):
    """``[(name, step)]`` of the program's spans on the host plane, in
    start order (``step`` None where a span has none)."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in tracing.SPANS:
                    stats = dict(ev.stats)
                    out.append((ev.start_ns, ev.name, stats.get("step")))
    return [(n, s) for _, n, s in sorted(out)]


def _steps(spans, name):
    return [s for n, s in spans if n == name]


_KW = dict(batch=2, seq=16, log_every=3,
           opt_cfg=OptimizerConfig(name="sgd", lr=0.1))


def test_train_single_spans(tmp_path):
    cfg = _preset("lmtiny")
    train_single(cfg, steps=2, **_KW)          # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "t")):
        train_single(cfg, steps=6, ckpt_dir=str(tmp_path / "ck"), **_KW)
    spans = _host_spans(str(tmp_path / "t"))
    every = list(range(6))
    assert _steps(spans, tracing.BATCH) == every
    assert _steps(spans, tracing.STEP) == every
    assert _steps(spans, tracing.FETCH) == every   # float(loss) each step
    assert _steps(spans, tracing.LOG) == [2, 5]
    assert _steps(spans, tracing.CKPT) == [6]      # the final save
    assert not _steps(spans, tracing.ROUND)
    # within a step: batch, then step dispatch, then the fetch
    order = [n for n, s in spans if s == 1 and n != tracing.GC]
    assert order == [tracing.BATCH, tracing.STEP, tracing.FETCH]


@pytest.mark.parametrize("async_rounds", [False, True])
def test_train_hermes_spans(tmp_path, async_rounds):
    cfg = _preset("lmtiny")
    hcfg = HermesConfig(lam=2, alpha=-0.5, compression="int4",
                        kernel_dispatch="off", async_rounds=async_rounds)
    kw = dict(_KW, pods=2, hcfg=hcfg)
    train_hermes(cfg, steps=2, **kw)
    with jax.profiler.trace(str(tmp_path / "t")):
        out = train_hermes(cfg, steps=6, **kw)
    spans = _host_spans(str(tmp_path / "t"))
    every = list(range(6))
    rounds = [0, 1, 3, 5]                         # i == 0 or (i+1) % lam
    assert out["rounds"] == len(rounds)
    assert _steps(spans, tracing.BATCH) == every
    assert _steps(spans, tracing.STEP) == every
    assert _steps(spans, tracing.FETCH) == [2, 5]
    assert _steps(spans, tracing.LOG) == [2, 5]
    evals = Counter(_steps(spans, tracing.EVAL))
    if async_rounds:
        # the drain commits the last payload at the boundary after the
        # last step; each commit re-evaluates the global model
        assert _steps(spans, tracing.ROUND) == rounds + [6]
        assert evals == Counter({0: 1, 1: 2, 3: 2, 5: 2, 6: 1})
    else:
        assert _steps(spans, tracing.ROUND) == rounds
        # pod_eval before the round, eval_if_push after it
        assert evals == Counter({r: 2 for r in rounds})
    order = [n for n, s in spans if s == 3 and n != tracing.GC]
    assert order[:4] == [tracing.BATCH, tracing.STEP, tracing.EVAL,
                         tracing.ROUND]


def test_gc_spans_land_on_the_trace(tmp_path):
    tracing.install_gc_span()
    tracing.install_gc_span()
    assert gc.callbacks.count(tracing._gc_span) == 1
    gc.collect()                                  # outside: records nothing
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
        gc.collect()
    assert _steps(_host_spans(str(tmp_path)), tracing.GC).count(None) >= 2
    assert not tracing._open_gc


P = 2
_PARAMS = {"w": jnp.ones((8, 512), jnp.float32),
           "b": jnp.ones((512,), jnp.float32)}


def _round_args(hcfg):
    pods = jax.tree.map(lambda x: jnp.stack([x, 1.01 * x]), _PARAMS)
    err = jax.tree.map(jnp.zeros_like, pods)
    return (pods, hermes_pod_state(hcfg, P),
            jnp.arange(1.0, P + 1.0, dtype=jnp.float32), _PARAMS,
            jnp.float32(3.0), err, jax.random.PRNGKey(0))


def _scopes(text):
    return {s for s in tracing.SCOPES if f"/{s}/" in text}


@pytest.mark.parametrize("clusters", [1, 2])
@pytest.mark.parametrize("compression", ["int4", "none"])
def test_round_families_carry_the_phase_scopes(clusters, compression):
    hcfg = HermesConfig(lam=1, alpha=-0.5, compression=compression,
                        kernel_dispatch="off", n_clusters=clusters)
    args = _round_args(hcfg)
    if compression == "none":
        args = args[:5] + (None,) + args[6:]
    sync = make_round_jit(hcfg).lower(*args).compile().as_text()
    dispatch, commit = make_async_round_jits(hcfg)
    dtext = dispatch.lower(*args).compile().as_text()
    pending = jax.eval_shape(dispatch, *args)["pending"]
    ctext = commit.lower(args[0], pending, _PARAMS).compile().as_text()
    phases = {tracing.GATE, tracing.ENCODE, tracing.MERGE, tracing.REFRESH}
    assert _scopes(sync) == phases
    # the two-tier dispatch reduces each cluster's payloads before the
    # slow-tier ship, so part of its merge runs there
    assert _scopes(dtext) == ({tracing.GATE, tracing.ENCODE}
                              | ({tracing.MERGE} if clusters > 1 else set()))
    assert _scopes(ctext) == {tracing.MERGE, tracing.REFRESH}
