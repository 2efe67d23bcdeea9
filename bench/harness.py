"""Drive the program's own entry points through one cell.

The window is a call of ``repro.launch.train.train_hermes`` (Hermes cells)
or ``train_single`` (single-replica cells), made as a user makes it: the
cell's model configuration, optimizer and Hermes settings (every field the
traffic file gives), batch, sequence, ``log_every``, the run's seed, and a
pod mesh for a placed cell.  The entry makes its own tokens and weights
from the seed.  Neither entry takes a time limit, so a run makes two calls:

1. a calibration call of ``2 x log_every`` steps, which loads or compiles
   every executable of the cell and gives the steady step time from its
   ``log_times`` (set-up);
2. the measured call: ``log_every`` warm-up steps, then as many whole
   ``log_every`` blocks as fill ``seconds``.  The window runs from the
   first to the last of its ``log_times``, the entry's own fetches, so
   both ends are synchronised with the device.

The entries return no state.  So that the check reads what the window's
own object computed, the harness wraps the factories the entry looks up by
name in its module (``make_pod_step``, ``make_round_jit``,
``make_async_round_jits``, and the ``partial`` that ``train_single`` jits
its step with).  The wrappers compute nothing for the entry: they pass
each call through, and read on the side (jitted readers that only read
their arguments) the batches fed, the losses and, on the first
``check_steps`` steps, the first gradient as the optimizer received it and
the state's change after those steps; on every round, the pods' test
losses.  Those readings are taken before the window opens.
"""
from __future__ import annotations

import contextlib
import gc
import math
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import weights as W

# The program's RMSNorm epsilon, fixed in ``models/layers.py``; dense
# blocks have no sliding window (``attn_window`` acts on local blocks only).
PROGRAM_RMS_EPS = 1e-6


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (first device: {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# The cell's settings as the program takes them
# ---------------------------------------------------------------------------

def program_config(config: Dict[str, Any]):
    """The registry architecture at the file's sizes (the published widths
    for a benchmark cell; the file lists in ``reduced`` what it cut)."""
    from repro.config import replace
    from repro.configs import get_config
    sizes = {"num_layers": config["num_hidden_layers"],
             "d_model": config["hidden_size"],
             "d_ff": config["intermediate_size"],
             "vocab_size": config["vocab_size"]}
    if "num_attention_heads" in config:
        sizes["num_heads"] = config["num_attention_heads"]
        sizes["num_kv_heads"] = config["num_key_value_heads"]
    if "head_size" in config:
        sizes["head_dim"] = config["head_size"]
        sizes["num_heads"] = sizes["num_kv_heads"] = (
            config["hidden_size"] // config["head_size"])
    if "rope_theta" in config:
        sizes["rope_theta"] = float(config["rope_theta"])
    mc = replace(get_config(config["registry"]),
                 **{k: (int(v) if k != "rope_theta" else v)
                    for k, v in sizes.items()})
    mc.validate()
    return mc


def check_runnable(config: Dict[str, Any], seq: int) -> None:
    """Refuse a configuration the program cannot run as it is stated."""
    eps = config.get("rms_norm_eps")
    if eps is not None and float(eps) != PROGRAM_RMS_EPS:
        raise ValueError(f"the configuration states rms_norm_eps {eps}; the "
                         f"program's RMSNorm runs {PROGRAM_RMS_EPS}")
    window = config.get("sliding_window")
    if window is not None and int(window) < seq:
        raise ValueError(f"the configuration states a sliding window of "
                         f"{window} at sequence {seq}; the program's dense "
                         f"attention has none")


def check_layout(mc, shapes) -> None:
    """The program's parameter tree must have the reference's layout."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_lm
    prog = jax.eval_shape(lambda k: init_lm(mc, k)[0],
                          jax.random.PRNGKey(0))
    got = {p: (tuple(s.shape), str(s.dtype)) for p, s in
           zip(W.leaf_paths(prog), jax.tree.leaves(prog))}
    want = {p: (tuple(s), str(jnp.dtype(jnp.float32))) for p, s in
            zip(W.leaf_paths_of_shapes(shapes), W.shape_leaves(shapes))}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))[:6]
        raise ValueError(f"program parameter layout differs from the "
                         f"reference's: {diff}")


def optimizer_config(traffic):
    from repro.config import OptimizerConfig
    return OptimizerConfig(**traffic["optimizer"])


def hermes_config(traffic):
    """Every ``HermesConfig`` field the traffic file gives, the program's
    defaults for the rest."""
    from repro.config import HermesConfig
    h = {k: tuple(v) if isinstance(v, list) else v
         for k, v in traffic["hermes"].items()}
    hcfg = HermesConfig(**h)
    hcfg.validate()
    return hcfg


def first_grad(opt_name, lr, b1):
    """The first gradient as the optimizer received it, from its state
    after one step: sgd's change is -lr * g; adamw's first moment is
    (1 - beta1) * g."""
    if opt_name == "sgd":
        return 1.0 / lr
    if opt_name == "adamw":
        return 1.0 / (1.0 - b1)
    raise ValueError(f"no first-gradient rule for optimizer {opt_name!r}")


# ---------------------------------------------------------------------------
# Readers of the entry's state
# ---------------------------------------------------------------------------

def _leaf_norms(tree):
    """Per-leaf L2 norms over every axis but the leading (pod) one."""
    import jax
    import jax.numpy as jnp
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)),
                             axis=tuple(range(1, x.ndim))))
            for x in jax.tree.leaves(tree)]


def make_probes(config, ref):
    """Jitted readers: per-leaf norms of a pod-stacked tree, and per-leaf
    norms of a pod-stacked tree's change from the seed's initial weights,
    which it draws again inside (the reference's copy of the program's
    draw, ``ref.init_params``), fused with the difference, so that no
    second copy of the model is held."""
    import jax

    @jax.jit
    def change(tree, key):
        p0 = ref.init_params(config, key)
        return _leaf_norms(jax.tree.map(lambda a, b: a - b[None], tree, p0))

    return change, jax.jit(_leaf_norms)


_COMPILES = [0]
_LISTENING: List[Callable] = []


def _count_compiles(event: str, *_, **__) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES[0] += 1


class Watch:
    """What one entry call's own programs produce, read on the side.

    ``kind`` is ``"hermes"`` (the pod step takes and returns pod-stacked
    trees) or ``"single"`` (``train_single``'s step: a state dict in, a
    state dict and a loss out; read with a pod axis of one)."""

    def __init__(self, kind, *, seed, check_steps, log_every, steps,
                 opt_name, probes, on_window_start=None):
        import jax
        self.kind, self.check_steps = kind, check_steps
        self.log_every, self.steps = log_every, steps
        self.opt_name = opt_name
        self.change, self.norms = probes
        self.key = jax.random.PRNGKey(seed)
        self.on_window_start = on_window_start
        self.calls = 0
        self.loss: List[Any] = []     # every step's loss (device)
        self.feed: List[Any] = []     # the first steps' batches (device)
        self.eval: List[Any] = []     # every round's test losses (device)
        self.round: Dict[str, Any] = {}
        self.grad = self.state_change = None
        self.t_window = None
        self.compiles = [0, 0]
        if _count_compiles not in _LISTENING:
            jax.monitoring.register_event_duration_secs_listener(
                _count_compiles)
            _LISTENING.append(_count_compiles)

    def _stack(self, tree):
        import jax
        if self.kind == "hermes":
            return tree
        return jax.tree.map(lambda x: x[None], tree)

    def _before(self, params):
        i = self.calls
        if i == self.check_steps:
            change = {"pods" if self.kind == "hermes" else "params":
                      self.change(self._stack(params), self.key)}
            if "w_global" in self.round:
                change["w_global"] = self.change(
                    self._stack_one(self.round["w_global"]), self.key)
            if self.round.get("error") is not None:
                change["error"] = self.norms(self.round["error"])
            self.state_change = change
            self.round = {}
        if i == self.log_every:
            self.t_window = time.time()
            if self.on_window_start is not None:
                self.on_window_start()
            self.compiles[0] = _COMPILES[0]
        if i == self.steps - 1:
            self.compiles[1] = _COMPILES[0]

    @staticmethod
    def _stack_one(tree):
        import jax
        return jax.tree.map(lambda x: x[None], tree)

    def _after(self, params, opt, loss, batch):
        i = self.calls
        self.loss.append(loss)
        if i < self.check_steps:
            self.feed.append(self._stack(batch))
        if i == 0:
            self.grad = (self.change(self._stack(params), self.key)
                         if self.opt_name == "sgd"
                         else self.norms(self._stack(opt["m"])))
        self.calls += 1

    def pod_step(self, step):
        def call(pod_params, pod_opt, batches):
            self._before(pod_params)
            pp, po, losses = step(pod_params, pod_opt, batches)
            self._after(pp, po, losses, batches)
            return pp, po, losses
        return call

    def single_step(self, step):
        def call(state, batch):
            self._before(state["params"])
            state, loss = step(state, batch)
            self._after(state["params"], state["opt"], loss, batch)
            return state, loss
        return call

    def round_step(self, rnd):
        def call(pod_params, gup, pod_losses, *rest):
            self.eval.append(pod_losses)
            out = rnd(pod_params, gup, pod_losses, *rest)
            if self.calls <= self.check_steps:
                self.round.update({k: out[k] for k in ("w_global", "error")
                                   if k in out})
            return out
        return call

    def commit_step(self, commit):
        def call(*args):
            out = commit(*args)
            if self.calls <= self.check_steps and "w_global" in out:
                self.round["w_global"] = out["w_global"]
            return out
        return call


@contextlib.contextmanager
def watching(watch: Watch):
    """Wrap, for one entry call, the factories ``repro.launch.train`` looks
    up by name; restore them afterwards."""
    import jax
    from repro.launch import train as T
    saved = {n: getattr(T, n) for n in
             ("make_pod_step", "make_round_jit", "make_async_round_jits",
              "partial")}

    def make_pod_step(*a, **k):
        return watch.pod_step(saved["make_pod_step"](*a, **k))

    def make_round_jit(*a, **k):
        return watch.round_step(saved["make_round_jit"](*a, **k))

    def make_async_round_jits(*a, **k):
        dispatch, commit = saved["make_async_round_jits"](*a, **k)
        return watch.round_step(dispatch), watch.commit_step(commit)

    def partial(fn, *a, **k):
        p = saved["partial"](fn, *a, **k)
        if fn is not jax.jit:
            return p
        return lambda f: watch.single_step(p(f))

    T.make_pod_step, T.make_round_jit = make_pod_step, make_round_jit
    T.make_async_round_jits, T.partial = make_async_round_jits, partial
    try:
        yield watch
    finally:
        for n, v in saved.items():
            setattr(T, n, v)


# ---------------------------------------------------------------------------
# One run of a cell
# ---------------------------------------------------------------------------

def _per_leaf(paths, arrays, scale=1.0):
    return {p: [float(v) * scale for v in np.atleast_1d(np.asarray(a))]
            for p, a in zip(paths, arrays)}


def run(cell, seed: int, seconds: float, *, mesh=None,
        on_window_start: Optional[Callable] = None,
        on_window_end: Optional[Callable] = None) -> Dict[str, Any]:
    """Set-up (the calibration call), the measured call with its window,
    and what the measured call's programs produced."""
    import jax
    from repro.dist.wire import get_format
    from repro.launch import train as T

    tr, config = cell["traffic"], cell["config"]
    kind = tr["loop"]
    B, S = int(tr["batch"]), int(tr["seq"])
    check_runnable(config, S)
    ocfg = optimizer_config(tr)
    log_every, check_steps = int(tr["log_every"]), int(tr["check_steps"])
    if log_every <= check_steps:
        raise ValueError("log_every must exceed check_steps: the checked "
                         "steps are read before the window opens")
    kw = dict(batch=B, seq=S, opt_cfg=ocfg, log_every=log_every, seed=seed)
    if kind == "hermes":
        P = int(tr["pods"])
        hcfg = hermes_config(tr)
        kw.update(pods=P, hcfg=hcfg, mesh=mesh)
        entry = T.train_hermes
    elif kind == "single":
        P = 1
        entry = T.train_single
    else:
        raise ValueError(f"unknown loop {kind!r}")
    probes = make_probes(config, cell["ref"])

    def call(steps, hook=None):
        watch = Watch(kind, seed=seed, check_steps=check_steps,
                      log_every=log_every, steps=steps, opt_name=ocfg.name,
                      probes=probes, on_window_start=hook)
        with watching(watch):
            out = entry(cell["model"], steps=steps, **kw)
        return watch, out

    _, cal = call(2 * log_every)
    (s0, t0), (s1, t1) = cal["log_times"][0], cal["log_times"][-1]
    step_s = (t1 - t0) / (s1 - s0)
    blocks = max(1, math.ceil(seconds / (log_every * step_s)))
    del cal

    # A full collection scans every object set-up left (imports, JAX's
    # caches, the calibration call): about 0.1 s, which lands in some
    # windows and not others.  Set-up's objects are frozen for the measured
    # call; the objects it makes are collected as usual.
    gc.collect()
    gc.freeze()
    try:
        watch, out = call(log_every * (blocks + 1), on_window_start)
    finally:
        gc.unfreeze()
    if on_window_end is not None:
        on_window_end()
    (s0, t0), (s1, t1) = out["log_times"][0], out["log_times"][-1]
    n_steps = s1 - s0

    r = jax.device_get({"loss": watch.loss, "feed": watch.feed,
                        "eval": watch.eval, "grad": watch.grad,
                        "change": watch.state_change})
    in_window = np.asarray(r["loss"][s0:s1], np.float64).reshape(n_steps, -1)
    failed = int((~np.isfinite(in_window)).any(axis=1).sum())
    paths = W.leaf_paths_of_shapes(cell["shapes"])
    gscale = first_grad(ocfg.name, ocfg.lr, ocfg.beta1)
    feed = [{k: np.asarray(b[k]) for k in ("tokens", "targets")}
            for b in r["feed"]]
    readings = {
        "loss": [list(map(float, np.atleast_1d(x)))
                 for x in r["loss"][:check_steps]],
        "feed": {k: np.stack([b[k] for b in feed]) for k in
                 ("tokens", "targets")},
        "grad": _per_leaf(paths, r["grad"], gscale),
        "change": {k: _per_leaf(paths, v) for k, v in r["change"].items()},
    }
    result = {"window_s": t1 - t0, "steps": n_steps,
              "tokens": n_steps * P * B * S, "t_window_start": watch.t_window,
              "failed": failed,
              "compiles_in_window": watch.compiles[1] - watch.compiles[0],
              "readings": readings}
    if kind == "hermes":
        readings["eval"] = [list(map(float, x)) for x in r["eval"]]
        readings["gates"] = [list(map(int, g)) for g in out["gates"]]
        steps_of = [s for s, _, _ in out["history"]]
        opened = sum(sum(g) for s, g in zip(steps_of, readings["gates"])
                     if s0 < s <= s1)
        fmt = get_format(hcfg.compression)
        per_pod = sum(fmt.payload_bytes((P,) + tuple(s))
                      for s in W.shape_leaves(cell["shapes"])) / P
        result.update(rounds=sum(s0 < s <= s1 for s in steps_of),
                      open_gates=opened, wire_bytes_per_pod=per_pod,
                      wire_bytes=opened * per_pod)
    return result
