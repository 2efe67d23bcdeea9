"""The plain reference of the first training steps of a cell.

It follows the cell's first ``check_steps`` steps in float32 at
``Precision.HIGHEST``: the model's loss and gradient (the configuration's
reference module), the optimizer update (sgd with global-norm clipping, or
adamw), and for a Hermes cell the round after each step where one is due:

* the gate (Algorithm 1): z-score of a pod's test loss against the last
  ``window`` losses, open at z <= alpha, alpha relaxed by beta after lam
  closed rounds;
* each open pod's delta (pod - global) plus its error residual, quantized
  to int4 in absmax blocks of 256 along the rightmost whole-block axis with
  stochastic rounding (its own noise), the residual kept;
* the loss-weighted merge w' = (w/L + sum_i (w + d_i)/l_i) / (1/L + sum 1/l_i)
  over open pods, and the refresh of open pods to w'.

The gate decisions are taken from the reference's gate applied to the
program's test losses, so that a rounding-level difference in a loss
cannot flip a decision; the test losses themselves are compared apart.
The gate then follows the program's test losses over every later round of
the run, the window's included.  The batches and initial weights are the
reference's own copies of the program's draws (``weights.py``,
``init_params`` of the configuration's reference).
Nothing here imports the program under test.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

import weights as W

BLOCK = 256
EPS = 1e-12


def _batch(tokens, targets, half=False):
    import jax.numpy as jnp
    targets = np.array(targets)
    if half:   # fault: the second half of the batch's tokens left out
        flat = targets.reshape(-1)
        flat[flat.size // 2:] = -1
    return {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(targets)}


def _norms(tree) -> List[float]:
    import jax
    import jax.numpy as jnp
    return [float(jnp.sqrt(jnp.sum(jnp.square(x))))
            for x in jax.tree.leaves(tree)]


def clip(grads, max_norm):
    import jax
    import jax.numpy as jnp
    if max_norm <= 0:
        return grads
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    s = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: g * s, grads)


def int4_roundtrip(x, key):
    """Blockwise absmax int4 with stochastic rounding: what the receiver
    decodes from one pod's leaf."""
    import jax
    import jax.numpy as jnp
    shape = x.shape if x.ndim else (1,)
    x = x.reshape(shape)
    ax = next((a for a in range(len(shape) - 1, -1, -1)
               if shape[a] % BLOCK == 0), len(shape) - 1)
    d = shape[ax]
    nb = -(-d // BLOCK)
    pad = [(0, 0)] * len(shape)
    pad[ax] = (0, nb * BLOCK - d)
    xb = jnp.pad(x, pad).reshape(shape[:ax] + (nb, BLOCK) + shape[ax + 1:])
    scale = jnp.maximum(jnp.max(jnp.abs(xb), axis=ax + 1, keepdims=True)
                        / 7.0, 1e-12)
    u = jax.random.uniform(key, xb.shape)
    q = jnp.clip(jnp.floor(xb / scale + u), -7.0, 7.0)
    out = (q * scale).reshape(shape[:ax] + (nb * BLOCK,) + shape[ax + 1:])
    return jax.lax.slice_in_dim(out, 0, d, axis=ax)


class Gate:
    """Algorithm 1 for one pod, in float32 as the configuration states."""

    def __init__(self, h):
        self.h = h
        self.queue: List[np.float32] = []
        self.alpha = np.float32(h["alpha"])
        self.n_iter = 0

    def __call__(self, loss: float) -> bool:
        h = self.h
        q = np.array(self.queue[-int(h.get("window", 10)):], np.float32)
        z = np.float32(np.inf)
        if len(q) >= 2:
            mu = np.float32(q.sum(dtype=np.float32) / np.float32(len(q)))
            var = np.float32(np.square(q - mu).sum(dtype=np.float32)
                             / np.float32(len(q)))
            sigma = np.float32(np.sqrt(var))
            if sigma > 1e-12:
                z = np.float32((np.float32(loss) - mu) / sigma)
        push = bool(z <= self.alpha)
        self.queue.append(np.float32(loss))
        self.n_iter = 0 if push else self.n_iter + 1
        if not push and self.n_iter >= int(h["lam"]):
            self.alpha = np.float32(min(self.alpha + np.float32(h["beta"]),
                                        np.float32(h.get("alpha_max", 0.0))))
            self.n_iter = 0
        self.alpha = np.float32(max(self.alpha,
                                    np.float32(h.get("alpha_min", -3.0))))
        return push


def readings(ref, cell, seed: int, *, prog_eval=None, low: bool = False,
             fault: Optional[str] = None, devices=None) -> Dict[str, Any]:
    """The reference's readings of the cell's first steps, in the same
    layout as the harness takes from the program.

    ``low`` computes in the control's precision.  ``fault`` plants one
    fault in the reference put in the program's place: ``"half_batch"``
    (the loss a mean over half of the batch's tokens), ``"no_exchange"``
    (the merge sees only the first pod's payload) or ``"gate_open"`` (every
    gate opens, whatever the z-score).  ``prog_eval`` holds the
    program's test losses of every round of its run, which drive the gate
    decisions: those of the first steps' rounds, and then the gate alone
    over the rest of the rounds.
    """
    import jax
    import jax.numpy as jnp

    config, tr = cell["config"], cell["traffic"]
    shapes = ref.param_shapes(config)
    paths = W.leaf_paths_of_shapes(shapes)
    hermes = tr["loop"] == "hermes"
    P = int(tr["pods"]) if hermes else 1
    n = int(tr["check_steps"])
    o = tr["optimizer"]
    lr, max_norm = float(o["lr"]), float(o.get("grad_clip", 0.0))
    devices = devices or jax.devices()[:1]

    def dev(i):
        return devices[i % len(devices)]

    if hermes:
        h = tr["hermes"]
        if (h.get("async_rounds") or int(h.get("n_clusters", 1)) > 1
                or float(h.get("participation_rate", 1.0)) < 1.0
                or h.get("compression", "int4") != "int4"
                or not h.get("error_feedback", True)
                or float(h["eta"]) != 1.0):
            raise ValueError("the reference follows synchronous single-tier "
                             "rounds in which every open gate ships int4 "
                             "with error feedback, merged at eta 1")
        feed, ev = W.hermes_batches(
            int(config["vocab_size"]), seed, pods=P, batch=int(tr["batch"]),
            seq=int(tr["seq"]), steps=n)
    else:
        feed = W.single_batches(int(config["vocab_size"]), seed,
                                batch=int(tr["batch"]), seq=int(tr["seq"]),
                                steps=n)
    key = jax.random.PRNGKey(seed)
    half = fault == "half_batch"

    with jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda k: ref.init_params(config, k))(key)
        vg = jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, b, config, low)))
        ev_loss = jax.jit(lambda p, b: ref.loss(p, b, config, low))
        eval_batch = _batch(ev["tokens"], ev["targets"]) if hermes else None

        out: Dict[str, Any] = {"loss": [], "eval": [], "gates": []}
        pods = [jax.device_put(p0, dev(i)) for i in range(P)]
        if o["name"] == "adamw":
            m = [jax.tree.map(jnp.zeros_like, p) for p in pods]
            v = [jax.tree.map(jnp.zeros_like, p) for p in pods]
        if hermes:
            w = p0
            L = 1e9
            err = [jax.tree.map(jnp.zeros_like, p) for p in pods]
            gates = [Gate(h) for _ in range(P)]
        for step in range(n):
            losses = []
            for i in range(P):
                b = jax.device_put(_batch(feed["tokens"][step, i],
                                          feed["targets"][step, i], half),
                                   dev(i))
                loss, g = vg(pods[i], b)
                g = clip(g, max_norm)
                losses.append(float(loss))
                if o["name"] == "sgd":
                    pods[i] = jax.tree.map(lambda p, gg: p - lr * gg,
                                           pods[i], g)
                elif o["name"] == "adamw":
                    b1, b2 = float(o.get("beta1", 0.9)), float(
                        o.get("beta2", 0.999))
                    eps, wd = float(o.get("eps", 1e-8)), float(
                        o.get("weight_decay", 0.0))
                    t = step + 1
                    m[i] = jax.tree.map(lambda a, gg: b1 * a + (1 - b1) * gg,
                                        m[i], g)
                    v[i] = jax.tree.map(
                        lambda a, gg: b2 * a + (1 - b2) * gg * gg, v[i], g)
                    pods[i] = jax.tree.map(
                        lambda p, a, c: p - lr * (
                            (a / (1 - b1 ** t))
                            / (jnp.sqrt(c / (1 - b2 ** t)) + eps) + wd * p),
                        pods[i], m[i], v[i])
                else:
                    raise ValueError(o["name"])
                if step == 0:
                    out.setdefault("grad_pods", []).append(_norms(g))
            out["loss"].append(losses)
            if hermes and (step == 0 or (step + 1) % int(h["lam"]) == 0):
                r = len(out["eval"])
                E = [float(ev_loss(pods[i], jax.device_put(eval_batch,
                                                           dev(i))))
                     for i in range(P)]
                decide = prog_eval[r] if prog_eval is not None else E
                open_ = [gates[i](decide[i]) or fault == "gate_open"
                         for i in range(P)]
                out["eval"].append(E)
                out["gates"].append([int(x) for x in open_])
                if any(open_):
                    w, pods, err, L = _merge(
                        w, pods, err, open_, E, L, jax.random.fold_in(
                            key, 0x7E5 + step), fault, dev,
                        lambda p: float(ev_loss(p, eval_batch)))
        if hermes and prog_eval is not None:
            # the gate alone over the rest of the run's rounds, on the
            # program's test losses
            for r in range(len(out["gates"]), len(prog_eval)):
                out["gates"].append([int(gates[i](prog_eval[r][i])
                                         or fault == "gate_open")
                                     for i in range(P)])
        grad_pods = out.pop("grad_pods")
        grad = {p: [gp[j] for gp in grad_pods] for j, p in enumerate(paths)}
        change: Dict[str, Any] = {}
        key_pods = "pods" if hermes else "params"
        per_pod = [_norms(jax.tree.map(
            lambda a, b: a - b, jax.device_put(pods[i], dev(0)), p0))
            for i in range(P)]
        change[key_pods] = {p: [pp[j] for pp in per_pod]
                            for j, p in enumerate(paths)}
        if hermes:
            wn = _norms(jax.tree.map(lambda a, b: a - b, w, p0))
            change["w_global"] = {p: [wn[j]] for j, p in enumerate(paths)}
            en = [_norms(e) for e in err]
            change["error"] = {p: [e[j] for e in en]
                               for j, p in enumerate(paths)}
    out["feed"] = feed
    out["grad"] = grad
    out["change"] = change
    out["p0"] = dict(zip(paths, _norms(p0)))
    if not hermes:
        del out["eval"], out["gates"]
    return out


def _merge(w, pods, err, open_, E, L, key, fault, dev, eval_global):
    """One open round: encode, merge, refresh.  Returns (w, pods, err, L)."""
    import jax
    import jax.numpy as jnp
    P = len(pods)
    home = dev(0)
    w1 = 1.0 / max(L, EPS)
    w2 = [1.0 / max(E[i], EPS) if open_[i] else 0.0 for i in range(P)]
    if fault == "no_exchange":   # the merge sees the first pod's row only
        w2 = [w2[0]] + [0.0] * (P - 1)
    denom = w1 + sum(w2)
    w_leaves, treedef = jax.tree.flatten(w)
    acc = [w1 * g for g in w_leaves]
    new_err = list(err)
    for i in range(P):
        if not open_[i]:
            continue
        eff = jax.tree.map(lambda p, g, e: p - g + e, pods[i],
                           jax.device_put(w, dev(i)), err[i])
        eff_leaves = jax.tree.leaves(eff)
        rec = [int4_roundtrip(x, jax.random.fold_in(key, j * P + i))
               for j, x in enumerate(eff_leaves)]
        new_err[i] = jax.tree.unflatten(
            treedef, [x - r for x, r in zip(eff_leaves, rec)])
        acc = [a + w2[i] * (g + jax.device_put(r, home))
               for a, g, r in zip(acc, w_leaves, rec)]
    w_new = jax.tree.unflatten(treedef, [a / denom for a in acc])
    pods = [jax.device_put(w_new, dev(i)) if open_[i] else pods[i]
            for i in range(P)]
    return w_new, pods, new_err, eval_global(w_new)
