"""The reference's own copy of how the program makes its inputs from the
seed, and the parameter-tree helpers the benchmark shares.

The reference imports nothing of the program under test, so it draws the
same numbers itself:

* ``lm_tokens``: the token stream of ``repro.data.synthetic.
  make_lm_dataset`` (Zipf(1.1) unigrams, a fixed successor with chance
  0.6), from the same PCG64 doubles, drawn in bulk;
* ``hermes_batches`` / ``single_batches``: the batches ``train_hermes``
  and ``train_single`` feed from that stream (``make_batches``), and the
  Hermes gate's held-out batch.

The initial weights are the configuration's reference's to draw
(``ref_dense.init_params``).  The harness counts the tokens the program
fed that this copy does not have (``feed_miss``).
"""
from __future__ import annotations

from typing import List

import numpy as np

ZIPF = 1.1
SUCCESSOR = 0.6


def lm_tokens(n: int, vocab: int, seed: int) -> np.ndarray:
    """``n`` int32 tokens, equal to ``make_lm_dataset(n, vocab, seed=seed)``.

    The program draws one double per token, and a second for a fresh
    Zipf token (inverse CDF, ``searchsorted(..., side="right")``); which
    doubles are which depends only on the doubles, so they are drawn at
    once and walked in order."""
    rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, vocab + 1) ** ZIPF
    base /= base.sum()
    cdf = base.cumsum()
    cdf /= cdf[-1]
    first = int(cdf.searchsorted(rng.random(), side="right"))
    boost = rng.integers(0, vocab, size=vocab).tolist()
    u = rng.random(2 * max(n - 1, 0))
    keep = (u < SUCCESSOR).tolist()
    fresh = cdf.searchsorted(u, side="right").tolist()
    out = [0] * n
    prev, j = first, 0
    out[0] = prev
    for i in range(1, n):
        if keep[j]:
            prev, j = boost[prev], j + 1
        else:
            prev, j = fresh[j + 1], j + 2
        out[i] = prev
    return np.asarray(out, np.int32)


def _rows(tokens: np.ndarray, batch: int, seq: int, rng) -> np.ndarray:
    """One ``make_batches`` draw: ``(batch, seq + 1)`` rows, inputs then
    the last target."""
    n = (len(tokens) - 1) // seq
    idx = rng.integers(0, n, batch)
    return np.stack([tokens[i * seq:(i + 1) * seq + 1] for i in idx])


def hermes_batches(vocab: int, seed: int, *, pods: int, batch: int,
                   seq: int, steps: int):
    """``train_hermes``'s feed: ``{"tokens", "targets"}``, each
    ``(steps, pods, batch, seq)``, for the first ``steps`` pod steps, and
    the gate's held-out batch, ``{"tokens", "targets"}`` of
    ``(min(batch, 8), seq)``.

    The entry builds a step's batch key by key, ``{k: stack([next(b)[k]
    for b in pods]) for k in ("tokens", "targets")}``, so each pod's
    tokens and targets come from two successive draws of its stream: a
    step's targets are not its tokens shifted by one.  The copy follows
    the program."""
    held = batch * seq + 1
    tokens = lm_tokens(batch * seq * 40 * pods + batch * seq + 2, vocab, seed)
    shards = np.array_split(tokens[:-held], pods)
    rngs = [np.random.default_rng(seed + i) for i in range(pods)]
    feed = {"tokens": [], "targets": []}
    for _ in range(steps):
        for k, cut in (("tokens", slice(0, -1)), ("targets", slice(1, None))):
            feed[k].append(np.stack([_rows(s, batch, seq, r)[:, cut]
                                     for s, r in zip(shards, rngs)]))
    ev = _rows(tokens[-held:], min(batch, 8), seq, np.random.default_rng(seed))
    return ({k: np.stack(v) for k, v in feed.items()},
            {"tokens": ev[:, :-1], "targets": ev[:, 1:]})


def single_batches(vocab: int, seed: int, *, batch: int, seq: int,
                   steps: int):
    """``train_single``'s feed: ``{"tokens", "targets"}``, each
    ``(steps, 1, batch, seq)``, one draw a step."""
    tokens = lm_tokens(batch * seq * 40 + 1, vocab, seed)
    rng = np.random.default_rng(seed)
    rows = np.stack([_rows(tokens, batch, seq, rng)[None]
                     for _ in range(steps)])
    return {"tokens": rows[..., :-1], "targets": rows[..., 1:]}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def leaf_paths(tree, is_leaf=None) -> List[str]:
    """``"a/b/c"`` names of a nested dict's leaves, in flatten order."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_leaf)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def leaf_paths_of_shapes(shapes) -> List[str]:
    """Leaf names of a nested dict whose leaves are shape tuples."""
    return leaf_paths(shapes, _is_shape)


def shape_leaves(shapes) -> list:
    import jax
    return jax.tree_util.tree_leaves(shapes, is_leaf=_is_shape)
