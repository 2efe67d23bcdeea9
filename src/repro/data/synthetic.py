"""Deterministic synthetic datasets.

No MNIST/CIFAR files are available offline, so the reproduction uses
structured synthetic classification sets with the same geometry:

* ``make_image_dataset`` — class-template images + per-sample Gaussian
  noise + random affine-ish jitter.  ``difficulty`` scales noise/overlap so
  the MNIST stand-in is easy (CNN -> ~98%+) and the CIFAR stand-in hard.
* ``make_lm_dataset`` — Zipf-distributed Markov token streams for LM smoke
  training.

Partitioners mirror the paper: IID uniform (MNIST case) and Dirichlet
class-skew (non-IID, CIFAR case).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def make_image_dataset(n: int, image_shape: Tuple[int, int, int],
                       num_classes: int, *, seed: int = 0,
                       difficulty: float = 0.35,
                       label_noise: float = 0.0) -> Dict[str, np.ndarray]:
    """Returns {"images": (n,H,W,C) float32, "labels": (n,) int32}."""
    rng = np.random.default_rng(seed)
    H, W, C = image_shape
    # smooth class templates: superpose a few random low-frequency bumps
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    templates = np.zeros((num_classes, H, W, C), np.float32)
    for c in range(num_classes):
        for _ in range(4):
            cy, cx = rng.uniform(0.15, 0.85, 2) * (H, W)
            s = rng.uniform(0.08, 0.25) * H
            amp = rng.uniform(0.6, 1.4)
            bump = amp * np.exp(-(((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s)))
            ch = rng.integers(0, C)
            templates[c, :, :, ch] += bump
    templates /= np.maximum(templates.max(axis=(1, 2, 3), keepdims=True), 1e-6)

    labels = rng.integers(0, num_classes, n).astype(np.int32)
    shifts_y = rng.integers(-2, 3, n)
    shifts_x = rng.integers(-2, 3, n)
    images = templates[labels].copy()
    for i in range(n):  # cheap spatial jitter
        images[i] = np.roll(images[i], (shifts_y[i], shifts_x[i]), axis=(0, 1))
    images += rng.normal(0, difficulty, images.shape).astype(np.float32)
    if label_noise > 0:
        flip = rng.random(n) < label_noise
        labels[flip] = rng.integers(0, num_classes, int(flip.sum()))
    return {"images": images.astype(np.float32), "labels": labels}


def make_lm_dataset(n_tokens: int, vocab: int, *, seed: int = 0,
                    order: int = 2) -> np.ndarray:
    """Markov token stream with Zipf unigram marginals; (n_tokens,) int32.

    Each token after the first is, with chance 0.6, its predecessor's fixed
    successor, else a fresh Zipf(1.1) draw.  A token takes one double to
    decide and a second for a fresh draw (inverse CDF, as
    ``rng.choice(vocab, p=...)`` draws), so the doubles are drawn in bulk
    and walked in order: the stream is that of drawing them one by one."""
    rng = np.random.default_rng(seed)
    base = 1.0 / np.arange(1, vocab + 1) ** 1.1
    base /= base.sum()
    cdf = base.cumsum()
    cdf /= cdf[-1]
    toks = np.empty(n_tokens, np.int32)
    toks[0] = cdf.searchsorted(rng.random(), side="right")
    # sparse bigram boosts for learnable structure: a deterministic successor
    boost = rng.integers(0, vocab, size=vocab).tolist()
    u = rng.random(2 * max(n_tokens - 1, 0))
    keep = (u < 0.6).tolist()
    fresh = cdf.searchsorted(u, side="right").tolist()
    prev, j = int(toks[0]), 0
    for i in range(1, n_tokens):
        if keep[j]:
            prev, j = boost[prev], j + 1
        else:
            prev, j = fresh[j + 1], j + 2
        toks[i] = prev
    return toks


def train_test_split(data: Dict[str, np.ndarray], test_frac: float = 0.15,
                     seed: int = 0):
    """The paper's fixed 85/15 split."""
    n = len(data["labels"])
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    k = int(n * (1 - test_frac))
    tr, te = perm[:k], perm[k:]
    take = lambda idx: {k2: v[idx] for k2, v in data.items()}
    return take(tr), take(te)


def iid_partition(n: int, num_workers: int, *, seed: int = 0) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n)
    return [np.sort(p) for p in np.array_split(perm, num_workers)]


def dirichlet_partition(labels: np.ndarray, num_workers: int, *,
                        alpha: float = 0.5, seed: int = 0) -> List[np.ndarray]:
    """Non-IID class-skew partition (standard federated benchmark recipe)."""
    rng = np.random.default_rng(seed)
    num_classes = int(labels.max()) + 1
    out: List[List[int]] = [[] for _ in range(num_workers)]
    for c in range(num_classes):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        props = rng.dirichlet([alpha] * num_workers)
        cuts = (np.cumsum(props) * len(idx)).astype(int)[:-1]
        for w, part in enumerate(np.split(idx, cuts)):
            out[w].extend(part.tolist())
    return [np.sort(np.array(o, dtype=np.int64)) for o in out]
