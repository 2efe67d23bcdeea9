"""Mixture-of-Experts: top-k router, dropless and capacity dispatch.

Three implementations of one layer (tested against each other):

* ``dense``    — one-hot einsum over the held experts; exact,
                 O(E·tokens·d·ff) FLOPs; smoke tests and the oracle.
* ``dropless`` — sort the assignments by held expert and run the expert
                 FFN as grouped products (``jax.lax.ragged_dot``) over the
                 sorted groups; no assignment is dropped.  The path of a
                 ``MoEConfig(dropless=True)`` layer.
* ``sorted``   — argsort tokens by expert, bucket into (E, C, d) with a
                 capacity C = ceil(top_k·tokens/E·capacity_factor), run the
                 expert FFN as one batched einsum, scatter back.  Tokens
                 beyond an expert's capacity are dropped, matching standard
                 TPU MoE practice.

A layer may hold a share of the experts (``MoEConfig.num_held`` from
``first_held``, expert parallelism): the router scores every expert, the
layer computes its own experts' part of the result, and assignments to
absent experts add nothing here.  Expert ``e``'s weights are drawn from
``fold_in(key, e)``, so a share holds exactly the uncut layer's rows.

Expert weights are stacked (E, d, ff) with the expert dim sharded over the
"model" axis (expert parallelism); GSPMD inserts the all-to-all.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.dist.sharding import AxisRules, constrain
from repro.models.layers import P, dense_init
from repro.utils import tracing


def _expert_init(key, me, shape, axes) -> P:
    """The held experts' stacked (held,) + ``shape`` weights, normal over
    the square root of ``shape[0]``; expert ``e`` from ``fold_in(key, e)``."""
    ids = jnp.arange(me.first_held, me.first_held + me.held)
    w = jax.vmap(lambda e: jax.random.normal(jax.random.fold_in(key, e),
                                             shape))(ids)
    return P(w * (1.0 / np.sqrt(shape[0])), tuple(axes))


def init_moe(cfg: ModelConfig, key) -> Dict[str, Any]:
    me = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    gated = cfg.mlp_kind == "swiglu"
    p: Dict[str, Any] = {
        "router": dense_init(ks[0], (d, me.num_experts), ("qkv", "expert")),
        "wi": _expert_init(ks[1], me, (d, me.expert_ff),
                           ("expert", "qkv", "expert_ff")),
        "wo": _expert_init(ks[2], me, (me.expert_ff, d),
                           ("expert", "expert_ff", "qkv")),
    }
    if gated:
        p["wg"] = _expert_init(ks[3], me, (d, me.expert_ff),
                               ("expert", "qkv", "expert_ff"))
    if me.num_shared_experts:
        sf = (me.shared_ff or me.expert_ff) * me.num_shared_experts
        p["shared_wi"] = dense_init(ks[4], (d, sf), ("qkv", "ff"))
        p["shared_wo"] = dense_init(ks[5], (sf, d), ("ff", "qkv"))
        if gated:
            p["shared_wg"] = dense_init(ks[6], (d, sf), ("qkv", "ff"))
    return p


def _act(cfg: ModelConfig, h, g=None):
    if cfg.mlp_kind == "swiglu":
        return jax.nn.silu(g) * h
    if cfg.mlp_kind == "relu_sq":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h)


def _router(p, x2d: jnp.ndarray, me
            ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x2d: (T, d) -> (top-k weights (T,k), top-k expert ids (T,k), softmax
    scores (T,E)).  The weights are renormalised to sum to one where
    ``norm_topk_prob``, else they are the scores themselves."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    wk, ids = jax.lax.top_k(probs, me.top_k)
    if me.norm_topk_prob:
        wk = wk / jnp.maximum(jnp.sum(wk, axis=-1, keepdims=True), 1e-9)
    return wk, ids, probs


def _held_slot(ids, me):
    """Each assignment's held expert, 0..held-1, and ``held`` for an
    assignment to an absent expert."""
    local = ids - me.first_held
    return jnp.where((local >= 0) & (local < me.held), local, me.held)


def balance_loss(probs, ids, me, batch: int) -> jnp.ndarray:
    """DeepSeek-V2's sequence-wise balance loss (``seq_aux``):
    alpha * mean over sequences of sum_e f_e P_e, with f_e the sequence's
    assignments to expert e times E / (S k) and P_e its mean score.  The
    router is whole on every chip, so every expert counts."""
    E, k = me.num_experts, me.top_k
    probs = probs.reshape(batch, -1, E)
    S = probs.shape[1]
    f = jax.nn.one_hot(ids.reshape(batch, S * k), E,
                       dtype=jnp.float32).sum(1) * (E / (S * k))
    return me.aux_loss_alpha * jnp.mean(jnp.sum(f * probs.mean(1), -1))


def _aux(probs, ids, me, batch: int) -> Dict[str, jnp.ndarray]:
    """The layer's balance-loss term and the assignments each held expert
    received (int32, (held,))."""
    load = jnp.bincount(_held_slot(ids, me).reshape(-1),
                        length=me.held + 1)[:me.held].astype(jnp.int32)
    balance = (balance_loss(probs, ids, me, batch) if me.aux_loss_alpha
               else jnp.float32(0.0))
    return {"balance": balance, "load": load}


def _shared(p, x, cfg) -> jnp.ndarray:
    dt = x.dtype
    h = jnp.einsum("...d,df->...f", x, p["shared_wi"].astype(dt))
    g = (jnp.einsum("...d,df->...f", x, p["shared_wg"].astype(dt))
         if "shared_wg" in p else None)
    h = _act(cfg, h, g)
    return jnp.einsum("...f,fd->...d", h, p["shared_wo"].astype(dt))


def moe_dense(p, x: jnp.ndarray, cfg: ModelConfig,
              rules: Optional[AxisRules], routed=None) -> jnp.ndarray:
    """Oracle: every held expert runs on every token.  ``routed`` is the
    router's output for ``x``, where the caller already has it."""
    me = cfg.moe
    dt = x.dtype
    B, S, d = x.shape
    x2d = x.reshape(B * S, d)
    wk, ids, _ = routed or _router(p, x2d, me)
    # combine weights (T, E), then the held experts' columns
    comb = jnp.zeros((B * S, me.num_experts), jnp.float32)
    comb = comb.at[jnp.arange(B * S)[:, None], ids].add(wk)
    comb = comb[:, me.first_held:me.first_held + me.held]
    h = jnp.einsum("td,edf->tef", x2d, p["wi"].astype(dt))
    g = jnp.einsum("td,edf->tef", x2d, p["wg"].astype(dt)) if "wg" in p else None
    h = _act(cfg, h, g)
    y = jnp.einsum("tef,efd->ted", h, p["wo"].astype(dt))
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), comb).astype(dt)
    out = out.reshape(B, S, d)
    if me.num_shared_experts:
        out = out + _shared(p, x, cfg)
    return out


def _dispatch_group(x2d, wk, ids, p, cfg: ModelConfig, capacity: int):
    """Sort-based dispatch of ONE token group.  x2d: (Tg, d)."""
    me = cfg.moe
    dt = x2d.dtype
    Tg, d = x2d.shape
    k, E = me.top_k, me.num_experts

    flat_ids = ids.reshape(-1)            # (Tg*k,)
    flat_w = wk.reshape(-1)
    token_of = jnp.repeat(jnp.arange(Tg), k)

    order = jnp.argsort(flat_ids, stable=True)          # group by expert
    sorted_e = flat_ids[order]
    sorted_tok = token_of[order]
    sorted_w = flat_w[order]

    seg_start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(Tg * k) - seg_start[sorted_e]
    keep = pos_in_e < capacity                          # capacity drop
    slot = jnp.where(keep, sorted_e * capacity + pos_in_e, E * capacity)

    bucket = jnp.zeros((E * capacity + 1, d), dt)
    bucket = bucket.at[slot].set(x2d[sorted_tok])
    eb = bucket[:-1].reshape(E, capacity, d)
    return eb, (slot, sorted_tok, sorted_w, keep)


def _combine_group(y, route, Tg: int, dt):
    """Scatter expert outputs of one group back to its tokens."""
    slot, sorted_tok, sorted_w, keep = route
    E, capacity, d = y.shape
    yflat = y.reshape(E * capacity, d)
    contrib = yflat[jnp.minimum(slot, E * capacity - 1)]
    contrib = jnp.where(keep[:, None], contrib * sorted_w[:, None].astype(dt),
                        jnp.zeros_like(contrib))
    out = jnp.zeros((Tg, d), jnp.float32).at[sorted_tok].add(
        contrib.astype(jnp.float32))
    return out.astype(dt)


def moe_sorted(p, x: jnp.ndarray, cfg: ModelConfig,
               rules: Optional[AxisRules],
               capacity: Optional[int] = None,
               groups: int = 1, routed=None) -> jnp.ndarray:
    """Production path: per-group sort dispatch + capacity-bucketed FFN.
    ``routed`` is the router's output for ``x``, where the caller already
    has it.

    ``groups`` partitions the tokens into independently-dispatched blocks
    aligned with the data-parallel shards: the argsort/bucketing stays LOCAL
    to each shard (no cross-data gathering), buckets carry a leading
    group dim sharded like the batch, and each group gets capacity/groups
    slots per expert (standard per-group capacity semantics).
    """
    me = cfg.moe
    dt = x.dtype
    B, S, d = x.shape
    T = B * S
    G = max(1, min(groups, T))
    while T % G:
        G //= 2  # fall back to a divisor
    Tg = T // G
    k, E = me.top_k, me.num_experts
    if capacity is None:
        capacity = int((k * Tg / E) * me.capacity_factor + 0.999)
        capacity = max(min(capacity, Tg), 1)
        capacity = ((capacity + 7) // 8) * 8

    xg = x.reshape(G, Tg, d)
    xg = constrain(xg, rules, "moe_group", None, None)
    wk, ids, _ = routed or _router(p, xg.reshape(T, d), me)
    wk = wk.reshape(G, Tg, k)
    ids = ids.reshape(G, Tg, k)

    eb, route = jax.vmap(
        lambda xx, ww, ii: _dispatch_group(xx, ww, ii, p, cfg, capacity)
    )(xg, wk, ids)
    eb = constrain(eb, rules, "moe_group", "expert", None, None)

    h = jnp.einsum("gecd,edf->gecf", eb, p["wi"].astype(dt))
    g = (jnp.einsum("gecd,edf->gecf", eb, p["wg"].astype(dt))
         if "wg" in p else None)
    h = _act(cfg, h, g)
    h = constrain(h, rules, "moe_group", "expert", None, "act_ff")
    y = jnp.einsum("gecf,efd->gecd", h, p["wo"].astype(dt))  # (G,E,C,d)

    out = jax.vmap(lambda yy, rr: _combine_group(yy, rr, Tg, dt))(y, route)
    out = constrain(out, rules, "moe_group", None, None)
    out = out.reshape(B, S, d)
    if me.num_shared_experts:
        out = out + _shared(p, x, cfg)
    return out


def moe_dropless(p, x: jnp.ndarray, cfg: ModelConfig,
                 rules: Optional[AxisRules]):
    """Dropless path over the held experts.  Returns ``(out, aux)``.

    The assignments are sorted by held expert, those to absent experts
    last; the first ``T * min(top_k, held)`` of them (every held assignment
    fits: a token picks an expert once) gather their tokens, and the
    expert FFN runs as three grouped products over the held experts'
    groups (``jax.lax.ragged_dot``; rows past the groups are zero
    padding).  The outputs, weighted by the router, are scatter-added back
    to their tokens; the shared experts are added once.
    """
    me = cfg.moe
    dt = x.dtype
    B, S, d = x.shape
    T, k, H = B * S, me.top_k, me.held
    x2d = x.reshape(T, d)
    with tracing.scope(tracing.ROUTE):
        wk, ids, probs = _router(p, x2d, me)
        aux = _aux(probs, ids, me, B)
    with tracing.scope(tracing.DISPATCH):
        slot = _held_slot(ids, me).reshape(-1)
        rows = jnp.argsort(slot, stable=True)[:T * min(k, H)]
        tok = rows // k
        held = slot[rows] < H
        # the grouped kernels leave the rows past the held groups unwritten
        # (on the TPU, whatever the buffer held, NaN included), forward and
        # in the input gradient: every product's output is zeroed there, so
        # that every operand of a product, and of its weight gradient, is
        # zero past the groups, and no gradient (the router weight's
        # included) reads those rows
        xs = jnp.where(held[:, None], x2d[tok], 0)

    def grouped(a, w):
        return jnp.where(held[:, None],
                         jax.lax.ragged_dot(a, w.astype(dt), aux["load"]), 0)

    with tracing.scope(tracing.EXPERTS):
        h = grouped(xs, p["wi"])
        g = grouped(xs, p["wg"]) if "wg" in p else None
        y = grouped(_act(cfg, h, g), p["wo"])
    with tracing.scope(tracing.DISPATCH):
        w = wk.reshape(-1)[rows]
        contrib = y.astype(jnp.float32) * w[:, None]
        out = jnp.zeros((T, d), jnp.float32).at[tok].add(contrib)
        out = out.astype(dt).reshape(B, S, d)
    if me.num_shared_experts:
        with tracing.scope(tracing.SHARED):
            out = out + _shared(p, x, cfg)
    return out, aux


def apply_moe(p, x: jnp.ndarray, cfg: ModelConfig,
              rules: Optional[AxisRules], impl: str = "auto",
              groups: int = 1):
    """``(out, aux)``; ``aux`` holds the layer's balance-loss term and the
    assignments each held expert received.  A dropless layer never takes
    the capacity path: ``"auto"`` and ``"sorted"`` run it dropless."""
    me = cfg.moe
    if impl in ("auto", "sorted") and me.dropless:
        impl = "dropless"
    elif impl == "auto":
        impl = "dense" if x.shape[0] * x.shape[1] <= 512 else "sorted"
    if impl == "dropless":
        return moe_dropless(p, x, cfg, rules)
    routed = _router(p, x.reshape(-1, x.shape[-1]), me)
    out = (moe_dense(p, x, cfg, rules, routed) if impl == "dense"
           else moe_sorted(p, x, cfg, rules, groups=groups, routed=routed))
    return out, _aux(routed[2], routed[1], me, x.shape[0])
