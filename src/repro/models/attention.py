"""Attention: GQA (+qk-norm, RoPE, sliding window), MLA, KV caches.

Three execution paths:
  * ``naive``   — materializes (Sq, Skv) scores; tests / tiny shapes.
  * ``blocked`` — flash-style online-softmax over KV chunks in pure jnp;
                  bounded memory, used by the dry-run / CPU path.
  * ``pallas``  — the TPU kernel in :mod:`repro.kernels.flash_attention`
                  (selected by ops-level dispatch, validated in interpret mode).

Decode uses a ring-buffer cache when the layer has a local window (bounded
state for long_500k) and a linear cache otherwise.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.dist.sharding import AxisRules, constrain
from repro.models.layers import (
    apply_rope, dense_init, ones_init, rms_norm_vec, yarn_mscale,
)
from repro.utils import tracing

NEG_INF = -1e30
# The blocked path's forward keeps every chunk's float32 scores for the
# backward pass up to this many bytes per call; past it the backward
# recomputes them chunk by chunk.  phi3-mini at 2 x 2048 tokens and 32
# heads keeps its 1 GiB; at 2 x 4096 tokens and 16 heads
# (DeepSeek-V2-Lite) they are 2 GiB a layer, and its five-layer step
# compiled for a 16 GiB v5e needs 19.0 GiB with them kept, 12.6 without.
SAVED_SCORES_MAX_BYTES = 1 << 30


# ---------------------------------------------------------------------------
# Core attention math
# ---------------------------------------------------------------------------

def naive_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    q_positions: Optional[jnp.ndarray] = None,
                    kv_positions: Optional[jnp.ndarray] = None,
                    scale: Optional[float] = None) -> jnp.ndarray:
    """Reference attention.  q:(B,Sq,H,D) k,v:(B,Skv,K,D); H = K*G."""
    B, Sq, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    qq = q.reshape(B, Sq, K, G, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qq.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = q_positions if q_positions is not None else jnp.arange(Sq)
    kpos = kv_positions if kv_positions is not None else jnp.arange(k.shape[1])
    mask = jnp.ones((Sq, k.shape[1]), dtype=bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    mask &= kpos[None, :] >= 0  # ring-buffer slots not yet written
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: int = 0,
                      q_positions: Optional[jnp.ndarray] = None,
                      kv_positions: Optional[jnp.ndarray] = None,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      scale: Optional[float] = None) -> jnp.ndarray:
    """Flash-style online-softmax attention with bounded temporaries.

    Scans KV chunks for each query chunk, carrying (acc, row_max, row_sum).
    Produces identical results to :func:`naive_attention` (fp32 accumulate).
    Where every chunk's float32 scores would take more than
    ``SAVED_SCORES_MAX_BYTES``, the backward pass recomputes each chunk's
    scores instead of keeping them from the forward pass.
    """
    B, Sq, H, D = q.shape
    Skv, K = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // K
    scale = scale if scale is not None else D ** -0.5
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad sequence dims to chunk multiples
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    qpos = q_positions if q_positions is not None else jnp.arange(Sq)
    kpos = kv_positions if kv_positions is not None else jnp.arange(Skv)
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, (0, pq), constant_values=qpos[-1])
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, (0, pk), constant_values=-1)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // kv_chunk
    saved = 4 * B * H * q.shape[1] * k.shape[1]

    qc = q.reshape(B, nq, q_chunk, K, G, D).astype(jnp.float32)
    kc = k.reshape(B, nk, kv_chunk, K, D).astype(jnp.float32)
    vc = v.reshape(B, nk, kv_chunk, K, Dv).astype(jnp.float32)
    qpc = qpos.reshape(nq, q_chunk)
    kpc = kpos.reshape(nk, kv_chunk)

    def q_block(carry, qi):
        del carry
        qb = qc[:, qi]          # (B, qc, K, G, D)
        qp = qpc[qi]            # (qc,)

        def kv_step(state, ki):
            acc, mx, sm = state
            kb, vb, kp = kc[:, ki], vc[:, ki], kpc[ki]
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * scale
            m = kp[None, :] >= 0
            if causal:
                m &= kp[None, :] <= qp[:, None]
            if window > 0:
                m &= (qp[:, None] - kp[None, :]) < window
            s = jnp.where(m[None, None, None], s, NEG_INF)
            new_mx = jnp.maximum(mx, jnp.max(s, axis=-1))
            alpha = jnp.exp(mx - new_mx)
            p = jnp.exp(s - new_mx[..., None])
            sm = sm * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bkgqs,bskd->bkgqd", p, vb)
            return (acc, new_mx, sm), None

        acc0 = jnp.zeros((B, K, G, q_chunk, Dv), jnp.float32)
        mx0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        sm0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        step = kv_step if saved <= SAVED_SCORES_MAX_BYTES else (
            jax.checkpoint(kv_step))
        (acc, mx, sm), _ = jax.lax.scan(step, (acc0, mx0, sm0),
                                        jnp.arange(nk))
        out = acc / jnp.maximum(sm, 1e-30)[..., None]  # (B,K,G,qc,D)
        return None, out

    _, outs = jax.lax.scan(q_block, None, jnp.arange(nq))
    # outs: (nq, B, K, G, qc, Dv) -> (B, nq*qc, H, Dv)
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5)).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Sq].astype(q.dtype)


def attention_impl(q, k, v, *, causal=True, window=0, q_positions=None,
                   kv_positions=None, impl: str = "auto", scale=None):
    if impl == "auto":
        impl = "naive" if q.shape[1] * k.shape[1] <= 256 * 256 else "blocked"
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=causal, window=window,
                                    q_positions=q_positions,
                                    kv_positions=kv_positions, scale=scale)
    if impl == "naive":
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_positions=q_positions,
                               kv_positions=kv_positions, scale=scale)
    return blocked_attention(q, k, v, causal=causal, window=window,
                             q_positions=q_positions,
                             kv_positions=kv_positions, scale=scale)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> Dict[str, Any]:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    H, K = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), ("qkv", "heads", "head_dim")),
        "wk": dense_init(ks[1], (d, K, hd), ("qkv", "kv_heads", "head_dim")),
        "wv": dense_init(ks[2], (d, K, hd), ("qkv", "kv_heads", "head_dim")),
        "wo": dense_init(ks[3], (H, hd, d), ("heads", "head_dim", "qkv")),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), ("head_dim",))
        p["k_norm"] = ones_init((hd,), ("head_dim",))
    return p


def _project_qkv(p, x, cfg: ModelConfig, positions):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm_vec(q, p["q_norm"])
        k = rms_norm_vec(k, p["k_norm"])
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _pad_heads(q: jnp.ndarray, K: int, pad_to: int):
    """Pad q heads per KV group to make total heads divisible by pad_to.

    Returns (padded q, original per-group size, padded per-group size).
    Padded heads have q=0 -> their outputs are sliced away, so the function
    is exactly preserved while the head dim becomes TP-shardable.
    """
    B, S, H, D = q.shape
    G = H // K
    target = ((H + pad_to - 1) // pad_to) * pad_to
    Gp = target // K
    if Gp == G:
        return q, G, G
    qg = q.reshape(B, S, K, G, D)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, Gp - G), (0, 0)))
    return qg.reshape(B, S, K * Gp, D), G, Gp


def _unpad_heads(out: jnp.ndarray, K: int, G: int, Gp: int):
    if Gp == G:
        return out
    B, S, Hp, D = out.shape
    return out.reshape(B, S, K, Gp, D)[:, :, :, :G].reshape(B, S, K * G, D)


def apply_attention(p, x: jnp.ndarray, cfg: ModelConfig,
                    rules: Optional[AxisRules], *,
                    positions: jnp.ndarray, causal: bool = True,
                    window: int = 0, impl: str = "auto",
                    kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                    ) -> jnp.ndarray:
    """Full-sequence attention (train / prefill).  x: (B, S, d)."""
    q, k, v = _project_qkv(p, x, cfg, positions)
    if kv is not None:  # cross-attention: keys/values supplied by encoder
        k, v = kv
        causal = False
    K = k.shape[2]
    G = Gp = q.shape[2] // K
    if cfg.tp_pad_heads and q.shape[2] % cfg.tp_pad_heads:
        q, G, Gp = _pad_heads(q, K, cfg.tp_pad_heads)
    q = constrain(q, rules, "batch", None, "act_heads", None)
    k = constrain(k, rules, "batch", None, "act_kv", None)
    v = constrain(v, rules, "batch", None, "act_kv", None)
    out = attention_impl(q, k, v, causal=causal, window=window,
                         q_positions=positions, impl=impl)
    out = constrain(out, rules, "batch", None, "act_heads", None)
    out = _unpad_heads(out, K, G, Gp)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                  window: int = 0, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Linear cache, or ring buffer of size `window` for local attention."""
    hd = cfg.resolved_head_dim
    slots = min(max_len, window) if window > 0 else max_len
    return {
        "k": jnp.zeros((batch, slots, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, slots, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.full((slots,), -1, jnp.int32),  # absolute position per slot
    }


def decode_attention(p, x: jnp.ndarray, cache: Optional[Dict[str, jnp.ndarray]],
                     cfg: ModelConfig, rules: Optional[AxisRules], *,
                     pos: jnp.ndarray, window: int = 0, impl: str = "auto",
                     cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                     ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Stateful attention: x: (B, T, d) starting at absolute position `pos`.

    T == 1 is token decode; T > 1 is prefill (cache written in one shot).
    Ring-buffer caches (window > 0) keep only the last `slots` positions.
    """
    T = x.shape[1]
    positions = pos + jnp.arange(T)
    q, k, v = _project_qkv(p, x, cfg, positions)
    if cross_kv is not None:
        ck, cv = cross_kv
        out = attention_impl(q, ck, cv, causal=False, impl=impl)
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        return out, cache
    slots = cache["k"].shape[1]
    if window > 0 and T > 1:
        # prefill a ring buffer: attend over the raw sequence, then store the
        # last `slots` keys/values at their modulo positions.
        out = attention_impl(q, k, v, causal=True, window=window,
                             q_positions=positions, impl=impl)
        tail = min(slots, T)
        tail_pos = positions[-tail:]
        idx = tail_pos % slots
        ck = cache["k"].at[:, idx].set(k[:, -tail:].astype(cache["k"].dtype))
        cv = cache["v"].at[:, idx].set(v[:, -tail:].astype(cache["v"].dtype))
        cpos = cache["pos"].at[idx].set(tail_pos.astype(jnp.int32))
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    else:
        slot = (jnp.where(window > 0, pos % slots, pos)).astype(jnp.int32)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions.astype(jnp.int32), slot, axis=0)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        out = attention_impl(q, ck, cv, causal=True, window=window,
                             q_positions=positions, kv_positions=cpos, impl=impl)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def init_mla(cfg: ModelConfig, key) -> Dict[str, Any]:
    m = cfg.mla
    assert m is not None
    d, H = cfg.d_model, cfg.num_heads
    nope = cfg.resolved_head_dim
    vd = m.v_head_dim or nope
    ks = jax.random.split(key, 6)
    return {
        # queries: full-rank (v2-lite), with nope + rope parts
        "wq": dense_init(ks[0], (d, H, nope + m.rope_head_dim),
                         ("qkv", "heads", "head_dim")),
        # KV: joint down-projection to the latent + shared rope key
        "w_dkv": dense_init(ks[1], (d, m.kv_lora_rank), ("qkv", "lora")),
        "w_kr": dense_init(ks[2], (d, m.rope_head_dim), ("qkv", "head_dim")),
        "kv_norm": ones_init((m.kv_lora_rank,), ("lora",)),
        # up-projections from the latent
        "w_uk": dense_init(ks[3], (m.kv_lora_rank, H, nope),
                           ("lora", "heads", "head_dim")),
        "w_uv": dense_init(ks[4], (m.kv_lora_rank, H, vd),
                           ("lora", "heads", "head_dim")),
        "wo": dense_init(ks[5], (H, vd, d), ("heads", "head_dim", "qkv")),
    }


def _mla_qkv(p, x, cfg: ModelConfig, positions):
    m = cfg.mla
    dt = x.dtype
    nope = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, cfg.rope_scaling)
    c_kv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt))
    c_kv = rms_norm_vec(c_kv, p["kv_norm"])
    k_rope = jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(dt))
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale of MLA: 1/sqrt(nope + rope head dims), times YaRN's
    mscale(mscale_all_dim) squared where the rope is scaled."""
    scale = (cfg.resolved_head_dim + cfg.mla.rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        scale *= yarn_mscale(ys.factor, ys.mscale_all_dim) ** 2
    return scale


def _mla_expand(p, c_kv, k_rope, dt):
    """Expand latent cache into per-head keys/values."""
    k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(dt))
    kr = jnp.broadcast_to(k_rope[:, :, None, :],
                          k_rope.shape[:2] + (k_nope.shape[2], k_rope.shape[-1]))
    k = jnp.concatenate([k_nope, kr], axis=-1)
    return k, v


def apply_mla(p, x: jnp.ndarray, cfg: ModelConfig, rules: Optional[AxisRules],
              *, positions: jnp.ndarray, impl: str = "auto") -> jnp.ndarray:
    dt = x.dtype
    with tracing.scope(tracing.MLA):
        q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions)
        k, v = _mla_expand(p, c_kv, k_rope, dt)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        q = constrain(q, rules, "batch", None, "act_heads", None)
        out = attention_impl(q, k, v, causal=True, q_positions=positions,
                             impl=impl, scale=mla_scale(cfg))
        out = constrain(out, rules, "batch", None, "act_heads", None)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, max_len, m.rope_head_dim), dtype),
        "pos": jnp.full((max_len,), -1, jnp.int32),
    }


def decode_mla(p, x: jnp.ndarray, cache: Dict[str, jnp.ndarray],
               cfg: ModelConfig, rules: Optional[AxisRules], *,
               pos: jnp.ndarray, impl: str = "auto"):
    """Stateful MLA: x: (B, T, d) at absolute start position `pos`."""
    dt = x.dtype
    positions = pos + jnp.arange(x.shape[1])
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions)
    ckv = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), pos, axis=1)
    ckr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), pos, axis=1)
    cpos = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], positions.astype(jnp.int32), pos, axis=0)
    new_cache = {"c_kv": ckv, "k_rope": ckr, "pos": cpos}
    k, v = _mla_expand(p, ckv.astype(dt), ckr.astype(dt), dt)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention_impl(q, k, v, causal=True, q_positions=positions,
                         kv_positions=cpos, impl=impl, scale=mla_scale(cfg))
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
    return out, new_cache
