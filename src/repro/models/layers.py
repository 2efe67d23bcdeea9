"""Core layer primitives: annotated params, norms, MLPs, RoPE, embeddings.

Parameters are plain nested dicts of jnp arrays.  During init every leaf is
wrapped in :class:`P` carrying its *logical* sharding axes; ``split_tree``
separates values from axes so callers get (params, param_axes) twins with
identical structure.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.dist.sharding import AxisRules, constrain


@dataclasses.dataclass
class P:
    """A parameter leaf annotated with logical sharding axes.

    Registered as a pytree node (axes are aux data) so annotated trees pass
    through vmap/eval_shape — vmapping a per-layer init produces stacked
    leaves whose axes are then prefixed with "layers" by ``relabel_stacked``.
    """

    value: jnp.ndarray
    axes: Tuple[Optional[str], ...]


jax.tree_util.register_pytree_node(
    P,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: P(children[0], axes),
)


def _is_p(x) -> bool:
    return isinstance(x, P)


def relabel_stacked(tree: Any, prefix: str = "layers") -> Any:
    """Prefix every leaf's axes with `prefix` (after a vmapped init)."""
    return jax.tree.map(lambda p: P(p.value, (prefix,) + p.axes), tree,
                        is_leaf=_is_p)


def split_tree(tree: Any) -> Tuple[Any, Any]:
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_p)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=_is_p)
    return values, axes


def stack_layers(trees) -> Any:
    """Stack per-layer annotated trees along a new leading 'layers' axis."""
    def stack(*ps):
        return P(jnp.stack([p.value for p in ps]), ("layers",) + ps[0].axes)
    return jax.tree.map(stack, *trees, is_leaf=_is_p)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, axes, dtype=jnp.float32, scale: Optional[float] = None) -> P:
    fan_in = shape[0] if len(shape) >= 2 else max(shape[0], 1)
    if len(shape) == 3:  # stacked expert weights (E, d, f): fan_in is dim 1
        fan_in = shape[1]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return P((jax.random.normal(key, shape) * s).astype(dtype), tuple(axes))


def zeros_init(shape, axes, dtype=jnp.float32) -> P:
    return P(jnp.zeros(shape, dtype), tuple(axes))


def ones_init(shape, axes, dtype=jnp.float32) -> P:
    return P(jnp.ones(shape, dtype), tuple(axes))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dim: int, axes=("embed",)) -> Any:
    if cfg.norm_kind == "layernorm":
        return {"scale": ones_init((dim,), axes), "bias": zeros_init((dim,), axes)}
    return {"scale": ones_init((dim,), axes)}


def apply_norm(p: Any, x: jnp.ndarray, cfg: ModelConfig, eps: float = 1e-6) -> jnp.ndarray:
    """Norms with fp32 statistics but a compute-dtype apply.

    Only the REDUCED statistics are fp32; the full activation is never
    materialized in fp32 (XLA otherwise hoists the convert into the remat
    residual buffer, doubling the saved-activation footprint — observed as
    f32 stacked residuals in the train dry-runs).
    """
    stats_in = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(stats_in, axis=-1, keepdims=True)
        var = jnp.var(stats_in, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
        y = (x - mu.astype(x.dtype)) * inv
        y = y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(stats_in), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
        y = x * inv * p["scale"].astype(x.dtype)
    return y


def rms_norm_vec(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMS norm over the last axis (qk-norm): fp32 stats, compute-dtype apply
    (avoids materializing an fp32 copy of the full head tensor)."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key) -> Any:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_kind == "swiglu":
        return {
            "wi": dense_init(ks[0], (d, f), ("qkv", "ff")),
            "wg": dense_init(ks[1], (d, f), ("qkv", "ff")),
            "wo": dense_init(ks[2], (f, d), ("ff", "qkv")),
        }
    return {
        "wi": dense_init(ks[0], (d, f), ("qkv", "ff")),
        "wo": dense_init(ks[2], (f, d), ("ff", "qkv")),
    }


def apply_mlp(p: Any, x: jnp.ndarray, cfg: ModelConfig,
              rules: Optional[AxisRules]) -> jnp.ndarray:
    dt = x.dtype
    if cfg.mlp_kind == "swiglu":
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        g = jnp.einsum("...d,df->...f", x, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    elif cfg.mlp_kind == "relu_sq":
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        h = jnp.square(jax.nn.relu(h))
    else:  # gelu
        h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
        h = jax.nn.gelu(h)
    h = constrain(h, rules, "batch", "seq", "act_ff") if h.ndim == 3 else h
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term, 0.1 m ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_freqs(head_dim: int, theta: float, scaling=None) -> np.ndarray:
    """Inverse rotary frequencies; with a ``YarnConfig``, YaRN's: the
    dimensions that turn more than ``beta_fast`` times over the original
    context keep theta's frequency, those that turn fewer than
    ``beta_slow`` times take it over ``factor``, with a linear ramp
    between (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    base = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                            / head_dim))
    if scaling is None:
        return base

    def dim_of(rotations):
        return (head_dim * math.log(scaling.original_max_position_embeddings
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(scaling.beta_fast)), 0)
    hi = min(math.ceil(dim_of(scaling.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - lo)
                   / max(hi - lo, 1e-3), 0.0, 1.0)
    return (base / scaling.factor * ramp + base * (1.0 - ramp)).astype(
        np.float32)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               scaling=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq).

    Angles/sin/cos are fp32 (tiny (seq, hd/2) tables); the rotation itself
    runs in the compute dtype so no fp32 copy of the full q/k tensor is
    materialized (sub-ULP difference vs the fp32 rotation for bf16 inputs).
    ``scaling`` (a ``YarnConfig``) gives YaRN's frequencies, and cos/sin
    carry mscale(mscale) / mscale(mscale_all_dim).
    """
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta, scaling))  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    m = 1.0 if scaling is None else (
        yarn_mscale(scaling.factor, scaling.mscale)
        / yarn_mscale(scaling.factor, scaling.mscale_all_dim))

    def table(f):
        t = f(angles) * m if m != 1.0 else f(angles)
        return t[..., :, None, :].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def sinusoidal_positions(seq_len: int, dim: int) -> jnp.ndarray:
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    div = np.exp(np.arange(0, dim, 2, dtype=np.float32) * (-np.log(10000.0) / dim))
    table = np.zeros((seq_len, dim), dtype=np.float32)
    table[:, 0::2] = np.sin(pos * div)
    table[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(table)


def sinusoidal_at(positions: jnp.ndarray, dim: int) -> jnp.ndarray:
    """Sinusoidal embedding at dynamic positions.  positions: (T,) -> (T, dim)."""
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32)
                  * (-jnp.log(10000.0) / dim))
    ang = positions.astype(jnp.float32)[:, None] * div
    out = jnp.zeros((positions.shape[0], dim), jnp.float32)
    out = out.at[:, 0::2].set(jnp.sin(ang))
    out = out.at[:, 1::2].set(jnp.cos(ang))
    return out


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key) -> Any:
    ks = jax.random.split(key, 2)
    p = {"table": dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                             ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                               ("embed", "vocab"))
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _embed_gather(table, tokens, rules: Optional[AxisRules], shape, dtype_name):
    return jnp.take(table, tokens, axis=0)


def _embed_gather_fwd(table, tokens, rules, shape, dtype_name):
    return jnp.take(table, tokens, axis=0), tokens


def _embed_gather_bwd(rules, shape, dtype_name, tokens, g):
    """Scatter-add the cotangent into a vocab-sharded zero table.

    Without the sharding constraint GSPMD materializes a FULL fp32
    (vocab, d) temp per scatter (observed: 3 GiB x15 for grok) — the
    constraint keeps the accumulation sharded over the model axis.
    """
    zeros = jnp.zeros(shape, jnp.float32)
    zeros = constrain(zeros, rules, "vocab", "embed")
    grad = zeros.at[tokens].add(g.astype(jnp.float32))
    grad = constrain(grad, rules, "vocab", "embed")
    return grad.astype(dtype_name), None


_embed_gather.defvjp(_embed_gather_fwd, _embed_gather_bwd)


def embed(p: Any, tokens: jnp.ndarray, cfg: ModelConfig,
          rules: Optional[AxisRules], dtype) -> jnp.ndarray:
    table = p["table"].astype(dtype)
    # the custom backward only pays off when the vocab dim actually shards
    # (otherwise it pins a replicated fp32 (V,d) zeros buffer — observed to
    # regress seamless, whose 256206 vocab is not 16-divisible)
    if rules is not None and rules.rules.get("vocab") is not None:
        x = _embed_gather(table, tokens, rules, table.shape, str(table.dtype))
    else:
        x = jnp.take(table, tokens, axis=0)
    return constrain(x, rules, "batch", "seq", "act_embed")


def unembed(p: Any, x: jnp.ndarray, cfg: ModelConfig,
            rules: Optional[AxisRules]) -> jnp.ndarray:
    w = p.get("head")
    if w is None:
        w = p["table"].T
    logits = jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
    # prefer vocab sharding (CE reductions psum over the model axis and the
    # head gradient is born sharded); when the vocab doesn't divide the TP
    # degree (seamless: 256206), fall back to sequence sharding — otherwise
    # the logits replicate across the model axis (observed 132 GiB/device)
    if rules is not None and rules.rules.get("act_vocab") is None:
        return constrain(logits, rules, "batch", "seq", "act_vocab")
    return constrain(logits, rules, "batch", None, "act_vocab")
