"""Plain float32 reference of a dense decoder: phi3-mini-3.8b.

Pre-norm decoder blocks (arXiv:2404.14219; the HuggingFace ``Phi3`` model):
RMSNorm, causal multi-head attention (within the configuration's
``sliding_window`` where it states one) with rotary position embeddings
(rotate-half form, theta from the configuration), SwiGLU MLP, final RMSNorm
and an untied output head; the loss is the mean next-token cross entropy.
Straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST``, attention scores materialised whole.  It imports
nothing of the program under test.

``low=True`` computes every matrix product from float8 operands (e4m3
forward, e5m2 cotangents, one scale per tensor): the control, one
precision step below the bfloat16 compute that the configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(config):
    d = config["hidden_size"]
    H = config["num_attention_heads"]
    K = config["num_key_value_heads"]
    hd = d // H
    f = config["intermediate_size"]
    V = config["vocab_size"]
    n = config["num_hidden_layers"]
    return {
        "embedding": {"table": (V, d), "head": (d, V)},
        "layers": {
            "norm1": {"scale": (n, d)},
            "mixer": {"wq": (n, d, H, hd), "wk": (n, d, K, hd),
                      "wv": (n, d, K, hd), "wo": (n, H, hd, d)},
            "norm2": {"scale": (n, d)},
            "mlp": {"wi": (n, d, f), "wg": (n, d, f), "wo": (n, f, d)},
        },
        "final_norm": {"scale": (d,)},
    }


def init_params(config, key):
    """The program's initial weights (``repro.models.init_lm``) for this
    layout, drawn again from ``key = jax.random.PRNGKey(seed)``: every
    matrix normal over the square root of its input axis as the program
    counts it (the second axis of a three-axis leaf), the embedding table
    normal(0, 1), norm scales 1."""
    d, V = config["hidden_size"], config["vocab_size"]
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    f, n = config["intermediate_size"], config["num_hidden_layers"]
    hd = d // H

    def dense(key, shape):
        fan = shape[1] if len(shape) == 3 else shape[0]
        return jax.random.normal(key, shape) * (1.0 / np.sqrt(fan))

    def block(key):
        ks = jax.random.split(key, 4)
        a = jax.random.split(ks[0], 6)
        m = jax.random.split(ks[1], 3)
        return {"norm1": {"scale": jnp.ones((d,), jnp.float32)},
                "mixer": {"wq": dense(a[0], (d, H, hd)),
                          "wk": dense(a[1], (d, K, hd)),
                          "wv": dense(a[2], (d, K, hd)),
                          "wo": dense(a[3], (H, hd, d))},
                "norm2": {"scale": jnp.ones((d,), jnp.float32)},
                "mlp": {"wi": dense(m[0], (d, f)), "wg": dense(m[1], (d, f)),
                        "wo": dense(m[2], (f, d))}}

    keys = jax.random.split(key, 8)
    e = jax.random.split(keys[0], 2)
    return {"embedding": {"table": jax.random.normal(e[0], (V, d)) * 1.0,
                          "head": dense(e[1], (d, V))},
            "layers": jax.vmap(block)(jax.random.split(keys[1], n)),
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}


def _round_scaled(x, dtype, top):
    """``x`` rounded to ``dtype`` under one per-tensor scale that maps its
    largest magnitude to ``top``, the format's largest finite value."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def fp8(x):
    """The fp8 training recipe's rounding of a matrix product operand:
    e4m3 forward; the cotangent rounded to e5m2 on the way back."""
    return _round_scaled(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_round_scaled(g, jnp.float8_e5m2, 57344.0),))


def mm(spec, a, b, low=False):
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta):
    """Rotate-half RoPE on (B, T, heads, hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


def forward(params, tokens, config, low=False):
    """Logits (B, T, V) for int tokens (B, T)."""
    eps = config["rms_norm_eps"]
    H = config["num_attention_heads"]
    K = config["num_key_value_heads"]
    x = params["embedding"]["table"][tokens]
    T = tokens.shape[1]
    pos = jnp.arange(T)
    causal = pos[:, None] >= pos[None, :]
    if config.get("sliding_window"):     # a query sees the last w keys
        causal &= pos[:, None] - pos[None, :] < config["sliding_window"]
    layers = params["layers"]
    for i in range(layers["norm1"]["scale"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], layers)
        at = lp["mixer"]
        h = rms_norm(x, lp["norm1"]["scale"], eps)
        q = rope(mm("btd,dhk->bthk", h, at["wq"], low), config["rope_theta"])
        k = rope(mm("btd,dhk->bthk", h, at["wk"], low), config["rope_theta"])
        v = mm("btd,dhk->bthk", h, at["wv"], low)
        if K != H:
            k = jnp.repeat(k, H // K, axis=2)
            v = jnp.repeat(v, H // K, axis=2)
        s = mm("bqhk,bshk->bhqs", q, k, low) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = mm("bhqs,bshk->bqhk", p, v, low)
        x = x + mm("bqhk,hkd->bqd", o, at["wo"], low)
        ml = lp["mlp"]
        h = rms_norm(x, lp["norm2"]["scale"], eps)
        a = mm("btd,df->btf", h, ml["wi"], low)
        g = mm("btd,df->btf", h, ml["wg"], low)
        x = x + mm("btf,fd->btd", jax.nn.silu(g) * a, ml["wo"], low)
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return mm("btd,dv->btv", x, params["embedding"]["head"], low)


def loss(params, batch, config, low=False):
    """Mean cross entropy over the targets that are >= 0."""
    logits = forward(params, batch["tokens"], config, low)
    tgt = batch["targets"]
    valid = tgt >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)
