"""Plain float32 reference of an RWKV-6 ("Finch") language model: rwkv6-3b.

Each layer is x + TimeMix(LN(x)), then x + ChannelMix(LN(x)) (arXiv:2404.05892):

* token shift xx = x[t-1] - x[t]; data-dependent lerp of five inputs
  (w, k, v, r, g) through a rank-32 tanh LoRA;
* data-dependent decay w_t = exp(-exp(d_base + (x_w A) B));
* the WKV recurrence, run token by token, per head (state D x D):
  y_t = r_t (S + diag(u) k_t^T v_t), S <- diag(w_t) S + k_t^T v_t;
* per-head group norm (eps 64e-5), gate silu(x_g W_g), output W_o;
* channel mix sigmoid(x_r W_r) * (relu(x_k W_k)^2 W_v).

Departures from the published model, where this follows the program's
stated equations: no LayerNorm after the embedding (ln0), and no tanh
inside the decay LoRA.  Straight ``jax.numpy`` in float32 at
``Precision.HIGHEST``; it imports nothing of the program under test.
``low=True`` computes every matrix product from float8 operands, as in
``ref_dense``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ref_dense import mm

MIX_LORA = 32
DECAY_LORA = 64


def param_shapes(config):
    d = config["hidden_size"]
    D = config["head_size"]
    H = d // D
    f = config["intermediate_size"]
    V = config["vocab_size"]
    n = config["num_hidden_layers"]
    return {
        "embedding": {"table": (V, d), "head": (d, V)},
        "layers": {
            "norm1": {"scale": (n, d), "bias": (n, d)},
            "mixer": {
                "mu_x": (n, d), "mu": (n, 5, d),
                "mix_w1": (n, d, 5 * MIX_LORA), "mix_w2": (n, 5, MIX_LORA, d),
                "decay_base": (n, d), "decay_w1": (n, d, DECAY_LORA),
                "decay_w2": (n, DECAY_LORA, d), "bonus_u": (n, H, D),
                "wr": (n, d, d), "wk": (n, d, d), "wv": (n, d, d),
                "wg": (n, d, d), "wo": (n, d, d),
                "ln_scale": (n, d), "ln_bias": (n, d)},
            "norm2": {"scale": (n, d), "bias": (n, d)},
            "mlp": {"mu_k": (n, d), "mu_r": (n, d), "wk": (n, d, f),
                    "wv": (n, f, d), "wr": (n, d, d)},
        },
        "final_norm": {"scale": (d,), "bias": (d,)},
    }


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, w, u):
    """Token-by-token recurrence; r, k, v, w: (B, T, H, D), u: (H, D)."""
    B, T, H, D = r.shape

    def step(S, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]                # (B,H,D,D)
        y = jnp.einsum("bhk,bhkv->bhv", rt, S + u[None, :, :, None] * kv,
                       precision=jax.lax.Precision.HIGHEST)
        return wt[..., None] * S + kv, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, jnp.zeros((B, H, D, D), jnp.float32), xs)
    return jnp.moveaxis(ys, 0, 1)


def time_mix(p, x, config, low):
    B, T, d = x.shape
    D = config["head_size"]
    H = d // D
    xx = shift(x) - x
    z = jnp.tanh(mm("btd,dl->btl", x + xx * p["mu_x"], p["mix_w1"], low))
    z = z.reshape(B, T, 5, MIX_LORA)
    off = mm("btnl,nld->nbtd", z, p["mix_w2"], low)
    x_w, x_k, x_v, x_r, x_g = (x + xx * (p["mu"][i] + off[i])
                               for i in range(5))
    r = mm("btd,de->bte", x_r, p["wr"], low).reshape(B, T, H, D)
    k = mm("btd,de->bte", x_k, p["wk"], low).reshape(B, T, H, D)
    v = mm("btd,de->bte", x_v, p["wv"], low).reshape(B, T, H, D)
    g = jax.nn.silu(mm("btd,de->bte", x_g, p["wg"], low))
    dec = p["decay_base"] + mm(
        "btl,le->bte", mm("btd,dl->btl", x_w, p["decay_w1"], low),
        p["decay_w2"], low)
    w = jnp.exp(-jnp.exp(dec)).reshape(B, T, H, D)
    y = wkv(r, k, v, w, p["bonus_u"])
    mu = jnp.mean(y, axis=-1, keepdims=True)
    var = jnp.mean((y - mu) ** 2, axis=-1, keepdims=True)
    y = ((y - mu) / jnp.sqrt(var + 64e-5)).reshape(B, T, d)
    y = y * p["ln_scale"] + p["ln_bias"]
    return mm("btd,de->bte", y * g, p["wo"], low)


def channel_mix(p, x, low):
    xx = shift(x) - x
    h = jax.nn.relu(mm("btd,df->btf", x + xx * p["mu_k"], p["wk"], low)) ** 2
    gate = jax.nn.sigmoid(mm("btd,de->bte", x + xx * p["mu_r"], p["wr"], low))
    return gate * mm("btf,fd->btd", h, p["wv"], low)


def forward(params, tokens, config, low=False):
    eps = config["layer_norm_epsilon"]
    x = params["embedding"]["table"][tokens]
    layers = params["layers"]
    for i in range(layers["norm1"]["scale"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], layers)
        x = x + time_mix(lp["mixer"], layer_norm(
            x, lp["norm1"]["scale"], lp["norm1"]["bias"], eps), config, low)
        x = x + channel_mix(lp["mlp"], layer_norm(
            x, lp["norm2"]["scale"], lp["norm2"]["bias"], eps), low)
    x = layer_norm(x, params["final_norm"]["scale"],
                   params["final_norm"]["bias"], eps)
    return mm("btd,dv->btv", x, params["embedding"]["head"], low)


def loss(params, batch, config, low=False):
    logits = forward(params, batch["tokens"], config, low)
    tgt = batch["targets"]
    valid = tgt >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[..., None],
                             axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - ll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)
