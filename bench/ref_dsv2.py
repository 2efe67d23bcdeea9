"""Plain float32 reference of one chip's share of DeepSeek-V2-Lite.

The model (arXiv:2405.04434; the HuggingFace ``DeepseekV2`` model and its
config.json): pre-norm decoder blocks, RMSNorm, multi-head latent attention
(full-rank queries with a nope part and a decoupled rope part; keys and
values up-projected from a 512-wide RMS-normed latent; one rope key shared
by every head) with YaRN rope scaling and a softmax scale that carries
mscale(mscale_all_dim)**2; the first ``first_k_dense_replace`` layers keep
a dense SwiGLU MLP, the rest are mixtures of experts: softmax router over
all routed experts, greedy top-k, weights not renormalised (times
``routed_scaling_factor``), SwiGLU experts, shared experts added to every
token, and the sequence-wise balance loss (``seq_aux``) of each expert
layer added to the mean next-token cross entropy.

The share (the configuration's ``deployment``): this chip holds routed
experts ``first_held_expert .. + n_routed_experts - 1`` of the published
count; the router scores all of them, and what an absent expert would have
added is left out.  Every matrix product is a ``jnp.einsum`` at
``Precision.HIGHEST``; attention materialises each head's scores whole,
and each held expert runs densely on every token, weighted by its combine
weight (zero where the token did not pick it).  Layers and heads are
recomputed in the backward pass (``jax.checkpoint``) so the reference fits
beside the optimizer state, and each stack of layers is a ``lax.scan``;
neither changes a number.  It imports nothing of
the program under test.

Departures from the published description:

* the rope rotates halves (``x1, x2``) of the rope part; the published
  weights store interleaved pairs, a fixed permutation of the rope columns
  of ``q_proj`` and ``kv_a_proj_with_mqa``, which is the same model for
  weights drawn at random;
* the balance loss is part of the loss value; the published trainer adds
  its gradient only.

``low=True`` is the control (every product from float8 operands,
``ref_dense.fp8``).  ``config["fault"]`` plants one fault for
``bench/calibrate_dsv2.py`` and the tests: ``"renorm"`` (top-k weights
renormalised), ``"capacity"`` (each held expert keeps its first
ceil(k T / E) assignments of the batch in token order), ``"no_mscale"``
(the softmax scale without YaRN's mscale**2), ``"drop_expert"`` (the first
held expert's output dropped), ``"no_shared"`` (the shared experts left
out).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ref_dense import mm, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("renorm", "capacity", "no_mscale", "drop_expert", "no_shared")


def _dims(config):
    c = config
    return dict(
        d=c["hidden_size"], H=c["num_attention_heads"],
        nope=c["qk_nope_head_dim"], rope=c["qk_rope_head_dim"],
        vd=c["v_head_dim"], r=c["kv_lora_rank"], f=c["intermediate_size"],
        fe=c["moe_intermediate_size"], E=c["published"]["n_routed_experts"],
        held=c["n_routed_experts"], first=c["first_held_expert"],
        k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
        V=c["vocab_size"], n=c["num_hidden_layers"],
        n0=c["first_k_dense_replace"])


def param_shapes(config):
    z = _dims(config)
    d, H, r, n0 = z["d"], z["H"], z["r"], z["n0"]
    nm = z["n"] - n0
    sf = z["shared"] * z["fe"]

    def mla(n):
        return {"wq": (n, d, H, z["nope"] + z["rope"]), "w_dkv": (n, d, r),
                "w_kr": (n, d, z["rope"]), "kv_norm": (n, r),
                "w_uk": (n, r, H, z["nope"]), "w_uv": (n, r, H, z["vd"]),
                "wo": (n, H, z["vd"], d)}

    return {
        "embedding": {"table": (z["V"], d), "head": (d, z["V"])},
        "dense_layers": {
            "norm1": {"scale": (n0, d)}, "mixer": mla(n0),
            "norm2": {"scale": (n0, d)},
            "mlp": {"wi": (n0, d, z["f"]), "wg": (n0, d, z["f"]),
                    "wo": (n0, z["f"], d)}},
        "layers": {
            "norm1": {"scale": (nm, d)}, "mixer": mla(nm),
            "norm2": {"scale": (nm, d)},
            "mlp": {"router": (nm, d, z["E"]),
                    "wi": (nm, z["held"], d, z["fe"]),
                    "wg": (nm, z["held"], d, z["fe"]),
                    "wo": (nm, z["held"], z["fe"], d),
                    "shared_wi": (nm, d, sf), "shared_wg": (nm, d, sf),
                    "shared_wo": (nm, sf, d)}},
        "final_norm": {"scale": (d,)},
    }


def init_params(config, key):
    """The program's initial weights (``repro.models.init_lm``) for this
    layout, drawn again from ``key = jax.random.PRNGKey(seed)``: every
    matrix normal over the square root of its input axis as the program
    counts it (the first axis of a two-axis leaf, the second of a
    three-axis one), routed expert ``e``'s matrices from
    ``fold_in(key, e)`` over the square root of their own first axis, the
    embedding table normal(0, 1), norm scales 1."""
    z = _dims(config)
    d, H, r, f, fe = z["d"], z["H"], z["r"], z["f"], z["fe"]
    sf = z["shared"] * fe

    def dense(key, shape):
        fan = shape[1] if len(shape) == 3 else shape[0]
        return jax.random.normal(key, shape) * (1.0 / np.sqrt(fan))

    def experts(key, shape):
        ids = jnp.arange(z["first"], z["first"] + z["held"])
        w = jax.vmap(lambda e: jax.random.normal(jax.random.fold_in(key, e),
                                                 shape))(ids)
        return w * (1.0 / np.sqrt(shape[0]))

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def mla(key):
        a = jax.random.split(key, 6)
        return {"wq": dense(a[0], (d, H, z["nope"] + z["rope"])),
                "w_dkv": dense(a[1], (d, r)),
                "w_kr": dense(a[2], (d, z["rope"])),
                "kv_norm": ones(r),
                "w_uk": dense(a[3], (r, H, z["nope"])),
                "w_uv": dense(a[4], (r, H, z["vd"])),
                "wo": dense(a[5], (H, z["vd"], d))}

    def block(key, moe):
        ks = jax.random.split(key, 4)
        m = jax.random.split(ks[1], 7 if moe else 3)
        if moe:
            mlp = {"router": dense(m[0], (d, z["E"])),
                   "wi": experts(m[1], (d, fe)), "wo": experts(m[2], (fe, d)),
                   "wg": experts(m[3], (d, fe)),
                   "shared_wi": dense(m[4], (d, sf)),
                   "shared_wo": dense(m[5], (sf, d)),
                   "shared_wg": dense(m[6], (d, sf))}
        else:
            mlp = {"wi": dense(m[0], (d, f)), "wg": dense(m[1], (d, f)),
                   "wo": dense(m[2], (f, d))}
        return {"norm1": {"scale": ones(d)}, "mixer": mla(ks[0]),
                "norm2": {"scale": ones(d)}, "mlp": mlp}

    keys = jax.random.split(key, 8)
    e = jax.random.split(keys[0], 2)
    return {
        "embedding": {"table": jax.random.normal(e[0], (z["V"], d)) * 1.0,
                      "head": dense(e[1], (d, z["V"]))},
        "layers": jax.vmap(lambda k: block(k, True))(
            jax.random.split(keys[1], z["n"] - z["n0"])),
        "dense_layers": jax.vmap(lambda k: block(k, False))(
            jax.random.split(keys[3], z["n0"])),
        "final_norm": {"scale": ones(d)},
    }


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(config):
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` inverse frequencies
    over the rope head dim."""
    rs, dim = config["rope_scaling"], config["qk_rope_head_dim"]
    base = float(config["rope_theta"])

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / rs["factor"]
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def _rope(x, inv, m):
    """Rotate-half rope on (B, T, heads, dim); cos and sin carry ``m``."""
    T = x.shape[1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None]
    cos = (jnp.cos(jnp.concatenate([ang, ang], -1)) * m)[None, :, None]
    sin = (jnp.sin(jnp.concatenate([ang, ang], -1)) * m)[None, :, None]
    h = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., h:], x[..., :h]], -1) * sin


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _attend(q, k, v, scale, low):
    """Causal softmax attention, one head at a time: (B, T, H, D)."""
    T = q.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def head(args):
        qh, kh, vh = args
        s = mm("bqd,bsd->bqs", qh, kh, low) * scale
        s = jnp.where(causal[None], s, -jnp.inf)
        return mm("bqs,bsd->bqd", jax.nn.softmax(s, axis=-1), vh, low)

    o = jax.lax.map(head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)


def _mla(at, h, config, low):
    z = _dims(config)
    rs = config["rope_scaling"]
    inv = yarn_inv_freq(config)
    m = _mscale(rs["factor"], rs["mscale"]) / _mscale(rs["factor"],
                                                      rs["mscale_all_dim"])
    q = mm("btd,dhk->bthk", h, at["wq"], low)
    q_nope, q_rope = q[..., :z["nope"]], _rope(q[..., z["nope"]:], inv, m)
    c = rms_norm(mm("btd,dr->btr", h, at["w_dkv"], low), at["kv_norm"],
                 config["rms_norm_eps"])
    k_rope = _rope(mm("btd,dk->btk", h, at["w_kr"], low)[:, :, None], inv, m)
    k_nope = mm("btr,rhk->bthk", c, at["w_uk"], low)
    v = mm("btr,rhk->bthk", c, at["w_uv"], low)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3] + (z["rope"],))],
        -1)
    scale = (z["nope"] + z["rope"]) ** -0.5
    if config.get("fault") != "no_mscale":
        scale *= _mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
    o = _attend(jnp.concatenate([q_nope, q_rope], -1), k, v, scale, low)
    return mm("bthk,hkd->btd", o, at["wo"], low)


def _swiglu(x, wi, wg, wo, low):
    return mm("tf,fd->td", jax.nn.silu(mm("td,df->tf", x, wg, low))
              * mm("td,df->tf", x, wi, low), wo, low)


def _moe(ml, h, config, low):
    """(the layer's output, its balance-loss term)."""
    z = _dims(config)
    fault = config.get("fault")
    B, T, d = h.shape
    x = h.reshape(B * T, d)
    probs = jax.nn.softmax(mm("td,de->te", x, ml["router"], low), axis=-1)
    wk, ids = jax.lax.top_k(probs, z["k"])
    if fault == "renorm":
        wk = wk / jnp.sum(wk, -1, keepdims=True)
    else:
        wk = wk * config["routed_scaling_factor"]
    mine = ids[..., None] == z["first"] + jnp.arange(z["held"])  # (N, k, held)
    comb = jnp.sum(jnp.where(mine, wk[..., None], 0.0), axis=1)  # (N, held)
    if fault == "capacity":
        cap = math.ceil(z["k"] * B * T / z["E"])
        comb = jnp.where(jnp.cumsum(mine.any(1), axis=0) <= cap, comb, 0.0)
    if fault == "drop_expert":
        comb = comb.at[:, 0].set(0.0)

    @jax.checkpoint
    def expert(out, w):
        wi, wg, wo, c = w
        return out + _swiglu(x, wi, wg, wo, low) * c[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                          (ml["wi"], ml["wg"], ml["wo"], comb.T))
    if fault != "no_shared":
        out = out + _swiglu(x, ml["shared_wi"], ml["shared_wg"],
                            ml["shared_wo"], low)
    # seq_aux: per sequence, f_e = assignments to e * E / (T k), P_e = mean
    # score; alpha * mean over sequences of sum_e f_e P_e
    f = jnp.sum(jax.nn.one_hot(ids.reshape(B, T * z["k"]), z["E"]),
                axis=1) * (z["E"] / (T * z["k"]))
    P = probs.reshape(B, T, z["E"]).mean(1)
    aux = config["aux_loss_alpha"] * jnp.mean(jnp.sum(f * P, -1))
    return out.reshape(B, T, d), aux


def forward(params, tokens, config, low=False):
    """(logits (B, T, V), summed balance-loss terms) for int tokens (B, T)."""
    eps = config["rms_norm_eps"]
    x = params["embedding"]["table"][tokens]

    def layer(lp, x, moe):
        x = x + _mla(lp["mixer"], rms_norm(x, lp["norm1"]["scale"], eps),
                     config, low)
        h = rms_norm(x, lp["norm2"]["scale"], eps)
        if moe:
            y, aux = _moe(lp["mlp"], h, config, low)
        else:
            ml = lp["mlp"]
            B, T, d = h.shape
            y = _swiglu(h.reshape(B * T, d), ml["wi"], ml["wg"], ml["wo"],
                        low).reshape(B, T, d)
            aux = jnp.float32(0.0)
        return x + y, aux

    total = 0.0
    for name, moe in (("dense_layers", False), ("layers", True)):
        x, aux = jax.lax.scan(
            jax.checkpoint(lambda x, lp, moe=moe: layer(lp, x, moe)), x,
            params[name])
        total = total + jnp.sum(aux)
    x = rms_norm(x, params["final_norm"]["scale"], eps)
    return mm("btd,dv->btv", x, params["embedding"]["head"], low), total


def loss(params, batch, config, low=False):
    """Mean cross entropy over the targets that are >= 0, plus the expert
    layers' balance-loss terms."""
    logits, aux = forward(params, batch["tokens"], config, low)
    tgt = batch["targets"]
    valid = tgt >= 0
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(tgt, 0)[..., None],
                             axis=-1)[..., 0]
    ce = jnp.sum(jnp.where(valid, lse - ll, 0.0)) / jnp.maximum(
        jnp.sum(valid), 1)
    return ce + aux
