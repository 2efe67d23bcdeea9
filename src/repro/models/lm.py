"""Composable language-model definition covering every assigned family.

One init / forward / decode implementation parameterized by ``ModelConfig``:

* dense / vlm:  [attn + mlp] x L, scanned, optional remat
* moe:          [attn(+MLA) + mlp] x first_dense_layers, then
                [attn(+MLA) + moe] x the rest, each stack scanned
* ssm (rwkv6):  [time-mix + channel-mix] x L, scanned
* hybrid:       unrolled (rec|attn pattern) blocks + mlp each
* audio:        encoder (bidirectional) + decoder (causal + cross) stacks

Parameters are annotated dict trees (see :mod:`repro.models.layers`);
``init_lm`` returns ``(params, param_axes)``.  All forward paths are pure
functions usable under ``jax.eval_shape`` so the multi-pod dry-run never
allocates full-size weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ShapeConfig
from repro.dist.sharding import AxisRules, constrain
from repro.models import layers as L
from repro.models import attention as A
from repro.models import moe as M
from repro.models import rwkv as R
from repro.models import rglru as G


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Block init / apply
# ---------------------------------------------------------------------------

def _block_kind(cfg: ModelConfig, layer_idx: int) -> str:
    if cfg.recurrent is not None:
        pat = cfg.recurrent.block_pattern
        if not pat:
            return "rwkv"
        return "rec" if pat[layer_idx % len(pat)] == "rec" else "attn_local"
    if cfg.moe is not None:
        return "moe"
    return "dense"


def init_block(cfg: ModelConfig, key, kind: str) -> Dict[str, Any]:
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": L.init_norm(cfg, cfg.d_model)}
    if kind == "rwkv":
        p["mixer"] = R.init_time_mix(cfg, ks[0])
        p["norm2"] = L.init_norm(cfg, cfg.d_model)
        p["mlp"] = R.init_channel_mix(cfg, ks[1])
        return p
    if kind == "rec":
        p["mixer"] = G.init_rglru_block(cfg, ks[0])
    elif cfg.mla is not None:
        p["mixer"] = A.init_mla(cfg, ks[0])
    else:
        p["mixer"] = A.init_attention(cfg, ks[0])
    p["norm2"] = L.init_norm(cfg, cfg.d_model)
    p["mlp"] = M.init_moe(cfg, ks[1]) if kind == "moe" else L.init_mlp(cfg, ks[1])
    return p


def init_cross_block(cfg: ModelConfig, key) -> Dict[str, Any]:
    """Decoder block with cross-attention (enc-dec)."""
    ks = jax.random.split(key, 3)
    p = init_block(cfg, ks[0], "dense")
    p["norm_x"] = L.init_norm(cfg, cfg.d_model)
    p["cross"] = A.init_attention(cfg, ks[1])
    return p


def apply_block(p, x: jnp.ndarray, cfg: ModelConfig,
                rules: Optional[AxisRules], *, kind: str,
                positions: jnp.ndarray, impl: str = "auto",
                moe_impl: str = "auto", rec_impl: str = "auto",
                moe_groups: int = 1,
                causal: bool = True,
                cache: Optional[Any] = None, pos: Optional[jnp.ndarray] = None,
                enc_out: Optional[jnp.ndarray] = None,
                cross_cache: Optional[Any] = None,
                ) -> Tuple[jnp.ndarray, Optional[Any], Optional[Any]]:
    """One residual block.  Returns (x, new_cache, aux): ``aux`` is an
    expert block's ``{"balance", "load"}`` (``moe.apply_moe``), else None."""
    x = constrain(x, rules, "batch", "seq", "act_embed")
    h = L.apply_norm(p["norm1"], x, cfg)
    new_cache = cache
    decode = cache is not None and pos is not None

    if kind == "rwkv":
        h, tm_state = R.apply_time_mix(
            p["mixer"], h, cfg, rules,
            state=cache if decode else None,
            impl=rec_impl)
        x = x + h
        h2 = L.apply_norm(p["norm2"], x, cfg)
        h2, cm_state = R.apply_channel_mix(
            p["mlp"], h2, cfg, rules, state=cache if decode else None)
        x = x + h2
        if decode:
            new_cache = {**tm_state, **cm_state}
        return x, new_cache, None

    if kind == "rec":
        h, rec_state = G.apply_rglru_block(
            p["mixer"], h, cfg, rules,
            state=cache if decode else None, impl=rec_impl)
        new_cache = rec_state if decode else cache
    elif cfg.mla is not None and kind in ("dense", "moe"):
        if decode:
            h, new_cache = A.decode_mla(p["mixer"], h, cache, cfg, rules,
                                        pos=pos, impl=impl)
        else:
            h = A.apply_mla(p["mixer"], h, cfg, rules, positions=positions,
                            impl=impl)
    else:
        window = cfg.attn_window if kind == "attn_local" else 0
        if decode:
            h, new_cache = A.decode_attention(p["mixer"], h, cache, cfg, rules,
                                              pos=pos, window=window, impl=impl)
        else:
            h = A.apply_attention(p["mixer"], h, cfg, rules,
                                  positions=positions, causal=causal,
                                  window=window, impl=impl)
    x = x + h

    # cross-attention (enc-dec decoder blocks)
    if "cross" in p:
        hx = L.apply_norm(p["norm_x"], x, cfg)
        if cross_cache is not None:
            hx, _ = A.decode_attention(p["cross"], hx, None, cfg, rules,
                                       pos=pos, cross_kv=cross_cache, impl=impl)
        else:
            assert enc_out is not None
            enc_pos = jnp.arange(enc_out.shape[1])
            kv = _cross_kv(p["cross"], enc_out, cfg, enc_pos)
            hx = A.apply_attention(p["cross"], hx, cfg, rules,
                                   positions=positions, kv=kv, impl=impl)
        x = x + hx

    h2 = L.apply_norm(p["norm2"], x, cfg)
    aux = None
    if kind == "moe":
        h2, aux = M.apply_moe(p["mlp"], h2, cfg, rules, impl=moe_impl,
                              groups=moe_groups)
    else:
        h2 = L.apply_mlp(p["mlp"], h2, cfg, rules)
    return x + h2, new_cache, aux


def _cross_kv(p, enc_out: jnp.ndarray, cfg: ModelConfig, enc_pos):
    """Compute cross-attention K,V from encoder output (no rope)."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    return k, v


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

def init_lm(cfg: ModelConfig, key) -> Tuple[Any, Any]:
    """Returns (params, param_axes) twins."""
    keys = jax.random.split(key, 8)
    tree: Dict[str, Any] = {"embedding": L.init_embedding(cfg, keys[0])}

    if cfg.recurrent is not None and cfg.recurrent.block_pattern:
        # hybrid: unrolled heterogeneous blocks
        bkeys = jax.random.split(keys[1], cfg.num_layers)
        tree["blocks"] = [
            init_block(cfg, bkeys[i], _block_kind(cfg, i))
            for i in range(cfg.num_layers)
        ]
    elif cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[1], cfg.num_encoder_layers)
        dkeys = jax.random.split(keys[2], cfg.num_layers)
        tree["encoder"] = L.relabel_stacked(
            jax.vmap(lambda k: init_block(cfg, k, "dense"))(ekeys))
        tree["decoder"] = L.relabel_stacked(
            jax.vmap(lambda k: init_cross_block(cfg, k))(dkeys))
    else:
        kind = _block_kind(cfg, 0)
        n0 = cfg.first_dense_layers
        lkeys = jax.random.split(keys[1], cfg.num_layers - n0)
        tree["layers"] = L.relabel_stacked(
            jax.vmap(lambda k: init_block(cfg, k, kind))(lkeys))
        if n0:
            dkeys = jax.random.split(keys[3], n0)
            tree["dense_layers"] = L.relabel_stacked(
                jax.vmap(lambda k: init_block(cfg, k, "dense"))(dkeys))

    tree["final_norm"] = L.init_norm(cfg, cfg.d_model)
    return L.split_tree(tree)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def _scan_stack(stacked_params, x, fn, remat: bool):
    """Scan a homogeneous layer stack.  fn(lp, x) -> (x, aux); returns the
    last x and the layers' stacked aux."""
    if remat:
        fn = jax.checkpoint(fn)
    return jax.lax.scan(lambda carry, lp: fn(lp, carry), x, stacked_params)


def _stacks(cfg: ModelConfig):
    """The scanned stacks of a homogeneous model in order, as (params key,
    block kind): the dense prefix first where the config has one."""
    head = [("dense_layers", "dense")] if cfg.first_dense_layers else []
    return head + [("layers", _block_kind(cfg, 0))]


def lm_forward(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
               rules: Optional[AxisRules] = None, *, impl: str = "auto",
               moe_impl: str = "auto", rec_impl: str = "auto",
               moe_groups: int = 1, collect_cache: bool = False,
               return_aux: bool = False):
    """Returns logits (B, S, V) (decoder logits for enc-dec), and optionally
    the prefill cache.  ``return_aux`` (homogeneous stacks) returns
    ``(logits, aux)``: the expert layers' stacked ``{"balance", "load"}``,
    None for a model without them."""
    dt = _dtype(cfg)
    emb = params["embedding"]

    if cfg.is_encoder_decoder:
        return _encdec_forward(params, batch, cfg, rules, impl=impl,
                               collect_cache=collect_cache)

    tokens = batch["tokens"]
    x = L.embed(emb, tokens, cfg, rules, dt)
    if cfg.frontend != "none" and "frontend_embeds" in batch:
        fe = batch["frontend_embeds"].astype(dt)
        x = jnp.concatenate([fe, x], axis=1)
    S = x.shape[1]
    positions = jnp.arange(S)

    if cfg.recurrent is not None and cfg.recurrent.block_pattern:
        caches = []
        for i, bp in enumerate(params["blocks"]):
            kind = _block_kind(cfg, i)
            fn = functools.partial(
                apply_block, cfg=cfg, rules=rules, kind=kind,
                positions=positions, impl=impl, rec_impl=rec_impl)
            if cfg.remat:
                fn = jax.checkpoint(fn)
            x, c, _ = fn(bp, x)
            if collect_cache:
                caches.append(_prefill_block_cache(bp, x, cfg, kind))
        x = L.apply_norm(params["final_norm"], x, cfg)
        logits = L.unembed(emb, x, cfg, rules)
        return (logits, caches) if collect_cache else logits

    aux = None
    for name, kind in _stacks(cfg):
        def layer_fn(lp, x, kind=kind):
            y, _, a = apply_block(lp, x, cfg, rules, kind=kind,
                                  positions=positions, impl=impl,
                                  moe_impl=moe_impl, rec_impl=rec_impl,
                                  moe_groups=moe_groups)
            return y, a

        x, aux = _scan_stack(params[name], x, layer_fn, cfg.remat)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(emb, x, cfg, rules)
    return (logits, aux) if return_aux else logits


def _encdec_forward(params, batch, cfg: ModelConfig, rules, *, impl,
                    collect_cache=False):
    dt = _dtype(cfg)
    frames = batch["frames"].astype(dt)  # pre-computed frontend embeddings
    enc_pos = jnp.arange(frames.shape[1])
    x = frames + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(dt)

    def enc_fn(lp, x):
        y, _, _ = apply_block(lp, x, cfg, rules, kind="dense",
                              positions=enc_pos, impl=impl, causal=False)
        return y, None

    enc_out, _ = _scan_stack(params["encoder"], x, enc_fn, cfg.remat)

    tokens = batch["tokens"]
    dec_pos = jnp.arange(tokens.shape[1])
    y = L.embed(params["embedding"], tokens, cfg, rules, dt)
    y = y + L.sinusoidal_positions(tokens.shape[1], cfg.d_model).astype(dt)

    def dec_fn(lp, y):
        z, _, _ = apply_block(lp, y, cfg, rules, kind="dense",
                              positions=dec_pos, impl=impl, enc_out=enc_out)
        return z, None

    y, _ = _scan_stack(params["decoder"], y, dec_fn, cfg.remat)
    y = L.apply_norm(params["final_norm"], y, cfg)
    logits = L.unembed(params["embedding"], y, cfg, rules)
    if collect_cache:
        return logits, {"enc_out": enc_out}
    return logits


def _prefill_block_cache(bp, x, cfg, kind):  # placeholder for hybrid prefill
    return None


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _fused_ce(logits, targets):
    """Mean masked CE with fp32 math but NO materialized fp32 logits copy:
    forward keeps only reduced stats; backward emits the softmax-minus-onehot
    cotangent directly in the logits dtype (bf16 on TPU), halving the
    largest train-step buffers (observed f32 (B,S,V) x ~20 copies)."""
    loss, _ = _fused_ce_fwd(logits, targets)
    return loss


def _fused_ce_fwd(logits, targets):
    mask = (targets >= 0).astype(jnp.float32)
    tgt = jnp.maximum(targets, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(
        logits, tgt[..., None], axis=-1)[..., 0].astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum((lse - ll) * mask) / denom
    return loss, (logits, lse, mask, tgt, denom)


def _fused_ce_bwd(res, g):
    logits, lse, mask, tgt, denom = res
    scale = (g * mask / denom)
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    grad = (p * scale[..., None]).astype(logits.dtype)
    grad = grad.at[
        jnp.arange(grad.shape[0])[:, None],
        jnp.arange(grad.shape[1])[None, :], tgt].add(
            -scale.astype(logits.dtype))
    return grad, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def lm_loss(params, batch: Dict[str, jnp.ndarray], cfg: ModelConfig,
            rules: Optional[AxisRules] = None, *, with_aux: bool = False,
            **fw):
    """Mean next-token cross entropy; an expert model adds its layers'
    balance-loss terms.  ``with_aux`` returns ``(loss, aux)``, ``aux`` the
    summed ``"balance"`` term and ``"load"``, the assignments each expert
    layer's held experts received, (expert layers, held); None without
    expert layers."""
    aux = None
    if cfg.moe is None:
        logits = lm_forward(params, batch, cfg, rules, **fw)
    else:
        logits, aux = lm_forward(params, batch, cfg, rules, return_aux=True,
                                 **fw)
        aux = {"balance": jnp.sum(aux["balance"]), "load": aux["load"]}
    targets = batch["targets"]
    # frontend positions prepend to the sequence; align targets to the tail
    if logits.shape[1] != targets.shape[1]:
        logits = logits[:, -targets.shape[1]:]
    loss = _fused_ce(logits, targets)
    if aux is not None:
        loss = loss + aux["balance"]
    return (loss, aux) if with_aux else loss


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               enc_len: int = 0, dtype=jnp.bfloat16) -> Any:
    """Build the per-layer decode cache pytree (stacked where scanned)."""
    if cfg.recurrent is not None and not cfg.recurrent.block_pattern:
        states = [R.init_rwkv_state(cfg, batch, dtype) for _ in range(cfg.num_layers)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)
    if cfg.recurrent is not None:
        caches = []
        for i in range(cfg.num_layers):
            if _block_kind(cfg, i) == "rec":
                caches.append(G.init_rglru_state(cfg, batch, dtype))
            else:
                caches.append(A.init_kv_cache(cfg, batch, max_len,
                                              window=cfg.attn_window, dtype=dtype))
        return caches
    if cfg.is_encoder_decoder:
        hd = cfg.resolved_head_dim
        Ld = cfg.num_layers
        self_caches = [A.init_kv_cache(cfg, batch, max_len, dtype=dtype)
                       for _ in range(Ld)]
        stacked_self = jax.tree.map(lambda *xs: jnp.stack(xs), *self_caches)
        cross = {
            "k": jnp.zeros((Ld, batch, enc_len, cfg.num_kv_heads, hd), dtype),
            "v": jnp.zeros((Ld, batch, enc_len, cfg.num_kv_heads, hd), dtype),
        }
        return {"self": stacked_self, "cross": cross}

    def stack(n):
        if cfg.mla is not None:
            caches = [A.init_mla_cache(cfg, batch, max_len, dtype)
                      for _ in range(n)]
        else:
            caches = [A.init_kv_cache(cfg, batch, max_len, dtype=dtype)
                      for _ in range(n)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)

    n0 = cfg.first_dense_layers
    if n0:   # one stacked cache per scanned stack
        return {"dense_layers": stack(n0), "layers": stack(cfg.num_layers - n0)}
    return stack(cfg.num_layers)


def decode_step(params, cache: Any, tokens: jnp.ndarray, pos: jnp.ndarray,
                cfg: ModelConfig, rules: Optional[AxisRules] = None, *,
                impl: str = "auto", moe_impl: str = "auto"
                ) -> Tuple[jnp.ndarray, Any]:
    """One token for the whole batch.  tokens: (B,1); pos: scalar int32."""
    dt = _dtype(cfg)
    emb = params["embedding"]
    x = L.embed(emb, tokens, cfg, rules, dt)
    if cfg.is_encoder_decoder:
        x = x + L.sinusoidal_at(pos[None], cfg.d_model).astype(dt)

    if cfg.recurrent is not None and cfg.recurrent.block_pattern:
        new_caches = []
        for i, bp in enumerate(params["blocks"]):
            kind = _block_kind(cfg, i)
            x, nc, _ = apply_block(bp, x, cfg, rules, kind=kind,
                                   positions=pos[None], impl=impl,
                                   cache=cache[i], pos=pos)
            new_caches.append(nc)
        x = L.apply_norm(params["final_norm"], x, cfg)
        return L.unembed(emb, x, cfg, rules), new_caches

    if cfg.is_encoder_decoder:
        def body(x, inp):
            lp, lself, lck, lcv = inp
            y, nc, _ = apply_block(lp, x, cfg, rules, kind="dense",
                                   positions=pos[None], impl=impl,
                                   cache=lself, pos=pos,
                                   cross_cache=(lck, lcv))
            return y, nc
        x, new_self = jax.lax.scan(
            body, x, (params["decoder"], cache["self"],
                      cache["cross"]["k"], cache["cross"]["v"]))
        x = L.apply_norm(params["final_norm"], x, cfg)
        return L.unembed(emb, x, cfg, rules), {"self": new_self,
                                               "cross": cache["cross"]}

    def scan(x, name, kind, stack_cache):
        def body(x, inp):
            lp, lcache = inp
            y, nc, _ = apply_block(lp, x, cfg, rules, kind=kind,
                                   positions=pos[None], impl=impl,
                                   moe_impl=moe_impl, cache=lcache, pos=pos)
            return y, nc
        return jax.lax.scan(body, x, (params[name], stack_cache))

    x, new_cache = _scan_stacks_cached(cfg, x, cache, scan)
    x = L.apply_norm(params["final_norm"], x, cfg)
    return L.unembed(emb, x, cfg, rules), new_cache


def _scan_stacks_cached(cfg: ModelConfig, x, cache, scan):
    """Run ``scan(x, params key, kind, stack cache) -> (x, new stack cache)``
    over the model's stacks; the cache is one stacked tree, or with a dense
    prefix one per stack (``init_cache``)."""
    if not cfg.first_dense_layers:
        return scan(x, "layers", _block_kind(cfg, 0), cache)
    new_cache = {}
    for name, kind in _stacks(cfg):
        x, new_cache[name] = scan(x, name, kind, cache[name])
    return x, new_cache


# ---------------------------------------------------------------------------
# Prefill: full-sequence stateful forward that writes the decode cache
# ---------------------------------------------------------------------------

def prefill_step(params, cache: Any, batch: Dict[str, jnp.ndarray],
                 cfg: ModelConfig, rules: Optional[AxisRules] = None, *,
                 impl: str = "auto", moe_impl: str = "auto",
                 moe_groups: int = 1) -> Tuple[jnp.ndarray, Any]:
    """Consume the prompt, write the cache, return last-position logits."""
    dt = _dtype(cfg)
    emb = params["embedding"]
    pos0 = jnp.int32(0)

    if cfg.is_encoder_decoder:
        # encode frames + build per-layer cross K,V; prime decoder with BOS
        frames = batch["frames"].astype(dt)
        enc_pos = jnp.arange(frames.shape[1])
        x = frames + L.sinusoidal_positions(frames.shape[1], cfg.d_model).astype(dt)

        def enc_fn(lp, x):
            y, _, _ = apply_block(lp, x, cfg, rules, kind="dense",
                                  positions=enc_pos, impl=impl, causal=False)
            return y, None

        enc_out, _ = _scan_stack(params["encoder"], x, enc_fn, cfg.remat)

        def cross_fn(_, lp):
            k, v = _cross_kv(lp["cross"], enc_out, cfg, enc_pos)
            return None, (k, v)

        _, (cks, cvs) = jax.lax.scan(cross_fn, None, params["decoder"])
        new_cache = {"self": cache["self"],
                     "cross": {"k": cks.astype(cache["cross"]["k"].dtype),
                               "v": cvs.astype(cache["cross"]["v"].dtype)}}
        bos = jnp.zeros((frames.shape[0], 1), jnp.int32)
        logits, new_cache = decode_step(params, new_cache, bos, pos0, cfg,
                                        rules, impl=impl)
        return logits, new_cache

    tokens = batch["tokens"]
    x = L.embed(emb, tokens, cfg, rules, dt)
    if cfg.frontend != "none" and "frontend_embeds" in batch:
        x = jnp.concatenate([batch["frontend_embeds"].astype(dt), x], axis=1)

    if cfg.recurrent is not None and cfg.recurrent.block_pattern:
        new_caches = []
        for i, bp in enumerate(params["blocks"]):
            kind = _block_kind(cfg, i)
            x, nc, _ = apply_block(bp, x, cfg, rules, kind=kind,
                                   positions=jnp.arange(x.shape[1]),
                                   impl=impl, cache=cache[i], pos=pos0)
            new_caches.append(nc)
        x = L.apply_norm(params["final_norm"], x, cfg)
        logits = L.unembed(emb, x[:, -1:], cfg, rules)
        return logits, new_caches

    def scan(x, name, kind, stack_cache):
        def body(x, inp):
            lp, lcache = inp
            y, nc, _ = apply_block(lp, x, cfg, rules, kind=kind,
                                   positions=jnp.arange(x.shape[1]),
                                   impl=impl, moe_impl=moe_impl, cache=lcache,
                                   pos=pos0, moe_groups=moe_groups)
            return y, nc
        fn = jax.checkpoint(body) if cfg.remat else body
        return jax.lax.scan(fn, x, (params[name], stack_cache))

    x, new_cache = _scan_stacks_cached(cfg, x, cache, scan)
    x = L.apply_norm(params["final_norm"], x, cfg)
    logits = L.unembed(emb, x[:, -1:], cfg, rules)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins for the dry-run)
# ---------------------------------------------------------------------------

def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """ShapeDtypeStruct inputs for (cfg, shape) — no device allocation."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = _dtype(cfg)
    sds = jax.ShapeDtypeStruct

    if shape.kind in ("train", "prefill"):
        if cfg.is_encoder_decoder:
            return {"frames": sds((B, S, cfg.d_model), dt),
                    "tokens": sds((B, S), i32),
                    "targets": sds((B, S), i32)}
        if cfg.frontend != "none":
            F = min(cfg.frontend_tokens, S // 2) or S // 8
            return {"tokens": sds((B, S - F), i32),
                    "frontend_embeds": sds((B, F, cfg.d_model), dt),
                    "targets": sds((B, S - F), i32)}
        return {"tokens": sds((B, S), i32), "targets": sds((B, S), i32)}
    # decode: one token against a cache of S
    return {"tokens": sds((B, 1), i32)}
