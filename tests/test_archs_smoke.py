"""Deliverable (f): reduced-config smoke test per assigned architecture.

One forward + one train step on CPU, asserting output shapes and no NaNs;
plus decode-vs-prefill logits parity for representative families.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import OptimizerConfig
from repro.configs import ASSIGNED_ARCHS, get_smoke_config, get_config
from repro.models import (
    init_lm, lm_forward, lm_loss, init_cache, decode_step, prefill_step,
)
from repro.optim import make_optimizer

B, S = 2, 16


def _batch(cfg):
    if cfg.is_encoder_decoder:
        return {"frames": jnp.ones((B, S, cfg.d_model), jnp.float32),
                "tokens": jnp.ones((B, S), jnp.int32),
                "targets": jnp.ones((B, S), jnp.int32)}
    if cfg.frontend != "none":
        return {"tokens": jnp.ones((B, S - 4), jnp.int32),
                "frontend_embeds": jnp.ones((B, 4, cfg.d_model), jnp.float32),
                "targets": jnp.ones((B, S - 4), jnp.int32)}
    return {"tokens": jnp.ones((B, S), jnp.int32),
            "targets": jnp.ones((B, S), jnp.int32)}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_shapes_no_nans(arch):
    cfg = get_smoke_config(arch)
    params, axes = init_lm(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = lm_forward(params, batch, cfg)
    tgt_len = batch["targets"].shape[1]
    assert logits.shape[0] == B and logits.shape[2] == cfg.vocab_size
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_reduces_loss(arch):
    cfg = get_smoke_config(arch)
    params, _ = init_lm(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(OptimizerConfig(name="adamw", lr=3e-3))
    opt_state = opt.init(params)
    batch = _batch(cfg)

    @jax.jit
    def step(params, opt_state):
        loss, g = jax.value_and_grad(lambda p: lm_loss(p, batch, cfg))(params)
        params, opt_state = opt.apply(params, g, opt_state)
        return params, opt_state, loss

    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0], losses  # overfits one batch


@pytest.mark.parametrize("arch", ["qwen3-8b", "rwkv6-3b", "recurrentgemma-2b",
                                  "deepseek-v2-lite-16b", "granite-34b"])
def test_decode_matches_forward(arch):
    """Token-by-token decode must reproduce full-forward logits."""
    cfg = get_smoke_config(arch)
    params, _ = init_lm(cfg, jax.random.PRNGKey(1))
    T = 12
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, T), 0, cfg.vocab_size)
    full = lm_forward(params, {"tokens": toks}, cfg, impl="naive")

    cache = init_cache(cfg, B, T + 2, dtype=jnp.float32)
    n_prefill = 7
    lg, cache = prefill_step(params, cache,
                             {"tokens": toks[:, :n_prefill]}, cfg,
                             impl="naive")
    np.testing.assert_allclose(
        np.asarray(lg[:, -1], np.float32),
        np.asarray(full[:, n_prefill - 1], np.float32), atol=2e-2)
    for t in range(n_prefill, T):
        lg, cache = decode_step(params, cache, toks[:, t:t + 1],
                                jnp.int32(t), cfg, impl="naive")
        np.testing.assert_allclose(
            np.asarray(lg[:, 0], np.float32),
            np.asarray(full[:, t], np.float32), atol=2e-2,
            err_msg=f"decode divergence at position {t}")


def test_full_configs_match_brief():
    """Exact numbers from the assignment brief."""
    expect = {
        "rwkv6-3b": (32, 2560, 8960, 65536),
        "phi3-mini-3.8b": (32, 3072, 8192, 32064),
        "qwen3-8b": (36, 4096, 12288, 151936),
        "yi-6b": (32, 4096, 11008, 64000),
        "granite-34b": (88, 6144, 24576, 49152),
        "llava-next-34b": (60, 7168, 20480, 64000),
        "seamless-m4t-large-v2": (24, 1024, 8192, 256206),
        "grok-1-314b": (64, 6144, 32768, 131072),
        "deepseek-v2-lite-16b": (27, 2048, 10944, 102400),  # dense layer
        "recurrentgemma-2b": (26, 2560, 7680, 256000),
    }
    for arch, (L, d, ff, v) in expect.items():
        cfg = get_config(arch)
        assert (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == \
            (L, d, ff, v), arch
    # extra structure checks
    assert get_config("grok-1-314b").moe.num_experts == 8
    assert get_config("grok-1-314b").moe.top_k == 2
    dsv2 = get_config("deepseek-v2-lite-16b")
    assert dsv2.moe.num_experts == 64 and dsv2.moe.top_k == 6
    assert dsv2.moe.expert_ff == 1408 and dsv2.first_dense_layers == 1
    assert not dsv2.moe.norm_topk_prob and dsv2.moe.dropless
    assert dsv2.rope_scaling.factor == 40
    assert dsv2.mla.kv_lora_rank == 512
    rg = get_config("recurrentgemma-2b")
    assert rg.recurrent.block_pattern == ("rec", "rec", "attn")
    assert get_config("granite-34b").num_kv_heads == 1
