"""Operations and bytes of one chip's share of DeepSeek-V2-Lite, computed
from its configuration file (``bench/configs/deepseek-v2-lite-ep8.json``).

Model FLOPs per training token count the work the chip does, forward and
backward (3 x the forward's matrix products, 2 FLOPs per multiply-add):
MLA's projections (full-rank queries, the joint latent-and-rope-key
down-projection, the latent's key and value up-projections, the output)
and its causal scores and value product ((seq + 1) / 2 keys on average),
the leading dense layers' SwiGLU MLP, each expert layer's router, shared
experts and the held experts' share of the assignments (top_k x held /
routed experts per token, the expectation under even routing), and the
output head over the vocabulary slice.  Recomputation under remat is not
counted.

The grouped expert products of a step: 3 products of 2 FLOPs per
multiply-add over the held assignments, forward and backward (x 3), and
the held experts' bfloat16 weights read forward and twice backward.
"""
from __future__ import annotations

from typing import Any, Dict


def held_per_token(config: Dict[str, Any]) -> float:
    """Expected assignments to the held experts per token."""
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["published"]["n_routed_experts"])


def flops_per_token(config: Dict[str, Any], seq: int) -> float:
    c = config
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab_size"]
    nope, rope = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    vd, r = c["v_head_dim"], c["kv_lora_rank"]
    f, fe = c["intermediate_size"], c["moe_intermediate_size"]
    n, n0 = c["num_hidden_layers"], c["first_k_dense_replace"]
    E = c["published"]["n_routed_experts"]
    ctx = (seq + 1) / 2.0
    mla = (d * H * (nope + rope) + d * (r + rope) + r * H * (nope + vd)
           + H * vd * d + H * (nope + rope) * ctx + H * vd * ctx)
    dense = 3 * d * f
    moe = (d * E + 3 * d * fe * c["n_shared_experts"]
           + held_per_token(c) * 3 * d * fe)
    forward = n * mla + n0 * dense + (n - n0) * moe + d * V
    return 3.0 * 2.0 * forward


def expert_products(config: Dict[str, Any], tokens: int) -> Dict[str, float]:
    """FLOPs and bytes of a step's grouped expert products over ``tokens``
    training tokens, all expert layers."""
    c = config
    d, fe = c["hidden_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] - c["first_k_dense_replace"]
    assignments = tokens * held_per_token(c)
    weights = 3 * c["n_routed_experts"] * d * fe * 2.0
    return {"flops": layers * 3 * 3 * 2.0 * assignments * d * fe,
            "bytes": layers * 3 * weights}
