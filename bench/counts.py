"""Operations and bytes the algorithm needs, computed from shapes.

Model FLOPs per training token count the forward and backward passes
(3 x the forward's matrix products, 2 FLOPs per multiply-add).  Causal
attention counts its lower triangle once: a token at position t attends to
t + 1 keys, so scores and the value product cost (seq + 1) / 2 keys on
average.  Recomputation under remat is not counted.

Wire-kernel bytes are the bytes the work needs to touch once, per open
round, over all pods:

* ``int4_pack``: the int8 nibbles in (1 B each), the packed bytes out
  (0.5 B each);
* ``dequant_merge``: the packed payload and its float32 absmax scales of
  every pod in, the float32 global model read and written.
"""
from __future__ import annotations

import math
from typing import Any, Dict

BLOCK = 256


def model_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    d = config["hidden_size"]
    V = config["vocab_size"]
    f = config["intermediate_size"]
    n = config["num_hidden_layers"]
    ctx = (seq + 1) / 2.0
    if "head_size" in config:                       # RWKV-6
        D = config["head_size"]
        lora = 2 * d * 5 * 32 + 5 * 2 * 32 * d + 2 * d * 64 + 2 * 64 * d
        proj = 5 * 2 * d * d                        # r, k, v, g, o
        wkv = 4 * d * D                             # r S and k^T v per head
        cmix = 2 * d * f + 2 * f * d + 2 * d * d
        layer = lora + proj + wkv + cmix
    else:                                           # dense attention
        H = config["num_attention_heads"]
        K = config["num_key_value_heads"]
        hd = d // H
        qkv = 2 * d * (H + 2 * K) * hd
        out = 2 * H * hd * d
        attn = 2 * 2 * H * hd * ctx
        mlp = 3 * 2 * d * f
        layer = qkv + out + attn + mlp
    head = 2 * d * V
    return 3.0 * (n * layer + head)


def _numel(shape) -> int:
    return int(math.prod(shape)) if shape else 1


def _blocked(shape):
    """(elements, blocks) of a leaf in the int4 wire layout: absmax blocks
    of 256 along the rightmost whole-block axis, else the last axis."""
    s = tuple(shape) or (1,)
    ax = next((a for a in range(len(s) - 1, -1, -1) if s[a] % BLOCK == 0),
              len(s) - 1)
    return _numel(s), _numel(s) // s[ax] * -(-s[ax] // BLOCK)


def wire_kernel_bytes(shapes, pods: int) -> Dict[str, float]:
    """Bytes per open round of the two wire kernels, from the parameter
    shapes (a nested dict of shape tuples) and the pod count."""
    from weights import shape_leaves
    pack = merge = 0.0
    for s in shape_leaves(shapes):
        n, nb = _blocked(s)
        pack += pods * (n * 1.0 + n * 0.5)
        merge += pods * (n * 0.5 + nb * 4.0) + 2 * n * 4.0
    return {"int4_pack": pack, "dequant_merge": merge}
