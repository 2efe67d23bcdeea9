"""FLOP and byte counts against hand counts at the published widths."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import spec  # noqa: E402


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_phi3_flops_per_token_hand_count():
    # d 3072, 32 heads of 96 (kv 32), d_ff 8192, vocab 32064, 1 layer,
    # sequence 2048 (a causal token sees 1024.5 keys on average)
    qkv = 3 * 2 * 3072 * 3072          # 56,623,104
    out = 2 * 3072 * 3072              # 18,874,368
    mlp = 3 * 2 * 3072 * 8192          # 150,994,944
    attn = 2 * 2 * 3072 * 1024.5       # 12,589,056
    head = 2 * 3072 * 32064            # 197,001,216
    forward = qkv + out + mlp + attn + head
    assert forward == 436_082_688
    got = counts.model_flops_per_token(_config("phi3-mini-3.8b"), 2048)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    # 4096 tokens a step: the 5.36 TFLOP of one Hermes iteration
    assert got * 4096 == pytest.approx(5.3586e12, rel=1e-4)


def test_rwkv6_flops_per_token_hand_count():
    # d 2560, heads of 64, d_ff 8960, vocab 65536, 2 layers
    mix_lora = 2 * 2560 * 160 + 5 * 2 * 32 * 2560     # 1,638,400
    decay_lora = 2 * 2560 * 64 + 2 * 64 * 2560        # 655,360
    rkvgo = 5 * 2 * 2560 * 2560                       # 65,536,000
    wkv = 4 * 2560 * 64                               # 655,360
    cmix = 2 * 2 * 2560 * 8960 + 2 * 2560 * 2560      # 104,857,600
    layer = mix_lora + decay_lora + rkvgo + wkv + cmix
    assert layer == 173_342_720
    forward = 2 * layer + 2 * 2560 * 65536
    got = counts.model_flops_per_token(_config("rwkv6-3b"), 2048)
    assert got == pytest.approx(3 * forward, rel=1e-12)


def test_wire_kernel_bytes_hand_count():
    ref = spec.reference_module(_config("phi3-mini-3.8b"))
    shapes = ref.param_shapes(_config("phi3-mini-3.8b"))
    # every phi3 leaf has a 3072-wide axis, a whole number of 256-blocks
    n = (2 * 32064 * 3072          # embedding table and head
         + 4 * 3072 * 3072         # q, k, v, o
         + 3 * 3072 * 8192         # wi, wg, wo
         + 3 * 3072)               # three RMSNorm scales
    assert n == 310_256_640
    got = counts.wire_kernel_bytes(shapes, pods=2)
    # pack: 1 B in, 0.5 B out per element, both pods
    assert got["int4_pack"] == 2 * 1.5 * n
    # merge: per pod 0.5 B of payload + a 4 B scale per 256 elements,
    # then the float32 global model read and written
    assert got["dequant_merge"] == 2 * (0.5 * n + 4 * n / 256) + 8 * n


def test_blocked_layout_falls_back_to_last_axis():
    # no whole-block axis: the last axis is padded to one block
    assert counts._blocked((3, 100)) == (300, 3)
    assert counts._blocked((512, 100)) == (51200, 200)
