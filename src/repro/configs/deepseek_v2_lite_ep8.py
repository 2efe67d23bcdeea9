"""deepseek-v2-lite-ep8 — one chip's share of deepseek-v2-lite-16b under
8-way expert parallelism.

Each expert layer is divided over 8 chips; this chip holds routed experts
0-7 of 64.  The router keeps its 64 outputs and top-6, and assignments to
the 56 absent experts add nothing here.  Attention, the dense layer, the
shared experts, the norms and the embedding are what every chip computes.
"""
from repro.config import replace
from repro.configs import deepseek_v2_lite_16b as full

CONFIG = replace(full.CONFIG, name="deepseek-v2-lite-ep8",
                 moe=replace(full.CONFIG.moe, num_held=8, first_held=0))


def smoke_config():
    """The smoke model's share: 2 of its 8 experts, from the third."""
    base = full.smoke_config()
    return replace(base, name="dsv2-ep-smoke",
                   moe=replace(base.moe, num_held=2, first_held=2))
