"""Model FLOP/s utilisation of the whole step of an expert-parallel share,
in %: the FLOPs per training token the chip does (``bench/
counts_dsv2.py``: attention, the dense layer, router, shared experts, the
held experts' assignments, the head over the vocabulary slice) times the
traced run's training tokens per second per chip, over the chip's bf16
peak."""
import counts_dsv2


def read(ctx):
    per_token = counts_dsv2.flops_per_token(ctx["cell"]["config"],
                                            int(ctx["cell"]["traffic"]["seq"]))
    return per_token * ctx["tokens_per_s"] / ctx["peaks"][
        "bf16_flops_per_s"] * 100.0
