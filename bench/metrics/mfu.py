"""Model FLOP/s utilisation of the whole step, in %: the forward and
backward FLOPs per training token (``counts.model_flops_per_token``)
times the traced run's training tokens per second per chip, over the
chip's bf16 peak."""


def read(ctx):
    peak = ctx["peaks"]["bf16_flops_per_s"]
    return ctx["flops_per_token"] * ctx["tokens_per_s"] / peak * 100.0
