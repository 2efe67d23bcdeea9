"""Share of the bf16 roofline the grouped expert products reach, in %: the
least time their FLOPs and the held expert weights' bytes need on the chip
(``bench/counts_dsv2.py: expert_products``, the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s), over the device time of the
``moe/experts`` ops per step (``bench/step_scopes.py``)."""
import counts_dsv2
import step_scopes


def read(ctx):
    ms = step_scopes.scope_ms(ctx, "moe/experts")
    if not ms:
        return None
    tr = ctx["cell"]["traffic"]
    work = counts_dsv2.expert_products(ctx["cell"]["config"],
                                       int(tr["batch"]) * int(tr["seq"]))
    peaks = ctx["peaks"]
    least = max(work["flops"] / peaks["bf16_flops_per_s"],
                work["bytes"] / peaks["hbm_bytes_per_s"])
    return least / (ms * 1e-3) * 100.0
