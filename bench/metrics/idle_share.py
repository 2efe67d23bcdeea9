"""Share of the steady window in which no operation runs on the device,
in %, averaged over the chips: 1 - union of op intervals / window, the
window bounded by the first and last pod-step executions of the trace."""


def read(ctx):
    t = ctx["trace"]
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
