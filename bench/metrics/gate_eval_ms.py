"""Device time per round of the gate's test-loss forwards: the pods'
(``pod_eval``) and, after an open round, the global model's
(``eval_if_push``), in ms."""
from trace_reduce import match


def read(ctx):
    rounds, pods = match(ctx["trace"]["modules"], r"pod_eval")
    _, glob = match(ctx["trace"]["modules"], r"eval_if_push")
    return (pods + glob) / rounds * 1e3 if rounds else None
