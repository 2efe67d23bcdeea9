"""Device time per execution of the pod-step executable (Hermes:
``make_pod_step``'s ``pod_step``; single: the ``step_fn`` step), in ms."""
from trace_reduce import match

STEP = r"pod_step|step_fn"


def read(ctx):
    count, seconds = match(ctx["trace"]["modules"], STEP)
    return seconds / count * 1e3 if count else None
