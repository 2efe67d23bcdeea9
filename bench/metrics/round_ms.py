"""Device time per round of the round executable (``make_round_jit``'s
jitted lambda), in ms: every execution in the window, open or closed."""
from trace_reduce import match

ROUND = r"lambda"


def read(ctx):
    count, seconds = match(ctx["trace"]["modules"], ROUND)
    return seconds / count * 1e3 if count else None
