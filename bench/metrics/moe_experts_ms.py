"""Device time per step of the expert layers' ``moe/experts`` ops (the
grouped products over the held experts; forward, recomputation and
backward), in ms (``bench/step_scopes.py``)."""
import step_scopes


def read(ctx):
    return step_scopes.scope_ms(ctx, "moe/experts")
