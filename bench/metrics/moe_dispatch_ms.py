"""Device time per step of the expert layers' ``moe/dispatch`` ops (sort
by held expert, token gather, scatter-combine; forward and backward), in
ms (``bench/step_scopes.py``)."""
import step_scopes


def read(ctx):
    return step_scopes.scope_ms(ctx, "moe/dispatch")
