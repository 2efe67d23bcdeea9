"""Device time per step of the ``mla/attend`` ops (MLA's projections,
rope, causal attention and output, every layer; forward, recomputation
and backward), in ms (``bench/step_scopes.py``)."""
import step_scopes


def read(ctx):
    return step_scopes.scope_ms(ctx, "mla/attend")
