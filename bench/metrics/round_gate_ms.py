"""Device time per round of the round's ``hermes/gate`` ops, in ms
(``bench/scopes.py``): every execution in the window, open or closed."""
import scopes


def read(ctx):
    return scopes.phase_ms(ctx["trace"], scopes.round_op_phases(ctx), "gate")
