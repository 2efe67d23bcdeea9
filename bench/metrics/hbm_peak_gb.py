"""``peak_bytes_in_use`` of the fullest chip after the window, in GB."""


def read(ctx):
    return ctx["memory_peak_bytes"] / 1e9
