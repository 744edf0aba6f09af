"""Device microseconds per gradient step under ``tac/sample*``: the index
draw, the gathers from the ring and the pixel decode (`harness/scopes.py`)."""

from benchmark.harness import scopes


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    return scopes.group_us(ctx, "sample", steps)
