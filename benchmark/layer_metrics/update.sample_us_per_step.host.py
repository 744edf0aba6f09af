"""Device microseconds per gradient step under ``tac/sample*``: the index
draw, the gathers from the ring and the pixel decode (`harness/scopes.py`).  Under a name of its own
for the cell whose end-to-end metric is env steps; a step is one member's."""

from benchmark.harness import scopes


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    return scopes.group_us(ctx, "sample", steps)
