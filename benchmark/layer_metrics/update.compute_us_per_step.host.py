"""Device microseconds per gradient step in the compute group: critic, actor,
alpha, optimizer and polyak scopes together (`harness/scopes.py`).  Under a name of its own
for the cell whose end-to-end metric is env steps; a step is one member's."""

from benchmark.harness import scopes


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    return scopes.group_us(ctx, "compute", steps)
