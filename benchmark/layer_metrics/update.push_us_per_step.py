"""Device microseconds per gradient step under ``tac/push``: the ring's
scatter of the window's chunk (`harness/scopes.py` joins the trace's operations
to the program's scope table)."""

from benchmark.harness import scopes


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    return scopes.group_us(ctx, "push", steps)
