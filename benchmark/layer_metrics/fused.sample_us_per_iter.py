"""Device microseconds per scan iteration under ``tac/sample*``."""

from benchmark.harness import scopes


def read(ctx):
    iters = ctx.n_windows * ctx.per_window.get("iterations", 0)
    return scopes.group_us(ctx, "sample", iters)
