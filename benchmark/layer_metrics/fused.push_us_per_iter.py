"""Device microseconds per scan iteration under ``tac/push`` (a push happens
once every ``update_every`` iterations; this is its share of one)."""

from benchmark.harness import scopes


def read(ctx):
    iters = ctx.n_windows * ctx.per_window.get("iterations", 0)
    return scopes.group_us(ctx, "push", iters)
