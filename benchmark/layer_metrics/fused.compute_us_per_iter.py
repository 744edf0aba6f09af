"""Device microseconds per scan iteration in the compute group (critic, actor,
alpha, optimizer, polyak)."""

from benchmark.harness import scopes


def read(ctx):
    iters = ctx.n_windows * ctx.per_window.get("iterations", 0)
    return scopes.group_us(ctx, "compute", iters)
