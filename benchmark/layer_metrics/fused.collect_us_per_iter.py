"""Device microseconds per scan iteration under ``tac/collect/*``: the policy's
forward and the env step of every env of every member."""

from benchmark.harness import scopes


def read(ctx):
    iters = ctx.n_windows * ctx.per_window.get("iterations", 0)
    return scopes.group_us(ctx, "collect", iters)
