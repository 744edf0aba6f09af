"""Device-busy microseconds per iteration of the fused epoch's scan (one env
step of every env of every member, its share of the pushes, one update of
every member)."""


def read(ctx):
    iters = ctx.n_windows * ctx.per_window.get("iterations", 0)
    if ctx.trace is None or not iters:
        return None
    return 1e6 * ctx.trace["busy_s"] / iters
