"""Device-busy microseconds per gradient step: the union of the device's
operation intervals in the traced window over the gradient steps dispatched
in it (one data-parallel step of the global batch counts once)."""


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    if ctx.trace is None or not steps:
        return None
    return 1e6 * ctx.trace["busy_s"] / steps
