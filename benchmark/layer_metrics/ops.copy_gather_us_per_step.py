"""Device microseconds per gradient step in copy, gather, scatter and
dynamic-slice operations, by the kinds the trace names."""

from benchmark.harness import trace


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    if ctx.trace is None or not steps:
        return None
    return 1e6 * trace.kind_seconds(
        ctx.trace, "copy", "gather", "scatter", "dynamic-slice", "dynamic-update-slice"
    ) / steps
