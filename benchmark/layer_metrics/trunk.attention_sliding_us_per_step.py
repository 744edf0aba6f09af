"""Device microseconds a gradient step under ``tac/trunk/attention/sliding``:
the sliding-window sublayers of a stack that mixes attention kinds (input
norm, projections, rotary, the windowed flash kernels, the output projection;
the per-head gate has a scope of its own).  Also counted in
``trunk.attention_us_per_step``."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/attention/sliding")
