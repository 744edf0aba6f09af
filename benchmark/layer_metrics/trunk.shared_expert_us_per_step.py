"""Device microseconds a gradient step under ``tac/trunk/moe/shared``: the
expert every token passes, on the full hidden width (it is also counted in
``trunk.moe_us_per_step``, which reads every scope under ``tac/trunk/moe``)."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/moe/shared")
