"""Device microseconds a gradient step under ``tac/trunk/attention``: the
input norm, the four projections, q/k norm, rotary positions, the flash
kernels."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/attention")
