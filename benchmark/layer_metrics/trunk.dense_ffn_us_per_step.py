"""Device microseconds a gradient step under ``tac/trunk/dense_ffn``: a
block's dense gated feed-forward and the norm before it (no scope under
``tac/trunk/moe``, so ``trunk.moe_us_per_step`` does not hold it)."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/dense_ffn")
