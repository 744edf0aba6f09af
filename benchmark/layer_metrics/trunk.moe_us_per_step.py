"""Device microseconds a gradient step under the expert layer's scopes
(``tac/trunk/moe/route``: the post-attention norm, the router and its top-k;
``tac/trunk/moe/experts``: sort, dispatch, grouped products, combine)."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/moe")
