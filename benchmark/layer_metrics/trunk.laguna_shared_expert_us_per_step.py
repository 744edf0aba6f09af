"""Device microseconds a gradient step under ``tac/trunk/moe/shared`` in the
``laguna`` trunk: the gated expert every token passes beside the routed ones
(also counted in ``trunk.moe_us_per_step``).  ``trunk.shared_expert_us_per_step``
reads the same scope for the ``nemotron_h`` cell; an accepted test holds its
list of cells to that one, so this cell brings a reader of its own."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/moe/shared")
