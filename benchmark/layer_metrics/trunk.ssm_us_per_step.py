"""Device microseconds a gradient step under the state-space mixers' scopes
(``tac/trunk/ssm/proj``: the layer's norm and the two projections;
``/conv``: the short convolution; ``/scan``: the recurrence over the history;
``/gate_norm``: the gated norm)."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/ssm")
