"""Device microseconds a gradient step under ``tac/trunk/attention/full``: the
full-attention sublayers of a stack that mixes attention kinds (input norm,
projections, rotary over a part of the head, the causal flash kernels, the
output projection; the per-head gate has a scope of its own).  Also counted
in ``trunk.attention_us_per_step``, which reads every scope under
``tac/trunk/attention``."""

from benchmark.harness import trunk_read


def read(ctx):
    return trunk_read.scope_us_per_step(ctx, "tac/trunk/attention/full")
