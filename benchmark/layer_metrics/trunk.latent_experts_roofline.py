"""The routed experts' grouped products' achieved share of the v5e roofline:
the least time the chip needs for their FLOPs and bytes at the counted
assignments (``harness/flops_hybrid.py``; recomputed products do not count)
over the device time of the grouped products, whatever implements them: the
operations under the scope ``tac/trunk/moe/experts/products`` (where
``ops/moe.py`` calls them) and XLA:TPU's own grouped-product kernels, which it
puts in the place of a ``ragged_dot`` under a name of its own
(``ragged-dot-none``) and without the program's scope (my chip run, PR 40: no
instruction of the compiled burst carries the scope; a kernel of our own
would)."""

from benchmark.harness import flops_hybrid, trunk_read

SCOPE = "tac/trunk/moe/experts/products"


def read(ctx):
    rows = trunk_read.assignments(ctx)
    model = trunk_read.model(ctx)
    if ctx.trace is None or rows is None or "expert_latent" not in model:
        return None
    scoped_us = trunk_read.scope_us_per_step(ctx, SCOPE) or 0.0
    spent = 1e-6 * scoped_us * trunk_read.steps(ctx) + (
        trunk_read.kernel_seconds(ctx, trunk_read.GROUPED_PRODUCT) or 0.0
    )
    if not spent:
        return None
    least = flops_hybrid.roofline_seconds(
        flops_hybrid.expert_flops_per_step(model, *rows),
        flops_hybrid.expert_bytes_per_step(model, *rows), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
