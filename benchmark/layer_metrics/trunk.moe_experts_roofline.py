"""The grouped products' achieved share of the v5e roofline: the least time
the chip needs for their FLOPs and bytes at the counted assignments
(``harness/flops_trunk.py``; recomputed products do not count) over the device
time of the grouped products, whatever implements them
(``trunk_read.grouped_product_seconds``: the scope
``tac/trunk/moe/experts/products`` beside XLA:TPU's ``ragged-dot-none``)."""

from benchmark.harness import flops_trunk, trunk_read


def read(ctx):
    spent = trunk_read.grouped_product_seconds(ctx)
    rows = trunk_read.assignments(ctx)
    if not spent or rows is None:
        return None
    model = trunk_read.model(ctx)
    least = flops_trunk.roofline_seconds(
        flops_trunk.expert_flops_per_step(model, *rows),
        flops_trunk.expert_bytes_per_step(model, *rows), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
