"""The routed experts' grouped products' achieved share of the v5e roofline:
the least time the chip needs for their FLOPs and bytes at the counted
assignments (``harness/flops_hybrid.py``; recomputed products do not count)
over the device time of the grouped products, whatever implements them
(``trunk_read.grouped_product_seconds``: the scope
``tac/trunk/moe/experts/products`` beside XLA:TPU's ``ragged-dot-none``)."""

from benchmark.harness import flops_hybrid, trunk_read


def read(ctx):
    rows = trunk_read.assignments(ctx)
    model = trunk_read.model(ctx)
    spent = trunk_read.grouped_product_seconds(ctx)
    if not spent or rows is None or "expert_latent" not in model:
        return None
    least = flops_hybrid.roofline_seconds(
        flops_hybrid.expert_flops_per_step(model, *rows),
        flops_hybrid.expert_bytes_per_step(model, *rows), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
