"""The routed experts' grouped products' achieved share of the v5e roofline
in the ``laguna`` trunk: the least time the chip needs for their FLOPs and
bytes at the counted assignments (``harness/flops_laguna.py``: the gated
form's three products over the stack's expert layers, its dense block
holding none; recomputed products do not count) over the device time of the
grouped products, whatever implements them
(``trunk_read.grouped_product_seconds``: the scope
``tac/trunk/moe/experts/products`` beside XLA:TPU's ``ragged-dot-none``)."""

from benchmark.harness import flops_laguna, trunk_read


def read(ctx):
    rows = trunk_read.assignments(ctx)
    model = trunk_read.model(ctx)
    spent = trunk_read.grouped_product_seconds(ctx)
    if not spent or rows is None or "dense_width" not in model:
        return None
    least = flops_laguna.roofline_seconds(
        flops_laguna.expert_flops_per_step(model, *rows),
        flops_laguna.expert_bytes_per_step(model, *rows), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
