"""Model FLOP/s utilization of the ``laguna`` trunk's update while the device
is busy: the FLOPs a step at the counted assignments and under each layer's
own mask (``harness/flops_laguna.py``: two trunk passes, one backward pass,
recomputation not counted) over the device-busy time a step, against the
table's bf16 peak.  Denominator: the trace's ``busy_s``, the union of all
events (``trunk.mfu``'s, by this family's count)."""

from benchmark.harness import flops_laguna, trunk_read


def read(ctx):
    rows = trunk_read.assignments(ctx)
    model = trunk_read.model(ctx)
    if ctx.trace is None or rows is None or not ctx.trace["busy_s"] or "window" not in model:
        return None
    per_step = flops_laguna.flops_per_step(model, ctx.config["sac"]["batch_size"], *rows)
    return 100.0 * per_step * trunk_read.steps(ctx) / ctx.trace["busy_s"] / (
        trunk_read.peak(ctx)["flops_bf16"]
    )
