"""Model FLOP/s utilization of the ``nemotron_h`` trunk's update while the
device is busy: the FLOPs a step at the counted assignments
(``harness/flops_hybrid.py``: two trunk passes, one backward pass,
recomputation not counted) over the device-busy time a step, against the
table's bf16 peak.  Denominator: the trace's ``busy_s``, the union of all
events (``trunk.mfu``'s, by this family's count)."""

from benchmark.harness import flops_hybrid, trunk_read


def read(ctx):
    rows = trunk_read.assignments(ctx)
    model = trunk_read.model(ctx)
    if ctx.trace is None or rows is None or not ctx.trace["busy_s"] or "pattern" not in model:
        return None
    per_step = flops_hybrid.flops_per_step(model, ctx.config["sac"]["batch_size"], *rows)
    return 100.0 * per_step * trunk_read.steps(ctx) / ctx.trace["busy_s"] / (
        trunk_read.peak(ctx)["flops_bf16"]
    )
