"""Model FLOP/s utilization of the trunk's update while the device is busy:
the FLOPs a step at the counted assignments (``harness/flops_trunk.py``:
two trunk passes, one backward pass, recomputation not counted) over the
device-busy time a step, against the table's bf16 peak.  Denominator: the
trace's ``busy_s``, the union of all events."""

from benchmark.harness import flops_trunk, trunk_read


def read(ctx):
    rows = trunk_read.assignments(ctx)
    if ctx.trace is None or rows is None or not ctx.trace["busy_s"]:
        return None
    per_step = flops_trunk.flops_per_step(
        trunk_read.model(ctx), ctx.config["sac"]["batch_size"], *rows
    )
    return 100.0 * per_step * trunk_read.steps(ctx) / ctx.trace["busy_s"] / (
        trunk_read.peak(ctx)["flops_bf16"]
    )
