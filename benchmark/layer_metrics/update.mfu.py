"""Model FLOP/s utilization of the update program while the device is busy:
the benchmark's FLOPs a step (harness/flops.py, a chip's own batch) over the
device-busy time a step, against the table's bf16 peak.

Denominator: the trace's ``busy_s``, the union of all events, the time under a
container that no operation covers included."""

from benchmark.harness import flops, peaks


def read(ctx):
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    if ctx.trace is None or not steps or not ctx.trace["busy_s"]:
        return None
    per_step = flops.flops_per_step(ctx.config["model"], ctx.config["sac"]["batch_size"])
    peak = peaks.peaks_for(ctx.device["kind"])["flops_bf16"]
    return 100.0 * per_step * steps / ctx.trace["busy_s"] / peak
