"""Model FLOP/s utilization of the update's arithmetic: the benchmark's FLOPs a
step (harness/flops.py) over all device-busy time not proven to be something
else, against the table's bf16 peak.  Proven: an operation the program itself
scoped as push or sample, and a data-movement opcode (copy, slice, gather ...)
with no scope of its own.  Time the instrument cannot name, and time it names
only by inheritance, stays in the denominator, so the figure can err only low.

Denominator: the trace's ``busy_s`` (the union of all events) less
``not_compute_s``.  The busy time under the burst's loop that no operation
covers (``container_gap_s`` of harness/scopes.py) is part of ``busy_s`` and is
not proven to be anything, so it stays in the denominator too."""

from benchmark.harness import flops, peaks, scopes


def read(ctx):
    s = scopes.summary(ctx)
    steps = ctx.n_windows * ctx.per_window["grad_steps"]
    if s is None or not steps:
        return None
    compute_s = ctx.trace["busy_s"] - s["not_compute_s"]
    per_step = flops.flops_per_step(ctx.config["model"], ctx.config["sac"]["batch_size"])
    peak = peaks.peaks_for(ctx.device["kind"])["flops_bf16"]
    return 100.0 * per_step * steps / compute_s / peak
