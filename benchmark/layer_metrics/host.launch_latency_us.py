"""Microseconds from the start of ``tac/host/burst_dispatch`` to the first
device operation of that window's run of the program (median over the traced
windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.latency_us(ctx, "launch")
