"""Microseconds from the end of the last device operation a window's
``tac/host/drain`` waited for (the reduction's) to the end of that span: the
fetch's way back and the thread's waking (median over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.latency_us(ctx, "wake")
