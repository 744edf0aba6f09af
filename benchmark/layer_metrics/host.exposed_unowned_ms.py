"""Milliseconds a traced window in which the device idles and no ``tac/host/``
span of the program covers the gap (mean over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.exposed_ms(ctx, window_spans.UNOWNED)
