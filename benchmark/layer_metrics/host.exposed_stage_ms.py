"""Milliseconds a traced window in which the device idles while the host is
inside ``tac/host/stage``: the idle gaps cut at the spans' edges, each piece to
the innermost span over it (mean over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.exposed_ms(ctx, "stage")
