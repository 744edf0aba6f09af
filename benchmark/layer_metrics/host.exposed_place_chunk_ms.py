"""Milliseconds a traced window in which the device idles while the host is
inside ``tac/host/place_chunk`` or one of its parts (``/transfer``,
``/unpack``): the idle gaps cut at the spans' edges (mean over the traced
windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.exposed_ms(ctx, "place_chunk")
