"""Milliseconds a traced window in which the device idles while the host is
inside ``tac/host/drain`` or one of its parts (``/reduce``, ``/fetch``): the
idle gaps cut at the spans' edges (mean over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.exposed_ms(ctx, "drain")
