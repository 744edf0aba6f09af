"""Milliseconds of the longest single idle gap of the device inside one
``tac/host/drain`` span, over the traced windows: far above the wake latency
where a traced window met a wait."""

from benchmark.harness import window_spans


def read(ctx):
    s = window_spans.summary(ctx)
    return None if s is None else s["drain_gap_max_ms"]
