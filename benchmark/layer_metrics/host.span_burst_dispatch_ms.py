"""Host milliseconds a traced window spends in the call that dispatches its
device work, until the call returns: the program's own
``tac/host/burst_dispatch`` span, opened inside ``update_burst`` / the fused
loop's ``epoch`` (mean over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.span_ms(ctx, "burst_dispatch")
