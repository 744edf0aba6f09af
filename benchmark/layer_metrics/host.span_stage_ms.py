"""Host milliseconds a traced window spends stacking its staged steps into one
chunk: the program's own ``tac/host/stage`` span, opened inside
``Trainer._build_chunk`` (mean over the traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.span_ms(ctx, "stage")
