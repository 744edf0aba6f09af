"""Host milliseconds a window spends stacking its staged steps into one chunk
(the benchmark's span around ``Trainer._build_chunk``)."""

from benchmark.harness import spans


def read(ctx):
    return spans.ms_per_window(ctx, "stage")
