"""Host milliseconds a traced window spends placing its chunk on the device:
the program's own ``tac/host/place_chunk`` span, opened inside
``shard_chunk_from_local`` / ``PopulationLearner.place_chunk`` (mean over the
traced windows)."""

from benchmark.harness import window_spans


def read(ctx):
    return window_spans.span_ms(ctx, "place_chunk")
