"""Host milliseconds a window spends placing its chunk on the device (the
benchmark's span around ``shard_chunk_from_local``)."""

from benchmark.harness import spans


def read(ctx):
    return spans.ms_per_window(ctx, "place_chunk")
