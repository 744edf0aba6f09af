"""Host milliseconds a window spends staging its chunk and placing it on the
device (the benchmark's spans around ``_build_chunk`` and
``shard_chunk_from_local``)."""


def read(ctx):
    totals = ctx.spans.totals()
    if "stage" not in totals or not ctx.n_windows:
        return None
    return 1e3 * (totals["stage"] + totals.get("place_chunk", 0.0)) / ctx.n_windows
