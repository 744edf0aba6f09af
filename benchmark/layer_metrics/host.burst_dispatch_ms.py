"""Host milliseconds a window spends dispatching its burst, until the call
returns (the benchmark's span around ``DataParallelSAC.update_burst``; the
wait for the burst's loss is ``drain`` and is not in it)."""

from benchmark.harness import spans


def read(ctx):
    return spans.ms_per_window(ctx, "burst_dispatch")
