"""Share of the window the Trainer spent waiting for the device: its
``tac/host/param_sync`` (the actor mirror's refresh, which waits for the burst)
and ``tac/host/drain`` annotations, on the profiler's clock."""

from benchmark.harness import scopes


def read(ctx):
    s = scopes.summary(ctx)
    if s is None or not s["window_s"]:
        return None
    wait = s["host"].get("param_sync", 0.0) + s["host"].get("drain", 0.0)
    return 100.0 * wait / s["window_s"]
