"""Host milliseconds per lockstep step under ``tac/host/env_step``: stepping every member's env and the episode bookkeeping
(the Trainer's own annotation, on the profiler's clock)."""

from benchmark.harness import scopes


def read(ctx):
    s = scopes.summary(ctx)
    steps = s["host_spans"].get("env_step", 0) if s is not None else 0
    if not steps:
        return None
    return 1e3 * s["host"].get("env_step", 0.0) / steps
