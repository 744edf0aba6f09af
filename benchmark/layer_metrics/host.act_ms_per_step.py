"""Host milliseconds per lockstep step under ``tac/host/act``: the host actor's forward
(the Trainer's own annotation, on the profiler's clock)."""

from benchmark.harness import scopes


def read(ctx):
    s = scopes.summary(ctx)
    steps = s["host_spans"].get("env_step", 0) if s is not None else 0
    if not steps:
        return None
    return 1e3 * s["host"].get("act", 0.0) / steps
