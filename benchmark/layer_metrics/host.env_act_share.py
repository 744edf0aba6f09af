"""Share of the window's wall time the Trainer spent acting and stepping envs
(its own PhaseTimer spans ``act`` + ``env_step``, host clock)."""


def read(ctx):
    spans = getattr(ctx.driver, "host_spans", lambda: [])()
    if not spans or not ctx.windows:
        return None
    lo, hi = ctx.windows[0][0], ctx.windows[-1][1]
    busy = sum(d for name, t0, d in spans if name in ("act", "env_step") and lo <= t0 < hi)
    return 100.0 * busy / (hi - lo)
