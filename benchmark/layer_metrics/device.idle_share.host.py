"""Share of the traced window in which no operation ran on the device
(averaged over chips), under a name of its own for the cells whose
end-to-end metric is env steps: the host sets their pace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
