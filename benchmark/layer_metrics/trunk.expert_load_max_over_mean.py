"""Imbalance of the held experts: the largest number of tokens a held expert
got in an update (the largest over the last window's updates and the layers)
over the mean, from the burst's own counters."""

from benchmark.harness import trunk_read


def read(ctx):
    c = trunk_read.counters(ctx)
    if not c or not c["trunk/expert_load_mean"]:
        return None
    return c["trunk/expert_load_max"] / c["trunk/expert_load_mean"]
