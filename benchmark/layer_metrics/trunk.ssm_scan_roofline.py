"""The recurrence's achieved share of the v5e roofline: the least time the
chip needs for its counted FLOPs and bytes (``harness/flops_hybrid.py``: what
the recurrence needs whatever computes it, recomputation not counted) over the
device time under the scope ``tac/trunk/ssm/scan``, so that it reads the same
work whether XLA's products or a kernel of our own run there."""

from benchmark.harness import flops_hybrid, trunk_read

SCOPE = "tac/trunk/ssm/scan"


def read(ctx):
    spent_us = trunk_read.scope_us_per_step(ctx, SCOPE)
    model = trunk_read.model(ctx)
    if not spent_us or "ssm_heads" not in model:
        return None
    batch = ctx.config["sac"]["batch_size"]
    least = flops_hybrid.roofline_seconds(
        flops_hybrid.scan_flops_per_step(model, batch),
        flops_hybrid.scan_bytes_per_step(model, batch), trunk_read.peak(ctx),
    )
    return 100.0 * least / (1e-6 * spent_us)
