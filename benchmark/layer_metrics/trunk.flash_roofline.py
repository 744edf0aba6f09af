"""The attention kernels' achieved share of the v5e roofline: the least time
the chip needs for the visible pairs' FLOPs and the kernels' bytes
(``harness/flops_trunk.py``; the probability tiles the backward kernels
rebuild do not count) over the device time of the kernels in the trace."""

from benchmark.harness import flops_trunk, trunk_read


def read(ctx):
    spent = trunk_read.kernel_seconds(ctx, trunk_read.FLASH)
    if not spent or trunk_read.counters(ctx) is None:
        return None
    model, batch = trunk_read.model(ctx), ctx.config["sac"]["batch_size"]
    least = flops_trunk.roofline_seconds(
        flops_trunk.attention_flops_per_step(model, batch),
        flops_trunk.attention_bytes_per_step(model, batch), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
