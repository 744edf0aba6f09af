"""The attention kernels' achieved share of the v5e roofline in a stack that
mixes sliding-window and full attention: the least time the chip needs for
the FLOPs of the pairs each layer's own mask lets through, at the heads its
kind holds, and for the kernels' bytes (``harness/flops_laguna.py``; the
probability tiles the backward kernels rebuild do not count) over the device
time of all flash kernels in the trace.  The count is of the work, whatever
implements it: a kernel that visits the causal triangle on a sliding layer
reads under half."""

from benchmark.harness import flops_laguna, trunk_read


def read(ctx):
    spent = trunk_read.kernel_seconds(ctx, trunk_read.FLASH)
    model = trunk_read.model(ctx)
    if not spent or trunk_read.counters(ctx) is None or "window" not in model:
        return None
    batch = ctx.config["sac"]["batch_size"]
    least = flops_laguna.roofline_seconds(
        flops_laguna.attention_flops_per_step(model, batch),
        flops_laguna.attention_bytes_per_step(model, batch), trunk_read.peak(ctx),
    )
    return 100.0 * least * trunk_read.steps(ctx) / spent
