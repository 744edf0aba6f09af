"""Key blocks the sliding layers' flash kernels visit over those the causal
mask would make them visit, at the cell's history and the blocks the program
picks for it: from ``ops/attention.py::visited_key_blocks``, the function
the kernels' grids are built from (a program without it, the parent's, is
not read)."""

from benchmark.harness import trunk_read


def read(ctx):
    from torch_actor_critic_tpu.ops import attention

    model = trunk_read.model(ctx)
    visited = getattr(attention, "visited_key_blocks", None)
    if visited is None or not model.get("window"):
        return None
    t, b = model["history_len"], model["block_length"]
    return visited(t, block_length=b, window=model["window"]) / visited(t, block_length=b)
