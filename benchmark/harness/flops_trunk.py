"""Operations and bytes one SAC gradient step on the SDAR history trunk needs,
from the sizes in its configuration file and the counted assignments.

A step makes two trunk passes (the target trunk on ``next_states``, the online
trunk on ``states``) and one backward pass of the online one, so a product of
the online pass counts three times (forward, and a backward at twice its
forward) and one of the target pass once.  Recomputed operations do not count:
neither a checkpointed block's second forward nor the probability tiles the
flash backward kernels rebuild.  Elementwise work, norms, softmax, Adam and
polyak are left out of the FLOPs; Adam and polyak are what the step's bytes
are made of.
"""

from __future__ import annotations

PASSES = 4  # forward-equivalents of a product of the online pass plus the target's


def visible_pairs(t: int, block_length: int) -> int:
    """(query, key) pairs the block-causal mask lets through in ``t`` steps."""
    full, rest = divmod(t, block_length)
    pairs = block_length * block_length * full * (full + 1) // 2
    return pairs + rest * (full * block_length + rest)


def dense_macs_per_token(model: dict) -> int:
    """Multiply-accumulates of one layer's projections and router for a token."""
    h, d = model["hidden"], model["head_dim"]
    q, kv = model["q_heads"] * d, model["kv_heads"] * d
    return h * q + 2 * h * kv + q * h + h * model["experts"]


def attention_flops_forward(model: dict, batch: int) -> int:
    """One layer's ``QK^T`` and ``PV`` over the visible pairs, all heads."""
    pairs = visible_pairs(model["history_len"], model["block_length"])
    return 2 * 2 * pairs * model["head_dim"] * model["q_heads"] * batch


def attention_flops_per_step(model: dict, batch: int) -> int:
    return PASSES * model["layers"] * attention_flops_forward(model, batch)


def expert_flops_per_row(model: dict) -> int:
    """Forward FLOPs of the three grouped products for one assignment."""
    return 2 * 3 * model["hidden"] * model["expert_width"]


def expert_flops_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """``rows_*``: assignments that landed on held experts, summed over
    layers, in the online and the target pass of one step."""
    return expert_flops_per_row(model) * (3 * rows_online + rows_target)


def flops_per_step(model: dict, batch: int, rows_online: float, rows_target: float) -> float:
    tokens = batch * model["history_len"]
    embed = model["obs_dim"] * model["hidden"]
    dense = 2 * tokens * (model["layers"] * dense_macs_per_token(model) + embed)
    hq, a = model["hidden"], model["act_dim"]
    heads = 2 * batch * (
        model["num_qs"] * ((hq + a) * model["q_hidden"] + model["q_hidden"]) * (1 + 3 + 2)
        + 2 * hq * a * (1 + 3)
    )
    return (
        PASSES * dense + attention_flops_per_step(model, batch)
        + expert_flops_per_step(model, rows_online, rows_target) + heads
    )


def expert_bytes_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """Bytes the grouped products have to move at the least, at the widths
    the kernel is handed (``ops/moe.py``: operands rounded to bfloat16 before
    the product, in a convert of their own whose time is not the kernel's;
    float32 out).  Forward, a row: gate and up read ``hidden`` at 2 B and
    write ``width`` at 4 B, down the other way round.  Backward, a row: the
    three input gradients read the incoming gradient at 2 B and write at 4 B,
    the three kernel gradients read both operands at 2 B.  The held kernels
    are read at 2 B by the two forward passes and by the input gradients, and
    their gradients written at 4 B."""
    h, f = model["hidden"], model["expert_width"]
    forward = 2 * (2 * h + f) + 4 * (2 * f + h)
    backward = 2 * (2 * f + h) + 4 * (2 * h + f) + 2 * 3 * (h + f)
    lo, hi = model["experts_held"]
    kernels = 3 * (hi - lo) * h * f * model["layers"]
    return (
        (forward + backward) * rows_online + forward * rows_target
        + (2 + 2 + 2 + 4) * kernels
    )


def attention_bytes_per_step(model: dict, batch: int) -> int:
    """Bytes the attention kernels have to move at the least: q, k, v read and
    the output written once a pass, in float32."""
    t, d = model["history_len"], model["head_dim"]
    per_pass = 4 * batch * t * d * (2 * model["q_heads"] + 2 * model["kv_heads"])
    return PASSES * model["layers"] * per_pass


def trunk_params(model: dict) -> int:
    """Parameters of the trunk as held here (the fill: 20 B each)."""
    h, d, f = model["hidden"], model["head_dim"], model["expert_width"]
    lo, hi = model["experts_held"]
    layer = dense_macs_per_token(model) + 2 * h + 2 * d + 3 * (hi - lo) * h * f
    return model["layers"] * layer + model["obs_dim"] * h + h


def row_bytes(model: dict) -> int:
    """Bytes of one replay row: two histories, action, reward, done."""
    return 2 * model["history_len"] * model["obs_dim"] * 4 + model["act_dim"] * 4 + 8


def at_rest_bytes(model: dict, ring_rows: int) -> int:
    """What the cell holds between steps: the trunk, its polyak target and
    Adam's two moments (16 B a parameter; the gradient is the step's), and
    the ring."""
    return 16 * trunk_params(model) + ring_rows * row_bytes(model)


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> float:
    """The least time the v5e needs for ``flops`` and ``bytes_``."""
    return max(flops / peaks["flops_bf16"], bytes_ / peaks["hbm_bytes_per_s"])
