"""Operations and bytes one SAC gradient step on the ``nemotron_h`` history
trunk needs, from the sizes in its configuration file and the counted
assignments: ``flops_trunk.py``'s counterpart, with its conventions.

A step makes two trunk passes (target on ``next_states``, online on
``states``) and one backward pass of the online one, so a product of the
online pass counts three times and one of the target pass once (``PASSES``
forward-equivalents).  Recomputed operations do not count, though the cell
recomputes every block.  Elementwise work, norms, the convolution's four taps,
Adam and polyak are left out of the FLOPs.

The recurrence is counted by what it needs whatever computes it: a head's
step multiplies its state ``(head_dim, state)`` by the decay, adds the outer
product ``dt x B^T`` and contracts it with ``C``: ``5 * head_dim * state``
operations (the chunked form's four products over the causal half of a chunk
come to the same within 3%); its bytes are its operands read and its output
written once, float32 as the convolution hands them over.
"""

from __future__ import annotations

from benchmark.harness.flops_trunk import roofline_seconds, row_bytes, visible_pairs  # noqa: F401

PASSES = 4


def _ssm(model: dict) -> tuple[int, int]:
    """(inner width, the width of ``B`` or ``C``) of the held heads."""
    return model["ssm_heads"] * model["ssm_head_dim"], model["ssm_groups"] * model["ssm_state"]


def mixer_macs_per_token(model: dict, kind: str) -> int:
    """Multiply-accumulates of one layer's dense products for a token: the
    projections, the router, the latent projections, the shared expert."""
    h = model["hidden"]
    if kind == "M":
        inner, bc = _ssm(model)
        return h * (2 * inner + 2 * bc + model["ssm_heads"]) + inner * h
    if kind == "*":
        q, kv = model["q_heads"] * model["head_dim"], model["kv_heads"] * model["head_dim"]
        return h * q + 2 * h * kv + q * h
    return (
        h * model["experts"] + 2 * h * model["expert_latent"]
        + 2 * h * model["shared_expert_width"]
    )


def dense_macs_per_token(model: dict) -> int:
    return sum(mixer_macs_per_token(model, kind) for kind in model["pattern"])


def attention_flops_per_step(model: dict, batch: int) -> int:
    pairs = visible_pairs(model["history_len"], 1)
    one = 2 * 2 * pairs * model["head_dim"] * model["q_heads"] * batch
    return PASSES * model["pattern"].count("*") * one


def scan_flops_forward(model: dict, batch: int) -> int:
    """One state-space layer's recurrence over a batch of histories."""
    per_head_step = 5 * model["ssm_head_dim"] * model["ssm_state"]
    return per_head_step * model["ssm_heads"] * model["history_len"] * batch


def scan_bytes_forward(model: dict, batch: int) -> int:
    inner, bc = _ssm(model)
    per_token = 4 * (inner + 2 * bc + model["ssm_heads"] + inner)  # x, B, C, dt in; y out
    return per_token * model["history_len"] * batch


def scan_flops_per_step(model: dict, batch: int) -> int:
    return PASSES * model["pattern"].count("M") * scan_flops_forward(model, batch)


def scan_bytes_per_step(model: dict, batch: int) -> int:
    """A backward pass reads the operands and the output's gradient and
    writes the operands' gradients: twice a forward pass's bytes."""
    return PASSES * model["pattern"].count("M") * scan_bytes_forward(model, batch)


def expert_flops_per_row(model: dict) -> int:
    """Forward FLOPs of the two grouped products for one assignment."""
    return 2 * 2 * model["expert_latent"] * model["expert_width"]


def expert_flops_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """``rows_*``: assignments that landed on held experts, summed over the
    expert layers, in the online and the target pass of one step."""
    return expert_flops_per_row(model) * (3 * rows_online + rows_target)


def expert_bytes_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """Bytes the grouped products have to move at the least, at the widths the
    kernel is handed (operands bfloat16, float32 out).  Forward, a row: up
    reads ``latent`` at 2 B and writes ``width`` at 4 B, down the other way
    round.  Backward, a row: the two input gradients read at 2 B and write at
    4 B, the two kernel gradients read both operands at 2 B.  The held kernels
    are read at 2 B by the two forward passes and by the input gradients, and
    their gradients written at 4 B."""
    lat, f = model["expert_latent"], model["expert_width"]
    forward = 6 * (lat + f)
    backward = 6 * (lat + f) + 2 * 2 * (lat + f)
    lo, hi = model["experts_held"]
    kernels = 2 * (hi - lo) * lat * f * model["pattern"].count("E")
    return (
        (forward + backward) * rows_online + forward * rows_target
        + (2 + 2 + 2 + 4) * kernels
    )


def flops_per_step(model: dict, batch: int, rows_online: float, rows_target: float) -> float:
    tokens = batch * model["history_len"]
    embed = model["obs_dim"] * model["hidden"]
    dense = 2 * tokens * (dense_macs_per_token(model) + embed)
    hq, a = model["hidden"], model["act_dim"]
    heads = 2 * batch * (
        model["num_qs"] * ((hq + a) * model["q_hidden"] + model["q_hidden"]) * (1 + 3 + 2)
        + 2 * hq * a * (1 + 3)
    )
    return (
        PASSES * dense + attention_flops_per_step(model, batch)
        + scan_flops_per_step(model, batch)
        + expert_flops_per_step(model, rows_online, rows_target) + heads
    )


def mixer_params(model: dict, kind: str) -> int:
    """Parameters of one layer as held here, its norm included."""
    h = model["hidden"]
    if kind == "M":
        inner, bc = _ssm(model)
        conv = (model["ssm_conv"] + 1) * (inner + 2 * bc)
        return mixer_macs_per_token(model, kind) + conv + 3 * model["ssm_heads"] + inner + h
    if kind == "*":
        return mixer_macs_per_token(model, kind) + h
    lo, hi = model["experts_held"]
    routed = 2 * (hi - lo) * model["expert_latent"] * model["expert_width"]
    return mixer_macs_per_token(model, kind) + model["experts"] + routed + h


def trunk_params(model: dict) -> int:
    """Parameters of the trunk as held here (the fill: 20 B each)."""
    layers = sum(mixer_params(model, kind) for kind in model["pattern"])
    return layers + model["obs_dim"] * model["hidden"] + model["hidden"]


def at_rest_bytes(model: dict, ring_rows: int) -> int:
    """The trunk, its polyak target and Adam's two moments (16 B a
    parameter; the gradient is the step's), and the ring."""
    return 16 * trunk_params(model) + ring_rows * row_bytes(model)
