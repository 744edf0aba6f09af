"""Operations and bytes one SAC gradient step on the ``laguna`` history trunk
needs, from the sizes in its configuration file and the counted assignments:
``flops_trunk.py``'s counterpart, with its conventions.

A step makes two trunk passes (target on ``next_states``, online on
``states``) and one backward pass of the online one, so a product of the
online pass counts three times and one of the target pass once (``PASSES``
forward-equivalents).  Recomputed operations do not count, though the cell
recomputes every block, nor do the probability tiles the flash backward
kernels rebuild.  Elementwise work, norms, rotary, the gates' sigmoid,
softmax, Adam and polyak are left out of the FLOPs.

Attention is counted by what each layer's own mask lets through, whatever
computes it: a full layer's causal pairs, a sliding layer's pairs inside its
window, times the query heads the layer's kind holds.  A kernel that visits
the causal triangle on a sliding layer does more work for the same count.
"""

from __future__ import annotations

from benchmark.harness.flops_trunk import roofline_seconds, row_bytes  # noqa: F401

PASSES = 4
WINDOWED, DENSE = "Ww", "fw"


def q_heads(model: dict, kind: str) -> int:
    return model["window_q_heads"] if kind in WINDOWED else model["q_heads"]


def visible_pairs(t: int, window: int | None = None) -> int:
    """(query, key) pairs of ``t`` steps under the causal mask, inside the
    ``window`` latest positions (the query's own among them) if there is one."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def layer_pairs(model: dict, kind: str) -> int:
    window = model["window"] if kind in WINDOWED else None
    return visible_pairs(model["history_len"], window)


def attention_macs_per_token(model: dict, kind: str) -> int:
    """A layer's four projections and its gate's."""
    h, d, n = model["hidden"], model["head_dim"], q_heads(model, kind)
    return h * n * d + 2 * h * model["kv_heads"] * d + n * d * h + h * n


def ffn_macs_per_token(model: dict, kind: str) -> int:
    """What every token passes of a layer's feed-forward: the dense one, or
    the router and the shared expert."""
    h = model["hidden"]
    if kind in DENSE:
        return 3 * h * model["dense_width"]
    return h * model["experts"] + 3 * h * model["shared_expert_width"]


def dense_macs_per_token(model: dict) -> int:
    return sum(
        attention_macs_per_token(model, kind) + ffn_macs_per_token(model, kind)
        for kind in model["pattern"]
    )


def attention_flops_forward(model: dict, batch: int, kind: str) -> int:
    """One layer's ``QK^T`` and ``PV`` over its visible pairs, its heads."""
    return 2 * 2 * layer_pairs(model, kind) * model["head_dim"] * q_heads(model, kind) * batch


def attention_flops_per_step(model: dict, batch: int, kinds: str | None = None) -> int:
    kinds = model["pattern"] if kinds is None else kinds
    return PASSES * sum(attention_flops_forward(model, batch, kind) for kind in kinds)


def attention_bytes_per_step(model: dict, batch: int, kinds: str | None = None) -> int:
    """Bytes the attention kernels have to move at the least: q, k, v read and
    the output written once a pass, in float32."""
    kinds = model["pattern"] if kinds is None else kinds
    t, d = model["history_len"], model["head_dim"]
    heads = sum(2 * q_heads(model, kind) + 2 * model["kv_heads"] for kind in kinds)
    return PASSES * 4 * batch * t * d * heads


def expert_layers(model: dict) -> int:
    return sum(kind not in DENSE for kind in model["pattern"])


def expert_flops_per_row(model: dict) -> int:
    """Forward FLOPs of the three grouped products for one assignment."""
    return 2 * 3 * model["hidden"] * model["expert_width"]


def expert_flops_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """``rows_*``: assignments that landed on held experts, summed over the
    expert layers, in the online and the target pass of one step."""
    return expert_flops_per_row(model) * (3 * rows_online + rows_target)


def expert_bytes_per_step(model: dict, rows_online: float, rows_target: float) -> float:
    """``flops_trunk.expert_bytes_per_step``'s count (operands bfloat16, float32
    out; the held kernels read at 2 B by the two forward passes and the input
    gradients, their gradients written at 4 B), over this stack's expert
    layers."""
    h, f = model["hidden"], model["expert_width"]
    forward = 2 * (2 * h + f) + 4 * (2 * f + h)
    backward = 2 * (2 * f + h) + 4 * (2 * h + f) + 2 * 3 * (h + f)
    lo, hi = model["experts_held"]
    kernels = 3 * (hi - lo) * h * f * expert_layers(model)
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
        + expert_flops_per_step(model, rows_online, rows_target) + heads
    )


def layer_params(model: dict, kind: str) -> int:
    """Parameters of one block as held here, its two norms included."""
    h = model["hidden"]
    held = 0
    if kind not in DENSE:
        lo, hi = model["experts_held"]
        held = 3 * (hi - lo) * h * model["expert_width"]
    return (
        attention_macs_per_token(model, kind) + ffn_macs_per_token(model, kind) + held + 2 * h
    )


def trunk_params(model: dict) -> int:
    """Parameters of the trunk as held here (the fill: 20 B each)."""
    layers = sum(layer_params(model, kind) for kind in model["pattern"])
    return layers + model["obs_dim"] * model["hidden"] + model["hidden"]


def at_rest_bytes(model: dict, ring_rows: int) -> int:
    """The trunk, its polyak target and Adam's two moments (16 B a
    parameter; the gradient is the step's), and the ring."""
    return 16 * trunk_params(model) + ring_rows * row_bytes(model)
