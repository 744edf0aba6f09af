"""Operations and bytes one SAC gradient step needs, from the sizes in a
configuration file.

The weighting is that of the repo's first benchmark script, which left the
tree at PR 31 (copied from it; it hard-coded the conv widths, this reads
them): dense multiply-accumulates times two, a backward pass at twice its
forward, the frozen critic of the policy loss at forward plus an input-only
backward.
Elementwise work, Adam and polyak are left out, and recomputed operations
do not count.
"""

from __future__ import annotations

import typing as t


def mlp_macs(sizes: t.Sequence[int]) -> int:
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def conv_tower_macs(model: dict) -> int:
    """One conv tower's forward multiply-accumulates for one frame: the
    VALID convolutions, then flatten -> dense -> ``cnn_features``."""
    h, w, c = model["frame"]
    macs = 0
    for f, k, s in zip(model["filters"], model["kernel_sizes"], model["strides"]):
        h = (h - k) // s + 1
        w = (w - k) // s + 1
        macs += h * w * f * k * k * c
        c = f
    return macs + h * w * c * model["cnn_dense_size"] + (
        model["cnn_dense_size"] * model["cnn_features"]
    )


def conv_only_macs(model: dict) -> int:
    """The convolutions alone (what the trace's convolution ops compute)."""
    h, w, c = model["frame"]
    macs = 0
    for f, k, s in zip(model["filters"], model["kernel_sizes"], model["strides"]):
        h = (h - k) // s + 1
        w = (w - k) // s + 1
        macs += h * w * f * k * k * c
        c = f
    return macs


def flops_per_step(model: dict, batch: int) -> int:
    """FLOPs of one gradient step (critic then actor update) at ``batch``."""
    hidden = list(model["hidden_sizes"])
    act = model["act_dim"]
    if model["family"] == "mlp":
        obs = model["obs_dim"]
        actor = mlp_macs([obs, *hidden]) + 2 * hidden[-1] * act
        critic = 2 * mlp_macs([obs + act, *hidden, 1])
        macs = actor + critic + 3 * critic + 3 * actor + 2 * critic
        return 2 * batch * macs
    if model["family"] == "visual":
        feat, cf = model["feature_dim"], model["cnn_features"]
        cnn = conv_tower_macs(model)
        actor = cnn + mlp_macs([feat, *hidden]) + 2 * (hidden[-1] + cf) * act
        critic_mlp = 2 * (mlp_macs([feat + act, *hidden, 1]) + (1 + cf))
        critic = 2 * cnn + critic_mlp
        # The frame is constant data: the frozen critic's input-only
        # backward never traverses its conv towers.
        macs = actor + critic + 3 * critic + 3 * actor + critic + critic_mlp
        return 2 * batch * macs
    raise ValueError(f"unknown model family {model['family']!r}")


def conv_flops_per_step(model: dict, batch: int) -> int:
    """FLOPs the convolution ops of one step compute: actor tower forward
    for the backup, target twin forward, online twin forward and backward,
    actor forward and backward, frozen twin forward."""
    if model["family"] != "visual":
        return 0
    conv = conv_only_macs(model)
    towers = 1 + 2 + 3 * 2 + 3 * 1 + 2
    return 2 * batch * conv * towers


def row_bytes(model: dict) -> int:
    """Bytes of one replay row: two observations, action, reward, done."""
    if model["family"] == "mlp":
        obs = model["obs_dim"] * 4
    else:
        h, w, c = model["frame"]
        obs = model["feature_dim"] * 4 + h * w * c
    return 2 * obs + model["act_dim"] * 4 + 8


def sample_bytes_per_step(model: dict, batch: int) -> int:
    """Bytes a step's uniform sample has to read from the ring."""
    return batch * row_bytes(model)


def conv_bytes_per_step(model: dict, batch: int) -> int:
    """Bytes the conv towers of one step have to move at the least: each
    tower pass reads its float32 input and weights and writes its output,
    layer by layer (a backward pass moves twice a forward's)."""
    if model["family"] != "visual":
        return 0
    h, w, c = model["frame"]
    per_pass = 0
    for f, k, s in zip(model["filters"], model["kernel_sizes"], model["strides"]):
        ho, wo = (h - k) // s + 1, (w - k) // s + 1
        per_pass += 4 * (batch * (h * w * c + ho * wo * f) + k * k * c * f)
        h, w, c = ho, wo, f
    return per_pass * (1 + 2 + 3 * 2 + 3 * 1 + 2)
