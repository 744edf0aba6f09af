"""Seeded weights for the ``nemotron_h`` history trunk, in the program's
layout, from its shapes alone: ``trunk_weights.py``'s counterpart.

As there, everything drawn is uniform in ``+-1/sqrt(fan_in)``, the torch
default (a projection has a kernel and no bias; the expert kernels' leading
axis and the Q heads' ensemble axis are no fan-in; a depthwise convolution's
fan-in is its taps, for its bias too), and a norm's weight is 1.  What the
family initialises by a convention of its own is drawn by that convention:

- ``dt_bias``: the inverse softplus of a step size log-uniform in
  ``[time_step_min, time_step_max]`` = [0.001, 0.1], never under
  ``time_step_floor`` 1e-4 (the three keys of the published config shape
  nothing else);
- ``A_log``: the logarithm of ``A`` uniform in [1, 16] (Mamba-2's range);
- ``D``: 1;
- ``router_bias``, the router's correction bias: training starts it at 0 and
  moves it by the experts' load, not by a gradient; a checkpoint's is not 0,
  so that the choice by ``score + bias`` differs from the choice by score:
  drawn uniform in +-0.02 (the scores' own spread at these weights is about
  +-0.14 round 0.5).
"""

from __future__ import annotations

import math
import typing as t

import jax
import jax.numpy as jnp

EXPERT_KERNELS = ("w_up", "w_down")
STACKED = ("ensemble",)
ONES = ("weight", "norm_weight", "D")
DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 0.1, 1e-4
BIAS_SPREAD = 0.02


def _dt_bias(key, shape, dtype):
    span = math.log(DT_MAX) - math.log(DT_MIN)
    dt = jnp.exp(jax.random.uniform(key, shape, dtype) * span + math.log(DT_MIN))
    dt = jnp.maximum(dt, DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


BY_CONVENTION = {
    "dt_bias": _dt_bias,
    "A_log": lambda key, shape, dtype: jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0)),
    "router_bias": lambda key, shape, dtype: jax.random.uniform(
        key, shape, dtype, -BIAS_SPREAD, BIAS_SPREAD
    ),
}


def init_params(key, abstract: t.Any):
    counter = [0]

    def next_key():
        counter[0] += 1
        return jax.random.fold_in(key, counter[0])

    def uniform(shape, dtype, fan_in):
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(next_key(), shape, dtype, -bound, bound)

    def walk(name, node, lead: int, taps: int = 1):
        if not isinstance(node, dict):
            if name in ONES:
                return jnp.ones(node.shape, node.dtype)
            if name in BY_CONVENTION:
                return BY_CONVENTION[name](next_key(), node.shape, node.dtype)
            if name == "conv_bias":
                return uniform(node.shape, node.dtype, taps)
            skip = lead + (1 if name in EXPERT_KERNELS else 0)
            return uniform(node.shape, node.dtype, math.prod(node.shape[skip:-1]))
        if "kernel" in node and "bias" in node:
            fan_in = math.prod(node["kernel"].shape[lead:-1])
            return {
                "kernel": uniform(node["kernel"].shape, node["kernel"].dtype, fan_in),
                "bias": uniform(node["bias"].shape, node["bias"].dtype, fan_in),
            }
        taps = node["conv_kernel"].shape[0] if "conv_kernel" in node else 1
        return {
            child: walk(child, sub, lead + (1 if child in STACKED else 0), taps)
            for child, sub in sorted(node.items())
        }

    return walk("", abstract, 0)


def seeded_params(sac, example_obs, key):
    """The policy head's and the critic's (trunk and Q heads) weights from
    ``key``, made on the device in one jitted call."""
    abstract = jax.eval_shape(sac.init_state, jax.random.key(0), example_obs)

    def make(k):
        ka, kc = jax.random.split(k)
        return (
            init_params(ka, abstract.actor_params),
            init_params(kc, abstract.critic_params),
        )

    return jax.jit(make)(key)
