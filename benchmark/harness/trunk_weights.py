"""Seeded weights for the SDAR history trunk, in the program's layout, from
its shapes alone.

``data.init_params`` knows ``{kernel, bias}`` pairs only.  Here a projection
has a kernel and no bias, a norm's ``weight`` starts at 1 (as published
checkpoints initialise it), the router is a bare ``(hidden, experts)`` matrix,
and the expert kernels ``w_gate`` / ``w_up`` / ``w_down`` carry a leading
expert axis that is no fan-in (nor is the Q heads' leading ensemble axis).
Everything drawn is uniform in ``+-1/sqrt(fan_in)``, the torch default.
"""

from __future__ import annotations

import math
import typing as t

import jax
import jax.numpy as jnp

EXPERT_KERNELS = ("w_gate", "w_up", "w_down")
STACKED = ("ensemble",)


def init_params(key, abstract: t.Any):
    counter = [0]

    def uniform(shape, dtype, fan_in):
        counter[0] += 1
        bound = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(
            jax.random.fold_in(key, counter[0]), shape, dtype, -bound, bound
        )

    def walk(name, node, lead: int):
        if not isinstance(node, dict):
            if name == "weight":
                return jnp.ones(node.shape, node.dtype)
            skip = lead + (1 if name in EXPERT_KERNELS else 0)
            fan_in = math.prod(node.shape[skip:-1])
            return uniform(node.shape, node.dtype, fan_in)
        if "kernel" in node and "bias" in node:
            fan_in = math.prod(node["kernel"].shape[lead:-1])
            return {
                "kernel": uniform(node["kernel"].shape, node["kernel"].dtype, fan_in),
                "bias": uniform(node["bias"].shape, node["bias"].dtype, fan_in),
            }
        return {
            child: walk(child, sub, lead + (1 if child in STACKED else 0))
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
