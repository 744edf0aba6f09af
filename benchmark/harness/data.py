"""Seeded inputs and weights, made on the device in bulk.

Everything is a pure function of ``--seed``: the same seed gives the same
rings, chunks and weights (on the same backend).  Bulk data comes from the
``rbg`` generator, which fills gigabytes in about a second on the chip; rings
are written slab by slab into one buffer so that the temporaries stay small.
"""

from __future__ import annotations

import functools
import math
import typing as t

import jax
import jax.numpy as jnp
import numpy as np


def _key(seed: int, stream: int, impl: str | None):
    """``seed`` may exceed 32 signed bits: its halves are folded in apart."""
    seed = int(seed)
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF), impl=impl)
    key = jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))
    return jax.random.fold_in(key, stream)


def data_key(seed: int, stream: int):
    """A key for bulk data (the ``rbg`` generator)."""
    return _key(seed, stream, "rbg")


def state_key(seed: int, stream: int = 0):
    """A key of the program's default kind (weights, the learner's stream)."""
    return _key(seed, stream, None)


def _draw(key, shape, dtype, kind: str):
    if kind == "frame":
        return jax.random.bits(key, shape, jnp.uint8)
    if kind == "action":
        return jax.random.uniform(key, shape, dtype, -1.0, 1.0)
    if kind == "done":
        return (jax.random.uniform(key, shape) < 0.01).astype(dtype)
    return jax.random.normal(key, shape, dtype)


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "kind", "slab", "sharding"))
def fill_leaf(key, shape, dtype, kind: str, slab: int, sharding=None):
    """A random array of ``shape`` whose axis 1 (the ring's rows) is written
    ``slab`` rows at a time."""
    rows = shape[1]
    if rows <= slab:
        return _draw(key, shape, dtype, kind)
    out = jnp.zeros(shape, dtype)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)

    def body(i, out):
        start = jnp.minimum(i * slab, rows - slab)
        part = _draw(
            jax.random.fold_in(key, i), (shape[0], slab) + tuple(shape[2:]), dtype, kind
        )
        return jax.lax.dynamic_update_slice(out, part, (0, start) + (0,) * (len(shape) - 2))

    return jax.lax.fori_loop(0, -(-rows // slab), body, out)


LEAF_KINDS = {"actions": "action", "done": "done"}


def fill_transitions(key, abstract: t.Any, slab: int = 8192, shardings: t.Any = None):
    """Random transitions shaped like ``abstract`` (a pytree of
    ``ShapeDtypeStruct`` with leading axes ``(streams, rows, ...)``): uint8
    leaves are uniform bytes, actions uniform in ``[-1, 1]``, ``done`` is 1 in
    a hundredth of the rows, everything else standard normal."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    sh = (
        jax.tree_util.tree_leaves(shardings) if shardings is not None
        else [None] * len(leaves)
    )
    out = []
    for i, ((path, leaf), s) in enumerate(zip(leaves, sh)):
        name = jax.tree_util.keystr(path)
        kind = next((k for tag, k in LEAF_KINDS.items() if tag in name), "normal")
        if leaf.dtype == jnp.uint8:
            kind = "frame"
        out.append(
            fill_leaf(
                jax.random.fold_in(key, i), tuple(leaf.shape), leaf.dtype, kind,
                min(slab, leaf.shape[1]), s,
            )
        )
    return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(abstract), out)


def _is_layer(node) -> bool:
    return isinstance(node, dict) and "kernel" in node and "bias" in node


def init_params(key, abstract: t.Any, stacked: t.Sequence[str] = ("ensemble",)):
    """Weights for a parameter tree of the program's layout, from its shapes
    alone: every ``{kernel, bias}`` pair is drawn uniformly in
    ``+-1/sqrt(fan_in)`` (the torch default the source uses).  A layer under a
    key in ``stacked`` carries a leading ensemble axis that is no fan-in."""
    counter = [0]

    def walk(node, lead: int):
        if _is_layer(node):
            k = node["kernel"]
            fan_in = math.prod(k.shape[lead:-1])
            bound = 1.0 / math.sqrt(fan_in)
            counter[0] += 1
            kk, kb = jax.random.split(jax.random.fold_in(key, counter[0]))
            return {
                "kernel": jax.random.uniform(kk, k.shape, k.dtype, -bound, bound),
                "bias": jax.random.uniform(kb, node["bias"].shape, k.dtype, -bound, bound),
            }
        return {
            name: walk(child, lead + (1 if name in stacked else 0))
            for name, child in sorted(node.items())
        }

    return walk(abstract, 0)
