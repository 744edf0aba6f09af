"""Seeded inputs and weights, made on the device in bulk.

Everything is a pure function of ``--seed``: the same seed gives the same
rings, chunks and weights (on the same backend).  Bulk data comes from the
``rbg`` generator, which fills gigabytes in about a second on the chip; rings
are written slab by slab into one buffer so that the temporaries stay small.

A ring leaf has two shapes.  The *transition's* is what the env's spec gives
one row of it (a frame ``(H, W, C)``, a reward ``()``): the seeded contents
are drawn in it and the reference reads it.  The *stored* one is the
program's business, taken from its own abstract ring (``(capacity, H, W, C)``
today; ``(capacity, H*W*C)`` is as good): rows are written reshaped to it and
read back by index along the rows axis, then reshaped to the transition's.
So how a program keeps its ring changes no seeded row.
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


@functools.partial(
    jax.jit, static_argnames=("shape", "dtype", "kind", "slab", "sharding", "row")
)
def fill_leaf(key, shape, dtype, kind: str, slab: int, sharding=None, row=None):
    """A random array of ``shape`` whose axis 1 (the ring's rows) is written
    ``slab`` rows at a time.  Every row is drawn in the shape ``row`` (the
    stored one, ``shape[2:]``, where none is given) and written reshaped."""
    rows = shape[1]
    row = tuple(shape[2:]) if row is None else row

    def draw(key, n):
        return _draw(key, (shape[0], n) + row, dtype, kind).reshape(
            (shape[0], n) + tuple(shape[2:])
        )

    if rows <= slab:
        return draw(key, rows)
    out = jnp.zeros(shape, dtype)
    if sharding is not None:
        out = jax.lax.with_sharding_constraint(out, sharding)

    def body(i, out):
        start = jnp.minimum(i * slab, rows - slab)
        part = draw(jax.random.fold_in(key, i), slab)
        return jax.lax.dynamic_update_slice(out, part, (0, start) + (0,) * (len(shape) - 2))

    return jax.lax.fori_loop(0, -(-rows // slab), body, out)


LEAF_KINDS = {"actions": "action", "done": "done"}


def transition_rows(stored: t.Any, obs_spec: t.Any, act_dim: int) -> t.Any:
    """One transition in the shapes the env's spec gives it, in the container
    (and with the dtypes) of ``stored``, the program's abstract ring."""
    def like(spec, leaves):
        return jax.tree_util.tree_map(
            lambda sp, leaf: jax.ShapeDtypeStruct(tuple(sp.shape), leaf.dtype), spec, leaves
        )

    scalar = lambda leaf: jax.ShapeDtypeStruct((), leaf.dtype)  # noqa: E731
    return stored.replace(
        states=like(obs_spec, stored.states),
        actions=jax.ShapeDtypeStruct((act_dim,), stored.actions.dtype),
        rewards=scalar(stored.rewards),
        next_states=like(obs_spec, stored.next_states), done=scalar(stored.done),
    )


def as_rows(tree: t.Any, rows: t.Any, lead: int) -> t.Any:
    """Leaves read from a ring, ``lead`` leading axes and then a stored row,
    with each row in its transition's shape (``rows``)."""
    return jax.tree_util.tree_map(
        lambda x, row: x.reshape(x.shape[:lead] + tuple(row.shape)), tree, rows
    )


def fill_transitions(
    key, abstract: t.Any, slab: int = 8192, shardings: t.Any = None, rows: t.Any = None
):
    """Random transitions shaped like ``abstract`` (a pytree of
    ``ShapeDtypeStruct`` with leading axes ``(streams, rows, ...)``): uint8
    leaves are uniform bytes, actions uniform in ``[-1, 1]``, ``done`` is 1 in
    a hundredth of the rows, everything else standard normal.  ``rows``
    (:func:`transition_rows`) is the shape each leaf's rows are drawn in,
    where ``abstract`` is a ring as the program stores it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    sh = (
        jax.tree_util.tree_leaves(shardings) if shardings is not None
        else [None] * len(leaves)
    )
    row_of = (
        [tuple(r.shape) for r in jax.tree_util.tree_leaves(rows)] if rows is not None
        else [None] * len(leaves)
    )
    out = []
    for i, ((path, leaf), s) in enumerate(zip(leaves, sh)):
        name = jax.tree_util.keystr(path)
        if row_of[i] is not None and math.prod(row_of[i]) != math.prod(leaf.shape[2:]):
            raise ValueError(
                f"ring leaf {name} stores rows of {tuple(leaf.shape[2:])}: not the "
                f"{row_of[i]} of a transition, reshaped"
            )
        kind = next((k for tag, k in LEAF_KINDS.items() if tag in name), "normal")
        if leaf.dtype == jnp.uint8:
            kind = "frame"
        out.append(
            fill_leaf(
                jax.random.fold_in(key, i), tuple(leaf.shape), leaf.dtype, kind,
                min(slab, leaf.shape[1]), s, row_of[i],
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
