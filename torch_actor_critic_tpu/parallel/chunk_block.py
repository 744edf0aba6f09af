"""A window's chunk as one block of host memory and one transfer.

A transfer to the device costs the host by the call and by the shape far
more than by the byte (PERF.md section 6, PR 35), so a chunk of seven
leaves pays seven times for bytes that one flat transfer carries. The
two halves of the cure live here, and meet only in the memory itself:

- :func:`block_views` (the host half, used by ``Trainer._build_chunk``)
  allocates one block ``uint8[n_local, bytes_per_slice]`` and hands out
  one numpy view a leaf, shaped and typed as the leaf, at a byte offset
  that is a multiple of :data:`ALIGN` in every slice. A leaf lies dense
  in its slice in the axis order it is asked for (C order by default):
  rows that were staged in another memory order, as rows fetched from a
  device are, keep it, so that writing them is a plain copy and no
  transposition on the host.
- :func:`place_block` (the device half, used by
  :func:`~torch_actor_critic_tpu.parallel.dp.shard_chunk_from_local`)
  and ``PopulationLearner.place_chunk``) recognises such a chunk *from
  its leaves* (:func:`find_block`: one owner, one stride, addresses
  inside it), puts the block on the device with one ``jax.device_put``
  sharded over its leading axis, and takes the leaves out of it there in
  one small jitted program whose layout is static (slice, bitcast,
  reshape, and a transpose for a leaf that does not lie in C order),
  with the shardings the leaf-by-leaf path would have given.

**A block is written once.** XLA's CPU client aliases 64-byte-aligned
numpy memory instead of copying it, so a block that was written again
after ``device_put`` would change a chunk in flight on the backend the
tests run on. ``block_views`` therefore allocates anew on every call and
nothing here pools.

:data:`transfers` counts which way each chunk crossed, where the choice
is made.
"""

from __future__ import annotations

import functools
import math
import typing as t

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Sharding

from torch_actor_critic_tpu.telemetry import recorder as spans

__all__ = [
    "ALIGN", "block_views", "find_block", "memory_order", "place_block",
    "transfers",
]

# Every leaf starts at a multiple of this many bytes, in the block and
# in each of its slices: whole lanes of a device tile, and past the 64
# bytes from which the CPU client aliases.
ALIGN = 128

# How many chunks crossed as one block and how many leaf by leaf, in
# this process (the Trainer reports what was added since it was built).
transfers: t.Dict[str, int] = {
    "chunk/packed_transfers": 0,
    "chunk/leafwise_transfers": 0,
}

# One leaf of a layout: byte offset in a slice, shape behind the leading
# axis, dtype, and those axes from the slowest in memory to the fastest.
_Leaf = t.Tuple[int, t.Tuple[int, ...], np.dtype, t.Tuple[int, ...]]


def _round_up(n: int) -> int:
    return -(-n // ALIGN) * ALIGN


def _dense_strides(
    shape: t.Sequence[int], itemsize: int, order: t.Sequence[int]
) -> t.Tuple[int, ...]:
    """Byte strides of an array of ``shape`` that lies dense with its
    axes from the slowest to the fastest as ``order`` names them."""
    strides, step = [0] * len(shape), itemsize
    for axis in reversed(order):
        strides[axis] = step
        step *= shape[axis]
    return tuple(strides)


def block_views(
    n_local: int, specs: t.Sequence[t.Tuple[t.Any, ...]]
) -> t.List[np.ndarray]:
    """One fresh block and a view of it for each ``(shape, dtype)`` or
    ``(shape, dtype, order)`` of ``specs``: view ``i`` has shape
    ``(n_local,) + shape``, lies dense inside each slice with the axes of
    ``shape`` from slowest to fastest as ``order`` names them (C order
    where it is left out), and starts at a multiple of :data:`ALIGN`
    bytes. The contents are whatever the allocator left."""
    leaves, off = [], 0
    for shape, dtype, *order in specs:
        shape, dtype = tuple(shape), np.dtype(dtype)
        order = order[0] if order else range(len(shape))
        leaves.append((off, shape, dtype, _dense_strides(shape, dtype.itemsize, order)))
        off += _round_up(math.prod(shape) * dtype.itemsize)
    per_slice = max(off, ALIGN)
    raw = np.empty(n_local * per_slice + ALIGN, np.uint8)
    start = -raw.ctypes.data % ALIGN
    return [
        np.ndarray(
            (n_local,) + shape, dtype, buffer=raw, offset=start + off,
            strides=(per_slice,) + strides,
        )
        for off, shape, dtype, strides in leaves
    ]


def memory_order(x: np.ndarray) -> t.Tuple[int, ...]:
    """The axes of ``x`` from the slowest in memory to the fastest."""
    return tuple(sorted(range(x.ndim), key=lambda a: -abs(x.strides[a])))


def _owner(x: np.ndarray):
    while isinstance(x.base, np.ndarray):
        x = x.base
    return x


@functools.lru_cache(maxsize=None)
def _bitcastable(dtype: np.dtype) -> bool:
    """Whether the device can take this dtype out of bytes and get what
    ``device_put`` of the leaf would have given: numbers only, and only
    at the width jax keeps them (a float64 leaf is *converted* on its
    way in when x64 is off, which a bitcast is not)."""
    return dtype.kind in "fiu" and jax.dtypes.canonicalize_dtype(dtype) == dtype


def find_block(
    leaves: t.Sequence[t.Any],
) -> t.Optional[t.Tuple[np.ndarray, t.Tuple[_Leaf, ...]]]:
    """The block ``leaves`` are views of, as ``uint8[n, bytes_per_slice]``,
    and where each leaf lies in a slice of it; ``None`` where the leaves
    are anything else (separately allocated, not numpy, of a dtype the
    device cannot take out of bytes, not dense inside a slice)."""
    if not leaves or not all(
        isinstance(x, np.ndarray) and x.ndim >= 1 for x in leaves
    ):
        return None
    owner = _owner(leaves[0])
    n = leaves[0].shape[0]
    if n < 1 or not owner.flags.c_contiguous:
        return None
    spans = []
    for x in leaves:
        row = x[0]
        order = memory_order(row)
        dense = all(
            size == 1 or stride == want
            for size, stride, want in zip(
                row.shape, row.strides,
                _dense_strides(row.shape, x.dtype.itemsize, order),
            )
        )
        if (
            _owner(x) is not owner
            or x.shape[0] != n
            or not _bitcastable(x.dtype)
            or not dense
        ):
            return None
        spans.append((x.ctypes.data, row.nbytes, order))
    start = min(a for a, _, _ in spans)
    extent = max(a + nb for a, nb, _ in spans) - start
    strides = {x.strides[0] for x in leaves} if n > 1 else {_round_up(extent)}
    if len(strides) != 1:
        return None
    (per_slice,) = strides
    begin = start - owner.ctypes.data
    if (
        per_slice < extent
        or begin < 0
        or begin + n * per_slice > owner.nbytes
    ):
        return None
    block = np.ndarray(
        (n, per_slice), np.uint8, buffer=owner, offset=begin,
    )
    layout = tuple(
        (a - start, x.shape[1:], x.dtype, order)
        for (a, _, order), x in zip(spans, leaves)
    )
    if any(off % dtype.itemsize for off, _, dtype, _ in layout):
        return None
    return block, layout


@functools.lru_cache(maxsize=32)
def _unpack_program(
    block_sharding: t.Optional[Sharding],
    layout: t.Tuple[_Leaf, ...],
    treedef: t.Any,
    shardings: t.Optional[t.Tuple[Sharding, ...]],
):
    """The jitted program that takes the leaves of ``layout`` out of a
    block placed as ``block_sharding`` and returns them as ``treedef``,
    each placed as its own entry of ``shardings`` (``None``: wherever
    the block is). Cached: one program a chunk structure."""

    def unpack(block):
        n = block.shape[0]
        leaves = []
        for off, shape, dtype, order in layout:
            count = math.prod(shape)
            piece = lax.slice(block, (0, off), (n, off + count * dtype.itemsize))
            if dtype.itemsize > 1:
                piece = piece.reshape((n, count, dtype.itemsize))
            # as it lies in memory, then the axes put back in their places
            leaf = lax.bitcast_convert_type(piece, dtype).reshape(
                (n,) + tuple(shape[a] for a in order)
            )
            if order != tuple(range(len(shape))):
                back = sorted(range(len(order)), key=order.__getitem__)
                leaf = lax.transpose(leaf, (0,) + tuple(1 + a for a in back))
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if block_sharding is None:
        return jax.jit(unpack)
    return jax.jit(
        unpack,
        in_shardings=block_sharding,
        out_shardings=jax.tree_util.tree_unflatten(treedef, list(shardings)),
    )


def place_block(
    chunk: t.Any,
    shardings: t.Any = None,
    block_sharding: t.Optional[Sharding] = None,
) -> t.Optional[t.Any]:
    """``chunk`` on the device, each leaf placed as its entry of
    ``shardings`` (a tree like ``chunk``), by ONE transfer: the block its
    leaves are views of (:func:`find_block`) goes over as
    ``block_sharding`` shards its leading axis and the leaves are taken
    out of it there. With neither sharding the block goes where
    ``jnp.asarray`` would put it and the leaves stay there, uncommitted
    like it. ``None`` where the leaves are no such block or the sharding
    reaches past this process: the caller then places them leaf by leaf.
    Either way :data:`transfers` counts it. The block's crossing is the
    span ``place_chunk/transfer``; the dispatch of the program that takes
    the leaves out of it is ``place_chunk/unpack``."""
    leaves, treedef = jax.tree_util.tree_flatten(chunk)
    local = block_sharding is None or block_sharding.is_fully_addressable
    found = find_block(leaves) if local else None
    if found is None:
        transfers["chunk/leafwise_transfers"] += 1
        return None
    transfers["chunk/packed_transfers"] += 1
    block, layout = found
    program = _unpack_program(
        block_sharding, layout, treedef,
        None if block_sharding is None
        else tuple(treedef.flatten_up_to(shardings)),
    )
    with spans.span(spans.PLACE_TRANSFER):
        if block_sharding is None:
            on_device = jnp.asarray(block)
        else:
            on_device = jax.device_put(block, block_sharding)
    with spans.span(spans.PLACE_UNPACK):
        return program(on_device)
