"""HBM-resident uniform-sampling ring replay buffer.

Capability twin of the reference's host-side NumPy ring buffers
(``ReplayBuffer``, ref ``buffer/replay_buffer.py:17-54``, and
``VisualReplayBuffer``, ref ``buffer/visual_replay_buffer.py:21-66``),
re-designed for TPU:

- **Device-resident**: the ring lives in HBM as preallocated
  ``jax.Array`` leaves of a :class:`~torch_actor_critic_tpu.core.types.BufferState`;
  ``push``/``sample`` are pure jittable functions, so sampling happens
  *inside* the fused SAC update step with zero host<->device traffic per
  gradient step (the reference converts NumPy->torch on every sample,
  ref ``replay_buffer.py:47-54``).
- **One generic implementation**: observations are pytrees, so the
  visual buffer is the same code over ``MultiObservation`` leaves —
  no subclass that overrides everything (ref
  ``visual_replay_buffer.py:21``: "subclasses ReplayBuffer but
  overrides everything"). Frames are stored **uint8** (4x less HBM than
  the reference's float object-arrays; 1e6 64x64x3 frames = 12 GB fp32
  vs 3 GB u8) and cast to float inside the model.
- **Chunked stores**: the host env loop accumulates ``update_every``
  transitions and pushes them in one call (one dispatch per burst
  instead of the reference's per-step ``store``,
  ref ``sac/algorithm.py:249``). A chunk is written as contiguous,
  in-place updates of the donated ring (``dynamic_update_slice``): one
  window of ``n`` rows at the write pointer and one at row 0, so a
  chunk that wraps needs no other path and no shape depends on data.
  Not a scatter over ``(ptr + arange(n)) % capacity``: for one, XLA:TPU
  relays every whole ring leaf into a layout of the scatter's own and
  back, once a window, which was 80% of the visual burst's device time
  and 95% of the population programs' (PERF.md, PR 24 and PR 25).
- **A row rests in the form the gather reads and the push writes**
  (:func:`stored_row_shape`). The TPU lays an array out in tiles of
  8 x 128 words over its two minor axes, and no axis of a
  ``(64, 64, 3)`` uint8 picture fills the 128 lanes: left in that
  shape, a frame leaf ``(capacity, 64, 64, 3)`` rests with the *rows*
  in the lanes, the gather wants the picture there, and the compiler
  bridged the two with a copy of each frame leaf a window, padded
  twofold: 1,216 of the visual burst's 1,709 us a step and 9.4 GiB of
  its scratch (PERF.md, PR 28). So a picture whose bytes are whole
  tiles is stored ``(capacity, bytes / 128, 128)``: tiles of its own,
  row after row, nothing padded. ``push`` reshapes the chunk it is
  given (pinned row-major, see :func:`_as_stored`) and updates in
  place; ``sample`` gathers whole rows as they lie; the learner shapes
  the sampled batch back (:func:`as_observations`). The burst then
  holds no instruction that passes over a frame leaf
  (``tests/test_chip_compile.py``): 426 us a step, of which the gather
  37 and the push 4, and 0.26 GiB of scratch (PERF.md, PR 30). Every
  other row rests in its own shape, as before: a vector narrower than a
  tile has no unpadded form with the row contiguous, the compiler lays
  it rows-in-lanes and converts what the gather reads once a window
  (14 us a step for the visual burst's feature leaves; half of the
  fused population epoch's sampling: PERF.md section 7 has what was
  tried).
- **Sampling is uniform with replacement** (``randint`` + ``take``).
  The reference samples *without* replacement via ``random.sample``
  (ref ``replay_buffer.py:46``); at 1e6-slot buffers and batch 64 the
  collision probability per batch is ~2e-3, a deliberate,
  XLA-friendly deviation (SURVEY.md §7 item 3). Before the buffer is
  full, indices are drawn over ``[0, size)`` exactly like the
  reference's ``range(self.size)``.

Donation: callers should jit ``push`` with ``donate_argnums=(0,)`` (the
trainer does) so XLA updates the ring in place instead of copying the
full 1e6-slot arrays per store.
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.custom_batching import custom_vmap
from jax.experimental.layout import Layout, with_layout_constraint

from torch_actor_critic_tpu.buffer.striped import (
    StripedBufferState,
    push_striped,
    sample_striped,
)
from torch_actor_critic_tpu.core.types import Batch, BufferState, MultiObservation
from torch_actor_critic_tpu.telemetry import scopes


LANES = 128  # the minor axis of a TPU tile, in elements
SUBLANES = 8  # its other axis, in 32-bit words


def stored_row_shape(row_shape: t.Sequence[int], dtype) -> t.Tuple[int, ...]:
    """The shape one row of a ring leaf rests in (module docstring).

    A row of three or more axes (a picture) whose elements fill whole
    tiles (8 x 128 words of 32 bits: 4,096 uint8, 1,024 float32) rests
    as ``(elements / 128, 128)``: tiles of its own, row after row.
    Every other row rests in its own shape.
    """
    row_shape = tuple(int(d) for d in row_shape)
    elements = int(np.prod(row_shape))
    tile = LANES * SUBLANES * max(4 // jnp.dtype(dtype).itemsize, 1)
    if len(row_shape) >= 3 and elements % tile == 0:
        return (elements // LANES, LANES)
    return row_shape


def _zeros_like_spec(capacity: int, spec: t.Any) -> t.Any:
    """Build zeroed ring arrays from a pytree of (shape, dtype) specs.

    A spec leaf is anything with ``.shape`` and ``.dtype`` (e.g. a
    ``jax.ShapeDtypeStruct`` or a concrete example array).
    """
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(
            (capacity,) + stored_row_shape(s.shape, s.dtype), s.dtype
        ),
        spec,
    )


def estimate_buffer_bytes(capacity: int, obs_spec: t.Any, act_dim: int) -> int:
    """HBM bytes one replay shard of ``capacity`` transitions occupies.

    Two observation copies (state, next_state) + action + reward + done
    per row — the planning number behind the trainer's HBM-budget
    warning (1e6 visual transitions at the wall-runner geometry come to
    ~26 GB — two uint8 frame copies plus features per row — which no
    single v5e's 16 GB can hold).
    """
    obs_bytes = sum(
        int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
        for s in jax.tree_util.tree_leaves(obs_spec)
    )
    row = 2 * obs_bytes + act_dim * 4 + 2 * 4
    return capacity * row


def nbytes(state: t.Any) -> int:
    """MEASURED bytes of a live buffer state's array leaves — the
    as-allocated companion to :func:`estimate_buffer_bytes`'s planning
    estimate (which knows nothing about striping, sequence-axis
    sharding or the vmapped device axis). Works on any buffer state
    pytree — ``BufferState``, ``StripedBufferState``, the dp-sharded
    per-device tree — and on abstract ``ShapeDtypeStruct`` leaves
    (shape x itemsize, no device query). Surfaced per epoch as
    ``replay/hbm_bytes`` when tiers are on (metrics.jsonl, next to the
    telemetry HBM watermarks).
    """
    total = 0
    for leaf in jax.tree_util.tree_leaves(state):
        n = getattr(leaf, "nbytes", None)
        if n is None:
            n = int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
        total += int(n)
    return total


def warn_if_buffer_exceeds_hbm(
    capacity: int,
    obs_spec: t.Any,
    act_dim: int,
    sp: int = 1,
    advice: str = "reduce buffer capacity or history_len",
) -> None:
    """Warn when one replay shard would crowd out update intermediates.

    The HBM-resident buffer is the design's core trade (zero
    host<->device replay traffic); an oversized capacity otherwise fails
    as an opaque allocator OOM mid-run. Shared by the host Trainer and
    the fused on-device loop so the device lookup / threshold logic
    cannot drift between them. A device that reports no ``bytes_limit``
    is not assumed to have one: the check is skipped and says so. ``sp`` > 1
    discounts sequence-history leaves whose T axis is sharded over the
    ring (``init_sharded_buffer``). No-op on CPU backends (host RAM,
    like the reference's buffer, ref ``buffer/replay_buffer.py``).

    ``advice`` names the caller's actual knobs: the host Trainer's
    per-device shard shrinks with dp, but the fused on-device loop
    broadcasts the FULL capacity to every dp slice — telling its users
    to "raise dp" would not reduce residency.
    """
    import logging

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return
    hbm = (dev.memory_stats() or {}).get("bytes_limit")
    if hbm is None:
        logging.getLogger(__name__).info(
            "%s reports no bytes_limit; replay-size check skipped", dev
        )
        return
    need = estimate_buffer_bytes(capacity, obs_spec, act_dim) // max(sp, 1)
    if need > 0.5 * hbm:
        logging.getLogger(__name__).warning(
            "replay shard needs ~%.1f GB of ~%.1f GB device memory; "
            "params, optimizer state and update intermediates share the "
            "rest — %s if allocation fails",
            need / 1024**3, hbm / 1024**3, advice,
        )


def init_replay_buffer(
    capacity: int,
    obs_spec: t.Any,
    act_dim: int,
    act_dtype=jnp.float32,
) -> BufferState:
    """Preallocate an empty ring buffer.

    ``obs_spec`` is a pytree of ``jax.ShapeDtypeStruct`` (or example
    arrays) describing ONE observation — a flat vector for MLP envs
    (ref ``replay_buffer.py:19-23``) or a ``MultiObservation`` spec for
    pixel envs. A leaf holds ``capacity`` rows in the shape
    :func:`stored_row_shape` gives a row of it.
    """
    data = Batch(
        states=_zeros_like_spec(capacity, obs_spec),
        actions=jnp.zeros((capacity, act_dim), act_dtype),
        rewards=jnp.zeros((capacity,), jnp.float32),
        next_states=_zeros_like_spec(capacity, obs_spec),
        done=jnp.zeros((capacity,), jnp.float32),
    )
    return BufferState(data=data, ptr=jnp.int32(0), size=jnp.int32(0))


def init_visual_replay_buffer(
    capacity: int,
    feature_dim: int,
    frame_shape: t.Tuple[int, int, int],
    act_dim: int,
) -> BufferState:
    """Convenience constructor for the mixed-observation buffer.

    Counterpart of the reference ``VisualReplayBuffer`` constructor
    (ref ``visual_replay_buffer.py:22-31``) with uint8 HWC frames.
    """
    obs_spec = MultiObservation(
        features=jax.ShapeDtypeStruct((feature_dim,), jnp.float32),
        frame=jax.ShapeDtypeStruct(tuple(frame_shape), jnp.uint8),
    )
    return init_replay_buffer(capacity, obs_spec, act_dim)


@custom_vmap
def _write_rows(ring: jax.Array, rows: jax.Array, at: jax.Array) -> jax.Array:
    """``ring`` with ``rows`` written at row ``at``: one contiguous,
    in-place ``dynamic-update-slice`` of a donated ring."""
    return lax.dynamic_update_slice_in_dim(ring, rows, at, 0)


@_write_rows.def_vmap
def _write_rows_of_members(axis_size, in_batched, ring, rows, at):
    """Under ``vmap`` (members, ``dp``) every member writes at its own
    ``at``. jax's own rule turns that into a scatter, and so would a
    batched ``dynamic_slice`` into a gather; for either, XLA:TPU picks
    a layout by the window's size and relays the whole ring into it
    (PERF.md, PR 25). One update a member, at a constant member index,
    stays in place in the layout the ring rests in."""
    ring, rows, at = (
        x if batched else jnp.broadcast_to(x, (axis_size,) + x.shape)
        for x, batched in zip((ring, rows, at), in_batched)
    )
    zeros = (0,) * (ring.ndim - 2)
    for member in range(axis_size):
        ring = lax.dynamic_update_slice(
            ring, rows[member:member + 1], (member, at[member]) + zeros
        )
    return ring, True


def _as_stored(rows: jax.Array, ring: jax.Array) -> jax.Array:
    """``rows`` (transitions, or rows as stored) in the shape a row of
    ``ring`` rests in.  Rows that change shape on the way are pinned
    row-major: left to itself the compiler carries the chunk's own
    layout (a picture's rows in the lanes) through the reshape into the
    update and from there onto the whole ring, which it then relays for
    the push and back for the gather, once a window (PERF.md, PR 30)."""
    if rows.shape[1:] == ring.shape[1:]:
        return rows
    rows = rows.reshape(rows.shape[:1] + ring.shape[1:])
    return with_layout_constraint(
        rows, Layout(major_to_minor=tuple(range(rows.ndim)))
    )


@jax.named_scope(scopes.PUSH)
def push(state: BufferState, chunk: Batch) -> BufferState:
    """Append a chunk of ``n`` transitions, overwriting oldest on wrap.

    Equivalent of ``n`` reference ``store`` calls
    (ref ``replay_buffer.py:29-43``): row ``j`` lands at
    ``(ptr + j) % capacity``, then ``ptr`` advances and ``size``
    saturates at capacity. ``n`` must be static (it is: the trainer
    always pushes ``update_every``-sized chunks). The rows are written
    in place, contiguously, wrap-around included; no scatter, so the
    ring keeps the layout it rests in (module docstring). A row of the
    chunk may come in a transition's shape or in the stored one.

    A striped (per-task) ring dispatches to
    :func:`~torch_actor_critic_tpu.buffer.striped.push_striped` — the
    one integration point that lets the fused burst/epoch programs ride
    either ring unchanged.
    """
    if isinstance(state, StripedBufferState):
        return push_striped(state, chunk)
    capacity = state.capacity
    n = jax.tree_util.tree_leaves(chunk)[0].shape[0]
    if n > capacity:
        # Two rows of the chunk would land on one row of the ring.
        raise ValueError(
            f"push: chunk of {n} transitions exceeds buffer capacity "
            f"{capacity}; use a larger buffer or smaller chunks."
        )
    # Rows ``[ptr, capacity)`` take the chunk's head and rows
    # ``[0, wrapped)`` its tail: two windows of ``n`` rows, one at
    # ``start`` and one at 0, each merged with the rows it holds and
    # written back, so a window that receives nothing rewrites what it
    # read. The first keeps rows only when the chunk wraps, and then it
    # is the ring's last ``n`` rows: those are read at a constant
    # offset (a read at a traced one is a gather under ``vmap``). The
    # second is read after the first is written: they may overlap.
    start = jnp.minimum(state.ptr, capacity - n)
    wrapped = state.ptr - start
    head = jnp.arange(n) >= wrapped

    def write(ring, new):
        new = _as_stored(new.astype(ring.dtype), ring)
        # seam[wrapped + j] = new[j]: where ``head``, seam[:n] is the
        # window at ``start``; elsewhere seam[n:] is the window at 0.
        seam = _write_rows(jnp.concatenate([new, new]), new, wrapped)
        mine = head.reshape((n,) + (1,) * (ring.ndim - 1))
        kept = lax.slice_in_dim(ring, capacity - n, capacity)
        ring = _write_rows(ring, jnp.where(mine, seam[:n], kept), start)
        kept = lax.slice_in_dim(ring, 0, n)
        return lax.dynamic_update_slice_in_dim(
            ring, jnp.where(mine, kept, seam[n:]), 0, 0
        )

    data = jax.tree_util.tree_map(write, state.data, chunk)
    return BufferState(
        data=data,
        ptr=(state.ptr + n) % capacity,
        size=jnp.minimum(state.size + n, capacity),
    )


def observation_spec(example_obs: t.Any) -> t.Any:
    """Shape and dtype of every leaf of one observation: what a learner
    keeps of the observation it was initialised on, so that sampled
    rows reach it in that shape however the ring stores them."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        example_obs,
    )


def _as_observed(rows: jax.Array, one: t.Any) -> jax.Array:
    """Sampled ``rows`` of one leaf in the shape ``one`` (its entry of an
    ``obs_spec``) has, where they lie in the form that shape rests in."""
    if rows.shape[1:] != stored_row_shape(one.shape, one.dtype):
        return rows
    return rows.reshape(rows.shape[:1] + tuple(one.shape))


@jax.named_scope(scopes.SAMPLE)
def as_observations(batch: Batch, obs_spec: t.Any) -> Batch:
    """``batch`` as :func:`sample` hands it on (rows as stored) with
    every row of ``states`` and ``next_states`` that rests in another
    shape than its own (:func:`stored_row_shape`) back in the one
    ``obs_spec`` (:func:`observation_spec`) gives it.  With no
    ``obs_spec`` the batch is handed on as it is, which is right only
    where every row rests in its own shape."""
    if obs_spec is None:
        return batch

    shaped = lambda obs: jax.tree_util.tree_map(_as_observed, obs, obs_spec)  # noqa: E731
    return batch.replace(
        states=shaped(batch.states), next_states=shaped(batch.next_states)
    )


@jax.named_scope(scopes.SAMPLE)
def sample(state: BufferState, key: jax.Array, batch_size: int) -> Batch:
    """Draw a uniform batch over the valid region ``[0, size)``.

    With replacement (deliberate deviation from ref
    ``replay_buffer.py:46``, see module docstring). The gathers are
    plain ``jnp.take`` of whole rows **as they are stored**: a frame
    that rests in tiles leaves as ``(batch, bytes / 128, 128)`` and
    :func:`as_observations` gives it its shape back (``run_update_burst``
    does, with the learner's ``obs_spec``). On the v5e a frame row is
    twelve contiguous kilobytes and the whole sample is 37 us a step in
    the visual burst (PERF.md, PR 30); until PR 30 the compiler copied
    each frame leaf into the gather's layout once a window, 30 ms for
    ``u8[200000, 64, 64, 3]``.

    An empty buffer raises eagerly; under ``jit`` the size is traced and
    cannot be checked, so the index range is clamped to ``[0, 1)`` —
    callers must gate on ``size > 0`` (the trainer's ``update_after``
    warmup guarantees this, ref ``sac/algorithm.py:273``).

    A striped (per-task) ring dispatches to
    :func:`~torch_actor_critic_tpu.buffer.striped.sample_striped`
    (task-balanced draws), mirroring :func:`push`.
    """
    if isinstance(state, StripedBufferState):
        return sample_striped(state, key, batch_size)
    if not isinstance(state.size, jax.core.Tracer) and int(state.size) == 0:
        raise ValueError("sample: replay buffer is empty (size == 0).")
    idx = jax.random.randint(key, (batch_size,), 0, jnp.maximum(state.size, 1))
    return jax.tree_util.tree_map(lambda ring: jnp.take(ring, idx, axis=0), state.data)


@jax.named_scope(scopes.SAMPLE)
def sample_fused_visual(
    state: BufferState,
    key: jax.Array,
    batch_size: int,
    out_dtype,
    augment: str = "none",
    pad: int = 4,
    normalize: bool = False,
    impl: str = "auto",
    interpret: bool = False,
    obs_spec: t.Any = None,
) -> Batch:
    """:func:`sample` for visual batches through the fused pixel
    pipeline (``ops/pixels.py``): non-frame leaves gather exactly like
    :func:`sample`; the two frame leaves decode, DrQ-shift and cast to
    ``out_dtype`` inside the fused gather, so the sampled frame batch
    never materializes as float32 in HBM (bf16 halves its footprint
    besides).

    The frame rows are gathered as stored, like :func:`sample`'s, and
    the pipeline is handed the sampled frames in ``obs_spec``'s shape
    (in the stored one without it): a gather of a gather, the same bits.

    Key discipline: with ``augment="none"`` the row draw consumes
    ``key`` exactly as :func:`sample` does, so at ``out_dtype=float32``
    this path is bitwise-identical to sample-then-decode-in-model —
    the ``pixel_pipeline="fused"`` f32 equivalence tests pin it. With
    ``augment="shift"`` the key splits three ways (rows, state shift,
    next-state shift): augmentation keys are consumed at sample time
    instead of inside the learner update (DrQ's independent
    per-example, per-use draws preserved).
    """
    from torch_actor_critic_tpu.ops.augment import shift_offsets
    from torch_actor_critic_tpu.ops.pixels import fused_frame_gather

    if not isinstance(state.data.states, MultiObservation):
        raise ValueError(
            "sample_fused_visual needs a MultiObservation (frame) "
            f"buffer; got {type(state.data.states).__name__}"
        )
    if not isinstance(state.size, jax.core.Tracer) and int(state.size) == 0:
        raise ValueError("sample: replay buffer is empty (size == 0).")
    if augment == "shift":
        k_idx, k_s, k_n = jax.random.split(key, 3)
        offs_s = shift_offsets(k_s, batch_size, pad)
        offs_n = shift_offsets(k_n, batch_size, pad)
    elif augment == "none":
        k_idx, offs_s, offs_n = key, None, None
    else:
        raise ValueError(f"unknown frame_augment mode {augment!r}")
    idx = jax.random.randint(
        k_idx, (batch_size,), 0, jnp.maximum(state.size, 1)
    )
    take = lambda ring: jnp.take(ring, idx, axis=0)  # noqa: E731
    rows = jnp.arange(batch_size)

    def gather(ring, offs):
        frames = take(ring)
        if obs_spec is not None:
            frames = _as_observed(frames, obs_spec.frame)
        return jax.named_scope(scopes.DECODE)(fused_frame_gather)(
            frames, rows, offsets=offs, pad=pad, normalize=normalize,
            out_dtype=out_dtype, impl=impl, interpret=interpret,
        )

    d = state.data
    return Batch(
        states=MultiObservation(
            features=take(d.states.features),
            frame=gather(d.states.frame, offs_s),
        ),
        actions=take(d.actions),
        rewards=take(d.rewards),
        next_states=MultiObservation(
            features=take(d.next_states.features),
            frame=gather(d.next_states.frame, offs_n),
        ),
        done=take(d.done),
    )
