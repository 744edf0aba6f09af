"""Fused replay-sample -> decode -> augment -> cast pixel pipeline.

The visual workload uses a small share of the chip's arithmetic
(PERF.md section 5, ``wallrunner_cnn_burst``: ``update.compute_mfu``).
Part of the gap is the pixel hot path: every gradient step
gathers a uint8 frame batch from the HBM ring (``buffer/replay.py``),
round-trips it through pad/crop augmentation (``ops/augment.py``) and
then materializes it as **float32** inside the CNN trunk
(``models/visual.py`` decodes ``frame.astype(float32) / 255``) — a
4x-width HBM write/read per forward, repeated across the four conv
towers of a SAC step.

This module fuses the whole chain into one kernel so the sampled frame
batch reaches the MXU in its compute dtype without ever existing as
f32 in HBM:

    replay-gather (+ frame stacking) -> uint8 decode -> DrQ random
    shift -> normalize -> cast to compute dtype

Three implementations of the same math, one contract — exactly the
``ops/attention.py`` scheme:

- :func:`gather_frames_reference` — pure jnp (gather + clipped-index
  shift + cast). Ground truth for tests; the training-path default on
  non-TPU backends.
- :func:`_gather_frames_pallas` — a Pallas TPU kernel: one program
  per example, each frame seen as a 2-D ``(H, W*C)`` slab (the view of
  a 3-channel frame that TPU tiling accepts), the replay rows selected
  via scalar-prefetch index maps (the ring never streams — ``S`` frame
  blocks of VMEM per program), the DrQ shift and the channel
  interleave of a stack expressed as one-hot **matmul-gathers**
  (MXU-friendly selection; exact for uint8 values, which are integers
  <= 255 and therefore exactly representable in f32 *and* bf16), the
  decode fused into the epilogue, output written directly in the
  compute dtype; the ``/ 255`` of a normalized decode follows in XLA
  (Mosaic's division rounds differently in the last bit). It compiles
  for the v5e at the wall-runner geometry (tests/test_chip_compile.py)
  and agrees bit for bit with the reference on the chip
  (chip_smoke.py, PR 21). It has NOT been timed: whether it wins is
  ROADMAP S3's question.
- :func:`fused_frame_gather` — the dispatch: ``'pallas'`` on a
  TPU-default backend, ``'xla'`` otherwise; ``interpret=True`` runs
  the kernel in the Pallas interpreter for CPU tests. Tracing the
  Pallas path on a non-TPU process raises at trace time (the
  ``flash_attention`` footgun guard).

Bit contract (pinned by tests/test_pixels.py): all three paths agree
BITWISE for every (out_dtype, normalize, augment, frame_stack)
combination, and the f32/no-augment output equals what the legacy path
computes inside the model (gather -> ``astype(float32)`` ->
``/ 255``), so switching ``pixel_pipeline="fused"`` at f32 changes
nothing but where the decode runs. Decode order is
``uint8 -> out_dtype -> (/255)``: integers <= 255 are exact in bf16,
so no f32 intermediate is needed for exact decoding, and the jaxpr of
the fused sample provably contains no f32 frame-batch tensor.

Frame stacking (``frame_stack > 1``) gathers the ``S`` ring rows
``idx - S + 1 .. idx`` (modular) and concatenates them on channels —
the gather-in-kernel formulation of a host-side frame stacker. NOTE:
the ring is a transition buffer, so stacked rows are consecutive
*pushes*; callers own the episode-boundary semantics (the built-in
envs bake temporal context into channels instead — see
``envs/pixel_pendulum.py`` — which is why training wires
``frame_stack=1`` today).
"""

from __future__ import annotations

import functools
import typing as t

import jax
import jax.numpy as jnp

from torch_actor_critic_tpu.ops.augment import shift_offsets

__all__ = [
    "fused_frame_gather",
    "gather_frames_reference",
    "stack_rows",
]


def stack_rows(
    idx: jax.Array, frame_stack: int, capacity: int
) -> jax.Array:
    """Ring rows backing a stacked gather: ``(B, S)`` int32, oldest
    first, newest (``idx`` itself) last, modular on the ring."""
    if frame_stack < 1:
        raise ValueError(f"frame_stack must be >= 1, got {frame_stack}")
    offsets = jnp.arange(frame_stack - 1, -1, -1, dtype=idx.dtype)
    return (idx[:, None] - offsets[None, :]) % capacity


def _decode(x: jax.Array, normalize: bool, out_dtype) -> jax.Array:
    """uint8 -> compute dtype, optionally rescaled to [0, 1].

    The cast precedes the divide ON PURPOSE: integers <= 255 are exact
    in every supported compute dtype (bf16 carries 8 significand bits),
    so decoding never needs an f32 intermediate — the property the
    no-f32-materialization test pins on the jaxpr.
    """
    x = x.astype(out_dtype)
    if normalize:
        x = x / jnp.asarray(255.0, out_dtype)
    return x


def _clipped_axis_indices(
    offsets: jax.Array, length: int, pad: int
) -> jax.Array:
    """Per-example source indices of a DrQ shift along one axis:
    ``clip(i + off - pad, 0, length-1)`` — identical to edge-padding by
    ``pad`` and cropping at ``off`` (``ops/augment.random_shift``),
    without materializing the padded frame."""
    return jnp.clip(
        jnp.arange(length)[None, :] + offsets[:, None] - pad, 0, length - 1
    )


def gather_frames_reference(
    ring: jax.Array,
    idx: jax.Array,
    offsets: jax.Array | None = None,
    pad: int = 4,
    normalize: bool = False,
    out_dtype=jnp.float32,
    frame_stack: int = 1,
) -> jax.Array:
    """Pure-jnp reference of the fused pipeline (ground truth).

    ``ring`` is the uint8 replay frame ring ``(capacity, H, W, C)``;
    ``idx`` the sampled rows ``(B,)``; ``offsets`` the per-example DrQ
    shift draws ``(B, 2)`` in ``[0, 2*pad]`` (None = no augmentation).
    Returns ``(B, H, W, frame_stack*C)`` in ``out_dtype``.
    """
    b = idx.shape[0]
    capacity, h, w, c = ring.shape
    rows = stack_rows(idx, frame_stack, capacity)
    frames = jnp.take(ring, rows.reshape(-1), axis=0).reshape(
        b, frame_stack, h, w, c
    )
    if offsets is not None:
        ys = _clipped_axis_indices(offsets[:, 0], h, pad)
        xs = _clipped_axis_indices(offsets[:, 1], w, pad)
        # Shift while still uint8: index moves, no arithmetic.
        frames = jnp.take_along_axis(
            frames, ys[:, None, :, None, None], axis=2
        )
        frames = jnp.take_along_axis(
            frames, xs[:, None, None, :, None], axis=3
        )
    out = _decode(frames, normalize, out_dtype)
    # (B, S, H, W, C) -> (B, H, W, S*C): temporal context on channels,
    # newest frame in the last C channels.
    return out.transpose(0, 2, 3, 1, 4).reshape(b, h, w, frame_stack * c)


# --------------------------------------------------------------------------
# Pallas TPU kernel
# --------------------------------------------------------------------------


def _pixel_kernel(rows_ref, *refs, augment: bool, out_dtype):
    """One example: ``S`` uint8 frames in, one decoded frame out.

    Everything is a 2-D ``(H, W*C)`` slab — the frame with its two minor
    axes merged, the only view of a 3-channel frame whose blocks the
    TPU's ``(sublane, 128-lane)`` tiling accepts. The replay rows were
    already selected by the scalar-prefetch index maps (``rows_ref``
    steers each ring BlockSpec), so the body sees ``S`` frames in VMEM.

    The DrQ shift and the channel interleave of a stack are one-hot
    **matmul-gathers** — selection expressed as MXU work: ``sy`` names
    the source row of every output row, ``src[s]`` the source lane in
    stack slot ``s`` of every output lane (-1: another slot's lane).
    One unit term per output element, so the result holds the original
    integers exactly, in the output dtype. The ``/ 255`` of a normalized
    decode is left to XLA, right behind the kernel: Mosaic's division
    and XLA's round differently in the last bit (seen on the v5e), and
    the bit contract is with the XLA reference.
    """
    del rows_ref  # consumed by the index maps
    if augment:
        sy_ref, src_ref, *frame_refs, o_ref = refs
    else:
        src_ref, *frame_refs, o_ref = refs
    n_stack = len(frame_refs)
    h, lanes_in = frame_refs[0].shape[1:]
    lanes_out = o_ref.shape[2]

    def load(ref):
        # Mosaic has no uint8 -> float cast; widen through int32.
        return ref[0].astype(jnp.int32).astype(jnp.float32)

    if not augment and n_stack == 1:
        out = load(frame_refs[0])
    else:
        if augment:
            onehot_y = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, (h, h), 1) == sy_ref[0],
                1.0, 0.0,
            )
        src_lane = jax.lax.broadcasted_iota(
            jnp.int32, (lanes_in, lanes_out), 0
        )
        out = jnp.zeros((h, lanes_out), jnp.float32)
        for s, ref in enumerate(frame_refs):
            f = load(ref)
            if augment:
                f = jnp.dot(onehot_y, f, preferred_element_type=jnp.float32)
            onehot_x = jnp.where(src_lane == src_ref[0, s:s + 1, :], 1.0, 0.0)
            out = out + jnp.dot(
                f, onehot_x, preferred_element_type=jnp.float32
            )
    o_ref[0] = out.astype(out_dtype)


def _gather_frames_pallas(
    ring: jax.Array,
    idx: jax.Array,
    offsets: jax.Array | None,
    pad: int,
    normalize: bool,
    out_dtype,
    frame_stack: int,
    interpret: bool,
) -> jax.Array:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not interpret and jax.default_backend() != "tpu":
        # Same trace-time guard as flash_attention: without it a
        # compiled Pallas call on a CPU/GPU process dies much later in
        # lowering with a cryptic Mosaic error.
        raise RuntimeError(
            "fused_frame_gather compiles Pallas TPU kernels but this "
            f"process's default backend is {jax.default_backend()!r}; "
            "use impl='xla' (the pure-jnp reference path) or pass "
            "interpret=True for CPU testing."
        )
    b = idx.shape[0]
    capacity, h, w, c = ring.shape
    n_stack = frame_stack
    rows = stack_rows(idx.astype(jnp.int32), n_stack, capacity)
    augment = offsets is not None
    # Source lane, in its own frame's merged (W*C) axis, of every lane
    # of the merged (W*S*C) output axis: lane x*(S*C) + s*C + ch reads
    # lane sx(x)*C + ch of stack slot s. Tiny int32 index arrays, built
    # outside the kernel so the body needs no integer division.
    lane = jnp.arange(w * n_stack * c, dtype=jnp.int32)
    x, slot, ch = lane // (n_stack * c), (lane // c) % n_stack, lane % c
    if augment:
        offsets = offsets.astype(jnp.int32)
        sx = _clipped_axis_indices(offsets[:, 1], w, pad).astype(jnp.int32)
        sx = sx[:, x]  # (B, W*S*C)
        sy = _clipped_axis_indices(offsets[:, 0], h, pad).astype(jnp.int32)
    else:
        sx = jnp.broadcast_to(x, (b, lane.shape[0]))
    src = jnp.where(
        slot[None, None, :] == jnp.arange(n_stack, dtype=jnp.int32)[None, :, None],
        (sx * c + ch)[:, None, :],
        -1,
    )  # (B, S, W*S*C)

    def per_example(*block):
        return pl.BlockSpec(block, lambda i, rows: (i,) + (0,) * (len(block) - 1))

    index_args, index_specs = [src], [per_example(1, n_stack, lane.shape[0])]
    if augment:
        index_args.insert(0, sy[:, :, None])
        index_specs.insert(0, per_example(1, h, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=index_specs + [
            pl.BlockSpec(
                (1, h, w * c), lambda i, rows, s=s: (rows[i, s], 0, 0)
            )
            for s in range(n_stack)
        ],
        out_specs=per_example(1, h, w * n_stack * c),
    )
    ring2d = ring.reshape(capacity, h, w * c)
    out = pl.pallas_call(
        functools.partial(
            _pixel_kernel, augment=augment, out_dtype=out_dtype,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, w * n_stack * c), out_dtype),
        interpret=interpret,
    )(rows, *index_args, *([ring2d] * n_stack))
    if normalize:
        out = out / jnp.asarray(255.0, out_dtype)
    return out.reshape(b, h, w, n_stack * c)


def fused_frame_gather(
    ring: jax.Array,
    idx: jax.Array,
    offsets: jax.Array | None = None,
    pad: int = 4,
    normalize: bool = False,
    out_dtype=jnp.float32,
    frame_stack: int = 1,
    impl: str = "auto",
    interpret: bool = False,
) -> jax.Array:
    """Dispatch: the Pallas kernel on a TPU-default backend, the jnp
    reference elsewhere (``'auto'`` decides at trace time, like
    ``ops/attention.attention``). All paths are bitwise-equal — the
    choice is a performance decision, never a numeric one."""
    if ring.dtype != jnp.uint8:
        raise ValueError(
            f"fused_frame_gather decodes uint8 replay frames, got "
            f"{ring.dtype}; the HBM ring stores frames as uint8 by "
            "design (buffer/replay.py)"
        )
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "pallas":
        return _gather_frames_pallas(
            ring, idx, offsets, pad, normalize, out_dtype, frame_stack,
            interpret,
        )
    if impl != "xla":
        raise ValueError(f"unknown impl {impl!r} (auto|pallas|xla)")
    return gather_frames_reference(
        ring, idx, offsets, pad, normalize, out_dtype, frame_stack
    )
