"""Scaled-dot-product attention: blockwise (XLA) and flash (Pallas TPU).

The reference has no attention anywhere — its models are feedforward
MLPs/CNNs over fixed-width observation vectors (SURVEY.md §5
"Long-context: absent by construction"). This module is the compute
core of the framework's long-context *extension*: sequence policies
(:mod:`torch_actor_critic_tpu.models.sequence`) and ring-attention
context parallelism (:mod:`torch_actor_critic_tpu.parallel.context`)
both reduce to the online-softmax block update defined here.

Three implementations of the same math, one contract:

- :func:`reference_attention` — materializes the full ``(Tq, Tk)``
  score matrix. O(T^2) memory; ground truth for tests.
- :func:`blockwise_attention` — FlashAttention-style online softmax
  over K/V blocks via ``lax.scan``: O(block) memory, differentiable,
  runs on any backend. This is the training-path default.
- :func:`flash_attention` — a Pallas TPU kernel of the same loop:
  grid ``(batch·heads, tile pairs)`` so VMEM only ever holds
  one ``(block, head_dim)`` tile of each operand (long sequences
  stream from HBM through the BlockSpec pipeline), MXU matmuls with
  f32 accumulators in VMEM scratch. The pairs are a schedule built from the
  mask's geometry (:func:`_pair_needed`, :func:`_tile_schedule`): a pair
  with nothing visible is no step of the grid and is never fetched. Wrapped
  in a ``custom_vjp`` whose backward is *also* Pallas (FlashAttention-2 style: forward saves the
  per-row logsumexp; dQ and dK/dV kernels recompute probability tiles
  from it), so training gets the kernel in both directions. Head dims
  are zero-padded to the 128-lane width transparently. The residual of the
  row statistics is private to the ``custom_vjp`` and rests compact:
  ``lse``, and the backward's ``delta = rowsum(dO * O)``, are
  ``(batch·heads, 1, seq)`` with a q block's rows in the lanes. The forward
  kernel writes ``lse`` in that form, one pass over ``dO`` and ``O`` writes
  ``delta`` in it, and the backward kernels read both as written: nothing
  is sliced out of, or broadcast into, a lane-wide ``(batch·heads, seq,
  128)`` copy in HBM.

Between the SDAR trunk's ``q_proj`` and those kernels lies one pass each way,
:func:`qk_norm_rope` (kernels ``qk_rope`` and ``qk_rope_bwd`` in a trace,
never ``attention``, which is how the benchmark finds the flash kernels): the
per-head RMS norm, rotary and the heads' transposition read the projection's
output once and write the kernels' ``(batch, heads, seq, d)`` once, and the
backward reads the kernels' ``dq`` and the saved projection output and writes
the projection's cotangent in its own layout. ``k`` and ``v`` (an eighth of
q's bytes each) are composed and transposed by XLA, and so is the kernels'
output on its way to ``o_proj`` and that product's cotangent on its way back:
a pass of our own there measured slower (PERF.md section 6, PR 39).

All take ``(batch, heads, seq, head_dim)`` arrays. ``q_offset`` /
``k_offset`` are *global* position offsets of the local q/k chunks —
the hook that lets ring attention apply a correct causal mask when the
sequence axis is sharded across devices.

Three generalisations, shared by all three: ``block_length`` ``b`` widens the
causal mask to *block-causal* (position ``i`` sees ``j`` iff
``j // b <= i // b``: causal across blocks of ``b``, full inside one;
``b = 1`` is the causal mask, by the same code as before); ``window`` ``w``
narrows it to a *sliding window* (``i`` sees ``j`` only if ``j > i - w``: the
``w`` latest positions, its own among them; ``None`` is no window), the
kernels visiting only the key blocks a row of query blocks can see, at both
edges (the schedule again: a block outside is neither computed nor fetched); and
grouped heads: ``k``/``v`` may carry ``heads // group`` heads, query head ``i``
then reads key/value head ``i // group``. The XLA paths repeat ``k`` and
``v``; the kernels read the shared head through their index maps, so no
repeated copy is ever written (the dK/dV kernel's schedule runs a key
block's query blocks once for each query head of the group, and the sum stays
in VMEM).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import typing as t

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = float("-inf")


def _visible(q_pos, k_pos, block_length: int = 1, window: int | None = None):
    """The (block-)causal mask, ``k_pos // b <= q_pos // b``, inside a sliding
    ``window`` if there is one: ``k_pos > q_pos - window``."""
    if block_length == 1:
        seen = q_pos >= k_pos
    else:
        seen = k_pos < (q_pos // block_length + 1) * block_length
    if window is None:
        return seen
    return seen & (k_pos > q_pos - window)


def _repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """``k``/``v`` with every shared head repeated for its query group."""
    h, hkv = q.shape[1], k.shape[1]
    if h == hkv:
        return k, v
    assert h % hkv == 0, (h, hkv)
    return jnp.repeat(k, h // hkv, axis=1), jnp.repeat(v, h // hkv, axis=1)


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    block_length: int = 1,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax(QK^T/sqrt(d))V with the full score matrix."""
    k, v = _repeat_kv(q, k, v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        tq, tk = scores.shape[-2], scores.shape[-1]
        q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        scores = jnp.where(
            _visible(q_pos, k_pos, block_length, window), scores, NEG_INF
        )
    # Rows with no visible key (possible when k_offset > q position, as
    # happens for future chunks in ring attention) would softmax to NaN;
    # zero them instead to match the online-softmax convention.
    all_masked = jnp.all(scores == NEG_INF, axis=-1, keepdims=True)
    weights = jax.nn.softmax(jnp.where(all_masked, 0.0, scores), axis=-1)
    weights = jnp.where(all_masked, 0.0, weights)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def online_block_update(
    q: jax.Array,
    k_blk: jax.Array,
    v_blk: jax.Array,
    m: jax.Array,
    l: jax.Array,
    acc: jax.Array,
    causal: bool = False,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    k_end: jax.Array | int | None = None,
    scale: float | None = None,
    block_length: int = 1,
    window: int | None = None,
) -> t.Tuple[jax.Array, jax.Array, jax.Array]:
    """One online-softmax accumulation step against a K/V block.

    Carries ``(m, l, acc)`` — running row max, normalizer, and
    unnormalized output — in float32. ``k_end`` (a *global* position
    bound) masks a pad tail; ``causal`` masks in global coordinates via
    the offsets. Safe when the block is entirely masked (contributes
    nothing). The single update body shared by the scan path here, the
    cross-device ring in ``parallel/context.py``, and mirrored by the
    Pallas kernel.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * scale
    if causal or k_end is not None:
        tq, tk = scores.shape[-2], scores.shape[-1]
        k_pos = k_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        valid = True
        if k_end is not None:
            valid = k_pos < k_end
        if causal:
            q_pos = q_offset + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            valid = valid & _visible(q_pos, k_pos, block_length, window)
        scores = jnp.where(valid, scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # exp(-inf - -inf) = NaN; a fully-masked row keeps m_new == -inf and
    # must contribute exp(...) = 0.
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(scores - safe_m[..., None])
    p = jnp.where(jnp.isneginf(scores), 0.0, p)
    alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l = l * alpha + jnp.sum(p, axis=-1)
    acc = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    return m_new, l, acc


def finalize_online(m: jax.Array, l: jax.Array, acc: jax.Array) -> jax.Array:
    """Normalize the online-softmax accumulator; all-masked rows → 0."""
    return acc / jnp.where(l == 0.0, 1.0, l)[..., None]


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    q_offset: jax.Array | int = 0,
    k_offset: jax.Array | int = 0,
    block_k: int = 256,
    block_length: int = 1,
    window: int | None = None,
) -> jax.Array:
    """Online-softmax attention scanning over K/V blocks.

    Never materializes the ``(Tq, Tk)`` matrix: peak memory is
    O(Tq · block_k) per (batch, head). Differentiable (plain jnp under
    ``lax.scan``), so it is the training-path implementation.
    """
    k, v = _repeat_kv(q, k, v)
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_k = min(block_k, tk)
    if tk % block_k:  # pad K/V to a block multiple; pad tail masked out
        pad = block_k - tk % block_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_blocks = k.shape[2] // block_k
    k_blocks = k.reshape(b, h, n_blocks, block_k, d).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(b, h, n_blocks, block_k, d).transpose(2, 0, 1, 3, 4)

    qf = q.astype(jnp.float32)
    init = (
        jnp.full((b, h, tq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, tq), jnp.float32),
        jnp.zeros((b, h, tq, d), jnp.float32),
    )
    padded = k.shape[2] != tk

    def body(carry, blk):
        j, k_blk, v_blk = blk
        m, l, acc = carry
        m, l, acc = online_block_update(
            qf, k_blk, v_blk, m, l, acc,
            causal=causal,
            q_offset=q_offset,
            k_offset=k_offset + j * block_k,
            k_end=k_offset + tk if padded else None,
            block_length=block_length,
            window=window,
        )
        return (m, l, acc), None

    idx = jnp.arange(n_blocks)
    (m, l, acc), _ = jax.lax.scan(body, init, (idx, k_blocks, v_blocks))
    return finalize_online(m, l, acc).astype(q.dtype)


# --------------------------------------------------------------------------
# Pallas TPU flash-attention kernel
# --------------------------------------------------------------------------

_LANE = 128  # TPU lane width: last tile dim, and scratch column count


def _acc_dot(a: jax.Array, b: jax.Array, dims) -> jax.Array:
    """``dot_general`` with f32 accumulation on MXU-native operands.

    Operands keep their storage dtype (bf16 stays bf16 — the MXU's fast
    mixed-precision path; upcasting to f32 first would force the ~4x
    slower f32 systolic passes). When exactly one side is an f32
    intermediate (the probability/ds tiles) and the other is sub-f32,
    the intermediate is cast DOWN to match — FlashAttention's standard
    TPU scheme; bf16 probabilities are inside the softmax's own error
    budget. f32-in/f32-out math is bit-identical to a plain f32 dot.

    Under the kernels' ``bf16_dots`` the operands that come from HBM reach
    here rounded to bfloat16 (:func:`_mxu`), and the rule above rounds the
    intermediates to match: float32 tiles in HBM and VMEM, one bfloat16 pass
    on the MXU with float32 accumulation, which is what XLA's default
    precision makes of a float32 product outside the kernels.
    """
    if a.dtype != b.dtype:
        if a.dtype == jnp.float32:
            a = a.astype(b.dtype)
        else:
            b = b.astype(a.dtype)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


# What stands beside a step in a schedule's ``kinds``: a row's first and last.
_FIRST, _LAST = 1, 2


def _pair_needed(
    tq: int, tk: int, block_q: int, block_k: int, causal: bool,
    block_length: int = 1, window: int | None = None,
) -> list:
    """``[q block][k block]``: whether some query of the q block sees some key
    of the k block, queries and keys at the positions ``0..``. From the static
    sizes alone, in plain Python at trace time: both edges of the mask are
    monotone in the query and in the key, so a tile's corners decide it. The
    last key a query ``r`` sees is ``(r // b + 1) * b - 1``, the first
    ``r - window + 1``."""

    def pair(iq, jk):
        if not causal:
            return True
        first_row, first_key = iq * block_q, jk * block_k
        last_row, last_key = first_row + block_q - 1, first_key + block_k - 1
        some = first_key <= (last_row // block_length + 1) * block_length - 1
        return some and (window is None or last_key > first_row - window)

    return [[pair(iq, jk) for jk in range(tk // block_k)] for iq in range(tq // block_q)]


# The most steps a sweep's schedule may hold: its ``int32`` tables (three, and
# a fourth in the dK/dV sweep) rest in the core's scalar memory for the whole
# call, 1 MiB on a v5e. Asked in the sandbox for the described chip, the
# compiler took the three kernels at 32,760 steps (512 KiB of tables in the
# dK/dV sweep) and refused them at 66,048 (PERF.md section 6, PR 48). A causal
# sweep of 512-wide tiles stays inside 32,768 steps up to histories of 130,560
# (the forward and dQ sweeps) or, with eight query heads to a key head, 46,080
# (dK/dV).
_SCHEDULE_STEPS_MAX = 1 << 15


def _tile_schedule(needed, group: int | None = None) -> tuple:
    """A sweep's steps over the pairs ``needed`` holds, rows in order, as
    ``int32`` tables with one entry a step: ``(rows, cols, kinds)`` for the
    forward and dQ sweeps (``group`` ``None``), which hold a q block
    (``rows``) and run over its k blocks (``cols``); ``(rows, cols, kinds,
    heads)`` for the dK/dV sweep, which holds a k block and runs over its q
    blocks once for each of the group's ``group`` query heads (``heads``) in
    turn. ``kinds`` flags a row's first and last step. A pair the mask
    empties is no step, so it is neither computed nor fetched. A row with no
    pair at all (a dK/dV row of keys past the last query: a query always sees
    a key) still has to write its zeros: it is one step on its first pair,
    which the mask hides whole, so the step adds nothing."""
    by_row = needed if group is None else zip(*needed)
    steps = []
    for row, row_needed in enumerate(by_row):
        pairs = [
            [row, col, 0, head]
            for head in range(group or 1)
            for col, seen in enumerate(row_needed) if seen
        ] or [[row, 0, 0, 0]]
        pairs[0][2] |= _FIRST
        pairs[-1][2] |= _LAST
        steps += pairs
    if len(steps) > _SCHEDULE_STEPS_MAX:
        raise ValueError(
            f"flash_attention: a sweep of {len(steps)} tile pairs is more than "
            f"the {_SCHEDULE_STEPS_MAX} whose schedule the kernels hold in "
            "scalar memory; use attention(impl='xla') or blockwise_attention "
            "for histories this long."
        )
    tables = tuple(np.asarray(steps, np.int32).T)
    return tables if group is not None else tables[:3]


def _check_window(tq: int, tk: int, causal: bool, window: int | None) -> None:
    if window is not None and (not causal or tq != tk):
        raise ValueError(
            "flash_attention: a window is a causal mask over queries and keys "
            f"at the same positions, got causal={causal}, Tq={tq}, Tk={tk}"
        )


def visited_key_blocks(
    t: int, block_q: int | None = None, block_k: int | None = None,
    block_length: int = 1, window: int | None = None,
) -> int:
    """(q block, k block) pairs the forward and dQ kernels compute for
    histories of ``t`` (blocks left out: the kernels' own choice for ``t``):
    the pairs the kernels' schedule is built from."""
    block_q, block_k = _check_blocks(t, t, block_q, block_k)
    return sum(map(sum, _pair_needed(t, t, block_q, block_k, True, block_length, window)))


def _mxu(x: jax.Array, mxu_dtype) -> jax.Array:
    return x if mxu_dtype is None else x.astype(mxu_dtype)


def _masked(scores, iq, jk, block_q, block_k, block_length, window, keys_first=False):
    """A tile's scores with the pairs the mask hides at ``-inf``: queries down
    the rows and keys along the lanes, or, ``keys_first``, the tile the other
    way round. Every tile of a causal call pays it, the ones the mask leaves
    whole too: once compiled it is a compare and a select a score vreg, and
    leaving it off whole tiles read level on the chip (PERF.md section 6, PR
    48)."""
    q_axis, k_axis = (1, 0) if keys_first else (0, 1)
    q_pos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, scores.shape, q_axis)
    k_pos = jk * block_k + jax.lax.broadcasted_iota(jnp.int32, scores.shape, k_axis)
    return jnp.where(_visible(q_pos, k_pos, block_length, window), scores, NEG_INF)


def _flash_kernel(
    rows_ref, cols_ref, kinds_ref, q_ref, k_ref, v_ref, o_ref, *rest,
    block_q: int, block_k: int, scale: float, causal: bool,
    save_lse: bool = False, block_length: int = 1, mxu_dtype=None,
    window: int | None = None,
):
    """One ``(batch·head, step)`` program of the forward sweep.

    The steps are the schedule's (:func:`_tile_schedule`): for a fixed q block
    its non-empty k blocks in order, carrying the online-softmax state in VMEM
    scratch (``m``/``l`` use column 0 of a (block_q, LANE) tile); the row's
    first step resets it, the last normalizes into ``o_ref``. Same update math
    as :func:`online_block_update`.
    """
    from jax.experimental import pallas as pl  # deferred: TPU-only path

    if save_lse:
        lse_ref, *rest = rest
    m_ref, l_ref, acc_ref = rest
    step = pl.program_id(1)
    kind = kinds_ref[step]
    iq, jk = rows_ref[step], cols_ref[step]

    @pl.when((kind & _FIRST) != 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = _mxu(q_ref[0], mxu_dtype)
    k_blk = _mxu(k_ref[0], mxu_dtype)
    v_blk = _mxu(v_ref[0], mxu_dtype)
    scores = _acc_dot(q, k_blk, ((1,), (1,))) * scale
    if causal:
        scores = _masked(scores, iq, jk, block_q, block_k, block_length, window)
    m = m_ref[:, 0]
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    # No isneginf guards in-kernel (unlike online_block_update, whose
    # ring-attention callers CAN see fully-masked rows): without a window a
    # row's sweep starts at k block 0, where every row sees key 0, so m_new
    # is finite from the first step on. Masked scores are -inf ->
    # exp(-inf - finite) = 0, and the first step's alpha =
    # exp(-inf - finite) = 0 wipes the zero-init state.
    # Under a window that does not hold: the first block of a row may lie
    # wholly before the window of the q block's later rows, whose m_new is
    # then still -inf. One select a row (not a tile) keeps exp(-inf - -inf)
    # out: such a row adds nothing and stays at zero.
    m_safe = m_new if window is None else jnp.where(m_new == NEG_INF, 0.0, m_new)
    p = jnp.exp(scores - m_safe[:, None])
    alpha = jnp.exp(m - m_safe)
    l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
    acc_ref[:] = acc_ref[:] * alpha[:, None] + _acc_dot(p, v_blk, ((1,), (0,)))
    m_ref[:, 0] = m_new

    @pl.when((kind & _LAST) != 0)
    def _finalize():
        l = l_ref[:, 0]
        o_ref[0] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)[:, None]
        ).astype(o_ref.dtype)
        if save_lse:
            # Per-row logsumexp — the only forward residual the flash
            # backward needs besides (q, k, v, o). INVARIANT: no
            # in-kernel row is ever fully masked (causal rows always
            # see key 0; there is no q/k offset on the Pallas path),
            # so l > 0 and lse is finite here — the l == 0 guard below
            # is defensive only, and the backward relies on finite lse
            # (it has no isneginf path; extending this kernel to
            # ring-attention offsets would need those guards back).
            # Stored compact, a q block's statistics as one row in the
            # lanes of a ``(batch·heads, 1, seq)`` array (the unit axis makes
            # the ``(1, block_q)`` block legal for Mosaic): the backward
            # kernels read it back in that form, so no lane-wide copy of the
            # statistics (128 times their size) is written, sliced or
            # broadcast in HBM.
            lse = jnp.where(
                l == 0.0, NEG_INF, m_ref[:, 0] + jnp.log(jnp.where(l == 0.0, 1.0, l))
            )
            lse_ref[0, 0] = lse


def _head_group(h: int, hkv: int) -> int:
    if h % hkv:
        raise ValueError(
            f"flash_attention: {h} query heads do not divide into {hkv} "
            "key/value heads"
        )
    return h // hkv


def _pad_head_dim(
    *arrays: jax.Array, lanes: int = _LANE
) -> t.Tuple[jax.Array, ...]:
    """Zero-pad the trailing (head) axis to a multiple of ``lanes``.

    ``lanes=128`` is the native lane width. ``lanes=64`` keeps a d=64
    head at its true width: the MXU still runs at most 50% on a 64-wide
    contraction either way (the 128x128 systolic array bound — see
    SCALING.md's attention roofline), but the q/k/v/o tiles carry half
    the HBM traffic and VMEM footprint of the zero-padded layout.

    ``lanes=64`` compiles for the v5e (tests/test_chip_compile.py) and
    matches the dense reference on the chip forward and backward
    (chip_smoke.py, PR 21). Whether it is FASTER has not been measured;
    128 stays the default until a chip timing says otherwise.
    """
    d = arrays[0].shape[-1]
    if d % lanes == 0:
        return arrays
    pad = lanes - d % lanes
    return tuple(
        jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) for x in arrays
    )


# Auto block-size cap: auto picks the largest block in
# {128, 256, 512} that tiles the sequence (fewer, larger tiles: less
# grid overhead and K/V re-reading), whatever the mask. Read on the chip
# inside the trunk cells' whole bursts (PERF.md section 6, PR 48; ms a
# window of ten steps, empty pairs already out of the grid): a window of
# 512 over histories of 4,096 at 512 / 256 / 128-wide tiles 2,789 / 2,891 /
# 3,088 (a sliding layer's kernels 0.76-1.21 ms a call at 512, 1.07-1.92 at
# 256, though 256 computes a quarter fewer score elements); the block-causal
# mask of 4 over 1,024 at 512 / 256 / 128: 1,813 / 2,020 / 2,456; the causal
# mask over 4,096 at 512 / 256: 2,789 / 3,056. A step of the grid costs more
# than the scores a smaller tile leaves out, so the tile is no function of
# the mask.
_AUTO_BLOCK_CAP = 512


def _auto_block(t: int, cap: int = _AUTO_BLOCK_CAP) -> int | None:
    """Largest block in {512, 256, 128} <= ``cap`` dividing ``t``
    (``t`` itself when ``t <= 128`` — the single-block case the old
    128 default already allowed). ``None`` when no such block exists:
    the shape set accepted here is exactly the old fixed-128 default's
    (so no shape silently moves from the XLA path onto never-validated
    degenerate Pallas tiles), only the chosen block can be larger."""
    if t <= 128:
        return t
    for b in (512, 256, 128):
        if b <= cap and t % b == 0:
            return b
    return None


def _check_blocks(tq: int, tk: int, block_q: int | None, block_k: int | None):
    block_q = _auto_block(tq) if block_q is None else min(block_q, tq)
    block_k = _auto_block(tk) if block_k is None else min(block_k, tk)
    if block_q is None or block_k is None or tq % block_q or tk % block_k:
        raise ValueError(
            f"flash_attention: Tq={tq} must divide by block_q={block_q} and "
            f"Tk={tk} by block_k={block_k} (None = no 128/256/512 block "
            "tiles the length); use attention(impl='xla') or "
            "blockwise_attention for ragged lengths."
        )
    return block_q, block_k


def _vmem_spec(block_shape, index_map):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(block_shape, index_map, memory_space=pltpu.VMEM)


def _q_held_specs(block_q: int, block_k: int, dp: int, group: int):
    """``(q tile, k/v tile, a q block's row statistics)``: a step's blocks in
    the forward and dQ sweeps, read from the schedule. A block on the held
    axis changes only when the row does, so an output there is written back
    once a row, whole. Row bh = batch * h + head of q reads row bh // group
    of k/v: batch * hkv + head // group, the shared head."""
    return (
        _vmem_spec((1, block_q, dp), lambda bh, s, rows, *_: (bh, rows[s], 0)),
        _vmem_spec((1, block_k, dp), lambda bh, s, rows, cols, _: (bh // group, cols[s], 0)),
        _vmem_spec((1, 1, block_q), lambda bh, s, rows, *_: (bh, 0, rows[s])),
    )


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool,
    save_lse: bool = False,
    pad_lanes: int = _LANE,
    block_length: int = 1,
    bf16_dots: bool = False,
    window: int | None = None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not interpret and jax.default_backend() != "tpu":
        # Without this, a compiled Pallas call on a CPU/GPU process dies
        # much later in lowering with a cryptic Mosaic error (the
        # trace-time 'auto' dispatch footgun, see attention()'s CAUTION
        # note). Trace-time default_backend is the right check: the
        # kernel choice is also made at trace time.
        raise RuntimeError(
            "flash_attention compiles Pallas TPU kernels but this "
            f"process's default backend is {jax.default_backend()!r}; "
            "use attention(..., impl='xla') (or inject "
            "models.sequence.xla_attention into sequence models), or "
            "pass interpret=True for CPU testing."
        )
    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = _head_group(h, hkv)
    _check_window(tq, tk, causal, window)
    block_q, block_k = _check_blocks(tq, tk, block_q, block_k)
    schedule = _tile_schedule(
        _pair_needed(tq, tk, block_q, block_k, causal, block_length, window)
    )
    if not (q.dtype == k.dtype == v.dtype):
        # _acc_dot's downcast rule is only safe for the kernels' own f32
        # intermediates; a mixed-dtype *input* would be silently rounded.
        raise ValueError(
            "flash_attention requires q/k/v to share one dtype, got "
            f"{q.dtype}/{k.dtype}/{v.dtype}; cast the operands first."
        )
    # The softmax scale uses the *logical* head dim; zero-pad the head
    # axis to the lane width (dot products are unchanged by zero columns,
    # padded output columns are sliced away).
    scale = 1.0 / math.sqrt(d)
    q, k, v = _pad_head_dim(q, k, v, lanes=pad_lanes)
    dp = q.shape[-1]
    qr = q.reshape(b * h, tq, dp)
    kr = k.reshape(b * hkv, tk, dp)
    vr = v.reshape(b * hkv, tk, dp)
    mxu_dtype = jnp.bfloat16 if bf16_dots else None
    qspec, kspec, rowspec = _q_held_specs(block_q, block_k, dp, group)
    out_shape = [jax.ShapeDtypeStruct((b * h, tq, dp), q.dtype)]
    out_specs = [qspec]
    if save_lse:
        out_shape.append(jax.ShapeDtypeStruct((b * h, 1, tq), jnp.float32))
        out_specs.append(rowspec)
    outs = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            block_q=block_q, block_k=block_k, scale=scale, causal=causal,
            save_lse=save_lse,
            block_length=block_length, mxu_dtype=mxu_dtype, window=window,
        ),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            # the tables: an index map takes them after the grid's indices, a
            # kernel before its operands
            num_scalar_prefetch=len(schedule),
            grid=(b * h, len(schedule[0])),
            in_specs=[qspec, kspec, kspec],
            out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANE), jnp.float32),  # m (col 0)
                pltpu.VMEM((block_q, _LANE), jnp.float32),  # l (col 0)
                pltpu.VMEM((block_q, dp), jnp.float32),     # acc
            ],
        ),
        # bh programs are independent; a row's steps carry the
        # online-softmax scratch and must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*schedule, qr, kr, vr)
    out = outs[0].reshape(b, h, tq, dp)[..., :d]
    if save_lse:
        return out, outs[1]
    return out


def _attn_probs(
    q, k, lse_ref, scale, causal, iq, jk, block_q, block_k, block_length, window,
    keys_first=False,
):
    """Recompute the (block_q, block_k) probability tile from saved lse.

    ``p[r, c] = exp(s[r, c] - lse[r])`` — exactly the forward's softmax
    weights, recovered without re-running the online max/normalizer scan.
    Shared by both backward kernels. ``keys_first`` (the dK/dV kernel): the
    tile transposed, ``(block_k, block_q)``, from ``K Qᵀ``: the products that
    sum over the queries then contract its lanes, as the MXU takes them, and
    ``lse`` is read as it rests, a q block's rows in the lanes.
    """
    if keys_first:
        s, lse = _acc_dot(k, q, ((1,), (1,))) * scale, lse_ref[0]
    else:
        s, lse = _acc_dot(q, k, ((1,), (1,))) * scale, lse_ref[0, 0][:, None]
    if causal:
        s = _masked(s, iq, jk, block_q, block_k, block_length, window, keys_first)
    # lse is finite for every row inside the kernel (each causal row
    # sees at least key 0 — see the forward's guard-removal note), and
    # masked scores are -inf -> exp(-inf - finite) = 0 with no NaN
    # path, so no isneginf passes are needed.
    return jnp.exp(s - lse)


def _flash_bwd_dq_kernel(
    rows_ref, cols_ref, kinds_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, block_q: int, block_k: int, scale: float, causal: bool,
    block_length: int = 1, mxu_dtype=None, window: int | None = None,
):
    """dQ: grid ``(batch·head, step)``, the forward kernel's sweep (a q block
    held, its non-empty k blocks in turn).

    ``ds = p · (dO Vᵀ − Δ)``, ``dq += ds K · scale`` accumulated in VMEM
    scratch over the row, written once on its last step. Δ is the
    precomputed ``rowsum(dO ∘ O)`` (standard FlashAttention-2 backward).
    """
    from jax.experimental import pallas as pl

    step = pl.program_id(1)
    kind = kinds_ref[step]
    iq, jk = rows_ref[step], cols_ref[step]

    @pl.when((kind & _FIRST) != 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q, do = _mxu(q_ref[0], mxu_dtype), _mxu(do_ref[0], mxu_dtype)
    k_blk, v_blk = _mxu(k_ref[0], mxu_dtype), _mxu(v_ref[0], mxu_dtype)
    p = _attn_probs(
        q, k_blk, lse_ref, scale, causal, iq, jk, block_q, block_k,
        block_length, window,
    )
    dpv = _acc_dot(do, v_blk, ((1,), (1,)))
    ds = p * (dpv - delta_ref[0, 0][:, None])
    dq_acc[:] += _acc_dot(ds, k_blk, ((1,), (0,))) * scale

    @pl.when((kind & _LAST) != 0)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    rows_ref, cols_ref, kinds_ref, heads_ref,
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, block_q: int, block_k: int, scale: float, causal: bool,
    block_length: int = 1, mxu_dtype=None, window: int | None = None,
):
    """dK/dV: grid ``(batch·kv-head, step)``, a k block held and its
    non-empty q blocks in turn.

    ``dv += pᵀ dO``; ``dk += dsᵀ Q · scale`` — both accumulated in VMEM
    scratch over the row. With grouped heads the row runs over the q blocks
    of every query head of the group in turn (the schedule's ``heads``, which
    only the index maps read), so the group's sum never leaves VMEM.
    """
    from jax.experimental import pallas as pl

    del heads_ref
    step = pl.program_id(1)
    kind = kinds_ref[step]
    jk, iq = rows_ref[step], cols_ref[step]

    @pl.when((kind & _FIRST) != 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q, do = _mxu(q_ref[0], mxu_dtype), _mxu(do_ref[0], mxu_dtype)
    k_blk, v_blk = _mxu(k_ref[0], mxu_dtype), _mxu(v_ref[0], mxu_dtype)
    # every tile keys first, ``(block_k, block_q)``: pᵀ and dsᵀ come out of
    # the products as the sums over the queries take them
    p = _attn_probs(
        q, k_blk, lse_ref, scale, causal, iq, jk, block_q, block_k,
        block_length, window, keys_first=True,
    )
    dv_acc[:] += _acc_dot(p, do, ((1,), (0,)))
    dpv = _acc_dot(v_blk, do, ((1,), (1,)))
    ds = p * (dpv - delta_ref[0])
    dk_acc[:] += _acc_dot(ds, q, ((1,), (0,))) * scale

    @pl.when((kind & _LAST) != 0)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, o, lse, g, causal, block_q, block_k, interpret,
    pad_lanes: int = _LANE, block_length: int = 1, bf16_dots: bool = False,
    window: int | None = None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    group = _head_group(h, hkv)
    block_q, block_k = _check_blocks(tq, tk, block_q, block_k)
    needed = _pair_needed(tq, tk, block_q, block_k, causal, block_length, window)
    mxu_dtype = jnp.bfloat16 if bf16_dots else None
    scale = 1.0 / math.sqrt(d)
    # The forward enforced a single q/k/v dtype; the cotangent can still
    # arrive wider (e.g. an f32 loss over a bf16 output) — align it so
    # _acc_dot never downcasts a genuine input unasked.
    g = g.astype(q.dtype)
    # Δ = rowsum(dO ∘ O): one reduce over g and o, fused by XLA and written
    # once, in the row form the kernels read (see the forward's lse); padded
    # head columns of o/g are zero so padding doesn't perturb it.
    delta = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    ).reshape(b * h, 1, tq)
    q, k, v, g = _pad_head_dim(q, k, v, g, lanes=pad_lanes)
    dp = q.shape[-1]
    qr = q.reshape(b * h, tq, dp)
    kr = k.reshape(b * hkv, tk, dp)
    vr = v.reshape(b * hkv, tk, dp)
    gr = g.reshape(b * h, tq, dp)

    def sweep(kernel, schedule, out_shape, in_specs, out_specs, acc):
        """One backward kernel over ``schedule``'s steps, ``acc`` the shape of
        its float32 accumulators."""
        return pl.pallas_call(
            functools.partial(
                kernel, block_q=block_q, block_k=block_k, scale=scale,
                causal=causal, block_length=block_length,
                mxu_dtype=mxu_dtype, window=window,
            ),
            out_shape=out_shape,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(schedule),
                grid=(out_shape[0].shape[0], len(schedule[0])),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM(acc, jnp.float32)] * len(out_shape),
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(*schedule, qr, kr, vr, gr, lse, delta)

    # dQ: the forward's sweep, the schedule's rows q blocks and its columns k
    # blocks.
    qspec, kspec, rowspec = _q_held_specs(block_q, block_k, dp, group)
    (dq,) = sweep(
        _flash_bwd_dq_kernel, _tile_schedule(needed),
        [jax.ShapeDtypeStruct((b * h, tq, dp), q.dtype)],
        [qspec, kspec, kspec, qspec, rowspec, rowspec], [qspec],
        (block_q, dp),
    )

    # dK/dV: the schedule's rows are k blocks, its columns q blocks. Row bkv
    # of k/v is read by the q rows bkv * group .. + group - 1, the step's
    # query head ``heads[s]`` of them.
    def q_row(bkv, s, heads):
        return bkv * group + heads[s]

    qspec = _vmem_spec(
        (1, block_q, dp),
        lambda bkv, s, rows, cols, _, heads: (q_row(bkv, s, heads), cols[s], 0),
    )
    kspec = _vmem_spec((1, block_k, dp), lambda bkv, s, rows, *_: (bkv, rows[s], 0))
    rowspec = _vmem_spec(
        (1, 1, block_q),
        lambda bkv, s, rows, cols, _, heads: (q_row(bkv, s, heads), 0, cols[s]),
    )
    dk, dv = sweep(
        _flash_bwd_dkv_kernel, _tile_schedule(needed, group),
        [
            jax.ShapeDtypeStruct((b * hkv, tk, dp), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, tk, dp), v.dtype),
        ],
        [qspec, kspec, kspec, qspec, rowspec, rowspec], [kspec, kspec],
        (block_k, dp),
    )

    dq = dq.reshape(b * h, tq, dp)[..., :d].reshape(b, h, tq, d)
    dk = dk.reshape(b * hkv, tk, dp)[..., :d].reshape(b, hkv, tk, d)
    dv = dv.reshape(b * hkv, tk, dp)[..., :d].reshape(b, hkv, tk, d)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    pad_lanes: int = _LANE,
    block_length: int = 1,
    bf16_dots: bool = False,
    window: int | None = None,
):
    """Pallas TPU flash attention, forward *and* backward kernels.

    The forward is the online-softmax streaming kernel; under
    ``jax.grad`` it additionally saves the per-row logsumexp, and the
    backward runs two Pallas kernels (dQ over k-blocks; dK/dV over
    q-blocks) that recompute probability tiles from the saved lse — the
    FlashAttention-2 scheme, O(block²) VMEM, no (Tq, Tk) matrix ever
    materialized in either direction.

    ``block_q``/``block_k`` default to auto: the largest block in
    {128, 256, 512} that tiles the sequence length — 512 is the chip's
    block-sweep optimum (2.8x the old 128-block default fwd+bwd bf16,
    see ``_AUTO_BLOCK_CAP``). Explicit values require ``Tq % block_q == 0``
    and ``Tk % block_k == 0`` (raises ``ValueError`` otherwise); any
    head dim works (zero-padded to the 128-lane width internally).
    ``interpret=True`` runs the kernels in the Pallas interpreter
    (CPU-testable; used by the test suite).

    ``block_length`` (with ``causal``) makes the mask block-causal and
    ``k``/``v`` may carry fewer, shared heads (module docstring).
    ``bf16_dots`` rounds the operands of every product to bfloat16 inside
    the kernels (see :func:`_acc_dot`); inputs, outputs and accumulators
    keep their dtype. ``window`` (with ``causal``, queries and keys at the
    same positions) narrows the mask to the ``window`` latest positions; all
    three kernels then sweep only the blocks inside it (module docstring).

    A sweep's schedule rests in scalar memory, so a call holds at most
    ``_SCHEDULE_STEPS_MAX`` tile pairs a head (a key head's whole group, in
    the dK/dV sweep) and raises ``ValueError`` past them: causal histories of
    46,080 at 512-wide tiles with eight query heads to a key head, 130,560
    with one. A head's q blocks run in turn on one core (the grid's second
    axis carries a row's state): only batch x heads is split across cores.
    """
    return _flash_forward(
        q, k, v, causal, block_q, block_k, interpret, pad_lanes=pad_lanes,
        block_length=block_length, bf16_dots=bf16_dots, window=window,
    )


def _flash_fwd(
    q, k, v, causal, block_q, block_k, interpret, pad_lanes, block_length,
    bf16_dots, window=None,
):
    out, lse = _flash_forward(
        q, k, v, causal, block_q, block_k, interpret, save_lse=True,
        pad_lanes=pad_lanes, block_length=block_length, bf16_dots=bf16_dots,
        window=window,
    )
    return out, (q, k, v, out, lse)


def _flash_bwd(
    causal, block_q, block_k, interpret, pad_lanes, block_length, bf16_dots,
    window, res, g,
):
    q, k, v, o, lse = res
    return _flash_backward(
        q, k, v, o, lse, g, causal, block_q, block_k, interpret,
        pad_lanes=pad_lanes, block_length=block_length, bf16_dots=bf16_dots,
        window=window,
    )


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# Between the projections and the kernels: one pass each way
# --------------------------------------------------------------------------

# A block: some rows of a few heads. 2 MiB (all 1,024 rows of four heads at the
# trunk cell's shapes) is the most whose double buffers the default scoped VMEM
# holds for the pass back (three blocks a program); the trunk cell's window read
# 1,888 ms with it, 1,895 at 1 MiB, 1,917 at 256 KiB (PERF.md section 6, PR 39).
_PASS_BLOCK_BYTES = 2 << 20
_PASS_BLOCK_HEADS = 4  # at most: a kernel's body is unrolled over them


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """``x / sqrt(mean(x^2) + eps) * weight`` over the last axis, the
    statistics in float32."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight


# YaRN's constants (arXiv 2309.00071, and every published ``rope_parameters``
# group of ``rope_type`` ``yarn`` this repo runs): the ramp between a
# frequency kept and one divided runs from the index that turns 32 times in
# the original positions to the one that turns once.
YARN_BETA_FAST = 32.0
YARN_BETA_SLOW = 1.0


def yarn_scale(factor: float) -> float:
    """What YaRN multiplies cosine and sine by: ``0.1 ln(factor) + 1`` (a
    published ``attention_factor`` of 1.4852 for a factor of 128 is this)."""
    return 0.1 * math.log(factor) + 1.0 if factor > 1.0 else 1.0


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary positions as a published ``rope_parameters`` group states them,
    where they are more than one ``theta`` over the whole head.

    ``share`` (``partial_rotary_factor``): the first ``share * d`` channels
    of a head rotate, the rest pass. ``yarn_factor`` above 1 (``rope_type``
    ``yarn``): each of the rotated channels' frequencies is blended between
    itself and itself over ``yarn_factor`` by a linear ramp over the
    frequencies' indices, from the index that turns ``YARN_BETA_FAST`` times
    in ``yarn_positions`` positions (rounded down; below it a frequency
    stays) to the one that turns ``YARN_BETA_SLOW`` times (rounded up; above
    it a frequency is divided whole), and cosine and sine are multiplied by
    :func:`yarn_scale`."""

    theta: float
    share: float = 1.0
    yarn_factor: float = 1.0
    yarn_positions: int = 0

    @property
    def scale(self) -> float:
        """What cosine and sine are multiplied by."""
        return yarn_scale(self.yarn_factor)

    def rotated(self, d: int) -> int:
        """How many of a head's ``d`` channels rotate."""
        return int(d * self.share)

    def inv_freq(self, d: int) -> jax.Array:
        """The ``rotated(d) / 2`` inverse frequencies."""
        r = self.rotated(d)
        index = jnp.arange(0, r, 2, dtype=jnp.float32)
        inv_freq = 1.0 / self.theta ** (index / r)
        if self.yarn_factor <= 1.0:
            return inv_freq

        def turns_at(turns: float) -> float:  # the index that turns so often
            return r * math.log(self.yarn_positions / (turns * 2 * math.pi)) / (
                2 * math.log(self.theta)
            )

        low = max(math.floor(turns_at(YARN_BETA_FAST)), 0)
        high = min(math.ceil(turns_at(YARN_BETA_SLOW)), r - 1)
        ramp = jnp.clip((index / 2 - low) / max(high - low, 1e-3), 0.0, 1.0)
        return inv_freq / self.yarn_factor * ramp + inv_freq * (1.0 - ramp)


def _rope_angles(pos: jax.Array, d: int, theta: float) -> jax.Array:
    """Rotate-half rotary's angles ``(T, d)`` at the positions ``pos``: the
    ``d / 2`` frequencies, once for each half of a head."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.concatenate([angles, angles], axis=-1)


def rotary(x: jax.Array, pos: jax.Array, theta: float | Rope) -> jax.Array:
    """Rotate-half rotary positions on ``x`` ``(B, T, heads, d)`` at the
    global positions ``pos`` ``(T,)``: one ``theta`` over the whole head, or
    what a :class:`Rope` says (a part of the head, frequencies scaled by
    band, cosine and sine scaled)."""
    d = x.shape[-1]
    if isinstance(theta, Rope):
        r = theta.rotated(d)
        angles = pos.astype(jnp.float32)[:, None] * theta.inv_freq(d)[None, :]
        angles = jnp.concatenate([angles, angles], axis=-1)[None, :, None, :]
        turned, x1, x2 = x[..., :r], x[..., : r // 2], x[..., r // 2: r]
        rotated = jnp.concatenate([-x2, x1], axis=-1)
        out = theta.scale * (
            turned * jnp.cos(angles) + rotated * jnp.sin(angles)
        )
        return out if r == d else jnp.concatenate([out, x[..., r:]], axis=-1)
    angles = _rope_angles(pos, d, theta)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * jnp.cos(angles) + rotated * jnp.sin(angles)


def rope_tables(pos: jax.Array, d: int, theta: float):
    """``cos`` and ``sin`` ``(T, d)`` of :func:`rotary` at the positions
    ``pos``, the sine signed: ``rotate_half(x) * sin`` is
    ``roll(x, d / 2) * signed_sin``, the half rotation a roll of the lanes."""
    angles = _rope_angles(pos, d, theta)
    sign = jnp.where(jnp.arange(d) < d // 2, -1.0, 1.0)
    return jnp.cos(angles), jnp.sin(angles) * sign


def _pass_block(t: int, heads: int, d: int) -> tuple[int | None, int]:
    """A block's rows and heads: the most heads up to ``_PASS_BLOCK_HEADS``
    that divide ``heads``, and the most rows that divide ``t``, are whole
    sublanes and keep the float32 block inside ``_PASS_BLOCK_BYTES``."""
    hb = max(n for n in range(1, _PASS_BLOCK_HEADS + 1) if heads % n == 0)
    fits = [
        r for r in range(8, t + 1, 8)
        if t % r == 0 and 4 * r * hb * d <= _PASS_BLOCK_BYTES
    ]
    return max(fits, default=None), hb


def _pass_fits(t: int, heads: int, d: int, dtype) -> bool:
    """Whether the pass has blocks for histories of ``t`` and ``heads`` heads
    of ``d``: float32, whole lanes a head, whole sublanes a block."""
    return (
        dtype == jnp.float32 and d % _LANE == 0
        and _pass_block(t, heads, d)[0] is not None
    )


def _half_roll(x: jax.Array) -> jax.Array:
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, x.shape[-1] // 2, x.ndim - 1)


def _qk_rope_kernel(y_ref, w_ref, cos_ref, sin_ref, o_ref, *, d: int, eps: float):
    """One ``(batch, row block, head block)`` program: each head of the
    block's rows normalised (statistics in float32), weighted, rotated and written
    to its own ``(rows, d)`` plane of the heads-first output."""
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    for h in range(o_ref.shape[1]):
        x = y_ref[0, :, h * d:(h + 1) * d]
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        xn = x * r * w
        o_ref[0, h] = xn * cos + _half_roll(xn) * sin


def _qk_rope_bwd_kernel(
    g_ref, y_ref, w_ref, cos_ref, sin_ref, dy_ref, dw_ref, *, d: int, eps: float
):
    """The same pass the other way: the kernels' heads-first cotangent and
    the saved projection output in, the projection's cotangent out in its
    own layout, and this block's share of the weight's gradient (eight
    sublanes of partial sums; the caller adds the blocks)."""
    w, cos, sin = w_ref[...], cos_ref[...], sin_ref[...]
    dw = jnp.zeros(dw_ref.shape[3:], jnp.float32)
    for h in range(g_ref.shape[1]):
        x = y_ref[0, :, h * d:(h + 1) * d]
        g = g_ref[0, h]
        r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
        n = x * r
        dxn = g * cos + _half_roll(g * sin)  # the roll is its own transpose
        dw = dw + jnp.sum((dxn * n).reshape(-1, *dw.shape), axis=0)
        dn = dxn * w
        dy_ref[0, :, h * d:(h + 1) * d] = r * (
            dn - n * jnp.mean(dn * n, axis=-1, keepdims=True)
        )
    dw_ref[0, 0, 0] = dw


def _pass_specs(b: int, t: int, heads: int, d: int):
    """The grid ``(batch, row block, head block)`` (heads innermost: a row
    block's tables stay) and the blocks of a pass: the projection's layout
    ``(batch, seq, heads·d)``, the kernels' ``(batch, heads, seq, d)``, a
    table's rows ``(seq, d)``, the norm's weight ``(1, d)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, hb = _pass_block(t, heads, d)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return (b, t // rows, heads // hb), dict(
        flat=vmem((1, rows, hb * d), lambda i, j, g: (i, j, g)),
        heads_first=vmem((1, hb, rows, d), lambda i, j, g: (i, g, j, 0)),
        table=vmem((rows, d), lambda i, j, g: (j, 0)),
        weight=vmem((1, d), lambda i, j, g: (0, 0)),
        share=vmem((1, 1, 1, 8, d), lambda i, j, g: (i, j, g, 0, 0)),
    )


def _pass_call(kernel, name, grid, in_specs, out_specs, out_shape, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        # The default scoped VMEM holds these blocks; a raised limit is taken
        # from what XLA keeps of the whole program's buffers there (the trunk
        # step's k and Adam operands left fast memory, PERF.md section 6).
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=interpret,
        # The trace names a kernel by this: ``benchmark``'s flash roofline
        # reads the kind ``attention``, which the pass's kernels must not match.
        name=name,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _qk_norm_rope_pass(y, weight, pos, theta, eps, interpret):
    return _qk_rope_fwd(y, weight, pos, theta, eps, interpret)[0]


def _qk_rope_fwd(y, weight, pos, theta, eps, interpret):
    b, t, heads, d = y.shape
    grid, spec = _pass_specs(b, t, heads, d)
    out = _pass_call(
        functools.partial(_qk_rope_kernel, d=d, eps=eps), "qk_rope", grid,
        [spec["flat"], spec["weight"], spec["table"], spec["table"]],
        spec["heads_first"], jax.ShapeDtypeStruct((b, heads, t, d), y.dtype),
        interpret,
    )(y.reshape(b, t, heads * d), weight.reshape(1, d), *rope_tables(pos, d, theta))
    return out, (y, weight, pos)


def _qk_rope_bwd(theta, eps, interpret, res, g):
    y, weight, pos = res
    b, t, heads, d = y.shape
    grid, spec = _pass_specs(b, t, heads, d)
    dy, dw = _pass_call(
        functools.partial(_qk_rope_bwd_kernel, d=d, eps=eps), "qk_rope_bwd", grid,
        [spec["heads_first"], spec["flat"], spec["weight"], spec["table"], spec["table"]],
        [spec["flat"], spec["share"]],
        [
            jax.ShapeDtypeStruct((b, t, heads * d), y.dtype),
            jax.ShapeDtypeStruct((*grid, 8, d), jnp.float32),
        ],
        interpret,
    )(g, y.reshape(b, t, heads * d), weight.reshape(1, d), *rope_tables(pos, d, theta))
    return dy.reshape(y.shape), jnp.sum(dw, axis=(0, 1, 2, 3)).astype(weight.dtype), None


_qk_norm_rope_pass.defvjp(_qk_rope_fwd, _qk_rope_bwd)


def qk_norm_rope(
    y: jax.Array,
    weight: jax.Array,
    pos: jax.Array,
    theta: float,
    eps: float,
    impl: str = "auto",
) -> jax.Array:
    """A projection's output ``(batch, seq, heads, d)`` to the kernels'
    ``(batch, heads, seq, d)``: per-head RMS norm (float32 statistics) times
    ``weight`` ``(d,)``, rotate-half rotary at the positions ``pos``
    ``(seq,)``, and the heads' transposition.

    ``'pallas'`` is one pass over the projection's output (read once,
    written once) and one back (the kernels' ``dq`` and the saved projection
    output in, the projection's cotangent out in its own layout), for
    float32, ``d`` a multiple of 128 and ``seq`` of 8; ``'interpret'`` the
    same kernels in the Pallas interpreter. ``'xla'`` composes
    :func:`rms_norm`, :func:`rotary` and ``transpose``, which XLA:TPU makes
    four passes over ``y`` of (PERF.md section 6); it is what the kernels are
    held to by the tests. ``'auto'``: the pass on a TPU where it has blocks,
    like :func:`attention` and with its CAUTION. The kernels are named
    ``qk_rope`` / ``qk_rope_bwd`` in a trace, not ``attention``."""
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        b, t, heads, d = y.shape
        impl = "pallas" if on_tpu and _pass_fits(t, heads, d, y.dtype) else "xla"
    if impl == "xla":
        return rotary(rms_norm(y, weight, eps), pos, theta).transpose(0, 2, 1, 3)
    return _qk_norm_rope_pass(y, weight, pos, theta, eps, impl == "interpret")


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    impl: str = "auto",
    block_q: int | None = None,
    block_k: int | None = None,
    block_length: int = 1,
    bf16_dots: bool = False,
    window: int | None = None,
) -> jax.Array:
    """Dispatch: ``'pallas'`` kernel on TPU-compatible shapes,
    ``'xla'`` blockwise scan otherwise; ``'auto'`` picks by the process
    default backend.

    CAUTION: ``'auto'`` bakes the choice in at trace time, so a
    function compiled for a *non-default* backend (e.g. the trainer's
    host-CPU actor mirror while TPU is default) must not rely on it —
    pass an explicit ``impl`` or, for the sequence models, inject
    ``models.sequence.xla_attention``. (``lax.platform_dependent`` is
    not an option: XLA still lowers the dead Pallas branch on CPU and
    ``pallas_call`` has no CPU lowering outside interpret mode.)
    Tracing the Pallas path on a non-TPU-default process raises a clear
    ``RuntimeError`` at trace time (tests/test_attention.py pins this)
    instead of a cryptic Mosaic lowering error.
    """
    if impl == "auto":
        on_tpu = jax.default_backend() == "tpu"
        # TPU tiles are (8, 128) for f32: besides block divisibility,
        # require sublane-aligned sequence lengths (T % 8 == 0) or the
        # kernel would compile sublane-unaligned tiles that are only
        # ever exercised in interpret mode. Auto blocks (None) accept
        # exactly the shape set the old fixed-128 default did (see
        # _auto_block); a None result routes to XLA like a ragged
        # length always has.
        bq = _auto_block(q.shape[2]) if block_q is None else block_q
        bk = _auto_block(k.shape[2]) if block_k is None else block_k
        shapes_ok = (
            bq is not None
            and bk is not None
            and q.shape[2] % 8 == 0
            and k.shape[2] % 8 == 0
            and q.shape[2] % min(bq, q.shape[2]) == 0
            and k.shape[2] % min(bk, k.shape[2]) == 0
        )
        impl = "pallas" if (on_tpu and shapes_ok) else "xla"
    if impl == "pallas":
        return flash_attention(
            q, k, v, causal, block_q, block_k, False, _LANE, block_length,
            bf16_dots, window,
        )
    return blockwise_attention(
        q, k, v, causal, block_k=128 if block_k is None else block_k,
        block_length=block_length, window=window,
    )
