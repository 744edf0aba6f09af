"""The flash kernels' tile-pair schedule (``ops/attention.py``): the pairs it
holds and its tables against a brute-force mask, and the kernels that run from
them (interpreted) against the dense reference where empty pairs, pairs the
mask leaves whole and pairs its edge cuts meet in one call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.ops import attention as ops

EMPTY, WHOLE, CUT = 0, 1, 2


def _brute_classes(tq, tk, block_q, block_k, causal, block_length, window):
    """Every tile pair from ``_visible`` over all positions: nothing of it
    visible, all of it, or the mask's edge across it."""
    if causal:
        seen = np.asarray(ops._visible(
            np.arange(tq)[:, None], np.arange(tk)[None, :], block_length, window
        ))
    else:
        seen = np.ones((tq, tk), bool)
    tiles = seen.reshape(tq // block_q, block_q, tk // block_k, block_k)
    some, every = tiles.any(axis=(1, 3)), tiles.all(axis=(1, 3))
    return np.where(every, WHOLE, np.where(some, CUT, EMPTY))


# t_q, t_k, block_q, block_k, causal, block_length, window, group, and the
# (empty, whole, cut) pairs of a head's sweep where a cell's layers run the
# shape: empty as the steps a rectangular grid (q blocks by the longest row of
# k blocks) would spend on them
MASKS = [
    pytest.param(4096, 4096, 512, 512, True, 1, None, 6, (28, 28, 8), id="laguna-full"),
    pytest.param(4096, 4096, 512, 512, True, 1, 512, 9, (1, 0, 15), id="laguna-sliding"),
    pytest.param(1024, 1024, 512, 512, True, 4, None, 8, (1, 1, 2), id="sdar"),
    pytest.param(4096, 4096, 256, 256, True, 1, 512, 9, (3, 15, 30), id="sliding-256"),
    pytest.param(1024, 1024, 256, 256, True, 4, None, 8, (6, 6, 4), id="sdar-256"),
    pytest.param(512, 512, 128, 64, True, 1, None, 1, None, id="causal-unequal"),
    pytest.param(512, 512, 64, 128, True, 1, None, 2, None, id="causal-unequal-grouped"),
    pytest.param(256, 256, 32, 32, True, 4, None, 4, None, id="block4"),
    pytest.param(256, 256, 64, 32, True, 32, None, 2, None, id="block32-unequal"),
    pytest.param(256, 256, 32, 64, True, 32, None, 1, None, id="block32-wide-keys"),
    pytest.param(512, 512, 128, 64, True, 1, 100, 3, None, id="window-unequal"),
    pytest.param(512, 512, 64, 128, True, 4, 70, 2, None, id="window-block4"),
    pytest.param(256, 256, 64, 64, True, 1, 300, 1, None, id="window-past-the-history"),
    pytest.param(128, 128, 16, 16, True, 1, 80, 2, None, id="window-three-classes"),
    pytest.param(64, 128, 32, 32, True, 1, None, 2, None, id="keys-past-the-last-query"),
    pytest.param(128, 64, 32, 32, True, 1, None, 1, None, id="queries-past-the-last-key"),
    pytest.param(128, 256, 32, 64, False, 1, None, 2, None, id="no-mask"),
]


@pytest.mark.parametrize(
    "tq, tk, block_q, block_k, causal, block_length, window, group, counts", MASKS
)
def test_the_schedule_is_the_masks_geometry(
    tq, tk, block_q, block_k, causal, block_length, window, group, counts
):
    """``_pair_needed`` against the mask over all positions, and both sweeps'
    tables against it: every non-empty pair once (once a query head of the
    group in the dK/dV sweep), rows in order, the flags on a row's first and
    last step and nowhere else, a row without a pair one step on a pair the
    mask hides whole."""
    needed = ops._pair_needed(tq, tk, block_q, block_k, causal, block_length, window)
    want = _brute_classes(tq, tk, block_q, block_k, causal, block_length, window)
    np.testing.assert_array_equal(needed, want != EMPTY)
    if counts is not None:
        longest = (want != EMPTY).sum(axis=1).max()
        empty = want.shape[0] * longest - (want != EMPTY).sum()
        assert (empty, (want == WHOLE).sum(), (want == CUT).sum()) == counts
        assert ops.visited_key_blocks(tq, block_q, block_k, block_length, window) == sum(counts[1:])
    if causal and tq == tk:
        visited = ops.visited_key_blocks(tq, block_q, block_k, block_length, window)
        assert visited == (want != EMPTY).sum()
    for heads, by_row in ((None, want), (group, want.T)):
        rows, cols, kinds, *head = ops._tile_schedule(needed, heads)
        assert len(head) == (heads is not None), "the heads' table in the dK/dV sweep alone"
        head = head[0] if head else np.zeros_like(rows)
        assert all(x.dtype == np.int32 for x in (rows, cols, kinds, head))
        assert np.all(np.diff(rows) >= 0), "rows in order"
        np.testing.assert_array_equal(np.unique(rows), np.arange(by_row.shape[0]))
        firsts = np.flatnonzero(np.diff(rows, prepend=-1))
        lasts = np.flatnonzero(np.diff(rows, append=by_row.shape[0]))
        np.testing.assert_array_equal(np.flatnonzero(kinds & ops._FIRST), firsts)
        np.testing.assert_array_equal(np.flatnonzero(kinds & ops._LAST), lasts)
        assert not np.any(kinds & ~(ops._FIRST | ops._LAST))
        steps = list(zip(rows.tolist(), head.tolist(), cols.tolist()))
        expected = [
            (row, h, col)
            for row in range(by_row.shape[0])
            for h in range(heads or 1)
            for col in np.flatnonzero(by_row[row] != EMPTY)
        ]
        bare = [(row, 0, 0) for row in range(by_row.shape[0]) if not (by_row[row] != EMPTY).any()]
        assert heads is not None or not bare, "a query always sees a key"
        assert sorted(steps) == sorted(expected + bare) and len(set(steps)) == len(steps)
        assert steps == sorted(steps), "a row's heads in turn, a head's blocks in order"


def test_a_schedule_past_the_scalar_memory_is_refused(monkeypatch):
    """The tables rest in scalar memory: a sweep of more steps than
    ``_SCHEDULE_STEPS_MAX`` is refused with the way out named, at trace time."""
    needed = ops._pair_needed(256, 256, 32, 32, True)
    monkeypatch.setattr(ops, "_SCHEDULE_STEPS_MAX", 36 * 2 - 1)
    assert len(ops._tile_schedule(needed)[0]) == 36
    with pytest.raises(ValueError, match="blockwise_attention"):
        ops._tile_schedule(needed, 2)


def _qkv(tq, tk, heads, kv_heads, d=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    return (
        jax.random.normal(k[0], (2, heads, tq, d)),
        jax.random.normal(k[1], (2, kv_heads, tk, d)),
        jax.random.normal(k[2], (2, kv_heads, tk, d)),
        jax.random.normal(k[3], (2, heads, tq, d)),
    )


def _both(fn, q, k, v, mix):
    """The output, and the gradients of a scalar of it."""
    (_, out), grads = jax.jit(jax.value_and_grad(
        lambda *a: (lambda out: (jnp.sum(out * mix), out))(fn(*a)), (0, 1, 2), has_aux=True
    ))(q, k, v)
    return out, grads


# t_q, t_k, block_q, block_k, causal, block_length, window, heads, kv_heads, bf16_dots
CALLS = [
    pytest.param(128, 128, 32, 32, True, 1, None, 2, 2, False, id="causal"),
    pytest.param(128, 128, 32, 32, True, 4, None, 4, 2, False, id="block4-grouped"),
    pytest.param(128, 128, 64, 32, True, 32, None, 2, 1, False, id="block32-unequal"),
    pytest.param(128, 128, 16, 16, True, 1, 80, 4, 2, False, id="window-grouped"),
    pytest.param(128, 128, 32, 16, True, 4, 70, 2, 2, False, id="window-block4-unequal"),
    pytest.param(64, 128, 32, 32, True, 1, None, 4, 2, False, id="keys-past-the-last-query"),
    pytest.param(64, 128, 32, 32, False, 1, None, 4, 2, False, id="no-mask-grouped"),
    pytest.param(128, 128, 32, 32, True, 4, None, 4, 2, True, id="block4-bf16-dots"),
    pytest.param(128, 128, 16, 16, True, 1, 80, 4, 1, True, id="window-bf16-dots"),
]


@pytest.mark.parametrize(
    "tq, tk, block_q, block_k, causal, block_length, window, heads, kv_heads, bf16_dots", CALLS
)
def test_the_kernels_run_every_kind_of_pair_in_one_call(
    monkeypatch, tq, tk, block_q, block_k, causal, block_length, window, heads, kv_heads,
    bf16_dots,
):
    """Forward and the three gradients of the scheduled kernels against
    ``reference_attention`` (``tests/test_attention.py``'s and
    ``test_trunk_attention.py``'s tolerances: float32 sums in another order;
    with ``bf16_dots`` bfloat16's rounding of the operands, 2e-2 of values
    O(1)), and to the bit against the same kernels run over every pair of the
    rectangle: a pair the mask empties adds zeros, so leaving it out of the
    grid changes no value."""
    q, k, v, mix = _qkv(tq, tk, heads, kv_heads)
    classes = _brute_classes(tq, tk, block_q, block_k, causal, block_length, window)
    if causal:
        assert {EMPTY, WHOLE, CUT} == set(np.unique(classes)), "every kind in one call"

    def flash(q, k, v):
        return ops.flash_attention(
            q, k, v, causal, block_q, block_k, True, 128, block_length, bf16_dots, window
        )

    def dense(q, k, v):
        return ops.reference_attention(
            q, k, v, causal=causal, block_length=block_length, window=window
        )

    with jax.default_matmul_precision("highest"):
        out, got = _both(flash, q, k, v, mix)
        ref, want = _both(dense, q, k, v, mix)
        every_pair = lambda *a, **kw: np.ones(classes.shape, bool).tolist()  # noqa: E731
        monkeypatch.setattr(ops, "_pair_needed", every_pair)
        masked, got_masked = _both(flash, q, k, v, mix)
    forward, backward = (2e-2, 5e-2) if bf16_dots else (1e-5, 1e-4)
    np.testing.assert_allclose(out, ref, atol=forward)
    np.testing.assert_array_equal(out, masked)
    for g, w, gm, name in zip(got, want, got_masked, ("dq", "dk", "dv")):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(g, w, atol=backward, err_msg=name)
        np.testing.assert_array_equal(g, gm, err_msg=name)
    if tk > tq and causal:  # no query sees them: the row's one step, hidden whole, wrote zeros
        assert not np.any(np.asarray(got[1])[:, :, tq:]) and not np.any(np.asarray(got[2])[:, :, tq:])
