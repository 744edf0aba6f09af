"""What ISSUE 36 settled, held by test: the staged pool lies as an env's rows
lie and stacks to the same chunks; every limit of ``correct`` lies between
the chip readings it was set from (``benchmark/data/limit_readings.json`` and
every ``limit_readings.<cell>.json`` a later PR adds beside it), in every
cell the benchmark has, by ``bench_cut``'s rules."""

import bench_cut
import numpy as np
import pytest

from benchmark.drivers import burst

READINGS = bench_cut.limit_readings()
NUMBERS = [(cell, number) for cell in sorted(READINGS) for number in READINGS[cell]]


def _fetched_pool(n_windows: int, rows: int):
    """A pool ``Batch`` as ``jax.device_get`` hands it back from the v5e: every
    leaf in another axis order than C, the frames' step axis fastest."""
    from torch_actor_critic_tpu.core.types import Batch, MultiObservation

    rng = np.random.default_rng(7)
    steps = n_windows * rows

    def leaf(shape, dtype):
        values = rng.integers(0, 255, size=(1, steps) + shape).astype(dtype)
        order = (0,) + tuple(range(2, values.ndim)) + (1,)  # steps last in memory
        return np.ascontiguousarray(values.transpose(order)).transpose(
            (0, values.ndim - 1) + tuple(range(1, values.ndim - 1))
        )

    obs = lambda: MultiObservation(  # noqa: E731
        features=leaf((12,), np.float32), frame=leaf((8, 8, 3), np.uint8)
    )
    return Batch(
        states=obs(), actions=leaf((5,), np.float32), rewards=leaf((), np.float32),
        next_states=obs(), done=leaf((), np.float32),
    )


def test_the_staged_pool_is_c_ordered_and_stacks_to_the_same_chunks():
    """Same bytes, other strides: every staged leaf of the pool the window
    reads is C-contiguous, and each window's chunk equals, byte for byte, the
    one stacked from the pool as it was fetched."""
    import jax

    from torch_actor_critic_tpu.sac.trainer import Trainer

    n_windows, rows = 3, 10
    fetched = _fetched_pool(n_windows, rows)
    assert not fetched.states.frame.flags["C_CONTIGUOUS"]
    old = burst.staged_windows(fetched, n_windows, rows)
    new = burst.staged_windows(burst.c_ordered(fetched), n_windows, rows)
    assert len(new) == n_windows and all(len(w) == rows for w in new)
    for step in (s for w in new for s in w):
        assert all(x.flags["C_CONTIGUOUS"] for x in jax.tree_util.tree_leaves(step))
    assert not all(
        x.flags["C_CONTIGUOUS"] for x in jax.tree_util.tree_leaves(old[0][0])
    )
    for w_old, w_new in zip(old, new):
        a = jax.tree_util.tree_leaves(Trainer._build_chunk(None, w_old))
        b = jax.tree_util.tree_leaves(Trainer._build_chunk(None, w_new))
        assert len(a) == len(b) == 7
        for x, y in zip(a, b):
            assert x.shape == y.shape and x.dtype == y.dtype
            assert np.ascontiguousarray(x).tobytes() == np.ascontiguousarray(y).tobytes()


@pytest.mark.parametrize("cell,number", NUMBERS, ids=lambda v: v)
def test_a_limit_lies_between_its_recorded_readings(cell, number):
    """``bench_cut.check_limit``: over the largest sound reading with half of
    it to spare; a separating number's under the float8 control's smallest."""
    bench_cut.check_limit(cell, number)


@pytest.mark.parametrize("cell", bench_cut.CELLS)
def test_every_cell_has_a_number_its_control_fails(cell):
    """Every cell, the parked among them, has its readings in one of the
    files, for every number it limits, and a number its control fails."""
    bench_cut.check_control_fails_a_number(cell)
