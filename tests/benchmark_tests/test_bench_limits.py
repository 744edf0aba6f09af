"""What ISSUE 36 settled, held by test: the staged pool lies as an env's rows
lie and stacks to the same chunks; every limit of ``correct`` lies between
the chip readings it was set from (``benchmark/data/limit_readings.json``);
the benchmark's spans are read apart by a reader each."""

import json
import os
import types

import numpy as np
import pytest
from bench_cut import ROOT

from benchmark.drivers import burst
from benchmark.harness import registry, spans

with open(os.path.join(ROOT, "benchmark", "data", "limit_readings.json")) as f:
    READINGS = json.load(f)
NUMBERS = [(cell, number) for cell in sorted(READINGS["cells"]) for number in READINGS["cells"][cell]]


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
    """The cell file's limit stands over the largest sound reading with half
    of it to spare (a sound reading past two thirds of a limit moves it), and
    a separating number's stands under the smallest reading of the float8
    control."""
    entry = READINGS["cells"][cell][number]
    limits = registry.load_workload(cell)["limits"]
    limit = (
        registry.load_workload(cell)["traffic"]["router_disagree_limit"]
        if number == "router_choices" else limits[number]
    )
    assert entry["limit"] == limit
    sound, control = entry["sound_max"], entry["control_min"]
    assert entry["sound_seeds"] >= 12
    assert sound <= limit * 2 / 3, (sound, limit)
    if entry["separates"]:
        assert entry["control_seeds"] >= 3
        assert control >= 3 * sound and limit < control, (sound, limit, control)
        # more of the room above the lower reading than under the upper one
        assert limit / sound >= control / limit or limit >= 2 * sound
    else:
        assert control < 3 * sound or limit < control


def test_every_cell_has_a_number_its_control_fails():
    for cell, numbers in READINGS["cells"].items():
        assert any(e["separates"] and e["control_min"] > e["limit"] for e in numbers.values()), cell
    cells = {w["name"] for w in registry.load_benchmark(parked=True)["workloads"]}
    assert set(READINGS["cells"]) == cells


@pytest.mark.parametrize("name", ["stage", "place_chunk", "burst_dispatch"])
def test_a_span_is_read_apart_and_silent_where_there_is_none(name):
    """``host.<span>_ms`` is the span's mean over the run's windows; the two
    that ``host.stage_place_ms`` sums add up to it; a run without the span
    (the fused cell) reads nothing."""
    recorded = spans.Spans()
    recorded.records += [
        ("stage", 0.0, 0.004), ("place_chunk", 0.004, 0.002),
        ("burst_dispatch", 0.006, 0.001), ("stage", 0.03, 0.002),
        ("place_chunk", 0.032, 0.002), ("burst_dispatch", 0.034, 0.003),
    ]
    ctx = types.SimpleNamespace(spans=recorded, n_windows=2)
    read = registry.load_layer_metric(f"host.{name}_ms")
    want = {"stage": 3.0, "place_chunk": 2.0, "burst_dispatch": 2.0}
    assert read(ctx) == pytest.approx(want[name])
    both = registry.load_layer_metric("host.stage_place_ms")(ctx)
    assert both == pytest.approx(want["stage"] + want["place_chunk"])
    silent = types.SimpleNamespace(spans=spans.Spans(), n_windows=2)
    assert read(silent) is None
    assert read(types.SimpleNamespace(spans=recorded, n_windows=0)) is None
    entry = next(m for m in registry.load_benchmark()["per_layer"] if m["name"] == f"host.{name}_ms")
    assert entry["layer"] == "host loop" and entry["moves"] == "grad_steps_per_s"
    assert entry["workloads"] == ["wallrunner_cnn_burst", "sdar30b_a3b_trunk_burst"]
