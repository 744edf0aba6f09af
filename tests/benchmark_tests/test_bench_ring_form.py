"""How the program stores its ring is the program's business (ROADMAP D11):
with every ring leaf kept ``(capacity, prod(row shape))`` the seeded rows, the
rows the reference is fed and every number ``correct`` compares are what they
are with the ring stored as today, digit for digit."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_cut import cut

from benchmark.drivers import _common
from benchmark.harness import data, main, registry


@pytest.fixture
def flat_ring(monkeypatch):
    """The program's ring with every leaf stored flat: a frame ``(capacity,
    H*W*C)``, a reward ``(capacity, 1)``.  ``push`` reshapes the chunk it is
    given, ``sample`` the batch it hands on; nothing in the program changes."""
    from torch_actor_critic_tpu.buffer import replay
    from torch_actor_critic_tpu.parallel import dp
    from torch_actor_critic_tpu.sac import algorithm, ondevice

    real_init, real_push, real_sample = replay.init_replay_buffer, replay.push, replay.sample
    row_shapes = []  # of one transition's leaves, in the ring's leaf order

    def flat(tree):
        return jax.tree_util.tree_map(lambda x: x.reshape(x.shape[0], -1), tree)

    def init(capacity, obs_spec, act_dim, act_dtype=jnp.float32):
        state = real_init(capacity, obs_spec, act_dim, act_dtype)
        row_shapes[:] = [x.shape[1:] for x in jax.tree_util.tree_leaves(state.data)]
        return state.replace(data=flat(state.data))

    def push(state, chunk):
        return real_push(state, flat(chunk))

    def sample(state, key, batch_size):
        leaves, treedef = jax.tree_util.tree_flatten(real_sample(state, key, batch_size))
        return jax.tree_util.tree_unflatten(treedef, [
            x.reshape((batch_size,) + shape) for x, shape in zip(leaves, row_shapes)
        ])

    for module in (replay, dp, ondevice):
        monkeypatch.setattr(module, "init_replay_buffer", init)
    for module in (algorithm, dp, ondevice):
        monkeypatch.setattr(module, "push", push)
    monkeypatch.setattr(algorithm, "sample", sample)
    return row_shapes


def _rehearse(cell_name, monkeypatch):
    """A whole rehearsal of the cell, and what its driver held after set-up."""
    bench, cell, config = cut(cell_name)
    seen = {}

    class Watched(registry.load_driver(cell["driver"])):
        def setup(self):
            super().setup()
            seen["stored"] = [x.shape for x in jax.tree_util.tree_leaves(self.buffer.data)]
            seen["pre_rows"] = self.pre_rows

    with monkeypatch.context() as m:
        m.setattr(registry, "load_driver", lambda *a, **k: Watched)
        result = main.run_cell(
            bench, cell, config, seed=2_800_000_007, seconds=0.3, trace=False, rehearsal=True
        )
    return result, seen


@pytest.mark.parametrize("cell_name", ["wallrunner_cnn_burst", "cheetah_pop32_fused"])
def test_a_ring_stored_flat_changes_no_number_compared(cell_name, request, monkeypatch):
    as_today, seen_today = _rehearse(cell_name, monkeypatch)
    row_shapes = request.getfixturevalue("flat_ring")
    flat, seen_flat = _rehearse(cell_name, monkeypatch)
    # the stub took: every stored leaf is (streams, capacity, prod(row))
    assert seen_flat["stored"] and all(len(shape) == 3 for shape in seen_flat["stored"])
    assert [shape[2] for shape in seen_flat["stored"]] == [math.prod(row) for row in row_shapes]
    assert seen_flat["stored"] != seen_today["stored"]
    # the rows the reference is fed are the same transitions, in a transition's shape
    for a, b in zip(jax.tree_util.tree_leaves(seen_today["pre_rows"]),
                    jax.tree_util.tree_leaves(seen_flat["pre_rows"])):
        assert a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    assert as_today["correct"] is True and flat["correct"] is True
    assert flat["comparisons"] == as_today["comparisons"]  # digit for digit
    assert set(flat["comparisons"]) >= {
        "loss_q.rel_gap", "loss_pi.rel_gap", "adam_nu.worst_leaf_gap",
        "param_change.worst_leaf_gap", "ring_write_pointer.gap",
    }


def _visual_ring(frame_stored, capacity=40, streams=2):
    from torch_actor_critic_tpu.buffer.replay import init_visual_replay_buffer

    one = jax.eval_shape(lambda: init_visual_replay_buffer(capacity, 5, (6, 4, 3), 2).data)
    spec = _common.EnvSpec({
        "family": "visual", "act_dim": 2, "act_limit": 1.0, "feature_dim": 5,
        "frame": [6, 4, 3],
    })
    rows = data.transition_rows(one, spec.obs_spec, 2)
    restore = lambda x, shape: jax.ShapeDtypeStruct((streams, capacity) + shape, x.dtype)  # noqa: E731
    stored = jax.tree_util.tree_map(lambda x: restore(x, x.shape[1:]), one)
    stored = stored.replace(
        states=stored.states.replace(frame=restore(one.states.frame, frame_stored)),
        next_states=stored.next_states.replace(frame=restore(one.states.frame, frame_stored)),
    )
    return stored, rows


@pytest.mark.parametrize("frame_stored", [(72,), (6, 12), (3, 4, 6)],
                         ids=["flat", "rows_of_pixels", "channels_first_shape"])
def test_seeded_rows_do_not_depend_on_the_stored_shape(frame_stored):
    """The same seed fills the same transitions whatever shape a row is stored
    in, slab by slab too, and reads them back in the transition's shape."""
    today, rows = _visual_ring((6, 4, 3))
    stored, _ = _visual_ring(frame_stored)
    key = data.data_key(2_800_000_011, 2)
    ring_today = data.fill_transitions(key, today, slab=16, rows=rows)
    unaware = data.fill_transitions(key, today, slab=16)  # as before this shape was told
    ring = data.fill_transitions(key, stored, slab=16, rows=rows)
    assert ring.states.frame.shape == (2, 40) + frame_stored
    for a, b, c in zip(*(jax.tree_util.tree_leaves(r) for r in (ring_today, unaware, ring))):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(np.asarray(a).reshape(c.shape), c)
    idx = jnp.array([[3, 39, 0], [7, 7, 21]])  # (streams, batch)
    got = _common.gather_rows(ring, idx, rows)
    want = _common.gather_rows(ring_today, idx, rows)
    assert got.states.frame.shape == (2, 3, 6, 4, 3) and got.rewards.shape == (2, 3)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got.states.frame[1, 2], ring_today.states.frame[1, 21])


def test_a_stored_row_of_another_size_is_refused():
    stored, rows = _visual_ring((71,))
    with pytest.raises(ValueError, match=r"frame.*stores rows of \(71,\)"):
        data.fill_transitions(data.data_key(1, 2), stored, rows=rows)
