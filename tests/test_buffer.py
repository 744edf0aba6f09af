"""Ring/sampling invariants for the device replay buffer.

The reference never tests its buffers (SURVEY.md §4 "Not tested");
these pin down the ring protocol the reference implements in
``buffer/replay_buffer.py:29-46``: pointer wraparound, size saturation,
oldest-overwrite, and sampling restricted to the valid region.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.buffer import (
    init_replay_buffer,
    init_visual_replay_buffer,
    push,
    sample,
)
from torch_actor_critic_tpu.core.types import Batch

OBS_DIM, ACT_DIM, CAP = 4, 2, 10


def _chunk(start: int, n: int) -> Batch:
    """n transitions whose reward encodes their global index."""
    r = jnp.arange(start, start + n, dtype=jnp.float32)
    return Batch(
        states=jnp.tile(r[:, None], (1, OBS_DIM)),
        actions=jnp.zeros((n, ACT_DIM)),
        rewards=r,
        next_states=jnp.tile(r[:, None] + 0.5, (1, OBS_DIM)),
        done=jnp.zeros((n,)),
    )


def test_push_advances_ptr_and_size():
    buf = init_replay_buffer(CAP, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = push(buf, _chunk(0, 3))
    assert int(buf.ptr) == 3 and int(buf.size) == 3
    buf = push(buf, _chunk(3, 4))
    assert int(buf.ptr) == 7 and int(buf.size) == 7


def test_push_wraparound_overwrites_oldest():
    buf = init_replay_buffer(CAP, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = push(buf, _chunk(0, 8))
    buf = push(buf, _chunk(8, 6))  # wraps: slots 8,9,0,1,2,3
    assert int(buf.ptr) == 4
    assert int(buf.size) == CAP
    rewards = np.asarray(buf.data.rewards)
    # slots 0..3 hold transitions 10..13; slots 4..7 hold 4..7; 8,9 hold 8,9
    np.testing.assert_array_equal(rewards, [10, 11, 12, 13, 4, 5, 6, 7, 8, 9])


def test_sample_only_valid_region():
    buf = init_replay_buffer(CAP, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = push(buf, _chunk(0, 3))  # only rewards 0,1,2 valid
    batch = sample(buf, jax.random.key(0), 256)
    assert set(np.asarray(batch.rewards).tolist()) <= {0.0, 1.0, 2.0}
    # states/next_states must be gathered consistently with rewards
    np.testing.assert_array_equal(
        np.asarray(batch.states)[:, 0], np.asarray(batch.rewards)
    )
    np.testing.assert_array_equal(
        np.asarray(batch.next_states)[:, 0], np.asarray(batch.rewards) + 0.5
    )


def test_sample_covers_full_buffer():
    buf = init_replay_buffer(CAP, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = push(buf, _chunk(0, CAP))
    batch = sample(buf, jax.random.key(1), 1024)
    seen = set(np.asarray(batch.rewards).tolist())
    assert seen == set(float(i) for i in range(CAP))


def test_push_sample_jit_and_donate():
    """push must jit with buffer donation (the trainer's hot path)."""
    buf = init_replay_buffer(CAP, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    push_jit = jax.jit(push, donate_argnums=(0,))
    buf = push_jit(buf, _chunk(0, 4))
    buf = push_jit(buf, _chunk(4, 4))
    assert int(buf.size) == 8
    batch = jax.jit(sample, static_argnums=(2,))(buf, jax.random.key(0), 16)
    assert batch.rewards.shape == (16,)


def test_visual_buffer_uint8_roundtrip():
    from torch_actor_critic_tpu.core.types import MultiObservation

    buf = init_visual_replay_buffer(CAP, feature_dim=3, frame_shape=(8, 8, 3), act_dim=2)
    assert buf.data.states.frame.dtype == jnp.uint8

    n = 4
    obs = MultiObservation(
        features=jnp.ones((n, 3)),
        frame=jnp.full((n, 8, 8, 3), 200, jnp.uint8),
    )
    chunk = Batch(
        states=obs,
        actions=jnp.zeros((n, 2)),
        rewards=jnp.arange(n, dtype=jnp.float32),
        next_states=obs,
        done=jnp.zeros((n,)),
    )
    buf = push(buf, chunk)
    batch = sample(buf, jax.random.key(0), 8)
    assert batch.states.frame.dtype == jnp.uint8
    assert int(batch.states.frame[0, 0, 0, 0]) == 200
    assert batch.states.features.shape == (8, 3)


def test_estimate_buffer_bytes():
    """Planning estimate behind the trainer's HBM-budget warning."""
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.buffer.replay import estimate_buffer_bytes
    from torch_actor_critic_tpu.core.types import MultiObservation

    flat = jax.ShapeDtypeStruct((17,), jnp.float32)
    # 2*17*4 (obs+next) + 6*4 (act) + 8 (reward+done) = 168 B/row
    assert estimate_buffer_bytes(1000, flat, 6) == 168_000

    vis = MultiObservation(
        features=jax.ShapeDtypeStruct((168,), jnp.float32),
        frame=jax.ShapeDtypeStruct((64, 64, 3), jnp.uint8),
    )
    per_row = 2 * (168 * 4 + 64 * 64 * 3) + 56 * 4 + 8
    assert estimate_buffer_bytes(10, vis, 56) == 10 * per_row
    # The motivating case: 1e6 visual transitions ~ 26 GB > any v5e.
    assert estimate_buffer_bytes(1_000_000, vis, 56) > 16 * 1024**3


# --------------------------------------------------------------------------
# push against a NumPy model of ``(ptr + arange(n)) % capacity``, bit for bit.


def _model_push(data, ptr, size, chunk, capacity):
    """The reference's ``store`` n times (ref ``replay_buffer.py:29-43``)."""
    n = len(jax.tree_util.tree_leaves(chunk)[0])
    idx = (ptr + np.arange(n)) % capacity
    data = jax.tree_util.tree_map(lambda r: np.array(r), data)
    for ring, new in zip(
        jax.tree_util.tree_leaves(data), jax.tree_util.tree_leaves(chunk)
    ):
        ring[idx] = new
    return data, (ptr + n) % capacity, min(size + n, capacity)


def _random_ring(rng, capacity, visual, ptr):
    """A ring full of random bits with its write pointer at ``ptr``."""
    if visual:
        buf = init_visual_replay_buffer(
            capacity, feature_dim=3, frame_shape=(4, 4, 3), act_dim=ACT_DIM
        )
    else:
        buf = init_replay_buffer(
            capacity, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM
        )
    data = jax.tree_util.tree_map(lambda x: _random_like(rng, x), buf.data)
    return buf.replace(data=data, ptr=jnp.int32(ptr), size=jnp.int32(ptr))


def _random_like(rng, x, lead=()):
    shape = tuple(lead) + x.shape
    if x.dtype == jnp.uint8:
        return jnp.asarray(rng.integers(0, 256, shape, dtype=np.uint8))
    # Every bit pattern of a finite float32, not only "nice" values.
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _random_chunk(rng, buf, n, lead=()):
    return jax.tree_util.tree_map(
        lambda ring: _random_like(
            rng, jax.ShapeDtypeStruct((n,) + ring.shape[1:], ring.dtype), lead
        ),
        buf.data,
    )


def _assert_same(buf, model):
    data, ptr, size = model
    for got, want in zip(
        jax.tree_util.tree_leaves(buf.data), jax.tree_util.tree_leaves(data)
    ):
        got = np.asarray(got)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(
            got.view(np.uint8), want.view(np.uint8)  # bitwise
        )
    assert int(buf.ptr) == ptr and int(buf.size) == size


def _case_single(rng, capacity, ptr, n, visual=False):
    buf = _random_ring(rng, capacity, visual, ptr)
    chunk = _random_chunk(rng, buf, n)
    model = _model_push(buf.data, ptr, ptr, jax.device_get(chunk), capacity)
    _assert_same(push(buf, chunk), model)


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _case_vmapped(rng, capacity, ptrs, n):
    """``jax.vmap(push)`` with a pointer of its own in every member."""
    members = [_random_ring(rng, capacity, False, p) for p in ptrs]
    chunks = [_random_chunk(rng, members[0], n) for _ in ptrs]
    out = jax.jit(jax.vmap(push), donate_argnums=(0,))(
        _stack(members), _stack(chunks)
    )
    for i, (buf, chunk, p) in enumerate(zip(members, chunks, ptrs)):
        model = _model_push(buf.data, p, p, jax.device_get(chunk), capacity)
        _assert_same(jax.tree_util.tree_map(lambda x: x[i], out), model)


def _case_nested(rng, capacity, ptrs, n):
    """``vmap(vmap(push))``: the member rule under a second batch axis."""
    members = [[_random_ring(rng, capacity, False, p) for p in row] for row in ptrs]
    chunks = [[_random_chunk(rng, members[0][0], n) for _ in row] for row in ptrs]
    out = jax.jit(jax.vmap(jax.vmap(push)))(
        _stack([_stack(row) for row in members]),
        _stack([_stack(row) for row in chunks]),
    )
    for i, row in enumerate(ptrs):
        for j, p in enumerate(row):
            model = _model_push(
                members[i][j].data, p, p, jax.device_get(chunks[i][j]), capacity
            )
            _assert_same(jax.tree_util.tree_map(lambda x: x[i, j], out), model)


def _case_two_donated(rng, capacity, ptr, n):
    """Two pushes in a row under ``jit`` with the ring donated: the
    second wraps."""
    buf = _random_ring(rng, capacity, False, ptr)
    model = (buf.data, ptr, ptr)
    push_jit = jax.jit(push, donate_argnums=(0,))
    for _ in range(2):
        chunk = _random_chunk(rng, buf, n)
        model = _model_push(*model, jax.device_get(chunk), capacity)
        buf = push_jit(buf, chunk)
    _assert_same(buf, model)


PUSH_CASES = [
    pytest.param(_case_single, dict(capacity=10, ptr=2, n=4), id="no-wrap"),
    pytest.param(_case_single, dict(capacity=10, ptr=6, n=4), id="ends-at-the-end"),
    pytest.param(_case_single, dict(capacity=10, ptr=8, n=5), id="wrap-mid-chunk"),
    pytest.param(_case_single, dict(capacity=10, ptr=9, n=4), id="ptr-at-last-row"),
    pytest.param(_case_single, dict(capacity=10, ptr=9, n=1), id="one-row-last"),
    pytest.param(_case_single, dict(capacity=10, ptr=3, n=1), id="one-row"),
    pytest.param(_case_single, dict(capacity=10, ptr=3, n=10), id="whole-ring-at-3"),
    pytest.param(_case_single, dict(capacity=10, ptr=0, n=10), id="whole-ring-at-0"),
    pytest.param(_case_single, dict(capacity=10, ptr=7, n=8), id="windows-overlap"),
    pytest.param(_case_single, dict(capacity=10, ptr=1, n=8), id="overlap-no-wrap"),
    pytest.param(
        _case_single, dict(capacity=10, ptr=8, n=5, visual=True),
        id="uint8-frames-wrap",
    ),
    pytest.param(
        _case_single, dict(capacity=10, ptr=2, n=4, visual=True),
        id="uint8-frames",
    ),
    pytest.param(
        _case_vmapped, dict(capacity=10, ptrs=(0, 3, 8, 9), n=4),
        id="vmap-own-ptr-one-wraps",
    ),
    pytest.param(
        _case_vmapped, dict(capacity=6, ptrs=(5, 0, 2), n=6),
        id="vmap-whole-ring",
    ),
    pytest.param(
        _case_nested, dict(capacity=10, ptrs=((1, 8), (9, 5)), n=4),
        id="vmap-of-vmap",
    ),
    pytest.param(
        _case_two_donated, dict(capacity=10, ptr=3, n=4), id="two-donated"
    ),
]


@pytest.mark.parametrize("case, kwargs", PUSH_CASES)
def test_push_matches_numpy_model(case, kwargs):
    case(np.random.default_rng(25), **kwargs)


def test_push_rejects_chunk_larger_than_ring():
    buf = init_replay_buffer(4, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    with pytest.raises(ValueError, match="exceeds buffer capacity"):
        push(buf, _chunk(0, 5))
