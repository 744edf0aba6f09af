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


# --------------------------------------------------------------------------
# The form a row rests in (``stored_row_shape``) changes no row: pushes that
# wrap, then samples, give bit for bit what the ring kept in the transitions'
# own shapes gives for the same keys.

TILE_FRAME = (32, 32, 4)  # 4,096 uint8: one whole tile, rests (32, 128)


def _visual_spec(frame_shape):
    from torch_actor_critic_tpu.core.types import MultiObservation

    return MultiObservation(
        features=jax.ShapeDtypeStruct((3,), jnp.float32),
        frame=jax.ShapeDtypeStruct(frame_shape, jnp.uint8),
    )


def _transitions(rng, obs_spec, n, lead=()):
    """``n`` random transitions in the shapes an env gives them."""
    def rows(spec):
        return _random_like(
            rng, jax.ShapeDtypeStruct((n,) + tuple(spec.shape), spec.dtype), lead
        )

    f32 = lambda *shape: rows(jax.ShapeDtypeStruct(shape, jnp.float32))  # noqa: E731
    return Batch(
        states=jax.tree_util.tree_map(rows, obs_spec), actions=f32(ACT_DIM),
        rewards=f32(), next_states=jax.tree_util.tree_map(rows, obs_spec), done=f32(),
    )


def _model_ring(obs_spec, capacity):
    """The ring as it was kept before: every row in its own shape."""
    rows = lambda spec: np.zeros((capacity,) + tuple(spec.shape), spec.dtype)  # noqa: E731
    f32 = lambda *shape: np.zeros((capacity,) + shape, np.float32)  # noqa: E731
    return Batch(
        states=jax.tree_util.tree_map(rows, obs_spec), actions=f32(ACT_DIM),
        rewards=f32(), next_states=jax.tree_util.tree_map(rows, obs_spec), done=f32(),
    )


def _assert_rows_equal(got, want):
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        a, b = np.atleast_1d(np.asarray(a)), np.atleast_1d(np.asarray(b))
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def _pushes_then_samples(obs_spec, members):
    """Three pushes of 4 into rings of 10 (the third wraps), then a sample,
    a member at a time against the model; ``members`` 0 is a single ring."""
    from torch_actor_critic_tpu.buffer.replay import (
        as_observations,
        observation_spec,
    )

    rng = np.random.default_rng(30)
    capacity, n, batch = 10, 4, 16
    spec = observation_spec(
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)
    )
    lead = (members,) if members else ()
    buf = init_replay_buffer(capacity, obs_spec, ACT_DIM)
    if members:
        buf = _stack([buf] * members)
    push_fn = jax.jit(jax.vmap(push) if members else push, donate_argnums=(0,))
    draw = lambda b, k: as_observations(sample(b, k, batch), spec)  # noqa: E731
    sample_fn = jax.jit(jax.vmap(draw) if members else draw)
    models = [(_model_ring(obs_spec, capacity), 0, 0) for _ in range(max(members, 1))]
    for _ in range(3):
        chunk = _transitions(rng, obs_spec, n, lead)
        buf = push_fn(buf, chunk)
        host = jax.device_get(chunk)
        for m in range(len(models)):
            mine = jax.tree_util.tree_map(lambda x: x[m], host) if members else host
            models[m] = _model_push(*models[m], mine, capacity)
    keys = jax.random.split(jax.random.key(7), members) if members else jax.random.key(7)
    got = sample_fn(buf, keys)
    for m, (data, ptr, size) in enumerate(models):
        key = keys[m] if members else keys
        idx = np.asarray(jax.random.randint(key, (batch,), 0, max(size, 1)))
        want = jax.tree_util.tree_map(lambda ring: ring[idx], data)
        _assert_rows_equal(
            jax.tree_util.tree_map(lambda x: x[m], got) if members else got, want
        )
        one = jax.tree_util.tree_map(lambda x: x[m], buf) if members else buf
        assert (int(one.ptr), int(one.size)) == (ptr, size) == (2, 10)
    return buf


FLAT_SPEC = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)


@pytest.mark.parametrize(
    "obs_spec, members, stored_row",
    [
        pytest.param(FLAT_SPEC, 0, (OBS_DIM,), id="flat"),
        pytest.param(_visual_spec(TILE_FRAME), 0, (32, 128), id="visual-in-tiles"),
        pytest.param(_visual_spec((8, 8, 3)), 0, (8, 8, 3), id="visual-own-shape"),
        pytest.param(FLAT_SPEC, 3, (OBS_DIM,), id="members-flat"),
        pytest.param(
            _visual_spec(TILE_FRAME), 3, (32, 128), id="members-visual-in-tiles"
        ),
    ],
)
def test_rows_do_not_depend_on_the_form_they_rest_in(obs_spec, members, stored_row):
    buf = _pushes_then_samples(obs_spec, members)
    last = jax.tree_util.tree_leaves(buf.data.states)[-1]  # the frame, if any
    assert last.shape == ((members,) if members else ()) + (10,) + stored_row


@pytest.mark.parametrize(
    "row, dtype, stored",
    [
        ((64, 64, 3), jnp.uint8, (96, 128)),    # the wall runner's frame
        ((32, 32, 4), jnp.uint8, (32, 128)),
        ((16, 16, 4), jnp.float32, (8, 128)),   # 1,024 words: one tile
        ((84, 84, 3), jnp.uint8, (84, 84, 3)),  # 21,168 bytes: no whole tiles
        ((8, 8, 3), jnp.uint8, (8, 8, 3)),
        ((1024, 17), jnp.float32, (1024, 17)),  # a history: two axes
        ((17,), jnp.float32, (17,)),
        ((), jnp.float32, ()),
    ],
)
def test_stored_row_shape(row, dtype, stored):
    from torch_actor_critic_tpu.buffer.replay import stored_row_shape

    assert stored_row_shape(row, dtype) == stored
    ring = init_replay_buffer(6, jax.ShapeDtypeStruct(row, dtype), ACT_DIM)
    assert ring.data.states.shape == (6,) + stored


@pytest.mark.parametrize("dp", [1, 2])
def test_dp_burst_samples_the_rows_a_ring_in_its_own_shape_would(dp, monkeypatch):
    """The whole burst (push, then sampled updates) on a ``dp`` mesh gives
    the same state, bit for bit, with the frame ring resting in tiles and
    with every row resting in its own shape."""
    from torch_actor_critic_tpu.buffer import replay
    from torch_actor_critic_tpu.parallel import DataParallelSAC, make_mesh
    from torch_actor_critic_tpu.parallel.dp import init_sharded_buffer, shard_chunk
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.utils.config import SACConfig

    frame = (64, 64, 4)  # 16,384 uint8: four tiles, rests (128, 128)
    obs_spec = _visual_spec(frame)
    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=4, update_every=6, buffer_size=20 * dp,
        cnn_dense_size=16,
    )
    env = type("Env", (), dict(act_dim=ACT_DIM, act_limit=1.0, obs_spec=obs_spec))
    rng = np.random.default_rng(31)
    chunks = [_transitions(rng, obs_spec, cfg.update_every, (dp,)) for _ in range(4)]

    def run():
        sac = make_learner(cfg, *build_models(cfg, env), ACT_DIM)
        mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
        learner = DataParallelSAC(sac, mesh)
        example = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)
        state = learner.init_state(jax.random.key(3), example)
        buf = init_sharded_buffer(20, obs_spec, ACT_DIM, mesh)
        shapes = [x.shape for x in jax.tree_util.tree_leaves(buf.data)]
        for chunk in chunks:  # 24 rows into rings of 20: the last push wraps
            state, buf, metrics = learner.update_burst(
                state, buf, shard_chunk(chunk, mesh), cfg.update_every
            )
        return jax.device_get((state, buf.ptr, buf.size, metrics)), shapes

    in_tiles, shapes = run()
    assert (dp, 20, 128, 128) in shapes
    monkeypatch.setattr(replay, "stored_row_shape", lambda row, dtype: tuple(row))
    own_shape, shapes = run()
    assert (dp, 20) + frame in shapes
    _assert_rows_equal(in_tiles, own_shape)


def test_fused_visual_sample_of_a_ring_in_tiles_is_sample_then_decode():
    """``sample_fused_visual`` at float32 on a frame ring resting in tiles:
    bitwise what ``sample`` and the in-model decode give for the same key."""
    from torch_actor_critic_tpu.buffer.replay import (
        as_observations,
        observation_spec,
        sample_fused_visual,
    )

    obs_spec = _visual_spec(TILE_FRAME)
    spec = observation_spec(
        jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)
    )
    rng = np.random.default_rng(32)
    buf = init_replay_buffer(10, obs_spec, ACT_DIM)
    assert buf.data.states.frame.shape == (10, 32, 128)
    for _ in range(3):
        buf = push(buf, _transitions(rng, obs_spec, 4))
    key = jax.random.key(11)
    plain = as_observations(sample(buf, key, 8), spec)
    for normalize in (False, True):
        fused = sample_fused_visual(
            buf, key, 8, jnp.float32, normalize=normalize, impl="xla", obs_spec=spec
        )
        decode = lambda f: f.astype(jnp.float32) / (255.0 if normalize else 1.0)  # noqa: E731
        want = plain.replace(
            states=plain.states.replace(frame=decode(plain.states.frame)),
            next_states=plain.next_states.replace(frame=decode(plain.next_states.frame)),
        )
        _assert_rows_equal(fused, want)


def test_a_buffer_checkpoint_in_the_old_form_restores_into_the_new(tmp_path):
    """A ``BufferState`` saved with every row in its own shape (before PR
    30) comes back in the shapes this build keeps it in, the same rows."""
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    rng = np.random.default_rng(33)
    obs_spec = _visual_spec(TILE_FRAME)
    new = _stack([init_replay_buffer(10, obs_spec, ACT_DIM)] * 2)  # (dp, rows, ...)
    old_data = _transitions(rng, obs_spec, 10, (2,))  # (2, 10) + a transition
    old = new.replace(data=old_data, ptr=jnp.array([3, 7]), size=jnp.array([10, 7]))
    assert old.data.states.frame.shape == (2, 10) + TILE_FRAME
    train_state = {"w": jnp.arange(3.0)}
    ckpt = Checkpointer(tmp_path)
    ckpt.save(1, train_state, old, wait=True)
    _, restored, _ = ckpt.restore(train_state, new)
    assert restored.data.states.frame.shape == (2, 10, 32, 128)
    np.testing.assert_array_equal(restored.ptr, [3, 7])
    as_old = jax.tree_util.tree_map(
        lambda x, o: np.asarray(x).reshape(o.shape), restored.data, old.data
    )
    _assert_rows_equal(as_old, old.data)
    # and one written in the new form restores as it is
    ckpt.save(2, train_state, restored, wait=True)
    _, again, _ = ckpt.restore(train_state, new, epoch=2)
    _assert_rows_equal(again, restored)
