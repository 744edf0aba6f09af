"""A window's chunk as one block and one transfer (parallel/chunk_block.py).

Two things are pinned here. The block's contract: ``_build_chunk`` returns
what stacking leaf by leaf returned, as views of one block that is never
written again. And equality: a chunk that crosses as one block is, on the
device, the chunk that crosses leaf by leaf, bit for bit, so nothing
downstream can tell which way it came.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.core.types import Batch, MultiObservation
from torch_actor_critic_tpu.parallel import (
    DataParallelSAC,
    chunk_block,
    init_sharded_buffer,
    make_mesh,
    shard_chunk_from_local,
)
from torch_actor_critic_tpu.sac.trainer import Trainer, build_models, make_learner
from torch_actor_critic_tpu.utils.config import SACConfig

PACKED, LEAFWISE = "chunk/packed_transfers", "chunk/leafwise_transfers"

# The three families' chunk rows as their cells stage them (PERF.md
# section 4): observation row(s), action width, steps a window.
FAMILIES = {
    "mlp": (lambda: (17,), 6, 50),
    "visual": (lambda: MultiObservation(features=(168,), frame=(64, 64, 3)), 56, 50),
    "history": (lambda: (1024, 17), 6, 10),
}


def _obs(rng, n, rows):
    if isinstance(rows, MultiObservation):
        return MultiObservation(
            features=rng.standard_normal((n,) + rows.features).astype(np.float32),
            frame=rng.integers(0, 256, (n,) + rows.frame, dtype=np.uint8),
        )
    return rng.standard_normal((n,) + rows).astype(np.float32)


def _staging(rows, act_dim, window, n=1, seed=0):
    """What the Trainer stages: one batched transition a lockstep step,
    rewards float32 and ``done`` as the env gave it (bool)."""
    rng = np.random.default_rng(seed)
    return [
        (
            _obs(rng, n, rows),
            rng.uniform(-1, 1, (n, act_dim)).astype(np.float32),
            rng.standard_normal(n).astype(np.float32),
            _obs(rng, n, rows),
            rng.random(n) < 0.3,
        )
        for _ in range(window)
    ]


def _stacked(staging) -> Batch:
    """The chunk as the Trainer built it before the block: every leaf
    stacked into an array of its own."""

    def stack_field(idx):
        return jax.tree_util.tree_map(
            lambda *xs: np.stack(xs, axis=1), *[tr[idx] for tr in staging]
        )

    return Batch(
        states=stack_field(0),
        actions=stack_field(1),
        rewards=stack_field(2).astype(np.float32),
        next_states=stack_field(3),
        done=stack_field(4).astype(np.float32),
    )


def _counts():
    return dict(chunk_block.transfers)


def _added(before):
    return {k: chunk_block.transfers[k] - before[k] for k in before}


def _assert_same_device_chunk(a: Batch, b: Batch):
    assert jax.tree_util.tree_structure(a) == jax.tree_util.tree_structure(b)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert isinstance(x, jax.Array) and isinstance(y, jax.Array)
        assert (x.shape, x.dtype) == (y.shape, y.dtype)
        assert x.sharding == y.sharding
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ------------------------------------------------------- the block's contract


@pytest.mark.parametrize("family", FAMILIES)
def test_build_chunk_equals_stacking_and_is_one_aligned_block(family):
    rows, act_dim, window = FAMILIES[family]
    staging = _staging(rows(), act_dim, window, n=2)
    chunk = Trainer._build_chunk(None, staging)  # as the benchmark calls it
    want = _stacked(staging)
    assert jax.tree_util.tree_structure(chunk) == jax.tree_util.tree_structure(want)
    leaves = jax.tree_util.tree_leaves(chunk)
    for got, ref in zip(leaves, jax.tree_util.tree_leaves(want)):
        assert isinstance(got, np.ndarray)
        assert (got.shape, got.dtype) == (ref.shape, ref.dtype)
        np.testing.assert_array_equal(got, ref)
    # one owner, every leaf at a multiple of 128 bytes in every slice
    owners = {id(chunk_block._owner(x)) for x in leaves}
    assert len(owners) == 1
    for x in leaves:
        assert x.ctypes.data % chunk_block.ALIGN == 0
        assert x.strides[0] % chunk_block.ALIGN == 0
    block, layout = chunk_block.find_block(leaves)
    assert block.dtype == np.uint8 and block.shape[0] == 2
    assert block.flags.c_contiguous and block.ctypes.data % chunk_block.ALIGN == 0
    assert all(off % chunk_block.ALIGN == 0 for off, _, _, _ in layout)
    assert [(s, d) for _, s, d, _ in layout] == [(x.shape[1:], x.dtype) for x in leaves]
    # rows staged in C order lie in C order
    assert all(order == tuple(range(len(s))) for _, s, _, order in layout)


def test_build_chunk_casts_rewards_and_done_and_promotes_like_stack():
    """``rewards`` / ``done`` come out float32 whatever was staged; an
    observation staged at two widths comes out at the wider, as
    ``np.stack`` promotes."""
    staging = _staging((3,), 2, 4, n=2)
    staging = [
        (o, a, r.astype(np.float64), no, d) for o, a, r, no, d in staging
    ]
    o, a, r, no, d = staging[1]
    staging[1] = (o.astype(np.float64), a, r, no, d)
    chunk = Trainer._build_chunk(None, staging)
    want = _stacked(staging)
    assert chunk.rewards.dtype == chunk.done.dtype == np.float32
    assert chunk.states.dtype == np.float64
    for got, ref in zip(
        jax.tree_util.tree_leaves(chunk), jax.tree_util.tree_leaves(want)
    ):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(got, ref)


def _in_memory_order(x, order):
    """``x``'s values in an array whose axes lie in memory from the
    slowest to the fastest as ``order`` names them."""
    back = sorted(range(len(order)), key=order.__getitem__)
    return np.ascontiguousarray(x.transpose(order)).transpose(back)


def test_rows_fetched_from_a_device_keep_their_memory_order_in_the_block():
    """The benchmark's staged frames come from ``jax.device_get`` and lie
    H, C, W in memory (the chip's strides, PERF.md section 6, PR 35):
    the block keeps that order as ``np.stack`` did, so staging is a
    plain copy, and the device puts the axes back."""
    rows, act_dim, window = FAMILIES["visual"]
    staging = [
        tuple(
            jax.tree_util.tree_map(
                lambda x: _in_memory_order(x, (0, 1, 3, 2)) if x.ndim == 4 else x,
                leaf,
            )
            for leaf in tr
        )
        for tr in _staging(rows(), act_dim, window, n=2)
    ]
    assert staging[0][0].frame.strides == (12288, 192, 1, 64)
    chunk = Trainer._build_chunk(None, staging)
    want = _stacked(staging)
    for got, ref in zip(
        jax.tree_util.tree_leaves(chunk), jax.tree_util.tree_leaves(want)
    ):
        assert (got.shape, got.dtype) == (ref.shape, ref.dtype)
        assert got.strides[1:] == ref.strides[1:]  # np.stack's own order
        np.testing.assert_array_equal(got, ref)
    assert chunk.states.frame.strides[1:] == (12288, 192, 1, 64)
    found = chunk_block.find_block(jax.tree_util.tree_leaves(chunk))
    assert found is not None
    assert found[1][1][3] == (0, 1, 3, 2)  # window, H, C, W
    mesh = make_mesh(dp=2, devices=jax.devices()[:2])
    before = _counts()
    packed = shard_chunk_from_local(chunk, mesh, sp=1)
    assert _added(before) == {PACKED: 1, LEAFWISE: 0}
    _assert_same_device_chunk(
        packed,
        shard_chunk_from_local(jax.tree_util.tree_map(np.array, want), mesh, sp=1),
    )


def test_build_chunk_refuses_steps_of_different_shapes():
    """An assignment into a view would broadcast a row that ``np.stack``
    refused; the block refuses it too."""
    staging = _staging((3,), 2, 4, n=2)
    o, a, r, no, d = staging[2]
    staging[2] = (o[:1], a, r, no, d)
    with pytest.raises(ValueError, match="same shape"):
        Trainer._build_chunk(None, staging)


def test_a_second_window_leaves_the_first_chunk_on_the_device_unchanged():
    """XLA's CPU client aliases aligned numpy memory, so a block written
    again after ``device_put`` would change a chunk in flight: every
    window gets a block of its own."""
    mesh = make_mesh(dp=2)
    rows, act_dim, window = FAMILIES["visual"]
    first = _staging(rows(), act_dim, window, n=2, seed=1)
    local = Trainer._build_chunk(None, first)
    want = jax.tree_util.tree_map(np.array, local)  # a copy, taken now
    on_device = shard_chunk_from_local(local, mesh, sp=1)
    jax.block_until_ready(on_device)
    first_block = chunk_block.find_block(jax.tree_util.tree_leaves(local))[0]
    del local
    for seed in (2, 3, 4):
        again = Trainer._build_chunk(
            None, _staging(rows(), act_dim, window, n=2, seed=seed)
        )
        block = chunk_block.find_block(jax.tree_util.tree_leaves(again))[0]
        assert not np.shares_memory(block, first_block)
        jax.block_until_ready(shard_chunk_from_local(again, mesh, sp=1))
    for got, ref in zip(
        jax.tree_util.tree_leaves(on_device), jax.tree_util.tree_leaves(want)
    ):
        np.testing.assert_array_equal(np.asarray(got), ref)


@pytest.mark.parametrize(
    "spoil",
    ["separate_leaves", "one_leaf_replaced", "float64_leaf", "bool_leaf",
     "strided_inside", "jax_leaves"],
)
def test_what_is_not_one_block_crosses_leaf_by_leaf(spoil):
    mesh = make_mesh(dp=2)
    staging = _staging((17,), 6, 8, n=2)
    chunk = Trainer._build_chunk(None, staging)
    if spoil == "separate_leaves":
        chunk = jax.tree_util.tree_map(np.array, chunk)
    elif spoil == "one_leaf_replaced":
        chunk = chunk.replace(actions=np.array(chunk.actions))
    elif spoil == "float64_leaf":
        # would be converted, not bitcast, on its way to the device
        staging[0] = (staging[0][0].astype(np.float64),) + staging[0][1:]
        chunk = Trainer._build_chunk(None, staging)
    elif spoil == "bool_leaf":
        views = chunk_block.block_views(2, [((8,), np.bool_), ((8, 6), np.float32)])
        views[0][:] = chunk.done > 0
        views[1][:] = chunk.actions
        chunk = chunk.replace(done=views[0], actions=views[1])
    elif spoil == "strided_inside":
        wide = chunk_block.block_views(2, [((8, 12), np.float32)])[0]
        wide[:] = 0
        chunk = jax.tree_util.tree_map(lambda x: x, chunk).replace(
            actions=wide[:, :, ::2]
        )
    elif spoil == "jax_leaves":
        chunk = jax.tree_util.tree_map(jnp.asarray, chunk)
    assert chunk_block.find_block(jax.tree_util.tree_leaves(chunk)) is None
    before = _counts()
    placed = shard_chunk_from_local(chunk, mesh, sp=1)
    assert _added(before) == {PACKED: 0, LEAFWISE: 1}
    for got, ref in zip(
        jax.tree_util.tree_leaves(placed), jax.tree_util.tree_leaves(chunk)
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref).astype(got.dtype))


def test_a_mesh_that_reaches_past_this_process_crosses_leaf_by_leaf():
    """The choice asks the sharding, not the process count."""
    chunk = Trainer._build_chunk(None, _staging((17,), 6, 4, n=2))
    far = types.SimpleNamespace(is_fully_addressable=False)
    before = _counts()
    assert chunk_block.place_block(chunk, None, far) is None
    assert _added(before) == {PACKED: 0, LEAFWISE: 1}


# ------------------------------------------------------------------ equality


@pytest.mark.parametrize("dp", [1, 4])
@pytest.mark.parametrize("family", FAMILIES)
def test_packed_chunk_equals_leafwise_chunk_on_the_device(family, dp):
    rows, act_dim, window = FAMILIES[family]
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    local = Trainer._build_chunk(None, _staging(rows(), act_dim, window, n=dp))
    loose = jax.tree_util.tree_map(np.array, local)
    before = _counts()
    packed = shard_chunk_from_local(local, mesh, sp=1)
    assert _added(before) == {PACKED: 1, LEAFWISE: 0}
    leafwise = shard_chunk_from_local(loose, mesh, sp=1)
    assert _added(before) == {PACKED: 1, LEAFWISE: 1}
    _assert_same_device_chunk(packed, leafwise)
    for x in jax.tree_util.tree_leaves(packed):
        assert len(x.sharding.device_set) == dp


def test_packed_chunk_keeps_the_history_axis_sharded_over_sp():
    """On an ``sp`` mesh a history leaf is sharded over its T axis too
    (``_leaf_spec``); the unpacking gives that sharding itself."""
    mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
    local = Trainer._build_chunk(None, _staging((8, 5), 2, 6, n=2))
    loose = jax.tree_util.tree_map(np.array, local)
    packed = shard_chunk_from_local(local, mesh, sp=2)
    leafwise = shard_chunk_from_local(loose, mesh, sp=2)
    _assert_same_device_chunk(packed, leafwise)
    assert packed.states.sharding.spec == jax.sharding.PartitionSpec("dp", None, "sp")
    assert packed.actions.sharding.spec == jax.sharding.PartitionSpec("dp")


# Small learners of the three families; the chunk keeps its family's form.
BURST_FAMILIES = {
    "mlp": (lambda: jax.ShapeDtypeStruct((17,), jnp.float32), 6, {}),
    "visual": (
        lambda: MultiObservation(
            features=jax.ShapeDtypeStruct((12,), jnp.float32),
            frame=jax.ShapeDtypeStruct((16, 16, 3), jnp.uint8),
        ),
        3,
        dict(filters=(8, 16), kernel_sizes=(4, 3), strides=(2, 1)),
    ),
    "history": (
        lambda: jax.ShapeDtypeStruct((8, 5), jnp.float32), 2,
        dict(history_len=8, seq_d_model=16, seq_num_heads=2, seq_num_layers=1),
    ),
}


@pytest.mark.parametrize("dp", [1, 4])
@pytest.mark.parametrize("family", BURST_FAMILIES)
def test_one_update_burst_from_either_chunk_is_identical(family, dp):
    obs_spec, act_dim, fields = BURST_FAMILIES[family]
    obs_spec = obs_spec()
    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=4, **fields)
    env = types.SimpleNamespace(obs_spec=obs_spec, act_dim=act_dim, act_limit=1.0)
    actor_def, critic_def = build_models(cfg, env)
    mesh = make_mesh(dp=dp, devices=jax.devices()[:dp])
    learner = DataParallelSAC(make_learner(cfg, actor_def, critic_def, act_dim), mesh)
    rows = jax.tree_util.tree_map(lambda s: tuple(s.shape), obs_spec)
    local = Trainer._build_chunk(None, _staging(rows, act_dim, 8, n=dp, seed=7))
    loose = jax.tree_util.tree_map(np.array, local)
    example = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)

    def burst(chunk):
        state = learner.init_state(jax.random.key(0), example)
        ring = init_sharded_buffer(32, obs_spec, act_dim, mesh, sp=learner.effective_sp)
        placed = shard_chunk_from_local(chunk, mesh, sp=learner.effective_sp)
        return jax.device_get(learner.update_burst(state, ring, placed, 2))

    before = _counts()
    from_block, from_leaves = burst(local), burst(loose)
    assert _added(before) == {PACKED: 1, LEAFWISE: 1}
    def bits(x):
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(x)

    for a, b in zip(
        jax.tree_util.tree_leaves(from_block), jax.tree_util.tree_leaves(from_leaves)
    ):
        np.testing.assert_array_equal(bits(a), bits(b))
    assert np.isfinite(np.asarray(from_block[2]["loss_q"])).all()


# --------------------------------------------------- who takes which path


def test_population_place_chunk_crosses_as_one_block():
    """A population's chunk stacks members on the leading axis; the
    block treats it as ``n_local``."""
    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.parallel.population import PopulationLearner
    from torch_actor_critic_tpu.sac import SAC

    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=4)
    sac = SAC(cfg, Actor(act_dim=6, hidden_sizes=(16, 16)),
              DoubleCritic(hidden_sizes=(16, 16)), 6)
    local = Trainer._build_chunk(None, _staging((17,), 6, 5, n=4))
    loose = jax.tree_util.tree_map(np.array, local)
    for mesh in (None, make_mesh(dp=1, devices=jax.devices()[:1]),
                 make_mesh(dp=2, devices=jax.devices()[:2])):
        pop = PopulationLearner(sac, 4, mesh)
        before = _counts()
        packed = pop.place_chunk(local)
        assert _added(before) == {PACKED: 1, LEAFWISE: 0}
        leafwise = pop.place_chunk(loose)
        assert _added(before) == {PACKED: 1, LEAFWISE: 1}
        for x, y in zip(jax.tree_util.tree_leaves(packed),
                        jax.tree_util.tree_leaves(leafwise)):
            assert (x.shape, x.dtype) == (y.shape, y.dtype)
            assert x.sharding.device_set == y.sharding.device_set
            # on one device nothing is committed, as jnp.asarray leaves it
            assert x.committed == y.committed
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_trainer_windows_cross_packed_and_the_prefetchers_refill_leafwise(tmp_path):
    """A ``Trainer.train`` run on a fully addressable mesh: every window's
    chunk crosses as one block, every refill chunk (the prefetcher's own
    arrays) leaf by leaf, and the recorder says so in the epoch event
    and in ``snapshot()``."""
    import json

    from torch_actor_critic_tpu.utils.tracking import Tracker

    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=8, epochs=2, steps_per_epoch=60,
        start_steps=20, update_after=20, update_every=10, buffer_size=100,
        max_ep_len=100, telemetry=True,
        replay_tiers="host", replay_refill=2, replay_prefetch=False,
    )
    tracker = Tracker(experiment="t", root=tmp_path)
    tr = Trainer("Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker)
    before = _counts()
    try:
        tr.train()
    finally:
        tr.close()
    windows = cfg.epochs * cfg.steps_per_epoch // cfg.update_every
    refills = int(tracker.metrics()[-1]["replay/refills_served"])
    assert refills > 0
    assert _added(before) == {PACKED: windows, LEAFWISE: refills}
    counters = tr.telemetry.snapshot()["counters"]
    assert counters[PACKED] == windows and counters[LEAFWISE] == refills
    events = [
        json.loads(line)
        for line in (tracker.run_dir / "telemetry.jsonl").read_text().splitlines()
    ]
    epochs = [e for e in events if e["type"] == "epoch"]
    assert [e["counters"][PACKED] for e in epochs] == [windows // 2, windows]
