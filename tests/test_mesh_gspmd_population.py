"""The member-sharded fused population on the named-mesh GSPMD substrate
(PR 8): the member axis laid over ``dp``, sharded against unsharded streams,
the PBT gather across devices, checkpoint and resume to the bit, the meshes
it refuses, and the driver's choice to shard — on the forced 8-device CPU
mesh (conftest.py). The substrate itself is ``test_mesh_gspmd.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.utils.config import SACConfig

# ------------------------------------------- member-sharded population


def _pop_loop(mesh, n_members=8, pbt=True):
    from torch_actor_critic_tpu.envs.ondevice import PendulumJax
    from torch_actor_critic_tpu.sac.ondevice import PopulationOnDeviceLoop

    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=8)
    sac = SAC(
        cfg,
        Actor(act_dim=1, hidden_sizes=cfg.hidden_sizes, act_limit=2.0),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        1,
    )
    return PopulationOnDeviceLoop(
        sac, PendulumJax, n_members=n_members, n_envs=2, pbt=pbt, mesh=mesh
    )


def test_population_member_axis_sharded_over_dp():
    """``--population 8`` on a dp=4 mesh: every member-stacked leaf —
    params, optimizer state, replay rings, env states, PRNG streams —
    spreads P('dp') across the 4 devices (2 members each), the epoch
    runs, per-member metrics stay distinct, and the layout survives
    the dispatch (donated buffers keep their sharding)."""
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    loop = _pop_loop(mesh)
    st, buf, es, keys, ps = loop.init(jax.random.key(1), buffer_capacity=2_000)
    for leaf in (
        jax.tree_util.tree_leaves(st.actor_params)[0],
        buf.data.states,
        jax.tree_util.tree_leaves(es)[0],
        ps.return_ema,
    ):
        assert len(leaf.sharding.device_set) == 4, leaf.sharding
        assert not leaf.sharding.is_fully_replicated
    st, buf, es, keys, m = loop.epoch(
        st, buf, es, keys, steps=20, update_every=10, warmup=True
    )
    st, buf, es, keys, m = loop.epoch(st, buf, es, keys, steps=20, update_every=10)
    losses = np.asarray(m["loss_q"])
    assert losses.shape == (8,) and np.all(np.isfinite(losses))
    assert len(set(np.round(losses, 6))) > 1  # distinct curves
    out_leaf = jax.tree_util.tree_leaves(st.actor_params)[0]
    assert len(out_leaf.sharding.device_set) == 4
    assert not out_leaf.sharding.is_fully_replicated


def test_population_sharded_matches_unsharded_streams():
    """Sharding the member axis is a layout decision, not an
    algorithmic one: the collect/replay/loss streams match the
    unsharded population bitwise (each member's program is untouched;
    only its placement moved)."""
    def run(mesh):
        loop = _pop_loop(mesh)
        st, buf, es, keys, ps = loop.init(
            jax.random.key(1), buffer_capacity=2_000
        )
        st, buf, es, keys, _ = loop.epoch(
            st, buf, es, keys, steps=20, update_every=10, warmup=True
        )
        st, buf, es, keys, m = loop.epoch(
            st, buf, es, keys, steps=20, update_every=10
        )
        return st, m

    _, m_sharded = run(make_mesh(dp=4, devices=jax.devices()[:4]))
    _, m_plain = run(None)
    np.testing.assert_array_equal(
        np.asarray(m_sharded["loss_q"]), np.asarray(m_plain["loss_q"])
    )
    np.testing.assert_array_equal(
        np.asarray(m_sharded["reward"]), np.asarray(m_plain["reward"])
    )


def test_population_pbt_gather_crosses_devices():
    """The exploit step's member gather is a real cross-device
    collective now: force a ranking where the winner lives on another
    device than the loser and check the loser's params become the
    winner's (and keep the member sharding)."""
    from torch_actor_critic_tpu.sac.ondevice import PBTState

    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    loop = _pop_loop(mesh)
    st, buf, es, keys, ps = loop.init(jax.random.key(1), buffer_capacity=2_000)
    # Member 0 (device 0) is the worst, member 7 (device 3) the best;
    # all ranked -> exploit fires.
    ps = PBTState(
        return_ema=jnp.arange(8, dtype=jnp.float32),
        ema_count=jnp.ones(8, jnp.int32),
        rng=ps.rng,
    )
    new_st, new_ps, ev = loop.pbt_step(st, ps)
    src = np.asarray(ev["src"])
    exploited = np.flatnonzero(np.asarray(ev["exploited"]))
    assert exploited.size > 0 and set(exploited) <= {0, 1}
    for m in exploited:
        assert src[m] >= 6  # copied from the top quantile
        got = jax.tree_util.tree_leaves(
            loop.extract_member(new_st, int(m)).actor_params
        )
        want = jax.tree_util.tree_leaves(
            loop.extract_member(st, int(src[m])).actor_params
        )
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    leaf = jax.tree_util.tree_leaves(new_st.actor_params)[0]
    assert len(leaf.sharding.device_set) == 4


def test_population_sharded_checkpoint_resume_is_bitwise(tmp_path):
    """PR 2/6 lossless-resume contract under the member sharding: save
    a sharded population mid-run, restore onto freshly-initialized
    sharded trees, continue — params and metrics match the
    uninterrupted run bitwise, and the restored arrays come back
    member-sharded."""
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer

    mesh = make_mesh(dp=4, devices=jax.devices()[:4])

    def fresh():
        loop = _pop_loop(mesh, pbt=False)
        return loop, *loop.init(jax.random.key(3), buffer_capacity=2_000)

    # Straight-through: 2 epochs, checkpointing after the first (the
    # epoch dispatch donates state+rings, so the save must happen
    # before the continuation consumes them).
    loop, st, buf, es, keys, ps = fresh()
    st, buf, es, keys, _ = loop.epoch(
        st, buf, es, keys, steps=20, update_every=10, warmup=True
    )
    st, buf, es, keys, m1 = loop.epoch(st, buf, es, keys, steps=20, update_every=10)
    ckpt = Checkpointer(str(tmp_path / "ck"))
    ckpt.save(
        0, st, buf,
        arrays={"env_states": es, "act_keys": keys},
        wait=True,
    )
    st, buf, es, keys, m2 = loop.epoch(st, buf, es, keys, steps=20, update_every=10)
    loop2, st2, buf2, es2, keys2, _ = fresh()
    st2, buf2, meta, arrays = ckpt.restore(
        st2, buf2,
        abstract_arrays={"env_states": es2, "act_keys": keys2},
    )
    ckpt.close()
    es2, keys2 = arrays["env_states"], arrays["act_keys"]
    leaf = jax.tree_util.tree_leaves(st2.actor_params)[0]
    assert len(leaf.sharding.device_set) == 4  # restored SHARDED
    assert not leaf.sharding.is_fully_replicated
    st2, buf2, es2, keys2, m2_resumed = loop2.epoch(
        st2, buf2, es2, keys2, steps=20, update_every=10
    )
    np.testing.assert_array_equal(
        np.asarray(m2_resumed["loss_q"]), np.asarray(m2["loss_q"])
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(st2.actor_params),
        jax.tree_util.tree_leaves(st.actor_params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_population_rejects_bad_meshes():
    """Indivisible populations and non-dp axes fail loudly at
    construction (the driver falls back to unsharded with a warning;
    the loop itself never silently mislays members)."""
    with pytest.raises(ValueError, match="divide evenly"):
        _pop_loop(make_mesh(dp=3, devices=jax.devices()[:3]), n_members=8)
    with pytest.raises(ValueError, match="dp mesh axis only"):
        _pop_loop(make_mesh(dp=2, fsdp=2, devices=jax.devices()[:4]))


def test_train_population_on_device_shards_when_divisible(tmp_path, caplog):
    """The driver wires the mesh through: a dp=4 mesh with population 8
    shards members (log line), an indivisible population falls back
    with a warning instead of failing."""
    import logging

    from torch_actor_critic_tpu.sac.ondevice import train_population_on_device

    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=8, population=8,
        on_device_envs=2, steps_per_epoch=20, update_every=10,
        start_steps=10, epochs=1, buffer_size=2_000, pbt_every=0,
    )
    mesh = make_mesh(dp=4, devices=jax.devices()[:4])
    with caplog.at_level(logging.INFO, logger="torch_actor_critic_tpu.sac.ondevice"):
        metrics = train_population_on_device(
            "Pendulum-v1", cfg, mesh=mesh, seed=0
        )
    assert any(
        "sharding population=8 over dp=4" in r.getMessage()
        for r in caplog.records
    )
    assert all(np.isfinite(metrics[f"loss_q_m{i}"]) for i in range(8))

    cfg7 = cfg.replace(population=7)
    with caplog.at_level(logging.WARNING, logger="torch_actor_critic_tpu.sac.ondevice"):
        metrics7 = train_population_on_device(
            "Pendulum-v1", cfg7, mesh=mesh, seed=0
        )
    assert all(np.isfinite(metrics7[f"loss_q_m{i}"]) for i in range(7))
