"""The named-mesh GSPMD substrate (PR 8): parity with the retired
shard_map path, the un-gated dp+tp/fsdp hybrid, size-thresholded fsdp
parameter sharding, the member-sharded fused population, named-axis
skew collectives, and per-device cost attribution — all on the forced
8-device CPU mesh (conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.parallel import (
    DataParallelSAC,
    init_sharded_buffer,
    make_mesh,
    shard_chunk,
)
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 4, 2


def make_sac(**overrides):
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=8, **overrides)
    return SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT_DIM,
    )


def make_chunk(key, n_dev, per_dev):
    ks = jax.random.split(key, 5)
    shape = (n_dev, per_dev)
    return Batch(
        states=jax.random.normal(ks[0], shape + (OBS_DIM,)),
        actions=jnp.tanh(jax.random.normal(ks[1], shape + (ACT_DIM,))),
        rewards=jax.random.normal(ks[2], shape),
        next_states=jax.random.normal(ks[3], shape + (OBS_DIM,)),
        done=jnp.zeros(shape),
    )


def _dp_inputs(dp, seed_buf=128, n_updates_chunk=10):
    state = dp.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    buf = init_sharded_buffer(
        seed_buf, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM,
        dp.mesh,
    )
    n_dev = dp.n_devices
    warm = shard_chunk(make_chunk(jax.random.key(1), n_dev, 32), dp.mesh)
    chunk = shard_chunk(
        make_chunk(jax.random.key(2), n_dev, n_updates_chunk), dp.mesh
    )
    return state, buf, warm, chunk


# ------------------------------------------------------- substrate parity


def test_gspmd_burst_matches_legacy_shard_map_burst():
    """THE substrate-parity pin: one update burst through the retired
    manual ``jax.shard_map`` body and through the jit-with-sharding
    path, same 2-device mesh, same inputs — params, opt state and
    metrics must agree. Proves the rebuild is a pure substrate swap:
    identical per-device key streams and math, only the mapping
    machinery changed (on CPU the two even agree bitwise; the pin is
    allclose so TPU reduction-order freedom can't break it)."""
    from jax import shard_map

    from torch_actor_critic_tpu.parallel import dp as dp_mod

    sac = make_sac()
    mesh = make_mesh(dp=2, devices=jax.devices()[:2])
    dp = DataParallelSAC(sac, mesh)
    num_updates = 3

    def legacy_burst(state, buffer, chunk):
        """The pre-PR-8 manual body, verbatim semantics: strip the
        device axis, fold ``axis_index('dp')`` into the rng, run the
        shared burst with named-axis pmean, restore a replicated rng."""
        buf_specs = dp_mod._buffer_specs(buffer, 1)
        chunk_specs = dp_mod._batch_specs(chunk, 1)

        def body(state, buffer, chunk):
            buffer = jax.tree_util.tree_map(lambda x: x[0], buffer)
            chunk = jax.tree_util.tree_map(lambda x: x[0], chunk)
            dev = jax.lax.axis_index("dp")
            local = state.replace(rng=jax.random.fold_in(state.rng, dev))
            local, buffer, metrics = sac.update_burst(
                local, buffer, chunk, num_updates, axis_name="dp"
            )
            state_out = local.replace(
                rng=jax.random.fold_in(state.rng, jnp.uint32(0xB0057))
            )
            metrics = jax.lax.pmean(metrics, "dp")
            buffer = jax.tree_util.tree_map(lambda x: x[None], buffer)
            return state_out, buffer, metrics

        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(P(), buf_specs, chunk_specs),
                out_specs=(P(), buf_specs, P()),
                axis_names={"dp"},
                check_vma=False,
            )
        )(state, buffer, chunk)

    state, buf, warm, chunk = _dp_inputs(dp)
    s_old, b_old, m_old = legacy_burst(state, buf, warm)
    s_old, b_old, m_old = legacy_burst(s_old, b_old, chunk)

    state, buf, warm, chunk = _dp_inputs(dp)
    s_new, b_new, m_new = dp.update_burst(state, buf, warm, num_updates)
    s_new, b_new, m_new = dp.update_burst(s_new, b_new, chunk, num_updates)

    assert int(s_new.step) == int(s_old.step) == 2 * num_updates
    for key in m_old:
        np.testing.assert_allclose(
            np.asarray(m_new[key]), np.asarray(m_old[key]),
            rtol=1e-6, atol=1e-7, err_msg=key,
        )
    for group in ("actor_params", "critic_params", "target_critic_params",
                  "pi_opt_state", "q_opt_state"):
        for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(getattr(s_new, group))[0],
            jax.tree_util.tree_leaves(getattr(s_old, group)),
        ):
            name = group + "/".join(
                str(getattr(p, "key", p)) for p in path
            )
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7,
                err_msg=name,
            )
    # Replay rings too: the push path swapped substrate as well.
    np.testing.assert_array_equal(
        np.asarray(b_new.size), np.asarray(b_old.size)
    )
    np.testing.assert_allclose(
        np.asarray(b_new.data.states), np.asarray(b_old.data.states),
        atol=0,
    )


def test_dp_burst_no_shard_map_on_hot_path():
    """The acceptance pin, promoted from a source-regex check to the
    tac-lint ``shard-map-hot-path`` rule (docs/ANALYSIS.md): any
    ``shard_map`` reference outside ``parallel/context.py`` must sit
    in the rule's checked allowlist (the manual-by-nature sp ring
    burst), and every allowlist entry must still match real code
    (``stale-allowlist``). Zero findings over the whole package means
    the allowlist is the single source of truth for where manual
    mapping is allowed to live."""
    import pathlib

    from torch_actor_critic_tpu.analysis import lint_paths

    pkg = pathlib.Path(
        __import__("torch_actor_critic_tpu").__file__
    ).parent
    findings = [
        f for f in lint_paths([str(pkg)])
        if f.rule in ("shard-map-hot-path", "stale-allowlist")
    ]
    assert findings == [], "\n".join(f.format() for f in findings)


# ----------------------------------------------------- hybrid, no gate


def test_dp_fsdp_hybrid_runs_without_version_gate():
    """(dp=2, fsdp=2) with the size threshold forced to 0: parameters
    really shard over fsdp, the burst compiles and runs under plain
    auto partitioning, and the update equals the all-replicated
    (fsdp=1) burst — fsdp changes layout, not math."""

    def run(fsdp):
        sac = make_sac()
        dp = DataParallelSAC(
            sac, make_mesh(dp=2, fsdp=fsdp, devices=jax.devices()[:2 * fsdp]),
            fsdp_min_bytes=0,
        )
        state, buf, warm, chunk = _dp_inputs(dp)
        if fsdp > 1:
            kern = state.actor_params["params"]["MLP_0"]["Dense_0"]["col"][
                "kernel"
            ]
            assert "fsdp" in (kern.sharding.spec or ())
            assert not kern.sharding.is_fully_replicated
        state, buf, _ = dp.update_burst(state, buf, warm, 2)
        state, buf, metrics = dp.update_burst(state, buf, chunk, 2)
        return state, metrics

    s_f, m_f = run(fsdp=2)
    s_r, m_r = run(fsdp=1)
    np.testing.assert_allclose(
        float(m_f["loss_q"]), float(m_r["loss_q"]), rtol=1e-5
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_f.critic_params),
        jax.tree_util.tree_leaves(s_r.critic_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ------------------------------------------------- fsdp sharding specs


def test_fsdp_spec_size_threshold_and_dim_choice():
    from torch_actor_critic_tpu.parallel.sharding import fsdp_spec

    big = jnp.zeros((128, 64))          # 32 KiB
    assert fsdp_spec(big, fsdp=4, min_bytes=0) == P("fsdp")
    # Largest divisible dim wins; dim 0 (96) > dim 1 (64) here.
    assert fsdp_spec(jnp.zeros((96, 64)), 4, 0) == P("fsdp")
    # dim 0 indivisible -> falls to the next divisible dim.
    assert fsdp_spec(jnp.zeros((97, 64)), 4, 0) == P(None, "fsdp")
    # Below threshold -> replicated.
    assert fsdp_spec(big, 4, big.nbytes + 1) == P()
    # Scalars / 1-D / fully indivisible -> replicated.
    assert fsdp_spec(jnp.zeros(()), 4, 0) == P()
    assert fsdp_spec(jnp.zeros((128,)), 4, 0) == P()
    assert fsdp_spec(jnp.zeros((3, 5)), 4, 0) == P()
    # fsdp=1 mesh -> replicated regardless of size.
    assert fsdp_spec(big, 1, 0) == P()


def test_fsdp_composes_with_tp_on_disjoint_dims():
    """A tp-taken dimension is skipped: fsdp lands on the largest
    remaining divisible dim, so the two families never collide."""
    from torch_actor_critic_tpu.parallel.sharding import fsdp_spec

    leaf = jnp.zeros((64, 32))
    assert fsdp_spec(leaf, 2, 0, taken=P(None, "tp")) == P("fsdp", "tp")
    assert fsdp_spec(leaf, 2, 0, taken=P("tp", None)) == P("tp", "fsdp")
    # Everything taken -> the tp spec passes through.
    assert fsdp_spec(jnp.zeros((64,)), 2, 0, taken=P("tp")) == P("tp")


def test_param_specs_replicate_scalars_and_small_arrays():
    """The scaling-book contract on a real model tree: scalars (step,
    log_alpha) and small arrays replicate, every spec on a trivial
    mesh is P()."""
    from torch_actor_critic_tpu.parallel.sharding import param_specs

    sac = make_sac()
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    trivial = make_mesh(dp=8)
    specs = jax.tree_util.tree_leaves(
        param_specs(state, trivial),
        is_leaf=lambda s: isinstance(s, P),
    )
    assert all(s == P() for s in specs)
    sharded = param_specs(
        state, make_mesh(dp=2, fsdp=4), min_bytes=0
    )
    assert sharded.log_alpha == P()
    assert sharded.step == P()
    kernel_specs = [
        s
        for path, s in jax.tree_util.tree_flatten_with_path(
            sharded.critic_params,
            is_leaf=lambda s: isinstance(s, P),
        )[0]
        if "kernel" in "/".join(str(getattr(p, "key", p)) for p in path)
    ]
    assert any("fsdp" in (s or ()) for s in kernel_specs)


# ------------------------------------------- named-axis skew collectives


def test_replica_skew_under_vmap_named_axis():
    """The dp-skew reductions read the SAME named axis whether the
    substrate is manual or a GSPMD vmap axis: pmax-pmin over
    ``axis_name='dp'`` inside jit-with-sharding equals the known
    spread."""
    from jax.sharding import NamedSharding
    from torch_actor_critic_tpu.diagnostics.ingraph import replica_skew

    mesh = make_mesh(dp=4, devices=jax.devices()[:4])

    def per_dev(v):
        skew = replica_skew({"diag/param_norm": v}, ("diag/param_norm",), "dp")
        return skew["diag/param_norm_skew"]

    def f(x):
        return jax.vmap(per_dev, axis_name="dp")(x)[0]

    xs = jax.device_put(
        jnp.asarray([0.0, 1.0, 2.0, 3.0]), NamedSharding(mesh, P("dp"))
    )
    out = jax.jit(
        f, in_shardings=NamedSharding(mesh, P("dp")),
        out_shardings=NamedSharding(mesh, P()),
    )(xs)
    assert float(out) == 3.0


def test_dp_skew_metrics_via_gspmd_burst_forced_devices():
    """Forced 4-device run of the NEW burst with diagnostics on: the
    desync canary still reads exactly 0.0 (pmean'd grads keep the
    per-device replicas bit-identical under the vmap substrate too)
    and per-shard grad skew is a real positive spread."""
    sac = make_sac(diagnostics="light")
    dp = DataParallelSAC(sac, make_mesh(dp=4, devices=jax.devices()[:4]))
    state, buf, warm, chunk = _dp_inputs(dp)
    _, _, m = dp.update_burst(state, buf, warm, 4)
    assert float(m["diag/param_norm_skew"]) == 0.0
    assert float(m["diag/grad_norm_q_skew"]) > 0.0
    assert float(m["diag/grad_norm_pi_skew"]) > 0.0


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


# --------------------------------------------- per-device cost division


def test_cost_registry_divides_by_mesh_size():
    """Satellite regression: registering the SAME dp=4 burst with and
    without ``devices=4`` must differ by exactly 4x on every cost
    column — roofline/MFU reads per-device FLOPs under dp>1."""
    from torch_actor_critic_tpu.telemetry.costmodel import CostRegistry

    sac = make_sac()
    dp = DataParallelSAC(sac, make_mesh(dp=4, devices=jax.devices()[:4]))
    state, buf, warm, chunk = _dp_inputs(dp)
    state, buf, _ = dp.update_burst(state, buf, warm, 2)
    fn = dp.burst_jit(2)
    assert fn is not None
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (state, buf, chunk),
    )
    reg = CostRegistry()
    whole = reg.register_jit("whole", fn, *abstract)
    per_dev = reg.register_jit("per_dev", fn, *abstract, devices=4)
    assert whole is not None and per_dev is not None
    assert per_dev["devices"] == 4
    for k in ("flops", "bytes_accessed"):
        assert whole[k] > 0
        np.testing.assert_allclose(per_dev[k], whole[k] / 4, rtol=1e-9)
