"""On-device env + fully-fused training loop.

Checks the pure-JAX pendulum against gymnasium's Pendulum-v1 dynamics
step-for-step, then drives the fused collect+update loop (an extension
the reference cannot express — its physics is host C code, SURVEY.md
§7 (e)).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.envs.ondevice import PendulumJax, get_on_device_env
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.sac.ondevice import OnDeviceLoop
from torch_actor_critic_tpu.utils.config import SACConfig


def test_pendulum_matches_gymnasium_dynamics():
    gymnasium = pytest.importorskip("gymnasium")
    genv = gymnasium.make("Pendulum-v1")
    genv.reset(seed=0)

    state = PendulumJax.reset(jax.random.key(0))
    theta, theta_dot = 0.7, -0.3
    genv.unwrapped.state = np.array([theta, theta_dot])
    state = state.replace(
        inner=(jnp.float32(theta), jnp.float32(theta_dot)),
        obs=PendulumJax._obs(jnp.float32(theta), jnp.float32(theta_dot)),
    )

    rng = np.random.default_rng(1)
    for _ in range(50):
        action = rng.uniform(-2.0, 2.0, size=(1,)).astype(np.float32)
        gobs, grew, _, _, _ = genv.step(action)
        state, out = PendulumJax.step(state, jnp.asarray(action))
        np.testing.assert_allclose(out.next_obs, gobs, atol=1e-4)
        np.testing.assert_allclose(float(out.reward), grew, atol=1e-4)
    genv.close()


def test_pendulum_auto_reset():
    state = PendulumJax.reset(jax.random.key(0))
    action = jnp.zeros((1,))
    for i in range(PendulumJax.max_episode_steps):
        state, out = PendulumJax.step(state, action)
    assert bool(out.ended)
    assert int(state.step_count) == 0  # fresh episode
    assert float(state.episode_return) == 0.0
    assert float(out.final_return) < 0.0  # the finished episode's return
    # and it keeps going
    state, out = PendulumJax.step(state, action)
    assert not bool(out.ended)
    assert int(state.step_count) == 1


def test_registry():
    from torch_actor_critic_tpu.envs.ondevice import CheetahRunJax

    assert get_on_device_env("Pendulum-v1") is PendulumJax
    assert get_on_device_env("HalfCheetah-v3") is CheetahRunJax
    assert get_on_device_env("HalfCheetah-v5") is CheetahRunJax
    assert get_on_device_env("Walker2d-v4") is None


def _loop(n_envs=8):
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=32)
    sac = SAC(
        cfg,
        Actor(act_dim=1, hidden_sizes=cfg.hidden_sizes, act_limit=2.0),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        1,
    )
    return OnDeviceLoop(sac, PendulumJax, n_envs=n_envs)


def test_fused_epoch_mechanics():
    loop = _loop()
    ts, buf, es, key = loop.init(jax.random.key(0), buffer_capacity=10_000)

    ts, buf, es, key, m = loop.epoch(ts, buf, es, key, steps=50, warmup=True)
    assert int(buf.size) == 50 * 8
    assert int(ts.step) == 0  # warmup: no gradient steps

    ts, buf, es, key, m = loop.epoch(ts, buf, es, key, steps=100, update_every=50)
    assert int(ts.step) == 100
    assert int(buf.size) == 150 * 8
    assert np.isfinite(float(m["loss_q"]))
    assert np.isfinite(float(m["loss_pi"]))


def test_fused_dp_epoch_on_mesh():
    """The fused loop data-parallelized over 4 devices: per-device env
    batches + replay shards, replicated params, one dispatch per epoch."""
    from torch_actor_critic_tpu.parallel import make_mesh

    mesh = make_mesh(dp=4)
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=16)
    sac = SAC(
        cfg,
        Actor(act_dim=1, hidden_sizes=cfg.hidden_sizes, act_limit=2.0),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        1,
    )
    loop = OnDeviceLoop(sac, PendulumJax, n_envs=4, mesh=mesh)
    ts, buf, es, key = loop.init(jax.random.key(0), buffer_capacity=5_000)
    assert jax.tree_util.tree_leaves(es.obs)[0].shape == (4, 4, 3)

    ts, buf, es, key, _ = loop.epoch(ts, buf, es, key, steps=50, warmup=True)
    np.testing.assert_array_equal(np.asarray(buf.size), np.full(4, 200))
    ts, buf, es, key, m = loop.epoch(ts, buf, es, key, steps=100, update_every=50)
    assert int(ts.step) == 100
    assert np.isfinite(float(m["loss_q"]))
    leaf = jax.tree_util.tree_leaves(ts.actor_params)[0]
    assert leaf.sharding.is_fully_replicated


def test_fused_training_improves_return():
    """~20k grad steps of fused SAC must beat the random policy by a
    wide margin (random pendulum ≈ -1200 per episode)."""
    loop = _loop(n_envs=8)
    ts, buf, es, key = loop.init(jax.random.key(1), buffer_capacity=100_000)
    ts, buf, es, key, m0 = loop.epoch(ts, buf, es, key, steps=200, warmup=True)
    first = None
    for _ in range(8):
        ts, buf, es, key, m = loop.epoch(ts, buf, es, key, steps=2500, update_every=50)
        if first is None:
            first = float(m["reward"])
    assert float(m["reward"]) > first + 100.0, (first, float(m["reward"]))
    assert float(m["reward"]) > -1000.0, float(m["reward"])


# ---------------------------------------------------------------- cheetah twin


def _cheetah_rollout(policy, key, n=300):
    from torch_actor_critic_tpu.envs.ondevice import CheetahRunJax as E

    def body(carry, t):
        s, k = carry
        k, k_act = jax.random.split(k)
        s, out = E.step(s, policy(t, s, k_act))
        return (s, k), (out.reward, s.obs)

    (_, _), (rews, obs) = jax.lax.scan(
        body, (E.reset(key), key), jnp.arange(n)
    )
    return float(rews.sum()), float(jnp.abs(obs).max())


def test_cheetah_interface_matches_halfcheetah():
    from torch_actor_critic_tpu.envs.ondevice import CheetahRunJax as E

    assert (E.obs_dim, E.act_dim, E.act_limit) == (17, 6, 1.0)
    s = E.reset(jax.random.key(0))
    assert s.obs.shape == (17,)
    s, out = E.step(s, jnp.zeros(6))
    assert out.next_obs.shape == (17,)
    assert float(out.terminated) == 0.0  # HalfCheetah never terminates


def test_cheetah_stable_and_noise_cannot_rectify():
    """Symmetric random torques must not extract forward motion from
    the friction model (the exploit a naive traction term admits), and
    the state must stay bounded under them."""
    ret_rand, max_obs = _cheetah_rollout(
        lambda t, s, k: jax.random.uniform(k, (6,), minval=-1, maxval=1),
        jax.random.key(0),
    )
    ret_zero, _ = _cheetah_rollout(
        lambda t, s, k: jnp.zeros(6), jax.random.key(0)
    )
    assert max_obs < 30.0, max_obs
    # random pays ctrl cost (~ -0.2/step) and gains no systematic speed
    assert ret_rand < ret_zero + 10.0, (ret_rand, ret_zero)


def test_cheetah_gait_propels():
    """A phase-correct sweep+lift gait runs forward; the phase-flipped
    one does not — the learnable skill exists and is phase-sensitive."""

    def gait(shift):
        def policy(t, s, k):
            ph = 2 * jnp.pi * t * 0.05 / 0.6
            return jnp.array([
                0.8 * jnp.sin(ph), 0.0, 0.9 * jnp.cos(ph + shift),
                0.8 * jnp.sin(ph + jnp.pi), 0.0,
                0.9 * jnp.cos(ph + jnp.pi + shift),
            ])

        return policy

    good, _ = _cheetah_rollout(gait(jnp.pi), jax.random.key(0))
    bad, _ = _cheetah_rollout(gait(0.0), jax.random.key(0))
    assert good > 100.0, good
    assert good > bad + 200.0, (good, bad)


def test_cheetah_auto_reset():
    from torch_actor_critic_tpu.envs.ondevice import CheetahRunJax as E

    s = E.reset(jax.random.key(0))
    step = jax.jit(E.step)
    for _ in range(E.max_episode_steps):
        s, out = step(s, jnp.zeros(6))
    assert bool(out.ended)
    assert int(s.step_count) == 0


def test_cheetah_fused_training_improves_return():
    """Fused SAC on the cheetah twin: a few thousand grad steps must
    at least learn to stop paying ctrl cost for nothing (random ≈ -280
    per 1000-step episode) and must not degrade from the first epoch."""
    from torch_actor_critic_tpu.envs.ondevice import CheetahRunJax

    cfg = SACConfig(hidden_sizes=(64, 64), batch_size=64)
    sac = SAC(
        cfg,
        Actor(act_dim=6, hidden_sizes=cfg.hidden_sizes, act_limit=1.0),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        6,
    )
    loop = OnDeviceLoop(sac, CheetahRunJax, n_envs=8)
    ts, buf, es, key = loop.init(jax.random.key(2), buffer_capacity=100_000)
    ts, buf, es, key, _ = loop.epoch(ts, buf, es, key, steps=500, warmup=True)
    first = None
    last = None
    for _ in range(5):
        ts, buf, es, key, m = loop.epoch(
            ts, buf, es, key, steps=1000, update_every=50
        )
        r = float(m["reward"])
        if np.isfinite(r):
            last = r
            if first is None:
                first = r
    assert last is not None and first is not None
    assert last > -150.0, (first, last)
    assert last > first - 25.0, (first, last)  # no degradation


class TestHistoryEnv:
    """history_env: the fused-loop twin of the host HistoryEnv wrapper
    (window semantics must match envs/wrappers.py:158)."""

    def test_reset_fills_window_and_step_rolls(self):
        from torch_actor_critic_tpu.envs.ondevice import history_env

        H = history_env(PendulumJax, 4)
        assert H.obs_shape == (4, 3)
        s = H.reset(jax.random.key(0))
        # Window filled with the initial observation, newest last.
        np.testing.assert_array_equal(
            np.asarray(s.obs), np.tile(np.asarray(s.inner.obs)[None], (4, 1))
        )
        a = jnp.array([0.5])
        s2, out = H.step(s, a)
        # Rolled: first 3 rows are the old last 3; newest is base obs.
        np.testing.assert_array_equal(
            np.asarray(s2.obs[:-1]), np.asarray(s.obs[1:])
        )
        np.testing.assert_array_equal(
            np.asarray(s2.obs[-1]), np.asarray(s2.inner.obs)
        )
        np.testing.assert_array_equal(
            np.asarray(out.next_obs), np.asarray(s2.obs)
        )

    def test_auto_reset_refills_window(self):
        from torch_actor_critic_tpu.envs.ondevice import history_env

        H = history_env(PendulumJax, 3)

        def body(s, _):
            s, out = H.step(s, jnp.array([0.1]))
            return s, out

        s = H.reset(jax.random.key(1))
        s, outs = jax.lax.scan(body, s, None, PendulumJax.max_episode_steps)
        assert bool(outs.ended[-1])
        # Post-reset window is constant at the fresh initial obs...
        np.testing.assert_array_equal(
            np.asarray(s.obs), np.tile(np.asarray(s.inner.obs)[None], (3, 1))
        )
        # ...but the pushed transition kept the PRE-reset final frame.
        assert not np.allclose(
            np.asarray(outs.next_obs[-1][-1]), np.asarray(s.obs[-1])
        )

    @pytest.mark.slow
    def test_fused_sequence_epoch(self):
        """SequenceActor/Critic train through the fused loop on-chip
        (wired by train_on_device for --on-device --history-len N)."""
        from torch_actor_critic_tpu.envs.ondevice import history_env
        from torch_actor_critic_tpu.models import (
            SequenceActor,
            SequenceDoubleCritic,
        )

        H = history_env(PendulumJax, 4)
        cfg = SACConfig(batch_size=16, history_len=4, seq_d_model=16,
                        seq_num_heads=2, seq_num_layers=1)
        sac = SAC(
            cfg,
            SequenceActor(act_dim=1, d_model=16, num_heads=2, num_layers=1,
                          max_len=4, act_limit=2.0),
            SequenceDoubleCritic(d_model=16, num_heads=2, num_layers=1,
                                 max_len=4),
            1,
        )
        loop = OnDeviceLoop(sac, H, n_envs=4)
        ts, buf, es, key = loop.init(jax.random.key(0), buffer_capacity=500)
        ts, buf, es, key, _ = loop.epoch(
            ts, buf, es, key, steps=20, update_every=10, warmup=True
        )
        ts, buf, es, key, m = loop.epoch(
            ts, buf, es, key, steps=20, update_every=10
        )
        assert np.isfinite(float(m["loss_q"]))
        assert np.isfinite(float(m["loss_pi"]))
        assert int(buf.size) == 160  # 2 epochs x 20 steps x 4 envs


def test_on_device_run_evaluates_through_host_eval_cli(tmp_path):
    """A run trained with the fused on-device loop must load through the
    product eval CLI and roll out on the real host env — the crossover
    the runs/train_proof/ artifacts rest on (checkpoint layout shared
    between OnDeviceLoop and the host Trainer, buffer excluded)."""
    from torch_actor_critic_tpu.run_agent import main as eval_main
    from torch_actor_critic_tpu.train import main as train_main

    train_main([
        "--environment", "Pendulum-v1",
        "--on-device", "true",
        "--on-device-envs", "2",
        "--devices", "1",
        "--runs-root", str(tmp_path),
        "--epochs", "1",
        "--steps-per-epoch", "40",
        "--update-every", "20",
        "--start-steps", "20",
        "--update-after", "20",
        "--batch-size", "16",
        "--buffer-size", "500",
        "--hidden-sizes", "16,16",
    ])
    run_id = next((tmp_path / "Default").iterdir()).name
    metrics = eval_main([
        "--run", run_id,
        "--runs-root", str(tmp_path),
        "--episodes", "2",
        "--headless",
        "--seed", "0",
    ])
    assert np.isfinite(metrics["ep_ret_mean"])
    assert metrics["ep_len_mean"] == 200.0


class TestPixelPendulumJax:
    """On-chip-rendered pixel twin (VERDICT r3 #1: the visual stack
    through the fused loop, frames rasterized in pure jnp)."""

    def test_renderer_matches_host_env(self):
        """render_rod_jax must be pixel-identical to the host env's
        numpy renderer across the angle range (incl. wrap-around)."""
        from torch_actor_critic_tpu.envs.pixel_pendulum import (
            render_rod,
            render_rod_jax,
        )

        for th in np.linspace(-7.0, 7.0, 29):
            np.testing.assert_array_equal(
                np.asarray(render_rod_jax(float(th))), render_rod(float(th))
            )

    def test_env_semantics(self):
        from torch_actor_critic_tpu.envs.ondevice import PixelPendulumJax

        st = PixelPendulumJax.reset(jax.random.key(0))
        o = st.obs
        assert o.frame.dtype == jnp.uint8
        # No motion at reset: both rod channels coincide; features = 0.
        np.testing.assert_array_equal(
            np.asarray(o.frame[..., 0]), np.asarray(o.frame[..., 1])
        )
        np.testing.assert_array_equal(np.asarray(o.features), 0.0)

        a = jnp.array([1.5])
        step = jax.jit(PixelPendulumJax.step)
        moved = False
        for _ in range(5):
            st, out = step(st, a)
            moved = moved or bool(
                (out.next_obs.frame[..., 0] != out.next_obs.frame[..., 1]).any()
            )
        assert moved  # velocity observable from the two-rod channels
        np.testing.assert_array_equal(np.asarray(out.next_obs.features), 1.5)

    def test_temporal_channel_order(self):
        """Channels are (t-2, t-1, t), pinned against the renderer: a
        reversed or shifted `next_hist` carry must fail here, not ship
        silently scrambling the velocity signal."""
        from torch_actor_critic_tpu.envs.ondevice import PixelPendulumJax
        from torch_actor_critic_tpu.envs.pixel_pendulum import render_rod_jax

        st = PixelPendulumJax.reset(jax.random.key(2))
        thetas = [float(st.inner[0])]
        a = jnp.array([1.0])
        step = jax.jit(PixelPendulumJax.step)
        for t in range(4):
            st, out = step(st, a)
            thetas.append(float(st.inner[0]))
            expected = [thetas[max(t - 1, 0)], thetas[t], thetas[t + 1]]
            for c, th in enumerate(expected):
                np.testing.assert_array_equal(
                    np.asarray(out.next_obs.frame[..., c]),
                    np.asarray(render_rod_jax(th)),
                )

    def test_auto_reset_restores_motionless_frame(self):
        from torch_actor_critic_tpu.envs.ondevice import PixelPendulumJax

        st = PixelPendulumJax.reset(jax.random.key(1))
        a = jnp.array([2.0])
        step = jax.jit(PixelPendulumJax.step)
        for i in range(PixelPendulumJax.max_episode_steps):
            st, out = step(st, a)
        assert bool(out.ended)
        # Post-reset obs: fresh episode, no motion, no previous action.
        np.testing.assert_array_equal(
            np.asarray(st.obs.frame[..., 0]), np.asarray(st.obs.frame[..., 1])
        )
        np.testing.assert_array_equal(np.asarray(st.obs.features), 0.0)
        # Pre-reset obs kept the old episode's (moving) pose for replay.
        assert int(st.step_count) == 0

    def test_fused_pixel_epoch(self):
        """The fused loop trains the visual stack end-to-end on the
        on-chip-rendered env: warmup fills the pytree buffer with uint8
        frames, a burst produces finite losses."""
        from torch_actor_critic_tpu.envs.ondevice import PixelPendulumJax
        from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
        from torch_actor_critic_tpu.sac.ondevice import _SpecView

        cfg = SACConfig(
            hidden_sizes=(16, 16), batch_size=8,
            filters=(8, 16), kernel_sizes=(4, 3), strides=(2, 2),
            cnn_dense_size=32, cnn_features=8, normalize_pixels=True,
        )
        actor, critic = build_models(cfg, _SpecView(PixelPendulumJax))
        sac = make_learner(cfg, actor, critic, PixelPendulumJax.act_dim)
        loop = OnDeviceLoop(sac, PixelPendulumJax, n_envs=4)
        ts, buf, es, key = loop.init(jax.random.key(0), buffer_capacity=2_000)
        ts, buf, es, key, _ = loop.epoch(ts, buf, es, key, steps=25, update_every=25, warmup=True)
        assert int(buf.size) == 25 * 4
        assert buf.data.states.frame.dtype == jnp.uint8
        ts, buf, es, key, m = loop.epoch(ts, buf, es, key, steps=25, update_every=25)
        assert int(ts.step) == 25
        assert np.isfinite(float(m["loss_q"]))
        assert np.isfinite(float(m["loss_pi"]))

    def test_history_wrap_rejected(self):
        from torch_actor_critic_tpu.envs.ondevice import (
            PixelPendulumJax,
            history_env,
        )

        with pytest.raises(ValueError, match="pytree"):
            history_env(PixelPendulumJax, 8)


def test_fused_loop_runs_td3_and_td3_visual():
    """The fused on-device loop is algorithm-agnostic: TD3 (delayed
    updates inside the burst scan) runs through make_learner unchanged,
    flat AND visual (on-chip-rendered pixel env + deterministic visual
    actor). Pinned so the shared-machinery property cannot regress."""
    from torch_actor_critic_tpu.envs.ondevice import (
        PendulumJax,
        PixelPendulumJax,
    )
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.sac.ondevice import OnDeviceLoop, _SpecView

    for env_cls, extra in (
        (PendulumJax, {}),
        (
            PixelPendulumJax,
            dict(filters=(8, 16), kernel_sizes=(4, 3), strides=(2, 2),
                 cnn_dense_size=32, cnn_features=8, normalize_pixels=True),
        ),
    ):
        cfg = SACConfig(
            algorithm="td3", hidden_sizes=(16, 16), batch_size=8, **extra
        )
        actor, critic = build_models(cfg, _SpecView(env_cls))
        learner = make_learner(cfg, actor, critic, env_cls.act_dim)
        loop = OnDeviceLoop(learner, env_cls, n_envs=4)
        ts, buf, es, key = loop.init(jax.random.key(0), buffer_capacity=1000)
        ts, buf, es, key, _ = loop.epoch(
            ts, buf, es, key, steps=25, update_every=25, warmup=True
        )
        ts, buf, es, key, m = loop.epoch(
            ts, buf, es, key, steps=25, update_every=25
        )
        assert int(ts.step) == 25, env_cls.__name__
        assert np.isfinite(float(m["loss_q"])), env_cls.__name__
        assert np.isfinite(float(m["loss_pi"])), env_cls.__name__


def test_balance_twin_resets_near_upright_including_auto_reset():
    from torch_actor_critic_tpu.envs.ondevice import PixelPendulumBalanceJax

    for i in range(5):
        st = PixelPendulumBalanceJax.reset(jax.random.key(i))
        assert abs(float(st.inner[0])) < 0.15 * np.pi + 1e-6
    # The auto-reset inside step must use the SUBCLASS distribution
    # (routed through cls.reset), not the base full-circle one.
    st = PixelPendulumBalanceJax.reset(jax.random.key(7))
    step = jax.jit(PixelPendulumBalanceJax.step)
    a = jnp.array([0.0])
    for _ in range(PixelPendulumBalanceJax.max_episode_steps):
        st, out = step(st, a)
    assert bool(out.ended)
    assert abs(float(st.inner[0])) < 0.15 * np.pi + 1e-6
