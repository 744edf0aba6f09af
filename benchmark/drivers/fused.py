"""Fused population epoch: ``PopulationOnDeviceLoop`` on the pure-JAX cheetah.

One window is one dispatch of the program's epoch: for every member,
``steps_per_dispatch`` vectorised env steps of ``n_envs`` envs with the policy
acting in the program, and after every ``update_every`` of them a push of the
collected transitions and as many sampled gradient steps.  The host does
nothing but dispatch and wait.  Members, envs, keys and learner state are made
as ``PopulationOnDeviceLoop.init`` makes them; the rings are made full on the
device from the seed instead of zero.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.drivers import _common
from benchmark.harness import check, data, draws


class Driver(_common.FollowedCall):
    def __init__(self, cell, config, seed, spans, overrides=None):
        self.cell, self.config, self.seed, self.spans = cell, config, seed, spans
        self.overrides = overrides or {}
        self.calls = 0
        self.losses = []

    def setup(self) -> None:
        from torch_actor_critic_tpu.core.types import BufferState
        from torch_actor_critic_tpu.envs.ondevice import get_on_device_env
        from torch_actor_critic_tpu.sac.ondevice import (
            PopulationOnDeviceLoop, _env_obs_spec, _wrap_and_build,
        )

        self.spans.lap("setup/import")
        traffic = self.cell["traffic"]
        self.cfg = _common.sac_config(self.config, self.cell, self.overrides.get("sac"))
        self.n = self.cfg.population
        self.n_envs = traffic["n_envs"]
        self.steps = traffic["steps_per_dispatch"]
        self.every = self.cfg.update_every
        self.cap = traffic["ring_rows"]
        env_cls, sac = _wrap_and_build(get_on_device_env(traffic["env"]), self.cfg)
        self.act_dim = env_cls.act_dim
        self.loop = PopulationOnDeviceLoop(
            sac, env_cls, n_members=self.n, n_envs=self.n_envs, pbt=False
        )
        obs_spec, zero_obs = _env_obs_spec(env_cls)

        def member_init(k):  # PopulationOnDeviceLoop.init, less the ring
            k_state, k_envs, k_act = jax.random.split(k, 3)
            ts = sac.init_state(k_state, zero_obs)
            es = jax.vmap(env_cls.reset)(jax.random.split(k_envs, self.n_envs))
            return ts, es, k_act

        state, self.env_states, self.act_keys = jax.jit(jax.vmap(member_init))(
            jax.random.split(data.state_key(self.seed, 4), self.n)
        )
        self.rng0 = jax.random.split(data.state_key(self.seed, 0), self.n)
        actor0, critic0 = _common.seeded_params(sac, zero_obs, self.seed, members=self.n)
        self.state = _common.with_params(state, actor0, critic0, self.rng0)
        self.actor0, self.critic0 = jax.device_get((actor0, critic0))

        self.spans.lap("setup/build_learner")
        # Stored as the program's own ring stores it; the seeded rows are a
        # transition's (harness/data.py).
        ring_abs = jax.eval_shape(
            lambda: jax.vmap(lambda _: self.loop.inner._init_buffer(self.cap, obs_spec))(
                jnp.arange(self.n)
            ).data
        )
        self.rows = data.transition_rows(ring_abs, obs_spec, self.act_dim)
        ring = data.fill_transitions(
            data.data_key(self.seed, 2), ring_abs,
            slab=traffic.get("fill_slab_rows", 65536), rows=self.rows,
        )
        self.buffer = BufferState(
            data=ring, ptr=jnp.zeros(self.n, jnp.int32),
            size=jnp.full(self.n, self.cap, jnp.int32),
        )

        self.spans.lap("setup/fill_ring")
        # The draws of the first dispatch (one unbroken key chain a member)
        # and the rows they find in the ring as it was filled.
        batch = self.cfg.batch_size
        n_updates = (self.steps // self.every) * self.cfg.updates_per_window
        _, self.idx, self.eps_q, self.eps_pi = jax.jit(jax.vmap(
            lambda k: draws.burst_draws(k, n_updates, batch, self.act_dim, self.cap)
        ))(self.rng0)
        self.pre_rows = _common.gather_rows(self.buffer.data, self.idx, self.rows)

        self.spans.lap("setup/draws_and_rows")
        metrics = self._dispatch()
        self.spans.lap("setup/first_call")
        self.first = _common.learner_snapshot(self.state, metrics)
        self.pushed_per_call = (self.steps // self.every) * self.every * self.n_envs
        self.first_pushed = jax.device_get(data.as_rows(jax.tree_util.tree_map(
            lambda leaf: leaf[:, : self.pushed_per_call], self.buffer.data
        ), self.rows, 2))
        # The second dispatch takes the first one's outputs, whose placement
        # differs from the freshly made inputs': it is the one that settles
        # what the window runs (the first compiles a program of its own).
        self._dispatch()
        self.spans.lap("setup/second_call")

    def _dispatch(self):
        from torch_actor_critic_tpu.utils.sync import drain

        with self.spans.span("epoch_dispatch"):
            self.state, self.buffer, self.env_states, self.act_keys, m = self.loop.epoch(
                self.state, self.buffer, self.env_states, self.act_keys,
                steps=self.steps, update_every=self.every,
            )
        with self.spans.span("drain"):
            drain(m["loss_q"])
        self.calls += 1
        self.note_losses(m)
        return m

    def window(self) -> None:
        self._dispatch()

    def per_window(self) -> dict:
        windows = self.steps // self.every
        return {
            "grad_steps": self.n * windows * self.cfg.updates_per_window,
            "env_steps": self.n * self.n_envs * self.steps,
            "iterations": self.steps,
        }

    def free(self) -> None:
        self.fold_losses()
        self.final = jax.device_get(
            {"step": self.state.step, "ptr": self.buffer.ptr}
        )
        self.state = self.buffer = self.env_states = None

    def check(self, mode: str = "highest"):
        windows = self.steps // self.every
        n_updates = windows * self.cfg.updates_per_window
        out = [
            check.Comparison("losses.non_finite", 0.0 if self.finite else 1.0, 0.0, "exact")
        ] + self.counter_checks(
            self.final["step"], self.final["ptr"], self.calls, n_updates,
            self.pushed_per_call, self.cap,
        )
        # Update u of a dispatch sees the pushes of its own window and of the
        # windows before it.  The pushed rows are read back from the ring: the
        # env and the acting policy are the program's, and the reference takes
        # what they produced as its feed.
        per_window = self.every * self.n_envs
        visible = (jnp.arange(n_updates) // self.cfg.updates_per_window + 1) * per_window
        rows = _common.member_rows(self.pre_rows, self.first_pushed, self.idx, self.cap, visible)
        return out + self.compare_first_call(
            mode, rows, _common.with_stream_axis(self.eps_q),
            _common.with_stream_axis(self.eps_pi), True,
        )
