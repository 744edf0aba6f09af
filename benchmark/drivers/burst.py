"""Replay-fed learner: the Trainer's window sequence with the env replaced by
a pool of seeded chunks.

One window is what ``Trainer.train`` does at a window boundary, through the
program's own functions: *stage* (``Trainer._build_chunk`` stacks 50 staged
steps into one host chunk), *place_chunk* (``shard_chunk_from_local``: host to
device), *burst_dispatch* (``DataParallelSAC.update_burst``: push, then 50
sampled gradient steps in one program, gradients averaged over ``dp``) and
*drain* (wait for the burst's loss).  The learner, its mesh and its ring are
built as ``Trainer.__init__`` builds them, except that the ring is made full
on the device from the seed, not pushed there chunk by chunk.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.drivers import _common
from benchmark.harness import check, data, draws


def c_ordered(pool):
    """A fetched pool with every leaf laid out C-ordered: the same bytes under
    other strides.

    ``jax.device_get`` hands a leaf back in the device's axis order (on the
    v5e a frame leaf's step axis fastest: one staged step's 12,288 bytes lie
    400 bytes apart), and stacking a window from that cost 2.2 ms where rows
    of their own cost 0.8 (my chip runs, PR 35 / 36).  No env hands a trainer
    such rows, so set-up lays the pool out once, before the window."""
    return jax.tree_util.tree_map(np.ascontiguousarray, pool)


def staged_windows(pool, n_windows: int, rows: int) -> list:
    """A pool ``Batch`` (leaves ``(n_dev, n_windows * rows, ...)``) as what
    ``Trainer._build_chunk`` stacks: for each window its ``rows`` staged
    steps, each the env's five-tuple."""
    return [
        [
            tuple(
                jax.tree_util.tree_map(lambda x: x[:, w * rows + s], leaf)
                for leaf in (pool.states, pool.actions, pool.rewards,
                             pool.next_states, pool.done)
            )
            for s in range(rows)
        ]
        for w in range(n_windows)
    ]


class Driver(_common.FollowedCall):
    def __init__(self, cell, config, seed, spans, overrides=None):
        self.cell, self.config, self.seed, self.spans = cell, config, seed, spans
        self.overrides = overrides or {}
        self.calls = 0
        self.losses = []

    # ------------------------------------------------------------ set-up
    def learner_config(self):
        """The program's ``SACConfig`` and the env's spec ``build_models`` reads."""
        return (
            _common.sac_config(self.config, self.cell, self.overrides.get("sac")),
            _common.EnvSpec(self.config["model"]),
        )

    def seeded_params(self, env):
        return _common.seeded_params(self.sac, env.example_obs(), self.seed)

    def _build_learner(self):
        from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
        from torch_actor_critic_tpu.parallel.mesh import make_mesh
        from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

        self.n_dev = self.cell["chips"]
        self.cfg, env = self.learner_config()
        self.n_updates = self.cfg.updates_per_window
        self.mesh = make_mesh(dp=self.n_dev, devices=jax.devices()[: self.n_dev])
        actor_def, critic_def = build_models(self.cfg, env)
        self.sac = make_learner(self.cfg, actor_def, critic_def, env.act_dim)
        self.dp = DataParallelSAC(self.sac, self.mesh)

        self.rng0 = data.state_key(self.seed, 0)
        self.actor0, self.critic0 = self.seeded_params(env)
        state = self.dp.init_state(jax.random.key(0), env.example_obs())
        self.state = _common.with_params(state, self.actor0, self.critic0, self.rng0)
        del state
        # Host copies: the burst donates the state these were placed into.
        self.actor0, self.critic0 = jax.device_get((self.actor0, self.critic0))
        return env

    def setup(self) -> None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
        from torch_actor_critic_tpu.core.types import BufferState
        from torch_actor_critic_tpu.sac import trainer  # noqa: F401 — timed as import

        self.spans.lap("setup/import")
        traffic = self.cell["traffic"]
        env = self._build_learner()

        self.spans.lap("setup/build_learner")
        # The ring: per-device shards (n_dev, rows/n_dev, ...), sharded over
        # dp like init_sharded_buffer's, full, with the write pointer at 0.
        # Its leaves are stored as the program's own ring stores them; the
        # seeded rows are a transition's (harness/data.py).
        self.cap = traffic["ring_rows"] // self.n_dev
        one = jax.eval_shape(
            lambda: init_replay_buffer(self.cap, env.obs_spec, env.act_dim).data
        )
        self.rows = data.transition_rows(one, env.obs_spec, env.act_dim)
        abstract = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((self.n_dev,) + x.shape, x.dtype), one
        )
        dp_sharding = NamedSharding(self.mesh, P("dp"))
        ring = data.fill_transitions(
            data.data_key(self.seed, 2), abstract,
            slab=traffic.get("fill_slab_rows", 8192),
            shardings=jax.tree_util.tree_map(lambda _: dp_sharding, abstract),
            rows=self.rows,
        )
        self.buffer = BufferState(
            data=ring,
            ptr=jax.device_put(np.zeros(self.n_dev, np.int32), dp_sharding),
            size=jax.device_put(np.full(self.n_dev, self.cap, np.int32), dp_sharding),
        )

        self.spans.lap("setup/fill_ring")
        # The pool of staged windows: host numpy, made before the window.
        self.window_rows = self.cfg.update_every
        step_abs = jax.tree_util.tree_map(
            lambda row: jax.ShapeDtypeStruct(
                (self.n_dev, traffic["pool_windows"] * self.window_rows) + row.shape,
                row.dtype,
            ),
            self.rows,
        )
        pool = c_ordered(jax.device_get(
            data.fill_transitions(data.data_key(self.seed, 3), step_abs)
        ))
        self.pool = staged_windows(pool, traffic["pool_windows"], self.window_rows)

        self.spans.lap("setup/chunk_pool")
        # What the first call will draw, and the rows it will find there.
        _, idx, self.eps_q, self.eps_pi = jax.jit(
            draws.dp_burst_draws, static_argnums=(1, 2, 3, 4, 5)
        )(self.rng0, self.n_dev, self.n_updates, self.cfg.batch_size, env.act_dim, self.cap)
        self.idx = idx
        self.pre_rows = _common.gather_rows(self.buffer.data, idx, self.rows, (0, 1), 1)

        self.spans.lap("setup/draws_and_rows")
        # First call: compiles, and is the call the reference follows.
        metrics = self._window(0)
        self.spans.lap("setup/first_call")
        self.first = _common.learner_snapshot(self.state, metrics)
        self._window(1)
        self.spans.lap("setup/second_call")

    # ------------------------------------------------------------ window
    def _window(self, i: int):
        from torch_actor_critic_tpu.parallel.dp import shard_chunk_from_local
        from torch_actor_critic_tpu.sac.trainer import Trainer
        from torch_actor_critic_tpu.utils.sync import drain

        with self.spans.span("stage"):
            local = Trainer._build_chunk(None, self.pool[i % len(self.pool)])
        with self.spans.span("place_chunk"):
            chunk = shard_chunk_from_local(local, self.mesh, sp=self.dp.effective_sp)
        with self.spans.span("burst_dispatch"):
            self.state, self.buffer, m = self.dp.update_burst(
                self.state, self.buffer, chunk, self.n_updates
            )
        with self.spans.span("drain"):
            drain(m["loss_q"])
        self.calls += 1
        self.note_losses(m)
        return m

    def window(self) -> None:
        self._window(self.calls)

    def per_window(self) -> dict:
        return {"grad_steps": self.n_updates, "env_steps": 0}

    def free(self) -> None:
        """Give the device back before the reference runs."""
        self.fold_losses()
        self.final = jax.device_get(
            {"step": self.state.step, "ptr": self.buffer.ptr, "size": self.buffer.size}
        )
        self.state = self.buffer = None

    # ------------------------------------------------------------- check
    def check(self, mode: str = "highest"):
        out = [
            check.Comparison("losses.non_finite", 0.0 if self.finite else 1.0, 0.0, "exact")
        ] + self.counter_checks(
            self.final["step"], self.final["ptr"], self.calls, self.n_updates,
            self.window_rows, self.cap,
        )
        return out + self.compare_first_call(
            mode, self.rows_of_first_call(), self.eps_q, self.eps_pi, False
        )

    def rows_of_first_call(self):
        """The filled ring's rows at the first call's draws, except where the
        first pushed chunk (at rows 0..n of each shard) had already landed."""
        from torch_actor_critic_tpu.sac.trainer import Trainer

        first_chunk = _common.batch_dict(Trainer._build_chunk(None, self.pool[0]))
        visible = jnp.full((self.n_updates,), self.window_rows)
        return jax.jit(jax.vmap(
            lambda p, ch, i: check.visible_rows(p, ch, i, 0, self.cap, visible),
            in_axes=(1, 0, 1), out_axes=1,
        ))(_common.batch_dict(self.pre_rows), first_chunk, self.idx)
