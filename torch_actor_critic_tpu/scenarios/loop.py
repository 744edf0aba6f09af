"""The scenario epoch program: the fused loop + per-axis metrics.

:class:`ScenarioOnDeviceLoop` is the ``OnDeviceLoop`` subclass scenario
envs train under (``loop_class_for`` routes multi-agent / multi-task
envs here; classic envs never touch this module — their epoch program
stays bitwise the base loop's, pinned by ``tests/test_scenarios.py``).
Three deltas, all inside the ONE compiled epoch:

- **extras accumulation** — scenario envs report per-axis metric
  components through ``StepOut.extras`` (``return_per_agent``,
  ``episodes_per_task``, ...); the collect scan sum-accumulates them
  alongside the episode stats and the epoch finalization turns them
  into ``reward_per_agent`` / ``reward_per_task`` metric vectors (host
  layout ``reward_a{i}`` / ``reward_t{i}``,
  ``diagnostics.split_scenario_metrics``).
- **striped replay** — multi-task envs get the per-task striped ring
  (``buffer/striped.py``) from the ``_init_buffer`` hook; the generic
  ``push``/``sample`` dispatch means the burst machinery (SAC and TD3,
  population included) is unchanged.
- **its own jit identity** — the epoch program registers under
  ``train/scenario_epoch`` with the recompilation watchdog and the
  ``CostRegistry`` (the ``analysis/reachability.py`` ``ENTRY_POINTS``
  table seeds tac-lint's traced-set walk from the builder below), so
  scenario compiles/costs are attributed separately from the classic
  loop's.

On a mesh, the dp program delegates to the base builder (same
jit-with-sharding layout); the per-device body is still this class's
``_epoch_body``, and the extra raw keys ride the ``_cross_replica_raw``
hook as ``psum`` (counts/returns add across replicas).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, push
from torch_actor_critic_tpu.buffer.striped import init_striped_replay_buffer
from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.sac.ondevice import Metrics, OnDeviceLoop
from torch_actor_critic_tpu.telemetry import scopes

_BASE_RAW_KEYS = ("loss_q", "loss_pi", "episodes", "return_sum")


class ScenarioOnDeviceLoop(OnDeviceLoop):
    """Fused epoch over scenario envs: per-agent/per-task metric
    accumulation + striped replay, same Anakin topology."""

    # Watchdog/cost-registry source of the scenario epoch program
    # (ENTRY_POINTS pins this builder; _note_epoch_cost and the
    # watchdog pick the name up through the shared epoch() driver).
    epoch_cost_name = "train/scenario_epoch"

    def _init_buffer(self, buffer_capacity: int, obs_spec):
        n_tasks = getattr(self.env, "n_tasks", 0)
        if n_tasks > 1:
            return init_striped_replay_buffer(
                buffer_capacity, obs_spec, self.env.act_dim, n_tasks
            )
        return init_replay_buffer(
            buffer_capacity, obs_spec, self.env.act_dim
        )

    # ----------------------------------------------------------- collect

    def _collect_window(self, params, env_states, act_key, length, warmup):
        """Base collect plus ``StepOut.extras`` sum-accumulation:
        returns the base five values and an extras dict of per-axis
        sums (empty for envs that report none)."""
        env = self.env

        def step_fn(carry, _):
            es, key = carry
            obs = es.obs
            with jax.named_scope(scopes.COLLECT_ACT):
                key, k_act = jax.random.split(key)
                if warmup:
                    actions = jax.random.uniform(
                        k_act,
                        (self.n_envs, env.act_dim),
                        minval=-env.act_limit,
                        maxval=env.act_limit,
                    )
                else:
                    actions, _ = self.sac.actor_def.apply(
                        params, obs, k_act, with_logprob=False
                    )
            with jax.named_scope(scopes.COLLECT_ENV):
                es, out = jax.vmap(env.step)(es, actions)
            transition = Batch(
                states=obs,
                actions=actions,
                rewards=out.reward,
                next_states=out.next_obs,
                done=out.terminated,
            )
            ended = out.ended.astype(jnp.float32)
            extras = {
                k: jnp.sum(v, axis=0) for k, v in (out.extras or {}).items()
            }
            stats = (
                jnp.sum(ended), jnp.sum(ended * out.final_return), extras,
            )
            return (es, key), (transition, stats)

        (env_states, act_key), (transitions, stats) = jax.lax.scan(
            step_fn, (env_states, act_key), xs=None, length=length
        )
        n_done = jnp.sum(stats[0])
        sum_ret = jnp.sum(stats[1])
        extras = {k: jnp.sum(v, axis=0) for k, v in stats[2].items()}
        return env_states, act_key, transitions, n_done, sum_ret, extras

    # ------------------------------------------------------------- epoch

    def _epoch_body(
        self,
        train_state,
        buffer,
        env_states,
        act_key,
        n_windows: int,
        update_every: int,
        warmup: bool,
        axis_name: str | None = None,
    ):
        """The base window scan with the extras keys carried through:
        losses average over windows, every count/return (extras
        included) sums."""

        def window(carry, _):
            ts, buf, es, key = carry
            es, key, transitions, n_done, sum_ret, extras = (
                self._collect_window(
                    ts.actor_params, es, key, update_every, warmup
                )
            )
            chunk = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), transitions
            )
            if warmup:
                buf = push(buf, chunk)
                m = {
                    "loss_q": jnp.float32(0.0),
                    "loss_pi": jnp.float32(0.0),
                }
            else:
                num_updates = self.sac.config.replace(
                    update_every=update_every
                ).updates_per_window
                ts, buf, m = self.sac.update_burst(
                    ts, buf, chunk, num_updates, axis_name=axis_name
                )
            stats = {
                "loss_q": m["loss_q"],
                "loss_pi": m["loss_pi"],
                "episodes": n_done,
                "return_sum": sum_ret,
                **extras,
            }
            return (ts, buf, es, key), stats

        (train_state, buffer, env_states, act_key), stats = jax.lax.scan(
            window,
            (train_state, buffer, env_states, act_key),
            xs=None,
            length=n_windows,
        )
        raw = {
            "loss_q": jnp.mean(stats["loss_q"]),
            "loss_pi": jnp.mean(stats["loss_pi"]),
        }
        for k, v in stats.items():
            if k not in ("loss_q", "loss_pi"):
                raw[k] = jnp.sum(v, axis=0)
        return train_state, buffer, env_states, act_key, raw

    @staticmethod
    def _cross_replica_raw(raw: Metrics, axis: str) -> Metrics:
        out = OnDeviceLoop._cross_replica_raw(raw, axis)
        for k, v in raw.items():
            if k not in _BASE_RAW_KEYS:
                out[k] = jax.lax.psum(v, axis)  # counts/returns add
        return out

    @staticmethod
    def _finalize_metrics(raw: Metrics) -> Metrics:
        """Base metrics plus the per-axis vectors. Broadcasting is
        written ``[..., None]``-style so the SAME function finalizes a
        member-stacked population epoch (leading (N,) axis)."""
        metrics = OnDeviceLoop._finalize_metrics(
            {k: raw[k] for k in _BASE_RAW_KEYS}
        )
        episodes = raw["episodes"]
        if "return_per_agent" in raw:
            metrics["reward_per_agent"] = jnp.where(
                episodes[..., None] > 0,
                raw["return_per_agent"]
                / jnp.maximum(episodes[..., None], 1.0),
                jnp.float32(jnp.nan),
            )
        if "episodes_per_task" in raw:
            ept = raw["episodes_per_task"]
            metrics["episodes_per_task"] = ept
            metrics["reward_per_task"] = jnp.where(
                ept > 0,
                raw["return_per_task"] / jnp.maximum(ept, 1.0),
                jnp.float32(jnp.nan),
            )
        return metrics

    def _build_epoch(self, steps: int, update_every: int, warmup: bool):
        """Scenario epoch builder — the ``train/scenario_epoch``
        ENTRY_POINTS seed: the single-device program is constructed
        HERE (tac-lint's reachability walk anchors on it); the mesh
        program delegates to the base builder, whose dp body already
        routes through this class's ``_epoch_body`` /
        ``_cross_replica_raw`` overrides."""
        if self.mesh is not None:
            return super()._build_epoch(steps, update_every, warmup)
        n_windows, rem = divmod(steps, update_every)
        if rem:
            raise ValueError(
                f"steps={steps} not a multiple of update_every={update_every}"
            )

        def epoch(train_state, buffer, env_states, act_key):
            ts, buf, es, key, raw = self._epoch_body(
                train_state, buffer, env_states, act_key,
                n_windows, update_every, warmup,
            )
            return ts, buf, es, key, self._finalize_metrics(raw)

        return jax.jit(epoch, donate_argnums=(0, 1))
