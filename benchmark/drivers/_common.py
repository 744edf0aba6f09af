"""What the drivers share: the program's configuration object built from a
configuration file, benchmark-made weights placed into the program's state,
and the capture of what the correctness check compares."""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import check, data, flops


def sac_config(config: dict, cell: dict, overrides: dict | None = None):
    """The program's ``SACConfig`` for a configuration file: its ``sac``
    block, the model's widths, and the cell's ring size."""
    from torch_actor_critic_tpu.utils.config import SACConfig

    model = config["model"]
    fields = dict(config["sac"])
    fields["hidden_sizes"] = tuple(model["hidden_sizes"])
    if model["family"] == "visual":
        for k in ("filters", "kernel_sizes", "strides"):
            fields[k] = tuple(model[k])
        fields["cnn_features"] = model["cnn_features"]
        fields["cnn_dense_size"] = model["cnn_dense_size"]
    fields["buffer_size"] = cell["traffic"]["ring_rows"]
    fields.update(overrides or {})
    return SACConfig(**fields)


class EnvSpec:
    """The attributes ``build_models`` reads off an env pool."""

    def __init__(self, model: dict):
        from torch_actor_critic_tpu.core.types import MultiObservation

        self.act_dim = model["act_dim"]
        self.act_limit = model["act_limit"]
        if model["family"] == "visual":
            self.obs_spec = MultiObservation(
                features=jax.ShapeDtypeStruct((model["feature_dim"],), jnp.float32),
                frame=jax.ShapeDtypeStruct(tuple(model["frame"]), jnp.uint8),
            )
        else:
            self.obs_spec = jax.ShapeDtypeStruct((model["obs_dim"],), jnp.float32)

    def example_obs(self):
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.obs_spec
        )


def seeded_params(sac, example_obs, seed: int, members: int = 0):
    """Actor and critic weights from the seed, in the program's layout, made
    on the device in one jitted call from the shapes alone."""
    abstract = jax.eval_shape(sac.init_state, jax.random.key(0), example_obs)
    a_abs, c_abs = abstract.actor_params, abstract.critic_params

    def make(key):
        ka, kc = jax.random.split(key)
        return data.init_params(ka, a_abs), data.init_params(kc, c_abs)

    key = data.state_key(seed, 1)
    if members:
        return jax.jit(jax.vmap(make))(jax.random.split(key, members))
    return jax.jit(make)(key)


def with_params(state, actor_params, critic_params, rng):
    """The program's freshly initialised state with the benchmark's weights
    and key in it; the target critic starts as a copy of the critic and the
    optimizer states stay at their zeros."""
    place = lambda new, old: jax.tree_util.tree_map(  # noqa: E731
        lambda n, o: jax.device_put(n, o.sharding), new, old
    )
    return state.replace(
        actor_params=place(actor_params, state.actor_params),
        critic_params=place(critic_params, state.critic_params),
        target_critic_params=place(
            jax.tree_util.tree_map(jnp.copy, critic_params),
            state.target_critic_params,
        ),
        rng=jax.device_put(rng, state.rng.sharding),
    )


def obs_dict(obs) -> t.Any:
    """A program observation (array or ``MultiObservation``) as the plain
    dict/array the reference reads."""
    if hasattr(obs, "frame"):
        return {"features": obs.features, "frame": obs.frame}
    return obs


def batch_dict(batch) -> dict:
    return {
        "states": obs_dict(batch.states), "actions": batch.actions,
        "rewards": batch.rewards, "next_states": obs_dict(batch.next_states),
        "done": batch.done,
    }


def learner_snapshot(state, metrics) -> dict:
    """What the check compares, fetched to the host right after a call."""
    got = {
        "loss_q": metrics["loss_q"], "loss_pi": metrics["loss_pi"],
        "actor": state.actor_params, "critic": state.critic_params,
        "pi_nu": state.pi_opt_state[0].nu, "q_nu": state.q_opt_state[0].nu,
        "step": state.step,
    }
    return jax.device_get(got)


def all_finite(*values) -> bool:
    return all(bool(np.all(np.isfinite(np.asarray(v)))) for v in values)


SAC_CONSTANTS = ("alpha", "gamma", "polyak", "lr", "reward_scale")


class FollowedCall:
    """What the drivers share of the comparison: the exact counters, and the
    reference following the first call.  A driver sets ``cell``, ``config``,
    ``actor0``, ``critic0`` (host copies of the seeded weights) and ``first``
    (:func:`learner_snapshot` after its first call)."""

    losses: list
    finite = True

    @staticmethod
    def at_rest_bytes(cell: dict, config: dict) -> int:
        """What the driver holds on a chip between steps: its rings, where the
        learner beside them is small."""
        rows = cell["traffic"]["ring_rows"] * config["sac"].get("population", 1)
        return rows // cell["chips"] * flops.row_bytes(config["model"])

    def note_losses(self, metrics) -> None:
        """Keep a call's losses on the device until the window has closed
        (two scalars a call).  Fetching them inside it, 65 calls at a time,
        cost the host 10 ms every 65th window: 0.6% of the visual cell's
        window time was the harness's own (my chip runs, PR 36)."""
        self.losses.append((metrics["loss_q"], metrics["loss_pi"]))

    def fold_losses(self) -> None:
        self.finite = self.finite and all_finite(*jax.device_get(self.losses))
        self.losses = []

    def counter_checks(self, step, ptr, calls: int, updates: int, rows: int, cap: int):
        """The gradient-step counter and every ring's write pointer advanced
        by exactly what ``calls`` calls dispatched."""
        return [
            check.Comparison(
                "grad_step_counter.gap",
                float(np.max(np.abs(np.asarray(step) - calls * updates))), 0.0, "exact",
            ),
            check.Comparison(
                "ring_write_pointer.gap",
                float(np.max(np.abs(np.asarray(ptr) - (calls * rows) % cap))), 0.0, "exact",
            ),
        ]

    def compare_first_call(self, mode: str, batches, eps_q, eps_pi, members: bool):
        sac = {k: self.config["sac"][k] for k in SAC_CONSTANTS}
        self._follow = lambda m: check.follow(
            model=self.config["model"], sac=sac, mode=m, actor0=self.actor0,
            critic0=self.critic0, batches=batches, eps_q=eps_q, eps_pi=eps_pi,
            members=members,
        )
        self._members = members
        return check.compare(
            self.first, self._follow(mode), self.actor0, self.critic0,
            self.cell["limits"], members,
        )

    def control(self, low: str, mode: str):
        """The control: the reference in the program's place, one precision
        lower (``low``), against the reference at ``mode``.  After ``check``."""
        return check.compare(
            self._follow(low), self._follow(mode), self.actor0, self.critic0,
            self.cell["limits"], self._members,
        )


def gather_rows(ring, idx, rows, in_axes=(0, 0), out_axis: int = 0):
    """The rows ``idx`` selects from every shard (or member) of ``ring``, on
    the host: stored leaves ``(streams, rows, ...)`` indexed along the rows
    axis by ``idx``'s stream, each row handed back in its transition's shape
    (``rows``: :func:`benchmark.harness.data.transition_rows`)."""
    def take(ring, idx):
        taken = jax.tree_util.tree_map(
            lambda leaf: jax.vmap(
                lambda r, i: jnp.take(r, i, axis=0), in_axes=in_axes, out_axes=out_axis
            )(leaf, idx),
            ring,
        )
        return data.as_rows(taken, rows, idx.ndim)

    return jax.device_get(jax.jit(take)(ring, idx))


def with_stream_axis(tree):
    """``(members, steps, batch, ...)`` leaves as one stream a member:
    ``(members, steps, 1, batch, ...)``."""
    return jax.tree_util.tree_map(lambda x: x[:, :, None], tree)


def member_rows(pre_rows, pushed, idx, cap: int, visible):
    """For every member, the rows each update of the first call samples
    (:func:`benchmark.harness.check.visible_rows`), with the stream axis."""
    rows = jax.jit(jax.vmap(
        lambda p, ch, i: check.visible_rows(p, ch, i, 0, cap, visible)
    ))(batch_dict(pre_rows), batch_dict(pushed), idx)
    return with_stream_axis(rows)
