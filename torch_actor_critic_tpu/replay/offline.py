"""``train.py --offline``: regularized SAC from the disk tier, no env.

The flywheel's consuming end. A :class:`~torch_actor_critic_tpu.replay.
diskstore.DiskTier` written by either producer — the trainer's spill
path or the serve-side :class:`~torch_actor_critic_tpu.replay.flywheel.
TransitionLogger` — becomes the whole dataset: chunks load into host
RAM once, a host RNG draws index batches, and the update program is a
``lax.scan`` burst over :meth:`SAC.update`-shaped steps, exactly the
online burst minus the in-graph ring push/sample (there is no ring —
the dataset IS the buffer).

Naive SAC on a fixed dataset overestimates Q off-support (the policy
proposes actions the data never took; the critic, never corrected,
extrapolates optimistically). ``--offline-reg`` counters it:

- ``bc``: behavior-cloning anchor on the actor —
  ``weight * mean((pi(s) - a_data)^2)`` added to the policy loss.
- ``cql``: conservative penalty on the critic —
  ``weight * mean(logsumexp_a Q(s, a) - Q(s, a_data))`` over K uniform
  proposals plus one policy action, pushing down out-of-distribution
  Q while holding up in-distribution Q (CQL(H), simplified).
- ``none``: plain SAC steps (the ablation baseline).

The burst program ``train/offline_burst`` is a checked jit entry point
(analysis/: ENTRY_POINTS + contract tables) — watchdog-scoped dispatch,
XLA cost registration, like every other compiled program in the repo.
"""

from __future__ import annotations

import logging
import typing as t

import numpy as np

from torch_actor_critic_tpu.core.types import Batch, MultiObservation
from torch_actor_critic_tpu.utils.config import SACConfig

logger = logging.getLogger(__name__)

__all__ = ["OfflineLearner", "train_offline", "OFFLINE_REGULARIZERS"]

OFFLINE_REGULARIZERS = ("none", "bc", "cql")

# Uniform action proposals per state for the CQL logsumexp (plus one
# policy action). Small by design: the penalty needs a handful of
# off-support probes, not an integral.
_CQL_NUM_RANDOM = 4


def _zeros_obs(spec: t.Any):
    import jax.numpy as jnp

    if isinstance(spec, MultiObservation):
        return MultiObservation(
            features=jnp.zeros(spec.features.shape, spec.features.dtype),
            frame=jnp.zeros(spec.frame.shape, spec.frame.dtype),
        )
    return jnp.zeros(spec.shape, spec.dtype)


class _DatasetSpec:
    """``build_models`` env shim (the serve.py ``_resolve_model``
    pattern): the three attributes model construction reads, recovered
    from the disk tier's meta instead of a live env."""

    def __init__(self, obs_spec: t.Any, act_dim: int, act_limit: float):
        self.obs_spec = obs_spec
        self.act_dim = act_dim
        self.act_limit = act_limit


class OfflineLearner:
    """Regularized SAC over a fixed host-resident dataset."""

    # The cost-registry/watchdog source name of the offline burst
    # program (checked ENTRY_POINTS + contract tables, analysis/).
    burst_cost_name = "train/offline_burst"

    def __init__(
        self,
        config: SACConfig,
        obs_spec: t.Any,
        act_dim: int,
        act_limit: float = 1.0,
    ):
        from torch_actor_critic_tpu.sac.trainer import (
            build_models,
            make_learner,
        )

        if config.offline_reg not in OFFLINE_REGULARIZERS:
            raise ValueError(
                f"offline_reg must be one of {OFFLINE_REGULARIZERS}, "
                f"got {config.offline_reg!r}"
            )
        self.config = config
        self.obs_spec = obs_spec
        self.act_dim = int(act_dim)
        self.act_limit = float(act_limit)
        spec = _DatasetSpec(obs_spec, self.act_dim, self.act_limit)
        actor_def, critic_def = build_models(config, spec)
        self.sac = make_learner(config, actor_def, critic_def, self.act_dim)
        self._burst = None
        self._burst_len: int | None = None
        self._cost_registered = False

    def init_state(self, key):
        return self.sac.init_state(key, _zeros_obs(self.obs_spec))

    # ------------------------------------------------------------- update

    def _offline_update(self, state, batch: Batch):
        """One regularized SAC step: ``reg='none'`` delegates to the
        exact online :meth:`SAC.update` program; ``bc``/``cql`` run the
        same critic→actor→(alpha)→polyak sequence with the penalty
        folded into the regularized loss."""
        cfg = self.config
        if cfg.offline_reg == "none":
            return self.sac.update(state, batch)

        import jax
        import jax.numpy as jnp
        import optax

        from torch_actor_critic_tpu.ops.polyak import polyak_update
        from torch_actor_critic_tpu.sac import losses
        from torch_actor_critic_tpu.sac.algorithm import dynamic_lr_step

        sac = self.sac
        weight = float(cfg.offline_reg_weight)
        rng, key_q, key_pi, key_reg = jax.random.split(state.rng, 4)
        if cfg.learn_alpha:
            alpha = jnp.exp(jax.lax.stop_gradient(state.log_alpha))
        else:
            alpha = jnp.float32(cfg.alpha)

        # --- critic step (+ CQL gap) ---
        def critic_objective(critic_params):
            loss, aux = losses.critic_loss(
                critic_params,
                actor_apply=sac._actor_apply,
                critic_apply=sac._critic_apply,
                actor_params=state.actor_params,
                target_critic_params=state.target_critic_params,
                batch=batch,
                key=key_q,
                alpha=alpha,
                gamma=cfg.gamma,
                reward_scale=cfg.reward_scale,
            )
            if cfg.offline_reg == "cql":
                k_rand, k_pi_cql = jax.random.split(key_reg)
                B = batch.actions.shape[0]
                rand_actions = jax.random.uniform(
                    k_rand,
                    (_CQL_NUM_RANDOM, B, self.act_dim),
                    minval=-self.act_limit,
                    maxval=self.act_limit,
                )
                pi_actions, _ = sac._actor_apply(
                    state.actor_params, batch.states, k_pi_cql
                )
                cand = jnp.concatenate(
                    [rand_actions, jax.lax.stop_gradient(pi_actions)[None]],
                    axis=0,
                )  # (K+1, B, act_dim)
                q_cand = jax.vmap(
                    lambda a: sac._critic_apply(
                        critic_params, batch.states, a
                    )
                )(cand)  # (K+1, num_qs, B)
                lse = jax.scipy.special.logsumexp(q_cand, axis=0)
                q_data = sac._critic_apply(
                    critic_params, batch.states, batch.actions
                )
                gap = jnp.mean(lse - q_data)
                loss = loss + weight * gap
                aux["offline/cql_gap"] = gap
            return loss, aux

        (loss_q, q_aux), q_grads = jax.value_and_grad(
            critic_objective, has_aux=True
        )(state.critic_params)
        q_updates, q_opt_state = dynamic_lr_step(
            sac._adam_core, sac.q_tx, q_grads, state.q_opt_state,
            state.critic_params, None,
        )
        critic_params = optax.apply_updates(state.critic_params, q_updates)

        # --- actor step (+ BC anchor) ---
        def actor_objective(actor_params):
            pi_obs = (
                batch.next_states if cfg.parity_pi_obs else batch.states
            )
            pi, logp_pi = sac._actor_apply(actor_params, pi_obs, key_pi)
            q_pi = sac._critic_apply(critic_params, batch.states, pi)
            loss = jnp.mean(alpha * logp_pi - jnp.min(q_pi, axis=0))
            aux = {
                "logp_pi": jnp.mean(logp_pi),
                "entropy": -jnp.mean(logp_pi),
            }
            if cfg.offline_reg == "bc":
                bc = jnp.mean((pi - batch.actions) ** 2)
                loss = loss + weight * bc
                aux["offline/bc_mse"] = bc
            return loss, aux

        (loss_pi, pi_aux), pi_grads = jax.value_and_grad(
            actor_objective, has_aux=True
        )(state.actor_params)
        pi_updates, pi_opt_state = dynamic_lr_step(
            sac._adam_core, sac.pi_tx, pi_grads, state.pi_opt_state,
            state.actor_params, None,
        )
        actor_params = optax.apply_updates(state.actor_params, pi_updates)

        # --- temperature (same as online; no-op graph when fixed) ---
        log_alpha = state.log_alpha
        alpha_opt_state = state.alpha_opt_state
        if cfg.learn_alpha:
            a_grad = jax.grad(
                lambda la: losses.alpha_loss(
                    la, pi_aux["logp_pi"], sac.target_entropy
                )
            )(state.log_alpha)
            a_updates, alpha_opt_state = sac.alpha_tx.update(
                a_grad, state.alpha_opt_state, state.log_alpha
            )
            log_alpha = optax.apply_updates(state.log_alpha, a_updates)

        target_critic_params = polyak_update(
            critic_params, state.target_critic_params, cfg.polyak
        )
        new_state = state.replace(
            step=state.step + 1,
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=target_critic_params,
            pi_opt_state=pi_opt_state,
            q_opt_state=q_opt_state,
            log_alpha=log_alpha,
            alpha_opt_state=alpha_opt_state,
            rng=rng,
        )
        metrics = {
            "loss_q": loss_q,
            "loss_pi": loss_pi,
            "alpha": jnp.exp(log_alpha) if cfg.learn_alpha else alpha,
            **q_aux,
            **pi_aux,
        }
        return new_state, metrics

    # -------------------------------------------------------------- burst

    def _build_burst(self, num_updates: int):
        """The ``train/offline_burst`` jit program: scan ``num_updates``
        regularized steps over a pre-stacked ``(num_updates, B, ...)``
        batch tree, donating the train state."""
        import jax
        import jax.numpy as jnp

        def _offline_burst(state, batches: Batch):
            def body(st, batch):
                return self._offline_update(st, batch)

            state, metrics = jax.lax.scan(body, state, batches)
            return state, jax.tree_util.tree_map(jnp.mean, metrics)

        del num_updates  # geometry is carried by the batch tree
        return jax.jit(_offline_burst, donate_argnums=(0,))

    def burst(self, state, batches: Batch):
        """Dispatch one burst under the watchdog's source scope
        (compiles attribute to ``train/offline_burst``)."""
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        num_updates = int(batches.rewards.shape[0])
        if self._burst is None or self._burst_len != num_updates:
            self._burst = self._build_burst(num_updates)
            self._burst_len = num_updates
        with get_watchdog().source(self.burst_cost_name):
            return self._burst(state, batches)

    def maybe_register_cost(self, state_abstract, batches_abstract) -> None:
        """Register the burst program's XLA cost analysis once
        (contract table: ``train/offline_burst`` cost registration)."""
        if self._cost_registered or self._burst is None:
            return
        self._cost_registered = True
        from torch_actor_critic_tpu.telemetry.costmodel import (
            get_cost_registry,
        )

        get_cost_registry().register_jit(
            self.burst_cost_name, self._burst, state_abstract,
            batches_abstract, devices=1,
        )


# ------------------------------------------------------------------- run


def _stack_batches(
    rows: t.Mapping[str, np.ndarray],
    sampler: np.random.Generator,
    num_updates: int,
    batch_size: int,
) -> Batch:
    """Draw ``num_updates`` independent uniform batches and stack them
    into one ``(num_updates, B, ...)`` scan tree (one host→device
    transfer per burst, like the online chunk placement)."""
    from torch_actor_critic_tpu.replay.diskstore import (
        rows_count,
        rows_to_batch,
        slice_rows,
    )

    import jax

    n = rows_count(rows)
    idx = sampler.integers(0, n, size=num_updates * batch_size)
    flat = rows_to_batch(slice_rows(rows, idx))
    lead = (num_updates, batch_size)
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x).reshape(lead + x.shape[1:]), flat
    )


def train_offline(
    config: SACConfig,
    tracker=None,
    checkpointer=None,
    seed: int = 0,
    telemetry=None,
) -> dict:
    """The ``train.py --offline`` entry: disk tier in, checkpoint out.

    Returns the final metrics dict (host floats) for smoke assertions.
    """
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.replay.diskstore import (
        DiskTier,
        obs_spec_from_json,
        rows_count,
    )

    if not config.offline_dataset:
        raise ValueError("--offline requires --offline-dataset DIR")
    tier = DiskTier(config.offline_dataset)
    try:
        meta = tier.meta
        if meta is None:
            raise ValueError(
                f"offline dataset {config.offline_dataset!r} has no "
                "meta.json (not a replay disk tier?)"
            )
        obs_spec = obs_spec_from_json(meta["obs"])
        act_dim = int(meta["act_dim"])
        act_limit = float(meta.get("act_limit", 1.0))
        rows = tier.read_all()
        if rows is None or rows_count(rows) == 0:
            raise ValueError(
                f"offline dataset {config.offline_dataset!r} is empty"
            )
        n_rows = rows_count(rows)
    finally:
        tier.close()

    learner = OfflineLearner(config, obs_spec, act_dim, act_limit)
    key = jax.random.PRNGKey(seed)
    state = learner.init_state(key)
    # numpy generator (host batch sampling), NOT a jax key — named to
    # keep tac-lint's key-spelling heuristic out of the picture.
    sampler = np.random.default_rng(seed)

    burst_len = max(1, min(config.update_every, config.offline_steps))
    total = int(config.offline_steps)
    logger.info(
        "offline: %d rows, %d steps (bursts of %d), reg=%s(%.3g)",
        n_rows, total, burst_len, config.offline_reg,
        config.offline_reg_weight,
    )
    done_steps = 0
    last_metrics: dict = {}
    epoch = 0
    while done_steps < total:
        k = min(burst_len, total - done_steps)
        batches = _stack_batches(rows, sampler, k, config.batch_size)
        state, metrics = learner.burst(state, batches)
        learner.maybe_register_cost(
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state
            ),
            jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, jnp.asarray(x).dtype
                ),
                batches,
            ),
        )
        done_steps += k
        last_metrics = {
            m: float(v) for m, v in metrics.items()
            if np.ndim(v) == 0
        }
        last_metrics["offline/steps"] = float(done_steps)
        last_metrics["offline/dataset_rows"] = float(n_rows)
        if tracker is not None:
            tracker.log_metrics(last_metrics, epoch)
        if telemetry is not None:
            telemetry.event(
                "offline", epoch=epoch, steps=done_steps,
                loss_q=last_metrics.get("loss_q"),
                loss_pi=last_metrics.get("loss_pi"),
            )
        epoch += 1

    if checkpointer is not None:
        checkpointer.save(
            epoch, state, None,
            extra={
                "config": config.to_json(),
                "offline": {
                    "dataset": config.offline_dataset,
                    "steps": done_steps,
                    "reg": config.offline_reg,
                },
                "step": done_steps,
            },
            wait=True,
        )
    return last_metrics
