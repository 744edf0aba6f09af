"""The TD3 learner: a second algorithm family over the same machinery.

Extension — the reference implements SAC only (ref ``sac/algorithm.py``)
despite its "actor-critic" name. TD3 (Fujimoto et al. 2018) reuses every
piece of this framework's infrastructure unchanged: the same
:class:`~torch_actor_critic_tpu.core.types.TrainState` pytree (its
``target_actor_params`` slot, ``None`` for SAC, holds the target
policy), the same HBM-resident replay, the same push-then-scan
``update_burst`` (:func:`torch_actor_critic_tpu.sac.algorithm.run_update_burst`),
the same ``DataParallelSAC`` mesh wrapper, Trainer host loop, Orbax
checkpointing and CLIs — algorithm choice is ``SACConfig.algorithm``.

The delayed policy/target update uses leafwise ``jnp.where`` selection
rather than ``lax.cond`` so the gradient ``pmean`` runs unconditionally
— collectives stay outside control flow, which every device must agree
on under ``shard_map``. The skipped steps freeze the policy optimizer
state too, matching the canonical algorithm (one Adam step per actual
policy update).
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from torch_actor_critic_tpu.buffer.replay import observation_spec
from torch_actor_critic_tpu.ops.augment import augment_batch
from torch_actor_critic_tpu.core.types import Batch, BufferState, TrainState
from torch_actor_critic_tpu.diagnostics import ingraph as diag
from torch_actor_critic_tpu.ops.polyak import polyak_update
from torch_actor_critic_tpu.sac.algorithm import (
    Metrics,
    _shared_diagnostics,
    dynamic_lr_step,
    run_update_burst,
)
from torch_actor_critic_tpu.td3 import losses
from torch_actor_critic_tpu.utils.config import SACConfig


def _select_tree(pred: jax.Array, on_true: t.Any, on_false: t.Any) -> t.Any:
    """Leafwise ``where`` over matching pytrees (works across the mixed
    float/int leaves of optax states)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(pred, a, b), on_true, on_false
    )


class TD3:
    """TD3 learner over (actor_def, critic_def) Flax modules.

    Same contract as :class:`~torch_actor_critic_tpu.sac.algorithm.SAC`
    (``init_state`` / ``update`` / ``update_burst`` / ``select_action``),
    so everything that drives a SAC learner — the mesh wrapper, the
    Trainer, the bench — drives this one. ``actor_def`` must be a
    deterministic policy honoring the shared actor ``apply`` signature
    (:class:`~torch_actor_critic_tpu.models.actor.DeterministicActor`).
    """

    def __init__(
        self,
        config: SACConfig,
        actor_def: nn.Module,
        critic_def: nn.Module,
        act_dim: int,
    ):
        self.config = config
        self.actor_def = actor_def
        self.critic_def = critic_def
        self.act_dim = act_dim
        self.act_limit = float(getattr(actor_def, "act_limit", 1.0))
        self.pi_tx = optax.adam(config.lr)
        self.q_tx = optax.adam(config.lr)
        self._adam_core = optax.scale_by_adam()
        self.obs_spec = None  # as SAC's: learned in init_state

    def default_hyperparams(self) -> t.Dict[str, jax.Array]:
        """PBT-perturbable hyperparameters (cf. SAC's): the two
        learning rates plus the target-policy smoothing noise std —
        TD3's temperature-analogue regularizer."""
        return {
            "actor_lr": jnp.float32(self.config.lr),
            "critic_lr": jnp.float32(self.config.lr),
            "target_noise": jnp.float32(self.config.target_noise),
        }

    # ------------------------------------------------------------------ init

    def init_state(self, key: jax.Array, example_obs: t.Any) -> TrainState:
        """Both target networks start as copies of their online nets
        (the TD3 analogue of the reference's ``deepcopy(critic)`` at
        train start, ref ``sac/algorithm.py:194-196``)."""
        self.obs_spec = observation_spec(example_obs)
        k_actor, k_critic, k_sample, k_state = jax.random.split(key, 4)
        example_act = jnp.zeros((self.act_dim,))
        actor_params = self.actor_def.init(k_actor, example_obs, k_sample)
        critic_params = self.critic_def.init(k_critic, example_obs, example_act)
        copy = lambda p: jax.tree_util.tree_map(jnp.copy, p)  # noqa: E731
        return TrainState(
            step=jnp.int32(0),
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=copy(critic_params),
            target_actor_params=copy(actor_params),
            pi_opt_state=self.pi_tx.init(actor_params),
            q_opt_state=self.q_tx.init(critic_params),
            # TD3 has no entropy temperature; the TrainState slots hold
            # inert leaves so one state type serves both algorithms.
            log_alpha=jnp.float32(0.0),
            alpha_opt_state=optax.EmptyState(),
            rng=k_state,
        )

    # ----------------------------------------------------------- apply fns

    def _actor_apply(self, params, obs, key, **kw):
        return self.actor_def.apply(params, obs, key, **kw)

    def _critic_apply(self, params, obs, action):
        return self.critic_def.apply(params, obs, action)

    def select_action(
        self, params, obs, key: jax.Array | None = None, deterministic: bool = False
    ):
        """Exploration noise lives inside the actor module (clipped
        Gaussian, :class:`DeterministicActor`); ``deterministic=True``
        is the noiseless eval policy."""
        action, _ = self.actor_def.apply(
            params, obs, key, deterministic=deterministic, with_logprob=False
        )
        return action

    # -------------------------------------------------------------- update

    def update(
        self, state: TrainState, batch: Batch, axis_name: str | None = None
    ) -> t.Tuple[TrainState, Metrics]:
        """One TD3 gradient step: critic always; policy + BOTH target
        nets every ``policy_delay``-th step.

        The actor gradient is computed (and ``pmean``-averaged) every
        step but applied only on the delayed cadence — see the module
        docstring for why this beats ``lax.cond`` under ``shard_map``.

        Tier-gated diagnostics mirror the SAC learner's (same keys,
        same reductions — sac/algorithm.py), so the shared burst and
        the Trainer's epoch aggregation treat both algorithms alike.
        """
        cfg = self.config
        tier = cfg.diagnostics
        # Per-run hyperparameters (PBT) — see the matching note in
        # sac/algorithm.py.
        hp = state.hyperparams if state.hyperparams is not None else {}
        if cfg.frame_augment != "none" and cfg.pixel_pipeline != "fused":
            rng, key_q, key_aug = jax.random.split(state.rng, 3)
            batch = augment_batch(
                batch, key_aug, cfg.frame_augment, cfg.augment_pad
            )
        else:
            # Parity path: keep the historical 2-way split (see the
            # matching note in sac/algorithm.py; fused-pipeline frames
            # arrive pre-shifted, so no augmentation key here either).
            rng, key_q = jax.random.split(state.rng)

        # --- critic step (every step) ---
        (loss_q, q_aux), q_grads = jax.value_and_grad(
            losses.critic_loss, has_aux=True
        )(
            state.critic_params,
            actor_apply=self._actor_apply,
            critic_apply=self._critic_apply,
            target_actor_params=state.target_actor_params,
            target_critic_params=state.target_critic_params,
            batch=batch,
            key=key_q,
            act_limit=self.act_limit,
            target_noise=hp.get("target_noise", cfg.target_noise),
            noise_clip=cfg.noise_clip,
            gamma=cfg.gamma,
            reward_scale=cfg.reward_scale,
            diagnostics=tier != "off",
        )
        diag_q = q_aux.pop("diag_q", None)
        diag_backup = q_aux.pop("diag_backup", None)
        diag_metrics: Metrics = {}
        if tier != "off":
            diag_metrics["diag/grad_norm_q"] = diag.global_norm(q_grads)
        if axis_name is not None:
            q_grads = jax.lax.pmean(q_grads, axis_name)
        q_updates, q_opt_state = dynamic_lr_step(
            self._adam_core, self.q_tx, q_grads, state.q_opt_state,
            state.critic_params, hp.get("critic_lr"),
        )
        critic_params = optax.apply_updates(state.critic_params, q_updates)
        if tier != "off":
            diag_metrics["diag/update_ratio_q"] = diag.norm_ratio(
                q_updates, state.critic_params
            )

        # --- delayed policy + target updates ---
        # step is 0-based pre-increment: delay=d applies the policy on
        # the d-th, 2d-th, ... gradient step, like the canonical
        # "if it % policy_delay == 0" over a 0-based iteration counter
        # offset so the first burst ends on an applied update.
        do_pi = (state.step + 1) % cfg.policy_delay == 0
        (loss_pi, pi_aux), pi_grads = jax.value_and_grad(
            losses.actor_loss, has_aux=True
        )(
            state.actor_params,
            actor_apply=self._actor_apply,
            critic_apply=self._critic_apply,
            critic_params=critic_params,
            batch=batch,
            diagnostics=tier != "off",
        )
        diag_pi = pi_aux.pop("diag_pi", None)
        if tier != "off":
            diag_metrics["diag/grad_norm_pi"] = diag.global_norm(pi_grads)
        if axis_name is not None:
            pi_grads = jax.lax.pmean(pi_grads, axis_name)
        pi_updates, pi_opt_new = dynamic_lr_step(
            self._adam_core, self.pi_tx, pi_grads, state.pi_opt_state,
            state.actor_params, hp.get("actor_lr"),
        )
        actor_new = optax.apply_updates(state.actor_params, pi_updates)
        if tier != "off":
            # The ratio reports the CANDIDATE step; on skipped
            # (delayed) steps the applied update is zero by selection.
            diag_metrics["diag/update_ratio_pi"] = diag.norm_ratio(
                pi_updates, state.actor_params
            )

        actor_params = _select_tree(do_pi, actor_new, state.actor_params)
        pi_opt_state = _select_tree(do_pi, pi_opt_new, state.pi_opt_state)
        target_actor_params = _select_tree(
            do_pi,
            polyak_update(actor_params, state.target_actor_params, cfg.polyak),
            state.target_actor_params,
        )
        target_critic_params = _select_tree(
            do_pi,
            polyak_update(critic_params, state.target_critic_params, cfg.polyak),
            state.target_critic_params,
        )

        new_state = TrainState(
            step=state.step + 1,
            actor_params=actor_params,
            critic_params=critic_params,
            target_critic_params=target_critic_params,
            target_actor_params=target_actor_params,
            pi_opt_state=pi_opt_state,
            q_opt_state=q_opt_state,
            log_alpha=state.log_alpha,
            alpha_opt_state=state.alpha_opt_state,
            rng=rng,
            hyperparams=state.hyperparams,
        )
        metrics = {
            "loss_q": loss_q,
            "loss_pi": loss_pi,
            **q_aux,
            **pi_aux,
        }
        if tier != "off":
            metrics.update(diag_metrics)
            metrics.update(
                _shared_diagnostics(
                    cfg, loss_q, loss_pi, diag_q, diag_backup, diag_pi,
                    self.act_limit,
                )
            )
        return new_state, metrics

    # --------------------------------------------------------------- burst

    def update_burst(
        self,
        state: TrainState,
        buffer_state: BufferState,
        chunk: Batch,
        num_updates: int,
        axis_name: str | None = None,
    ) -> t.Tuple[TrainState, BufferState, Metrics]:
        """Same fused push-then-scan burst as SAC's (one device
        dispatch per ``update_every`` window)."""
        return run_update_burst(
            self.update, self.config, state, buffer_state, chunk,
            num_updates, axis_name, self.obs_spec,
        )
