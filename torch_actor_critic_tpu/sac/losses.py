"""SAC loss functions as pure pytree-in/scalar-out functions.

Math twins of the reference losses (ref ``sac/algorithm.py:30-74``),
re-expressed functionally so ``jax.value_and_grad`` replaces
``backward()`` and the no-grad Bellman backup is simply "computed from
target params that aren't differentiated".

Two reference quirks are handled explicitly:

- **Policy-loss observation** (ref ``sac/algorithm.py:37-38``): the
  reference samples ``pi`` from ``next_state`` but evaluates Q at
  ``state``. ``parity_pi_obs=True`` reproduces that; the default uses
  ``state`` for both (spinningup semantics, SURVEY.md §7 item 4).
- The reference's second bug — policy grads effectively never averaged
  across MPI workers due to a ``mpi_avg_grads``-before-``backward()``
  misordering (ref ``sac/algorithm.py:155-156``) — is **not**
  reproducible in this design: replicated parameters with in-step
  ``pmean`` cannot drift apart per-device. It is a silent-divergence
  bug, not a capability; single-process reference behavior (where the
  misorder is a no-op, ref ``sac/mpi.py:79-80``) is what we match.

The ensemble critic returns ``(num_qs, batch)``; ``min`` over axis 0
generalizes the reference's ``torch.min(q1, q2)``.
"""

from __future__ import annotations

import typing as t

import jax
import jax.numpy as jnp

from torch_actor_critic_tpu.core.types import Batch


def critic_loss(
    critic_params: t.Any,
    *,
    actor_apply: t.Callable,
    critic_apply: t.Callable,
    actor_params: t.Any,
    target_critic_params: t.Any,
    batch: Batch,
    key: jax.Array,
    alpha: jax.Array,
    gamma: float,
    reward_scale: float,
    diagnostics: bool = False,
) -> t.Tuple[jax.Array, t.Dict[str, jax.Array]]:
    """Twin-critic Bellman MSE (ref ``eval_q_loss``, ``sac/algorithm.py:46-74``).

    backup = reward_scale * r + gamma * (1 - done) * (min_i Q_targ_i(s', a')
    - alpha * logp(a'|s')), a' ~ pi(.|s'); loss = sum_i mean((Q_i(s,a) -
    backup)^2). The backup is wrapped in ``stop_gradient`` — the
    functional equivalent of the reference's ``torch.no_grad()`` block.

    ``diagnostics=True`` additionally returns the raw ``(num_qs, B)``
    Q surface and the backup vector under ``diag_q``/``diag_backup``
    (stop-gradient'd) so the learner can reduce Q stats and TD-error
    histograms in-graph without recomputing the forward — the caller
    pops them from the aux before they reach metrics.
    """
    next_action, next_logp = actor_apply(actor_params, batch.next_states, key)
    q_target = critic_apply(target_critic_params, batch.next_states, next_action)
    q_target_min = jnp.min(q_target, axis=0)
    backup = reward_scale * batch.rewards + gamma * (1.0 - batch.done) * (
        q_target_min - alpha * next_logp
    )
    backup = jax.lax.stop_gradient(backup)

    q = critic_apply(critic_params, batch.states, batch.actions)  # (num_qs, B)
    # Sum of per-head mean MSEs, like loss_q1 + loss_q2 (ref :69-74).
    loss = jnp.sum(jnp.mean((q - backup[None, :]) ** 2, axis=-1))
    aux = {"q_mean": jnp.mean(q), "backup_mean": jnp.mean(backup)}
    if diagnostics:
        aux["diag_q"] = jax.lax.stop_gradient(q)
        aux["diag_backup"] = backup
    return loss, aux


def actor_loss(
    actor_params: t.Any,
    *,
    actor_apply: t.Callable,
    critic_apply: t.Callable,
    critic_params: t.Any,
    batch: Batch,
    key: jax.Array,
    alpha: jax.Array,
    parity_pi_obs: bool = False,
    diagnostics: bool = False,
) -> t.Tuple[jax.Array, t.Dict[str, jax.Array]]:
    """Policy loss (ref ``eval_pi_loss``, ``sac/algorithm.py:30-43``).

    ``mean(alpha * logp_pi - min_i Q_i(s, pi))``. Critic params are not
    differentiated (grad is taken w.r.t. ``actor_params`` only), which
    subsumes the reference's requires_grad freeze/unfreeze dance
    (ref ``sac/algorithm.py:144-160``).

    ``diagnostics=True`` returns the raw policy actions under
    ``diag_pi`` (stop-gradient'd; popped by the caller) for the
    tanh-saturation reduction.
    """
    pi_obs = batch.next_states if parity_pi_obs else batch.states
    pi, logp_pi = actor_apply(actor_params, pi_obs, key)
    q_pi = critic_apply(critic_params, batch.states, pi)
    q_pi_min = jnp.min(q_pi, axis=0)
    loss = jnp.mean(alpha * logp_pi - q_pi_min)
    aux = {"logp_pi": jnp.mean(logp_pi), "entropy": -jnp.mean(logp_pi)}
    if diagnostics:
        aux["diag_pi"] = jax.lax.stop_gradient(pi)
    return loss, aux


def alpha_loss(
    log_alpha: jax.Array, logp_pi: jax.Array, target_entropy: float
) -> jax.Array:
    """Learned-temperature loss (SAC v2 extension; the reference fixes
    alpha, ref ``main.py:148``): ``-log_alpha * (logp_pi + H_target)``.
    """
    return -log_alpha * (jax.lax.stop_gradient(logp_pi) + target_entropy)


# --------------------------------------------------------------------------
# One history trunk shared by actor and critics (models/sequence.py)
# --------------------------------------------------------------------------
#
# The critic's tree holds the trunk and the Q heads; the actor's holds the
# policy head alone. ``features_apply(critic_params, obs) -> (h, stats)`` is
# one trunk pass (``h``: the last step's features), ``q_apply(critic_params,
# h, action)`` the Q heads and ``head_apply(actor_params, h, key)`` the
# policy head. A gradient step makes two trunk passes and one backward pass:
#
# - the target trunk on ``next_states``: its features feed the target Q heads
#   and the policy head that draws ``a'`` (the target is the polyak average of
#   the online trunk; a third pass, of the online trunk on ``next_states``
#   for ``a'`` alone, would cost a quarter more of the step);
# - the online trunk on ``states``, differentiated by the critic loss, which
#   is what trains it. The policy loss and the entropy term read the same
#   features through ``stop_gradient`` against the updated Q heads, the way
#   DrQ-v2 encodes once, steps the critic and encoder, and hands the detached
#   encoding to the actor step.


def shared_trunk_critic_loss(
    critic_params: t.Any,
    *,
    features_apply: t.Callable,
    q_apply: t.Callable,
    head_apply: t.Callable,
    actor_params: t.Any,
    target_critic_params: t.Any,
    batch: Batch,
    key: jax.Array,
    alpha: jax.Array,
    gamma: float,
    reward_scale: float,
) -> t.Tuple[jax.Array, t.Dict[str, t.Any]]:
    """:func:`critic_loss` on a shared trunk. The aux carries the online
    features (``features``, stop-gradient'd, for the actor step) and both
    passes' trunk statistics."""
    h_next, stats_target = features_apply(target_critic_params, batch.next_states)
    next_action, next_logp = head_apply(actor_params, h_next, key)
    q_target_min = jnp.min(q_apply(target_critic_params, h_next, next_action), axis=0)
    backup = reward_scale * batch.rewards + gamma * (1.0 - batch.done) * (
        q_target_min - alpha * next_logp
    )
    backup = jax.lax.stop_gradient(backup)

    h, stats = features_apply(critic_params, batch.states)
    q = q_apply(critic_params, h, batch.actions)  # (num_qs, B)
    loss = jnp.sum(jnp.mean((q - backup[None, :]) ** 2, axis=-1))
    aux = {
        "q_mean": jnp.mean(q), "backup_mean": jnp.mean(backup),
        "features": jax.lax.stop_gradient(h),
        "stats": jax.lax.stop_gradient(stats),
        "stats_target": jax.lax.stop_gradient(stats_target),
    }
    return loss, aux


def shared_trunk_actor_loss(
    actor_params: t.Any,
    *,
    head_apply: t.Callable,
    q_apply: t.Callable,
    critic_params: t.Any,
    features: jax.Array,
    key: jax.Array,
    alpha: jax.Array,
) -> t.Tuple[jax.Array, t.Dict[str, jax.Array]]:
    """:func:`actor_loss` on the trunk's features, which carry no gradient:
    ``actor_params`` are the policy head alone."""
    pi, logp_pi = head_apply(actor_params, features, key)
    q_pi_min = jnp.min(q_apply(critic_params, features, pi), axis=0)
    loss = jnp.mean(alpha * logp_pi - q_pi_min)
    return loss, {"logp_pi": jnp.mean(logp_pi), "entropy": -jnp.mean(logp_pi)}
