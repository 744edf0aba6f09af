"""Properties of the fused SAC update step.

The reference never tests its losses or train loop (SURVEY.md §4);
these pin down the semantics of one gradient step and the
push-then-scan update burst.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torch_actor_critic_tpu.buffer import init_replay_buffer, push
from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.sac import SAC, losses
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 4, 2


def make_sac(**overrides):
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=8, **overrides)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes, act_limit=1.0)
    critic = DoubleCritic(hidden_sizes=cfg.hidden_sizes, num_qs=cfg.num_qs)
    return SAC(cfg, actor, critic, ACT_DIM)


def make_batch(key, n=8):
    ks = jax.random.split(key, 5)
    return Batch(
        states=jax.random.normal(ks[0], (n, OBS_DIM)),
        actions=jnp.tanh(jax.random.normal(ks[1], (n, ACT_DIM))),
        rewards=jax.random.normal(ks[2], (n,)),
        next_states=jax.random.normal(ks[3], (n, OBS_DIM)),
        done=(jax.random.uniform(ks[4], (n,)) < 0.2).astype(jnp.float32),
    )


@pytest.fixture
def sac_and_state():
    sac = make_sac()
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    return sac, state


def test_init_state_target_equals_critic(sac_and_state):
    _, state = sac_and_state
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, state.critic_params, state.target_critic_params
    )
    assert int(state.step) == 0


def test_update_is_pure_and_deterministic(sac_and_state):
    sac, state = sac_and_state
    batch = make_batch(jax.random.key(1))
    s1, m1 = sac.update(state, batch)
    s2, m2 = sac.update(state, batch)
    jax.tree_util.tree_map(np.testing.assert_array_equal, s1.actor_params, s2.actor_params)
    assert float(m1["loss_q"]) == float(m2["loss_q"])


def test_update_moves_params_and_polyak_target(sac_and_state):
    sac, state = sac_and_state
    batch = make_batch(jax.random.key(1))
    new_state, metrics = jax.jit(sac.update)(state, batch)

    # params moved
    assert not np.allclose(
        np.asarray(jax.tree_util.tree_leaves(new_state.actor_params)[0]),
        np.asarray(jax.tree_util.tree_leaves(state.actor_params)[0]),
    )
    # target = polyak * old_target + (1-polyak) * NEW critic (post-step),
    # matching reference update order (critic step, then polyak over the
    # stepped critic, sac/algorithm.py:276-278).
    p = sac.config.polyak
    expected = jax.tree_util.tree_map(
        lambda new_c, old_t: p * old_t + (1 - p) * new_c,
        new_state.critic_params,
        state.target_critic_params,
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        expected,
        new_state.target_critic_params,
    )
    for k in ("loss_q", "loss_pi", "q_mean", "logp_pi"):
        assert np.isfinite(float(metrics[k])), k
    assert int(new_state.step) == 1


def test_fixed_alpha_is_constant(sac_and_state):
    sac, state = sac_and_state
    batch = make_batch(jax.random.key(1))
    new_state, metrics = sac.update(state, batch)
    assert float(new_state.log_alpha) == float(state.log_alpha)
    np.testing.assert_allclose(float(metrics["alpha"]), 0.2, rtol=1e-6)


def test_learned_alpha_moves():
    sac = make_sac(learn_alpha=True)
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))
    new_state, _ = sac.update(state, batch)
    assert float(new_state.log_alpha) != float(state.log_alpha)
    # target_entropy defaults to -act_dim
    assert sac.target_entropy == -float(ACT_DIM)


def test_critic_loss_matches_manual_computation(sac_and_state):
    sac, state = sac_and_state
    batch = make_batch(jax.random.key(1))
    key = jax.random.key(7)
    cfg = sac.config

    loss, _ = losses.critic_loss(
        state.critic_params,
        actor_apply=sac._actor_apply,
        critic_apply=sac._critic_apply,
        actor_params=state.actor_params,
        target_critic_params=state.target_critic_params,
        batch=batch,
        key=key,
        alpha=jnp.float32(cfg.alpha),
        gamma=cfg.gamma,
        reward_scale=cfg.reward_scale,
    )

    # Manual replication with the same key.
    a2, logp = sac.actor_def.apply(state.actor_params, batch.next_states, key)
    qt = sac.critic_def.apply(state.target_critic_params, batch.next_states, a2)
    backup = np.asarray(batch.rewards) + cfg.gamma * (
        1 - np.asarray(batch.done)
    ) * (np.min(np.asarray(qt), axis=0) - cfg.alpha * np.asarray(logp))
    q = np.asarray(sac.critic_def.apply(state.critic_params, batch.states, batch.actions))
    expected = sum(np.mean((q[i] - backup) ** 2) for i in range(2))
    np.testing.assert_allclose(float(loss), expected, rtol=1e-5)


def test_parity_pi_obs_flag_changes_loss():
    """parity_pi_obs=True must sample pi from next_states (ref quirk)."""
    sac_fixed = make_sac(parity_pi_obs=False)
    sac_parity = make_sac(parity_pi_obs=True)
    state = sac_fixed.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))

    kwargs = dict(
        actor_apply=sac_fixed._actor_apply,
        critic_apply=sac_fixed._critic_apply,
        critic_params=state.critic_params,
        batch=batch,
        key=jax.random.key(2),
        alpha=jnp.float32(0.2),
    )
    l_fixed, _ = losses.actor_loss(state.actor_params, parity_pi_obs=False, **kwargs)
    l_parity, _ = losses.actor_loss(state.actor_params, parity_pi_obs=True, **kwargs)
    assert float(l_fixed) != float(l_parity)

    # With states == next_states the two must agree exactly.
    same_batch = batch.replace(next_states=batch.states)
    kwargs["batch"] = same_batch
    l1, _ = losses.actor_loss(state.actor_params, parity_pi_obs=False, **kwargs)
    l2, _ = losses.actor_loss(state.actor_params, parity_pi_obs=True, **kwargs)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_update_burst_end_to_end(sac_and_state):
    sac, state = sac_and_state
    buf = init_replay_buffer(64, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = push(buf, make_batch(jax.random.key(5), n=32))

    chunk = make_batch(jax.random.key(6), n=10)
    burst = jax.jit(sac.update_burst, static_argnums=(3,), donate_argnums=(0, 1))
    state2, buf2, metrics = burst(state, buf, chunk, 5)
    assert int(state2.step) == 5
    assert int(buf2.size) == 42
    assert np.isfinite(float(metrics["loss_q"]))
    assert metrics["loss_q"].shape == ()  # averaged over the burst


def test_redq_wide_ensemble_updates():
    """num_qs=4 (REDQ-style): the vmapped ensemble generalizes past the
    reference's hardwired twin — wider min-clipping targets train with
    finite losses and a (4, B) Q surface."""
    sac = make_sac(num_qs=4)
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))
    q = sac.critic_def.apply(state.critic_params, batch.states, batch.actions)
    assert q.shape == (4, 8)
    new_state, metrics = jax.jit(sac.update)(state, batch)
    assert np.isfinite(float(metrics["loss_q"]))
    assert np.isfinite(float(metrics["loss_pi"]))
    # All four members moved.
    for i in range(4):
        a = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x[i], state.critic_params)
        )[0]
        b = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x[i], new_state.critic_params)
        )[0]
        assert not np.allclose(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_update_burst_donates_buffer_in_hlo(sac_and_state):
    """The fused burst's replay buffer MUST be donated (input-output
    aliased in the compiled HLO). Losing donation would silently
    deep-copy the multi-GB HBM ring on every dispatch — the ring that
    stays on the device is the design the framework trades on
    (SURVEY.md §7; PERF.md section 5 shows what a whole-ring copy
    costs a window).

    Differential: the same burst is compiled with and without the
    buffer in donate_argnums, and the alias-count delta must cover the
    buffer's 7 leaves (5 Batch fields + ptr + size) — train-state
    donation alone cannot satisfy this, so a regression that drops
    ONLY the buffer from donation turns the test red."""
    sac, state = sac_and_state
    buf = init_replay_buffer(256, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM)
    buf = jax.jit(push, donate_argnums=(0,))(buf, make_batch(jax.random.key(2), 64))

    def alias_count(donate):
        hlo = (
            jax.jit(sac.update_burst, static_argnums=(3,), donate_argnums=donate)
            .lower(state, buf, make_batch(jax.random.key(3), 10), 5)
            .compile()
            .as_text()
        )
        return hlo.count("must-alias") + hlo.count("may-alias")

    with_buffer = alias_count((0, 1))
    state_only = alias_count((0,))
    assert with_buffer - state_only >= 7, (with_buffer, state_only)


def test_burst_unroll_auto_resolves_by_backend(monkeypatch):
    """Default burst_unroll=0 is 'auto': 5 on the TPU backend, 1
    elsewhere. Both branches are pinned by patching the backend probe
    (the property reads it at call time); explicit values pass through
    unchanged and negatives are rejected at construction."""
    assert SACConfig().burst_unroll == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert SACConfig().resolved_burst_unroll == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert SACConfig().resolved_burst_unroll == 5
    assert SACConfig(burst_unroll=3).resolved_burst_unroll == 3
    with pytest.raises(ValueError, match="burst_unroll"):
        SACConfig(burst_unroll=-1)


def test_update_burst_unroll_is_semantics_preserving():
    """burst_unroll is a pure scheduling knob: the unrolled scan must
    produce exactly the same learner state and metrics as unroll=1
    (including a length that does not divide by the unroll factor)."""
    results = []
    for unroll in (1, 4):
        sac = make_sac(burst_unroll=unroll)
        state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
        buf = init_replay_buffer(
            64, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM
        )
        buf = push(buf, make_batch(jax.random.key(5), n=32))
        chunk = make_batch(jax.random.key(6), n=10)
        st, _, m = jax.jit(sac.update_burst, static_argnums=(3,))(
            state, buf, chunk, 6
        )
        results.append((st, m))
    (st1, m1), (st4, m4) = results
    np.testing.assert_allclose(float(m1["loss_q"]), float(m4["loss_q"]), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(st1.actor_params),
        jax.tree_util.tree_leaves(st4.actor_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
