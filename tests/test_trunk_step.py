"""The SDAR history trunk at a small size on the CPU, seeded weights: the
trunk's forward and the shared-trunk SAC step against the plain reference
(``benchmark/harness/reference_trunk.py``, which imports nothing of the
program), and the step's scopes. The bursts the benchmark has are
``test_trunk_bursts.py``'s."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trunk_helpers import (
    ACT,
    HISTORY,
    MODEL,
    OBS,
    SAC_MATH,
    _batch,
    _learner,
    _seeded_state,
)

from benchmark.harness import reference_trunk
from torch_actor_critic_tpu.models import SequenceTrunk, TrunkSpec, policy_params
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
from torch_actor_critic_tpu.utils.config import SACConfig

# ------------------------------------------------------- trunk and SAC step


class _Built:
    """A configuration's learner and its seeded state, and ``SAC.update``
    compiled for them the first time a test asks (batches are of one shape,
    and a plain ``jit`` donates nothing: the state is every test's to read)."""

    def __init__(self, **overrides):
        self.cfg, self.sac = _learner(**overrides)
        self.state = _seeded_state(self.sac)

    @functools.cached_property
    def step(self):
        return jax.jit(self.sac.update).lower(self.state, _batch(2)).compile()


@pytest.fixture(scope="module")
def built():
    """``built(**overrides)``: one :class:`_Built` for each configuration the
    module's tests ask for, so that those asking for the same one share its
    build and its compile. (A test that patches something before it builds
    calls ``_learner`` itself.)"""
    made = {}

    def of(**overrides):
        key = tuple(sorted(overrides.items()))
        if key not in made:
            made[key] = _Built(**overrides)
        return made[key]

    return of


@pytest.fixture(scope="module")
def reference_step():
    """``reference_step(state, mode)``: the plain reference's step from
    ``state`` on ``_batch(2)`` and the noise the program draws from the
    state's key, as one compiled call, computed once a mode for the tests
    that hold a configuration's step to it. The seeded state is the same
    whatever a configuration recomputes, reports or rounds; a second asker's
    is checked against the first's."""
    done = {}

    def of(state, mode="highest"):
        start = reference_trunk.init_state(state.actor_params, state.critic_params)
        asked = (start, jax.random.key_data(state.rng))
        if mode not in done:
            batch = _batch(2)
            _, key_q, key_pi = jax.random.split(state.rng, 3)
            eps = lambda k: jax.random.normal(k, (1, 4, ACT), jnp.float32)  # noqa: E731
            b = dict(states=batch.states, actions=batch.actions, rewards=batch.rewards,
                     next_states=batch.next_states, done=batch.done)
            update = jax.jit(functools.partial(
                reference_trunk.update, model=MODEL, sac=SAC_MATH, mode=mode
            ))
            done[mode] = asked, update(
                start, jax.tree_util.tree_map(lambda x: x[None], b), eps(key_q), eps(key_pi)
            )
        first, result = done[mode]
        jax.tree_util.tree_map(np.testing.assert_array_equal, asked, first)
        return result

    return of


def _trunk_and_reference(cfg, params, obs):
    """The configuration's trunk and the float32 reference's on ``obs``, each
    one compiled call."""
    trunk = SequenceTrunk(spec=TrunkSpec.from_config(cfg))
    got = jax.jit(lambda p, o: trunk.apply({"params": p}, o))(params, obs)
    want, _ = jax.jit(lambda p, o: reference_trunk.trunk(p, o, MODEL, "highest"))(params, obs)
    return got, want


def test_trunk_forward_matches_the_reference(built):
    params = built().state.critic_params["params"]["trunk"]
    got, want = _trunk_and_reference(built().cfg, params, _batch(1).states)
    np.testing.assert_allclose(got, want, atol=2e-5)  # float32, another order of sums


def test_a_trunk_that_keeps_the_kernels_off_keeps_the_selections_off_too(monkeypatch):
    """The Trainer's host mirror is compiled for the CPU beside a TPU, and
    ``auto`` is resolved by the process's default backend: a trunk handed
    ``xla_attention`` takes the selection as XLA composes it too, so its
    program holds no kernel (128 experts: a size the kernels have blocks for)."""
    from torch_actor_critic_tpu.models.sequence import SparseMoE, xla_attention

    cfg, _ = _learner(trunk_experts=128, trunk_experts_held=(8, 16))
    spec = TrunkSpec.from_config(cfg)
    obs = _batch(1).states
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mirror = SequenceTrunk(spec=spec, attention_fn=xla_attention)
    params, apply = jax.jit(mirror.init)(jax.random.key(0), obs), jax.jit(mirror.apply)
    text = apply.lower(params, obs).as_text()
    assert "tpu_custom_call" not in text and "router_top_k" not in text
    assert np.all(np.isfinite(apply(params, obs)))
    # the layer by itself reaches for the kernels, which a CPU cannot lower
    layer, u = SparseMoE(spec), jnp.zeros((4, HISTORY, spec.hidden))
    with pytest.raises(Exception, match="[Ii]nterpret|CPU|cpu"):
        jax.jit(layer.apply).lower(jax.eval_shape(layer.init, jax.random.key(0), u), u)


def test_the_stated_precision_rounds_the_kernels_operands_on_the_cpu_too(built, reference_step):
    """``trunk_bf16_dots`` (the default, what the benchmark's configuration
    states and the chip times) is a property of the configuration and not of
    the platform: on the CPU the expert products round their operands too.
    XLA:CPU's own products stay float32, so the trunk lands a bfloat16
    rounding (2^-9 of values near 3, through two layers) from the float32
    reference: well over float32's order-of-sums 2e-5, well under 1e-2. One
    step at that precision keeps the critic loss within 1% of the reference
    that rounds every product."""
    this = built(trunk_bf16_dots=True)
    cfg, state, batch = this.cfg, this.state, _batch(2)
    assert TrunkSpec.from_config(cfg).bf16_dots and TrunkSpec().bf16_dots
    got, want = _trunk_and_reference(cfg, state.critic_params["params"]["trunk"], batch.states)
    assert 1e-4 < float(jnp.max(jnp.abs(got - want))) < 1e-2
    _, metrics = this.step(state, batch)
    _, loss_q, _, _, _ = reference_step(state, "bf16_operands")
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-2)


@pytest.mark.parametrize("remat", [0, 1])
def test_shared_trunk_step_matches_the_reference(remat, built, reference_step):
    """One gradient step of the program (``SAC.update``) against the plain
    reference on the same batch and noise: losses, every parameter after the
    step, the polyak target, Adam's second moments, and every expert choice.
    Tolerances as ``test_bench_correct.py``'s: float32, another order."""
    this = built(trunk_remat=remat, trunk_report_choices=True)
    state = this.state
    new_state, metrics = this.step(state, _batch(2))
    ref, loss_q, loss_pi, chosen, _ = reference_step(state)
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-5)
    assert float(metrics["loss_pi"]) == pytest.approx(float(loss_pi), rel=1e-5)
    np.testing.assert_array_equal(metrics["trunk/choices_first"], chosen[0])
    assert float(metrics["trunk/held_assignments"]) == float(
        np.isin(np.asarray(chosen[0]), [2, 3, 4, 5]).sum()
    )
    # Adam's first step moves an element by lr * g / (|g| + 1e-8): where the
    # gradient is near that epsilon the two sides' float32 sums decide what
    # fraction of lr = 3e-4 it moves, so parameters are held to 1e-5 absolute
    # (3% of a step) and Adam's second moments, which are plain squares, to
    # a relative 1e-3.
    for got, want, tol in (
        (new_state.actor_params, ref["actor"], dict(rtol=2e-4, atol=1e-5)),
        (new_state.critic_params, ref["critic"], dict(rtol=2e-4, atol=1e-5)),
        (new_state.target_critic_params, ref["target"], dict(rtol=2e-4, atol=1e-7)),
        (new_state.q_opt_state[0].nu, ref["q_nu"], dict(rtol=1e-3, atol=1e-12)),
        (new_state.pi_opt_state[0].nu, ref["pi_nu"], dict(rtol=1e-3, atol=1e-12)),
    ):
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, **tol)


def test_the_trunk_is_trained_by_the_critic_loss_alone(built):
    """The actor's trained parameters are the policy head; the trunk lives in
    the critic's tree, the target covers it, and acting takes both."""
    sac, state = built().sac, built().state
    assert set(state.actor_params["params"]) == {"mu", "log_std"}
    assert set(state.critic_params["params"]) == {"trunk", "ensemble"}
    assert set(state.target_critic_params["params"]) == {"trunk", "ensemble"}
    new_state, _ = built().step(state, _batch(3))
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), new_state.critic_params, state.critic_params
    )
    assert all(jax.tree_util.tree_leaves(moved))  # every leaf of trunk and heads
    acting = policy_params(new_state.actor_params, new_state.critic_params)
    assert set(acting["params"]) == {"mu", "log_std", "trunk"}
    action = sac.select_action(acting, _batch(3).states, jax.random.key(1))
    assert action.shape == (4, ACT) and bool(jnp.all(jnp.abs(action) <= 1.0))


def test_policy_params_is_the_identity_for_separate_networks():
    cfg = SACConfig(batch_size=4)
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0, obs_spec=jax.ShapeDtypeStruct((OBS,), jnp.float32)
    )
    sac = make_learner(cfg, *build_models(cfg, env), ACT)
    state = jax.jit(sac.init_state)(jax.random.key(0), jnp.zeros((OBS,)))
    assert policy_params(state.actor_params, state.critic_params) is state.actor_params


def test_the_trunks_parts_carry_their_own_scopes(built):
    """The compiled step names the trunk's parts inside ``tac/critic``: the
    innermost scope of an instruction is the part's, in the forward pass and
    in the hand-written backward passes (flash kernels, expert layer) alike."""
    from torch_actor_critic_tpu.telemetry import scopes

    text = built().step.as_text()
    table = scopes.scope_table(text)
    found = {s.rstrip(scopes.INHERITED) for counts in table.values() for s in counts if s}
    assert {
        scopes.TRUNK_EMBED, scopes.TRUNK_ATTENTION, scopes.TRUNK_MOE_ROUTE,
        scopes.TRUNK_MOE_EXPERTS, scopes.CRITIC, scopes.ACTOR, scopes.OPTIMIZER,
        scopes.POLYAK,
    } <= found
    assert scopes.scope_of("jit(f)/tac/critic/jvp(x)/tac/trunk/moe/experts/dot") == (
        scopes.TRUNK_MOE_EXPERTS
    )
    # a backward-pass instruction of the expert layer keeps the layer's scope
    backward = [
        line for line in text.splitlines()
        if "transpose(" in line and scopes.TRUNK_MOE_EXPERTS in line
    ]
    assert backward
