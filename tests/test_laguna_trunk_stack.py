"""The ``laguna`` history trunk as a stack at a small size on the CPU, seeded
weights: built from its pattern with head counts by layer kind, the whole
trunk and one SAC step against the plain reference
(``benchmark/harness/reference_laguna_trunk.py``, which imports nothing of the
program), what a step trains and names, what the configuration refuses, and
the stack through ``Trainer``. The window, rotary and each sublayer are
``test_laguna_trunk.py``'s."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from laguna_helpers import HIDDEN, SHARE, WHOLE, YARN, T, _inputs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import reference_laguna_trunk as reference  # noqa: E402
from benchmark.harness import reference_trunk, trunk_weights  # noqa: E402
from torch_actor_critic_tpu.models import SequenceTrunk, TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.models import sequence  # noqa: E402
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner  # noqa: E402
from torch_actor_critic_tpu.telemetry import scopes  # noqa: E402
from torch_actor_critic_tpu.utils import config as config_mod  # noqa: E402
from torch_actor_critic_tpu.utils.config import SACConfig  # noqa: E402

# --------------------------------------------------------- the stack, the normal path

HISTORY, OBS, ACT = 12, 5, 3
SMALL = dict(
    trunk_pattern="fWWWF", trunk_hidden=HIDDEN, trunk_q_heads=2, trunk_window_q_heads=3,
    trunk_kv_heads=1, trunk_head_dim=8, trunk_window=5, trunk_rope_theta=5e5,
    trunk_window_rope_theta=1e4, trunk_rope_share=0.5, trunk_rope_yarn_factor=8.0,
    trunk_rope_yarn_positions=8, trunk_qk_norm=False, trunk_head_gate=True, trunk_dense_width=48, trunk_experts=32,
    trunk_experts_per_tok=4, trunk_expert_width=12, trunk_experts_held=(8, 16),
    trunk_routed_scale=2.5, trunk_shared_expert_width=12, trunk_block_length=1,
    trunk_remat=5, trunk_bf16_dots=False, history_len=HISTORY, batch_size=4, update_every=3,
    buffer_size=64,
)
MODEL = dict(
    {k[len("trunk_"):]: v for k, v in SMALL.items() if k.startswith("trunk_")},
    **YARN, rms_eps=1e-6, act_limit=1.0,
)
SAC_MATH = dict(alpha=0.2, gamma=0.99, polyak=0.995, lr=3e-4, reward_scale=1.0)


def _learner(**overrides):
    cfg = SACConfig(**{**SMALL, **overrides})
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0, obs_spec=jax.ShapeDtypeStruct((HISTORY, OBS), jnp.float32),
    )
    return cfg, make_learner(cfg, *build_models(cfg, env), ACT)


def _seeded_state(sac, seed=7):
    example = jnp.zeros((HISTORY, OBS))
    actor0, critic0 = trunk_weights.seeded_params(sac, example, jax.random.key(seed))
    return jax.jit(sac.init_state)(jax.random.key(0), example).replace(
        actor_params=actor0, critic_params=critic0,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic0),
    )


def _batch(seed=3):
    from torch_actor_critic_tpu.core.types import Batch

    k = jax.random.split(jax.random.key(seed), 5)
    return Batch(
        states=jax.random.normal(k[0], (4, HISTORY, OBS)),
        actions=jax.random.uniform(k[1], (4, ACT), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (4,)),
        next_states=jax.random.normal(k[3], (4, HISTORY, OBS)),
        done=(jax.random.uniform(k[4], (4,)) < 0.3).astype(jnp.float32),
    )


def test_the_stack_is_built_from_the_pattern_with_head_counts_by_kind():
    cfg, sac = _learner()
    state = jax.eval_shape(sac.init_state, jax.random.key(0), jnp.zeros((HISTORY, OBS)))
    trunk = state.critic_params["params"]["trunk"]
    assert sorted(trunk) == ["embed", "final_norm"] + [f"layer_{i}" for i in range(5)]
    assert "mlp" in trunk["layer_0"] and "moe" not in trunk["layer_0"]
    assert all("moe" in trunk[f"layer_{i}"] and "mlp" not in trunk[f"layer_{i}"] for i in range(1, 5))
    q_columns = [trunk[f"layer_{i}"]["attention"]["q_proj"]["kernel"].shape[1] for i in range(5)]
    assert q_columns == [16, 24, 24, 24, 16]  # 2, 3, 3, 3, 2 heads of 8
    gates = [trunk[f"layer_{i}"]["attention"]["g_proj"]["kernel"].shape[1] for i in range(5)]
    assert gates == [2, 3, 3, 3, 2]
    assert "q_norm" not in trunk["layer_1"]["attention"]
    spec = TrunkSpec.from_config(cfg)
    assert [spec.window_of(kind) for kind in spec.kinds] == [None, 5, 5, 5, None]
    assert set(config_mod.TRUNK_LAYER_KINDS) == sequence.TWO_SUBLAYERS | {
        sequence.STATE_SPACE, sequence.ATTENTION, sequence.EXPERTS
    }
    assert spec.window_of("") is None and spec.q_heads_of("") == 2  # a layer that names no kind


@pytest.fixture(scope="module")
def step():
    _, sac = _learner(trunk_report_choices=True)
    state, batch = _seeded_state(sac), _batch(2)
    compiled = jax.jit(sac.update).lower(state, batch).compile()
    return state, batch, compiled


def test_trunk_forward_matches_the_reference(step):
    cfg, obs = SACConfig(**SMALL), _batch(1).states
    params = step[0].critic_params["params"]["trunk"]
    with jax.default_matmul_precision("highest"):
        got = jax.jit(SequenceTrunk(spec=TrunkSpec.from_config(cfg)).apply)({"params": params}, obs)
        want, chosen = jax.jit(lambda p, o: reference.trunk(p, o, MODEL, "highest"))(params, obs)
    assert chosen.shape == (4, 4 * HISTORY, 4)
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_shared_trunk_step_matches_the_reference(step):
    """One gradient step of the program (``SAC.update``) against the shared
    reference step over this family's forward, on the same batch and noise:
    losses, every parameter, the polyak target, Adam's second moments and
    every expert choice (``test_trunk.py``'s tolerances)."""
    state, batch, compiled = step
    new_state, metrics = compiled(state, batch)
    _, key_q, key_pi = jax.random.split(state.rng, 3)
    eps = lambda k: jax.random.normal(k, (1, 4, ACT), jnp.float32)  # noqa: E731
    lead = lambda tree: jax.tree_util.tree_map(lambda x: x[None], tree)  # noqa: E731
    b = dict(states=batch.states, actions=batch.actions, rewards=batch.rewards,
             next_states=batch.next_states, done=batch.done)
    ref, loss_q, loss_pi, chosen, _ = jax.jit(lambda st, b, eq, ep: reference_trunk.update(
        st, b, eq, ep, MODEL, SAC_MATH, "highest", reference.features,
    ))(reference_trunk.init_state(state.actor_params, state.critic_params), lead(b),
       eps(key_q), eps(key_pi))
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-4)
    assert float(metrics["loss_pi"]) == pytest.approx(float(loss_pi), rel=1e-4)
    np.testing.assert_array_equal(metrics["trunk/choices_first"], chosen[0])
    assert float(metrics["trunk/held_assignments"]) == float(
        np.isin(np.asarray(chosen[0]), np.arange(8, 16)).sum()
    )
    # Adam's first step moves an element by lr * g / (|g| + 1e-8), so where a
    # gradient is all rounding the two sides' sums decide what part of lr =
    # 3e-4 it moves: a parameter is held to a third of a step (read: 3.9e-5
    # in one element of a router's 1,024, every other within 1e-5), and the
    # gradients themselves by Adam's second moments, their plain squares.
    for got, want, tol in (
        (new_state.actor_params, ref["actor"], dict(rtol=2e-4, atol=1e-4)),
        (new_state.critic_params, ref["critic"], dict(rtol=2e-4, atol=1e-4)),
        (new_state.target_critic_params, ref["target"], dict(rtol=2e-4, atol=1e-7)),
        (new_state.q_opt_state[0].nu, ref["q_nu"], dict(rtol=1e-3, atol=1e-12)),
        (new_state.pi_opt_state[0].nu, ref["pi_nu"], dict(rtol=1e-3, atol=1e-12)),
    ):
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, **tol)


def test_a_step_trains_every_leaf_and_names_its_parts(step):
    state, batch, compiled = step
    new_state, _ = compiled(state, batch)
    moved, _ = jax.tree_util.tree_flatten_with_path(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), new_state.critic_params, state.critic_params
    ))
    assert [jax.tree_util.keystr(path) for path, m in moved if not m] == []
    table = scopes.scope_table(compiled.as_text())
    found = {s.rstrip(scopes.INHERITED) for counts in table.values() for s in counts if s}
    assert {
        scopes.TRUNK_ATTENTION_FULL, scopes.TRUNK_ATTENTION_SLIDING, scopes.TRUNK_ATTENTION_GATE,
        scopes.TRUNK_DENSE_FFN, scopes.TRUNK_MOE_SHARED, scopes.TRUNK_MOE_ROUTE,
        scopes.TRUNK_MOE_EXPERTS, scopes.TRUNK_EMBED,
    } <= found <= set(scopes.SCOPES)
    assert scopes.TRUNK_ATTENTION not in found  # every attention sublayer names its kind
    # the readers that sum tac/trunk/attention still find them
    assert all(s.startswith(scopes.TRUNK_ATTENTION) for s in (
        scopes.TRUNK_ATTENTION_FULL, scopes.TRUNK_ATTENTION_SLIDING, scopes.TRUNK_ATTENTION_GATE,
    ))
    assert scopes.scope_of(
        "jit(f)/tac/critic/tac/trunk/attention/sliding/tac/trunk/attention/gate/mul"
    ) == scopes.TRUNK_ATTENTION_GATE


# ----------------------------------------------------------- what is refused


def test_the_configuration_refuses_what_the_stack_cannot_be():
    with pytest.raises(ValueError) as unknown:
        SACConfig(trunk_pattern="fWXF", history_len=8)
    for letter, what in config_mod.TRUNK_LAYER_KINDS.items():
        assert f"{letter!r}: {what}" in str(unknown.value)
    assert "['X']" in str(unknown.value)
    with pytest.raises(ValueError, match="trunk_window=0"):
        SACConfig(**{**SMALL, "trunk_window": 0})
    with pytest.raises(ValueError, match="trunk_dense_width=0"):
        SACConfig(**{**SMALL, "trunk_dense_width": 0})
    with pytest.raises(ValueError, match="trunk_window_q_heads=3"):
        SACConfig(**{**SMALL, "trunk_kv_heads": 2})
    with pytest.raises(ValueError, match="an even number of channels"):
        SACConfig(**{**SMALL, "trunk_rope_share": 0.3})
    with pytest.raises(ValueError, match="trunk_rope_yarn_positions"):
        SACConfig(**{**SMALL, "trunk_rope_yarn_positions": 0})
    again = SACConfig.from_json(SACConfig(**SMALL).to_json())
    assert again == SACConfig(**SMALL)


def test_a_shared_trunk_under_sp_is_refused_and_a_window_is_never_dropped():
    from torch_actor_critic_tpu.parallel.context import make_ring_attention_fn
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC

    _, sac = _learner()
    mesh = types.SimpleNamespace(shape={"dp": 1, "sp": 2})
    with pytest.raises(ValueError, match="not sharded over sp=2"):
        DataParallelSAC(sac, mesh)
    # an attention_fn that knows no window is told of it, and refuses
    spec, u = TrunkSpec(**{**WHOLE, **SHARE}), _inputs()
    ring = make_ring_attention_fn("sp", 2)
    layer = sequence.GroupedQueryAttention(spec, ring, kind="W")
    with pytest.raises(TypeError, match="window|block_length"):
        layer.init(jax.random.key(0), u, jnp.arange(T))


def test_the_trainer_builds_and_updates_the_laguna_trunk():
    """``Trainer`` on a history env with the pattern in its configuration:
    the normal path (the CLI hands ``--trunk-pattern`` and the other
    ``--trunk-*`` flags to the same fields); the host mirror's attention is
    ``xla_attention`` under the same window."""
    from torch_actor_critic_tpu.sac.trainer import Trainer

    cfg = SACConfig(**{
        **SMALL, "trunk_pattern": "fW", "trunk_remat": 0, "history_len": 6, "trunk_window": 3,
        "epochs": 1, "steps_per_epoch": 40, "start_steps": 10, "update_after": 10,
        "update_every": 10, "buffer_size": 200, "max_ep_len": 20,
    })
    trainer = Trainer("Pendulum-v1", cfg, seed=1)
    try:
        metrics = trainer.train()
        trunk = trainer.state.critic_params["params"]["trunk"]
        assert set(trunk["layer_0"]) == {"attention", "input_norm", "post_attention_norm", "mlp"}
        assert int(trainer.state.step) == 30 and np.isfinite(metrics["loss_q"])
    finally:
        trainer.close()
