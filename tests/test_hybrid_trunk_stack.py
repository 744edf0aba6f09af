"""The ``nemotron_h`` history trunk as a stack at a small size on the CPU:
built from its pattern through ``build_models``, one SAC step on seeded
weights (what it trains, what it counts, the scopes its parts carry), and the
``Trainer``'s normal path. The layers are ``test_hybrid_trunk.py``'s."""

import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import hybrid_weights  # noqa: E402
from torch_actor_critic_tpu.models import TrunkSpec  # noqa: E402
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner  # noqa: E402
from torch_actor_critic_tpu.telemetry import scopes  # noqa: E402
from torch_actor_critic_tpu.utils.config import SACConfig  # noqa: E402

HIDDEN, HISTORY, OBS, ACT = 32, 12, 5, 3
SMALL = dict(
    trunk_pattern="EMEM*", trunk_hidden=HIDDEN, trunk_q_heads=2, trunk_kv_heads=1,
    trunk_head_dim=8, trunk_experts=16, trunk_experts_per_tok=4, trunk_expert_width=12,
    trunk_experts_held=(2, 6), trunk_block_length=1, trunk_rms_eps=1e-5,
    trunk_qk_norm_rope=False, trunk_router="sigmoid", trunk_routed_scale=2.5,
    trunk_expert_form="relu2", trunk_expert_latent=16, trunk_shared_expert_width=20,
    trunk_ssm_heads=4, trunk_ssm_head_dim=4, trunk_ssm_groups=2, trunk_ssm_state=8,
    trunk_ssm_chunk=4, trunk_remat=5, trunk_bf16_dots=False,
    history_len=HISTORY, batch_size=4, update_every=3, buffer_size=64,
)


def _learner(**overrides):
    cfg = SACConfig(**{**SMALL, **overrides})
    env = types.SimpleNamespace(
        act_dim=ACT, act_limit=1.0, obs_spec=jax.ShapeDtypeStruct((HISTORY, OBS), jnp.float32),
    )
    return cfg, make_learner(cfg, *build_models(cfg, env), ACT)


def test_the_stack_is_built_from_the_pattern():
    """One block a letter, each one mixer behind one norm; the SDAR stack is
    the same mechanism's ``S`` blocks, under the names it always had."""
    _, sac = _learner()
    state = jax.eval_shape(sac.init_state, jax.random.key(0), jnp.zeros((HISTORY, OBS)))
    trunk = state.critic_params["params"]["trunk"]
    assert sorted(trunk) == ["embed", "final_norm"] + [f"layer_{i}" for i in range(5)]
    assert all(set(trunk[f"layer_{i}"]) == {"norm", "mixer"} for i in range(5))
    assert set(trunk["layer_1"]["mixer"]) == {
        "in_proj", "conv_kernel", "conv_bias", "dt_bias", "A_log", "D", "norm_weight", "out_proj",
    }
    assert set(trunk["layer_4"]["mixer"]) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert set(trunk["layer_0"]["mixer"]) == {
        "router", "router_bias", "latent_down", "latent_up", "w_up", "w_down",
        "shared_up", "shared_down",
    }
    assert trunk["layer_0"]["mixer"]["w_up"].shape == (4, 16, 12)  # held, latent, width
    assert trunk["layer_1"]["mixer"]["in_proj"]["kernel"].shape == (HIDDEN, 2 * 16 + 2 * 16 + 4)
    sdar = SACConfig(trunk_block="sdar_moe", trunk_layers=3, history_len=HISTORY)
    assert sdar.shared_trunk and TrunkSpec.from_config(sdar).kinds == "SSS"
    assert TrunkSpec.from_config(SACConfig(**SMALL)).kinds == "EMEM*"
    assert not SACConfig().shared_trunk
    with pytest.raises(ValueError, match="one letter a layer"):
        SACConfig(trunk_pattern="EMX")
    with pytest.raises(ValueError, match="whole groups"):
        SACConfig(**{**SMALL, "trunk_ssm_heads": 3})


@pytest.fixture(scope="module")
def compiled_step():
    """One SAC step on seeded weights, compiled once for the tests that run
    it and the one that reads its text."""
    from torch_actor_critic_tpu.core.types import Batch

    _, sac = _learner(trunk_report_choices=True)
    example = jnp.zeros((HISTORY, OBS))
    actor0, critic0 = hybrid_weights.seeded_params(sac, example, jax.random.key(7))
    state = jax.jit(sac.init_state)(jax.random.key(0), example).replace(
        actor_params=actor0, critic_params=critic0,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic0),
    )
    k = jax.random.split(jax.random.key(3), 5)
    batch = Batch(
        states=jax.random.normal(k[0], (4, HISTORY, OBS)),
        actions=jax.random.uniform(k[1], (4, ACT), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (4,)),
        next_states=jax.random.normal(k[3], (4, HISTORY, OBS)),
        done=(jax.random.uniform(k[4], (4,)) < 0.3).astype(jnp.float32),
    )
    return state, batch, jax.jit(sac.update).lower(state, batch).compile()


def test_a_step_trains_every_leaf_but_the_correction_bias_and_counts_its_experts(compiled_step):
    state, batch, step = compiled_step
    new_state, metrics = step(state, batch)
    moved, _ = jax.tree_util.tree_flatten_with_path(jax.tree_util.tree_map(
        lambda a, b: bool(jnp.any(a != b)), new_state.critic_params, state.critic_params
    ))
    still = [jax.tree_util.keystr(path) for path, m in moved if not m]
    assert len(still) == 2 and all("router_bias" in name for name in still), still
    # the two expert layers' choices (4 a token) and the held experts' counters
    assert metrics["trunk/choices_first"].shape == (2, 4 * HISTORY, 4)
    assert 0 < float(metrics["trunk/held_assignments"]) <= 2 * 4 * HISTORY * 4
    assert float(metrics["trunk/expert_load_max"]) >= float(metrics["trunk/expert_load_mean"])
    assert np.isfinite(float(metrics["loss_q"])) and np.isfinite(float(metrics["loss_pi"]))


def test_the_hybrid_trunks_parts_carry_their_own_scopes(compiled_step):
    """The compiled step names the new parts: the state-space mixer's four,
    the latent projections, the shared expert and the grouped products inside
    the expert layer's scope, in the backward pass too."""
    table = scopes.scope_table(compiled_step[2].as_text())
    found = {s.rstrip(scopes.INHERITED) for counts in table.values() for s in counts if s}
    assert {
        scopes.TRUNK_SSM_PROJ, scopes.TRUNK_SSM_CONV, scopes.TRUNK_SSM_SCAN,
        scopes.TRUNK_SSM_GATE_NORM, scopes.TRUNK_MOE_LATENT, scopes.TRUNK_MOE_SHARED,
        scopes.TRUNK_MOE_PRODUCTS, scopes.TRUNK_MOE_EXPERTS, scopes.TRUNK_MOE_ROUTE,
        scopes.TRUNK_ATTENTION, scopes.TRUNK_EMBED,
    } <= found
    assert scopes.scope_of(
        "jit(f)/tac/critic/tac/trunk/moe/experts/tac/trunk/moe/experts/products/ragged_dot"
    ) == scopes.TRUNK_MOE_PRODUCTS
    assert scopes.scope_of("jit(f)/tac/trunk/moe/experts/gather") == scopes.TRUNK_MOE_EXPERTS
    assert set(scopes.SCOPES) >= found


def test_the_trainer_builds_and_updates_the_hybrid_trunk():
    """``Trainer`` on a history env with the pattern in its configuration:
    the normal path, no side script (the CLI hands ``--trunk-pattern`` to the
    same field)."""
    from torch_actor_critic_tpu.sac.trainer import Trainer

    cfg = SACConfig(**{
        **SMALL, "history_len": 6, "epochs": 1, "steps_per_epoch": 40, "start_steps": 10,
        "update_after": 10, "update_every": 10, "buffer_size": 200, "max_ep_len": 20,
    })
    trainer = Trainer("Pendulum-v1", cfg, seed=1)
    try:
        metrics = trainer.train()
        trunk = trainer.state.critic_params["params"]["trunk"]
        assert set(trunk["layer_1"]["mixer"]) >= {"in_proj", "A_log", "out_proj"}
        assert int(trainer.state.step) == 30 and np.isfinite(metrics["loss_q"])
    finally:
        trainer.close()
