"""The data-parallel bursts the benchmark has, at a small size on the CPU: the
reference MLP's, the visual stack's and the small transformer sequence
stack's lower to the text they did before the shared trunk rewired their
losses, and the SDAR cell's small burst chooses and learns under the
selection what it did with ``lax.top_k`` and the mask."""

import hashlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from trunk_helpers import route_by_sort_and_mask

from torch_actor_critic_tpu.ops import moe
from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
from torch_actor_critic_tpu.utils.config import SACConfig

# sha256 of the lowered (StableHLO) data-parallel burst of the reference MLP,
# the visual stack and the small transformer sequence stack, read on the
# parent commit of PR 26 (09dbf80) and on PR 26: the trunk rewired the losses
# those programs share, and they lower to the same text.  A PR that means to
# change one of these programs replaces its hash (scripts in CHANGES.md, PR 26).
GOLDEN = {
    "mlp": "6607b7a076c9a5453c89339f460dae787fb2b6855ecdf1259849ebb8fd9476a6",
    "visual": "4d30ef026bbd543419d11e497cc54a95f30a98692a59026881a5672d7e681621",
    "sequence": "b638b4523cb7e1661437e32ac1670a3ead78a71741e9683d4d99dac3b26d9c7a",
}


def _burst_text(cfg, obs_spec, act_dim):
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
    from torch_actor_critic_tpu.core.types import BufferState
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.parallel.mesh import make_mesh

    env = types.SimpleNamespace(act_dim=act_dim, act_limit=1.0, obs_spec=obs_spec)
    sac = make_learner(cfg, *build_models(cfg, env), act_dim)
    learner = DataParallelSAC(sac, make_mesh(dp=1, devices=jax.devices()[:1]))
    example = jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), obs_spec)
    state = jax.eval_shape(sac.init_state, jax.random.key(0), example)

    def rows(n):
        one = jax.eval_shape(lambda: init_replay_buffer(n, obs_spec, act_dim).data)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype), one
        )

    index = jax.ShapeDtypeStruct((1,), jnp.int32)
    ring = BufferState(data=rows(256), ptr=index, size=index)
    chunk = rows(cfg.update_every)
    return learner._build_burst(cfg.update_every, state, ring, chunk).lower(
        state, ring, chunk
    ).as_text()


@pytest.mark.parametrize("family", sorted(GOLDEN))
def test_the_bursts_the_benchmark_has_lower_to_what_they_did(family):
    from torch_actor_critic_tpu.core.types import MultiObservation

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    if family == "mlp":
        text = _burst_text(SACConfig(batch_size=8, update_every=4, buffer_size=256), f32(17), 6)
    elif family == "visual":
        spec = MultiObservation(
            features=f32(12), frame=jax.ShapeDtypeStruct((44, 44, 3), jnp.uint8)
        )
        text = _burst_text(SACConfig(batch_size=4, update_every=2, buffer_size=256), spec, 5)
    else:
        cfg = SACConfig(batch_size=4, update_every=2, buffer_size=256, history_len=8)
        text = _burst_text(cfg, f32(8, 5), 3)
    assert hashlib.sha256(text.encode()).hexdigest() == GOLDEN[family]


def test_the_sdar_burst_chooses_and_learns_what_it_did_with_top_k_and_the_mask(monkeypatch):
    """The selection changed ``route`` under the SDAR cell's feet (PR 41; until
    then this test pinned that burst's lowered text, PR 40): its small
    data-parallel burst, run with the selection and with ``lax.top_k`` and the
    mask in ``route``'s place, reports the same choices to the element and
    leaves the same state within float32 rounding."""
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel.dp import (
        DataParallelSAC, init_sharded_buffer, shard_chunk,
    )
    from torch_actor_critic_tpu.parallel.mesh import make_mesh

    cfg = SACConfig(
        trunk_block="sdar_moe", history_len=64, batch_size=4, update_every=3, buffer_size=256,
        burst_unroll=1, trunk_hidden=64, trunk_q_heads=4, trunk_kv_heads=2, trunk_head_dim=16,
        trunk_layers=2, trunk_experts=16, trunk_experts_held=(2, 6), trunk_experts_per_tok=4,
        trunk_expert_width=48, trunk_remat=1, trunk_report_choices=True, trunk_bf16_dots=False,
    )
    spec = jax.ShapeDtypeStruct((64, 5), jnp.float32)
    env = types.SimpleNamespace(act_dim=3, act_limit=1.0, obs_spec=spec)
    mesh = make_mesh(dp=1, devices=jax.devices()[:1])
    k = jax.random.split(jax.random.key(11), 5)
    rows = Batch(
        states=jax.random.normal(k[0], (1, 40, 64, 5)),
        actions=jax.random.uniform(k[1], (1, 40, 3), minval=-1.0, maxval=1.0),
        rewards=jax.random.normal(k[2], (1, 40)),
        next_states=jax.random.normal(k[3], (1, 40, 64, 5)),
        done=(jax.random.uniform(k[4], (1, 40)) < 0.3).astype(jnp.float32),
    )

    def burst(route):
        if route is not None:
            monkeypatch.setattr(moe, "route", route)
        learner = DataParallelSAC(make_learner(cfg, *build_models(cfg, env), 3), mesh)
        state = learner.init_state(jax.random.key(5), jnp.zeros(spec.shape))
        ring = init_sharded_buffer(256, spec, 3, mesh)
        state, _, metrics = learner.update_burst(state, ring, shard_chunk(rows, mesh), 3)
        return jax.device_get((state, metrics))

    (state, metrics), (want_state, want) = burst(None), burst(route_by_sort_and_mask)
    assert metrics["trunk/choices_first"].shape == (2, 4 * 64, 4)
    np.testing.assert_array_equal(metrics["trunk/choices_first"], want["trunk/choices_first"])
    for tree, want_tree in (
        (state.critic_params, want_state.critic_params),
        (state.actor_params, want_state.actor_params),
        (state.target_critic_params, want_state.target_critic_params),
    ):
        # Three Adam steps of 3e-4 each. Adam divides a gradient by its own
        # size, so where one is all rounding (a router's, a sum of terms that
        # cancel) the two programs' last bits move a parameter by a few
        # hundredths of a step (read: 1.1e-5 in 37 of a router's 1,024).
        for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(want_tree)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=0.03 * 3 * cfg.lr)
    assert float(metrics["loss_q"]) == pytest.approx(float(want["loss_q"]), rel=1e-6)
