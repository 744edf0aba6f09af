"""End-to-end SAC training with the sequence-policy stack.

history_len > 1 routes Pendulum through HistoryEnv → SequenceActor /
SequenceDoubleCritic → the same fused DP burst as the MLP stack — the
sequence extension trains through the identical algorithm path
(SURVEY.md §5: capability absent from the reference by construction).
"""

import jax
import jax.numpy as jnp
import pytest
import numpy as np

from torch_actor_critic_tpu.envs.wrappers import HistoryEnv, make_env
from torch_actor_critic_tpu.models import sequence
from torch_actor_critic_tpu.models.sequence import SequenceTrunk, TrunkSpec
from torch_actor_critic_tpu.ops import attention as attention_ops
from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.sac.trainer import Trainer
from torch_actor_critic_tpu.utils.config import SACConfig

SEQ_TINY = dict(
    batch_size=16,
    epochs=1,
    steps_per_epoch=40,
    start_steps=10,
    update_after=10,
    update_every=10,
    buffer_size=500,
    max_ep_len=200,
    history_len=4,
    seq_d_model=16,
    seq_num_heads=2,
    seq_num_layers=1,
)


def test_history_env_window_semantics():
    env = make_env("Pendulum-v1|history:3", seed=0)
    assert isinstance(env, HistoryEnv)
    assert env.obs_spec.shape == (3, 3)
    obs = env.reset(seed=0)
    # window starts filled with the initial observation
    np.testing.assert_array_equal(obs[0], obs[2])
    first = obs[-1].copy()
    obs2, _, _, _ = env.step(env.sample_action())
    # rolled: newest last, previous newest shifted to slot -2
    np.testing.assert_array_equal(obs2[1], first)
    assert not np.array_equal(obs2[-1], first)
    env.close()


@pytest.mark.slow
def test_sequence_sac_trains_end_to_end():
    tr = Trainer("Pendulum-v1", SACConfig(**SEQ_TINY), mesh=make_mesh(dp=2), seed=1)
    from torch_actor_critic_tpu.models import SequenceActor

    assert isinstance(tr.sac.actor_def, SequenceActor)
    metrics = tr.train()
    assert int(tr.state.step) == 30  # 3 update windows x 10 steps
    assert np.isfinite(metrics["loss_q"])
    assert np.isfinite(metrics["loss_pi"])
    ev = tr.evaluate(episodes=1)
    assert np.isfinite(ev["ep_ret_mean"])
    tr.close()


@pytest.mark.slow
def test_sequence_sac_trains_with_sp_sharded_histories():
    """Capstone integration: the HOST trainer end-to-end on a (dp=2,
    sp=2) mesh — history windows staged by the env loop, sharded over
    the T axis at rest and in the burst, ring attention inside the loss
    applies, grads pmean'd over {dp, sp}. The whole sp gradient path
    driven by the real training shell, not a synthetic chunk."""
    cfg = SACConfig(**{**SEQ_TINY, "history_len": 8})
    tr = Trainer("Pendulum-v1", cfg, mesh=make_mesh(dp=2, sp=2), seed=2)
    try:
        assert tr.dp.sac_sp is not None  # ring path engaged in the burst
        assert tr.dp.effective_sp == 2
        # replay histories really laid out over sp
        assert len(tr.buffer.data.states.sharding.device_set) == 4
        metrics = tr.train()
        assert int(tr.state.step) == 30
        assert np.isfinite(metrics["loss_q"])
        ev = tr.evaluate(episodes=1)
        assert np.isfinite(ev["ep_ret_mean"])
    finally:
        tr.close()


# ----------------------- the SDAR trunk between its projections and the kernels


@pytest.fixture
def kernels_here(monkeypatch):
    """The trunk told its target is a TPU, the Pallas kernels interpreted: the
    path the chip takes (flash kernels, and ahead of them the one pass that
    norms, rotates and transposes) runs on the CPU."""
    from jax.experimental import pallas as pl

    compiled = pl.pallas_call
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pl, "pallas_call", lambda *a, **kw: compiled(*a, **{**kw, "interpret": True})
    )


def _sdar_trunk(heads=(2, 1), head_dim=128, remat=0, attention_fn=sequence.default_attention):
    spec = TrunkSpec(
        hidden=32, q_heads=heads[0], kv_heads=heads[1], head_dim=head_dim, layers=2,
        experts=4, experts_per_tok=2, expert_width=16, experts_held=(0, 4), remat=remat,
        bf16_dots=False,  # float32 all through: a last bit's difference stays one
    )
    return SequenceTrunk(spec=spec, attention_fn=attention_fn)


def _histories(seed=0, b=2, t=8, obs=5):
    return jax.random.normal(jax.random.key(seed), (b, t, obs))


def _seeded(trunk, x):
    """Parameters with norm weights off their initial ones, so that a weight
    applied twice or not at all shows."""
    params = {"params": trunk.init(jax.random.key(1), x)["params"]}
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)
    ])


def _pallas_names(jaxpr) -> list:
    """The ``name`` of every Pallas call of a jaxpr, nested ones too (the
    flash kernels are given none)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names += _pallas_names(inner)
    return names


@pytest.mark.parametrize("heads", [(32, 4), (4, 4)], ids=["32-over-4", "4-over-4"])
@pytest.mark.parametrize("how", ["plain", "remat", "vmap"])
def test_trunk_one_pass_is_the_composition(kernels_here, monkeypatch, how, heads):
    """The whole trunk, forward and every parameter's gradient, with the one
    pass ahead of the flash kernels (q's norm, rotary and transposition, and
    one back) against the same trunk composing ``RMSNorm``, ``rotary`` and
    ``transpose`` ahead of the same kernels: grouped and ungrouped heads,
    under ``nn.remat`` of the first block and under the burst's ``vmap``. The
    pass alone is held to 1e-6 (``test_attention.py``); two layers on, 5e-6 of
    a leaf's largest value."""
    trunk = _sdar_trunk(heads, remat=1 if how == "remat" else 0)
    x = _histories()
    params = _seeded(trunk, x)

    def run(passes):
        def loss(p, x):  # traced anew on every run, and compiled as one program
            return jnp.sum(trunk.apply(p, x) ** 2)

        names = _pallas_names(jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr)
        assert ("qk_rope" in names) == ("qk_rope_bwd" in names) == passes
        assert None in names
        if how == "vmap":
            stack = lambda a: jnp.stack([a, 1.5 * a])  # noqa: E731
            fn = jax.jit(jax.vmap(jax.value_and_grad(loss)))
            return fn(jax.tree_util.tree_map(stack, params), stack(x))
        return jax.jit(jax.value_and_grad(loss))(params, x)

    got = run(True)
    monkeypatch.setattr(attention_ops, "_pass_fits", lambda *a, **kw: False)
    want = run(False)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=5e-6 * float(jnp.max(jnp.abs(w))) + 1e-9)


@pytest.mark.parametrize("case", ["passes", "head_dim-64", "xla_attention"])
def test_trunk_takes_the_one_pass_only_where_the_kernels_run(kernels_here, case):
    """Taken: the default attention on a TPU, heads of whole lanes. Not taken,
    the composition instead: a head of 64, the host mirror's ``xla_attention``
    (no kernel at all, on a process whose default backend is a TPU)."""
    kw = {
        "passes": {},
        "head_dim-64": dict(head_dim=64),
        "xla_attention": dict(attention_fn=sequence.xla_attention),
    }[case]
    trunk = _sdar_trunk(**kw)
    x = _histories()
    params = {"params": jax.eval_shape(trunk.init, jax.random.key(1), x)["params"]}
    grad = jax.grad(lambda p, x: jnp.sum(trunk.apply(p, x)))
    names = _pallas_names(jax.make_jaxpr(grad)(params, x).jaxpr)
    # q in each of two layers, there and back
    passes = 2 if case == "passes" else 0
    assert names.count("qk_rope") == names.count("qk_rope_bwd") == passes
    # forward, dQ and dK/dV kernels a layer
    assert names.count(None) == (0 if case == "xla_attention" else 6), names
