"""How ``correct`` is decided, shown to pass and shown to fail: the plain
reference against the program, the control one precision lower, and a timed
path broken underneath a whole run."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_cut import cut

from benchmark import control
from benchmark.harness import draws, main, reference


def _program(config):
    from benchmark.drivers import _common
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    cfg = _common.sac_config(config, {"traffic": {"ring_rows": 64}})
    env = _common.EnvSpec(config["model"])
    actor_def, critic_def = build_models(cfg, env)
    sac = make_learner(cfg, actor_def, critic_def, env.act_dim)
    return cfg, env, sac


@pytest.mark.parametrize("cell_name", ["wallrunner_cnn_burst", "cheetah_pop32_fused"])
def test_reference_update_matches_the_program_step(cell_name):
    """One gradient step of the program (``SAC.update``) on a seeded batch
    against the plain reference on the same batch and the same noise."""
    from benchmark.drivers import _common
    from benchmark.harness import data
    from torch_actor_critic_tpu.core.types import Batch

    _, _, config = cut(cell_name)
    cfg, env, sac = _program(config)
    model, b = config["model"], cfg.batch_size
    actor0, critic0 = _common.seeded_params(sac, env.example_obs(), 11)
    state = sac.init_state(jax.random.key(0), env.example_obs())
    rng = data.state_key(11, 0)
    state = state.replace(
        actor_params=actor0, critic_params=critic0,
        target_critic_params=jax.tree_util.tree_map(jnp.copy, critic0), rng=rng,
    )
    spec = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((1, b) + s.shape, s.dtype), env.obs_spec
    )
    f32 = lambda *shape: jax.ShapeDtypeStruct((1, b) + shape, jnp.float32)  # noqa: E731
    abstract = Batch(states=spec, actions=f32(env.act_dim), rewards=f32(),
                     next_states=spec, done=f32())
    batch = jax.tree_util.tree_map(
        lambda x: x[0], data.fill_transitions(data.data_key(11, 5), abstract)
    )
    new_state, metrics = jax.jit(sac.update)(state, batch)
    # the step's noise, by the program's key discipline (harness/draws.py)
    _, key_q, key_pi = jax.random.split(rng, 3)
    eps = lambda k: jax.random.normal(k, (1, b, env.act_dim), jnp.float32)  # noqa: E731
    lead = lambda tree: jax.tree_util.tree_map(lambda x: x[None], tree)  # noqa: E731
    sac_math = {k: config["sac"][k] for k in ("alpha", "gamma", "polyak", "lr", "reward_scale")}
    ref, loss_q, loss_pi = reference.update(
        reference.init_state(actor0, critic0), lead(_common.batch_dict(batch)),
        eps(key_q), eps(key_pi), model, sac_math,
    )
    assert float(metrics["loss_q"]) == pytest.approx(float(loss_q), rel=1e-5)
    assert float(metrics["loss_pi"]) == pytest.approx(float(loss_pi), rel=1e-5)
    for got, want in (
        (new_state.actor_params, ref["actor"]), (new_state.critic_params, ref["critic"]),
        (new_state.target_critic_params, ref["target"]),
        (new_state.q_opt_state[0].nu, ref["q_nu"]), (new_state.pi_opt_state[0].nu, ref["pi_nu"]),
    ):
        for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=1e-7)


def test_draws_follow_the_program_key_discipline():
    """``burst_draws`` gives the indices ``run_update_burst`` samples."""
    from torch_actor_critic_tpu.buffer.replay import init_replay_buffer, sample

    key = jax.random.key(5)
    _, idx, eps_q, _ = draws.burst_draws(key, 3, 8, 2, 100)
    ring = init_replay_buffer(100, jax.ShapeDtypeStruct((1,), jnp.float32), 2)
    ring = ring.replace(
        data=ring.data.replace(rewards=jnp.arange(100, dtype=jnp.float32)),
        size=jnp.int32(100),
    )
    rng = key
    for step in range(3):
        rng, sample_key = jax.random.split(rng)
        np.testing.assert_array_equal(sample(ring, sample_key, 8).rewards, idx[step])
        rng, key_q, _ = jax.random.split(rng, 3)
        np.testing.assert_array_equal(jax.random.normal(key_q, (8, 2)), eps_q[step])
    assert idx.shape == (3, 8) and int(idx.max()) < 100


@pytest.mark.parametrize("cell_name", ["wallrunner_cnn_burst", "cheetah_pop32_host"])
def test_control_one_precision_lower_comes_out_not_correct(cell_name):
    """The control (the reference in the program's place, float8 operands)
    misses the limits the sound program keeps, by three times or more on the
    number that separates them."""
    _, cell, config = cut(cell_name)
    values = control.readings(cell, config, 23, None, 1, low="fp8_operands")
    numbers = ("loss_q.rel_gap", "loss_pi.rel_gap", "adam_nu.worst_leaf_gap",
               "param_change.worst_leaf_gap")
    limit = cell["limits"]["loss_q"]
    assert all(values[n] <= limit for n in numbers), values
    assert any(values["fp8_operands:" + n] > limit for n in numbers), values
    assert max(values["fp8_operands:" + n] / max(values[n], 1e-12) for n in numbers) > 3


def test_data_parallel_burst_on_four_devices_matches_the_reference():
    """The four-chip path of the burst driver (gradients averaged over dp),
    rehearsed on four virtual devices."""
    bench, cell, config = cut("wallrunner_cnn_burst", chips=4)
    result = main.run_cell(
        bench, cell, config, seed=41, seconds=0.3, trace=False, rehearsal=True
    )
    assert result["correct"] is True and result["device"]["count"] == 4


@pytest.mark.parametrize("fault", ["state_unchanged", "loss_altered"])
def test_a_broken_timed_path_comes_out_not_correct(fault, monkeypatch, capsys):
    """The whole of a run with the timed path broken underneath: a burst that
    returns its parameters unchanged, or one whose loss is altered where it is
    produced.  Such a run still ends as every run ends: ``comparisons`` last
    in its result line and the ``check ...`` lines last on standard error,
    each value beside its limit (all the driver's record keeps of it)."""
    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC

    real = DataParallelSAC.update_burst

    def broken(self, state, buffer, chunk, num_updates):
        # the burst donates its state: keep what it held, to hand it back
        kept = jax.tree_util.tree_map(jnp.copy, (state.actor_params, state.critic_params))
        new_state, new_buffer, metrics = real(self, state, buffer, chunk, num_updates)
        if fault == "state_unchanged":
            new_state = new_state.replace(actor_params=kept[0], critic_params=kept[1])
        else:
            metrics = {**metrics, "loss_q": metrics["loss_q"] * 1.01}
        return new_state, new_buffer, metrics

    monkeypatch.setattr(DataParallelSAC, "update_burst", broken)
    bench, cell, config = cut("wallrunner_cnn_burst")
    result = main.run_cell(
        bench, cell, config, seed=43, seconds=0.3, trace=False, rehearsal=True
    )
    assert result["correct"] is False and result["failed"] == result["attempted"]
    failed = {k for k, (v, lim) in result["comparisons"].items() if v > lim}
    expect = "param_change.worst_leaf_gap" if fault == "state_unchanged" else "loss_q.rel_gap"
    assert expect in failed, result["comparisons"]
    capsys.readouterr()
    main.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert line["correct"] is False and list(line)[-1] == "comparisons"
    assert err.strip().splitlines() == [
        f"check {name}: {value!r} against {limit!r}"
        for name, (value, limit) in line["comparisons"].items()
    ]
    value, limit = line["comparisons"][expect]
    assert value > limit and f"check {expect}: {value!r} against {limit!r}" in err
