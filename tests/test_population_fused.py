"""Population-fused on-device training (sac/ondevice.py).

The correctness contract has three layers, each pinned here:

1. **Bitwise member independence** — member ``i``'s epoch output is
   bitwise invariant to what the other population slots contain (the
   clone test): no leakage through replay sampling, optimizer state or
   PRNG streams, proven at full float precision.
2. **Stacked-single equivalence** — with PBT off, a population epoch is
   N single-learner :class:`OnDeviceLoop` epochs: warmup collection
   (envs, replay rings, PRNG streams) and loss streams are bitwise
   equal; parameter trajectories agree to float-accumulation order
   (vmap batches the backward matmuls, which XLA may legally
   reassociate — the same documented tolerance as
   ``tests/test_population.py``).
3. **On-device PBT** — per-member hyperparameters thread through
   ``TrainState.hyperparams`` (bitwise-neutral at default values), and
   the exploit/explore step copies winner params and perturbs loser
   hyperparameters entirely in-graph.

The driver (``train_population_on_device``: checkpoint, resume, export, the
CLI) and the per-member normalizer are ``test_population_fused_driver.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np

from population_fused_helpers import _assert_bitwise, _leaves

from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.buffer.replay import init_replay_buffer
from torch_actor_critic_tpu.envs.ondevice import PendulumJax
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.sac import SAC
from torch_actor_critic_tpu.sac.ondevice import (
    OnDeviceLoop,
    PBTState,
    PopulationOnDeviceLoop,
)
from torch_actor_critic_tpu.utils.config import SACConfig

OBS, ACT = 3, 1
N_ENVS = 4


def _sac(**over):
    cfg = SACConfig(hidden_sizes=(16, 16), batch_size=8, **over)
    return SAC(
        cfg,
        Actor(act_dim=ACT, hidden_sizes=cfg.hidden_sizes, act_limit=2.0),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT,
    )


# ------------------------------------------------------- core equivalence


def test_warmup_epoch_bitwise_equals_stacked_single_runs():
    """PBT off, no updates: the vmapped collect path — env physics,
    replay pushes, PRNG streams, episode stats — is bitwise-equal to N
    separate single-learner OnDeviceLoop runs seeded with the member
    keys."""
    sac = _sac()
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 3, n_envs=N_ENVS)
    root = jax.random.key(0)
    ts, buf, es, keys, _ = pop.init(root, buffer_capacity=512)
    ts, buf, es, keys, m = pop.epoch(
        ts, buf, es, keys, steps=20, update_every=10, warmup=True
    )
    single = OnDeviceLoop(sac, PendulumJax, n_envs=N_ENVS)
    member_keys = jax.random.split(root, 3)
    for i in range(3):
        sts, sbuf, ses, skey = single.init(member_keys[i], buffer_capacity=512)
        sts, sbuf, ses, skey, sm = single.epoch(
            sts, sbuf, ses, skey, steps=20, update_every=10, warmup=True
        )
        slice_i = lambda t: jax.tree_util.tree_map(lambda x: x[i], t)  # noqa: E731
        _assert_bitwise(slice_i(buf), sbuf, f"replay ring, member {i}")
        _assert_bitwise(slice_i(es), ses, f"env states, member {i}")
        _assert_bitwise(slice_i(ts), sts, f"train state, member {i}")
        _assert_bitwise(keys[i], skey, f"act key, member {i}")
        np.testing.assert_array_equal(
            np.asarray(m["episodes"])[i], np.asarray(sm["episodes"])
        )


def test_update_epoch_matches_stacked_single_runs():
    """PBT off, with gradient bursts: loss streams and parameter
    trajectories agree to the documented float-reassociation tolerance.
    The vmapped population and a single run are differently batched
    computations of the same numbers (vmap batches the forward and
    backward matmuls), and XLA promises no bitwise equality between
    those — the bitwise pin at equal shapes is
    ``test_member_independence_is_bitwise`` below."""
    sac = _sac()
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 2, n_envs=N_ENVS)
    root = jax.random.key(1)
    ts, buf, es, keys, _ = pop.init(root, buffer_capacity=512)
    ts, buf, es, keys, _ = pop.epoch(
        ts, buf, es, keys, steps=10, update_every=10, warmup=True
    )
    ts, buf, es, keys, m = pop.epoch(ts, buf, es, keys, steps=20, update_every=10)
    assert int(np.asarray(ts.step)[0]) == 20

    single = OnDeviceLoop(sac, PendulumJax, n_envs=N_ENVS)
    member_keys = jax.random.split(root, 2)
    for i in range(2):
        sts, sbuf, ses, skey = single.init(member_keys[i], buffer_capacity=512)
        sts, sbuf, ses, skey, _ = single.epoch(
            sts, sbuf, ses, skey, steps=10, update_every=10, warmup=True
        )
        sts, sbuf, ses, skey, sm = single.epoch(
            sts, sbuf, ses, skey, steps=20, update_every=10
        )
        for loss in ("loss_q", "loss_pi"):
            np.testing.assert_allclose(
                np.asarray(m[loss])[i], np.asarray(sm[loss]),
                rtol=2e-5, atol=2e-6,
            )
        got = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda x: x[i], ts.actor_params)
        )
        want = jax.tree_util.tree_leaves(sts.actor_params)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-6)
        # Ring cursors advanced identically.
        assert int(np.asarray(buf.size)[i]) == int(sbuf.size)
        assert int(np.asarray(buf.ptr)[i]) == int(sbuf.ptr)


def test_member_independence_is_bitwise():
    """The no-leakage pin, at FULL precision: run a diverse population,
    then rerun the SAME compiled epoch with every slot holding member
    i's state — slot i's complete output (params, optimizer moments,
    replay ring, env states, PRNG) must be bitwise identical. Any
    cross-member coupling whatsoever fails this."""
    sac = _sac()
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 3, n_envs=N_ENVS)
    root = jax.random.key(2)

    def fresh():
        ts, buf, es, keys, _ = pop.init(root, buffer_capacity=512)
        return pop.epoch(
            ts, buf, es, keys, steps=10, update_every=10, warmup=True
        )[:4]

    ts, buf, es, keys = fresh()
    out_div = pop.epoch(ts, buf, es, keys, steps=10, update_every=10)

    for i in (0, 2):
        ts, buf, es, keys = fresh()
        rep = lambda x: jnp.repeat(x[i][None], 3, axis=0)  # noqa: E731
        clone = lambda t: jax.tree_util.tree_map(rep, t)  # noqa: E731
        out_clone = pop.epoch(
            clone(ts), clone(buf), clone(es), clone(keys),
            steps=10, update_every=10,
        )
        for got, want in zip(out_clone, out_div):
            _assert_bitwise(
                jax.tree_util.tree_map(lambda x: x[i], got),
                jax.tree_util.tree_map(lambda x: x[i], want),
                f"member {i} output depends on other slots",
            )


# -------------------------------------------------- hyperparam threading


def _chunk(key, window=10):
    ks = jax.random.split(key, 5)
    return Batch(
        states=jax.random.normal(ks[0], (window, OBS)),
        actions=jax.random.uniform(ks[1], (window, ACT), minval=-1, maxval=1),
        rewards=jax.random.normal(ks[2], (window,)),
        next_states=jax.random.normal(ks[3], (window, OBS)),
        done=jnp.zeros((window,)),
    )


def _burst(sac, state, n=3):
    buf = init_replay_buffer(64, jax.ShapeDtypeStruct((OBS,), jnp.float32), ACT)
    return sac.update_burst(state, buf, _chunk(jax.random.key(5)), n)


def test_default_hyperparams_are_bitwise_neutral():
    """TrainState.hyperparams at the configured values must reproduce
    the plain (hyperparams=None) program bit-for-bit — the dynamic-lr
    path replays optax.adam's exact op sequence."""
    sac = _sac()
    base = sac.init_state(jax.random.key(3), jnp.zeros((OBS,)))
    plain, _, mp = _burst(sac, base)
    hp, _, mh = _burst(sac, base.replace(hyperparams=sac.default_hyperparams()))
    _assert_bitwise(plain.actor_params, hp.actor_params)
    _assert_bitwise(plain.critic_params, hp.critic_params)
    _assert_bitwise(plain.pi_opt_state, hp.pi_opt_state)
    _assert_bitwise(plain.q_opt_state, hp.q_opt_state)
    np.testing.assert_array_equal(np.asarray(mp["loss_q"]), np.asarray(mh["loss_q"]))
    assert hp.hyperparams is not None  # carried through the scan


def test_hyperparams_actually_steer_the_update():
    sac = _sac()
    base = sac.init_state(jax.random.key(4), jnp.zeros((OBS,)))
    hp = sac.default_hyperparams()

    # actor_lr = 0 freezes the actor while the critic still learns
    frozen, _, _ = _burst(
        sac, base.replace(hyperparams={**hp, "actor_lr": jnp.float32(0.0)})
    )
    _assert_bitwise(frozen.actor_params, base.actor_params)
    assert not all(
        np.array_equal(a, b)
        for a, b in zip(_leaves(frozen.critic_params), _leaves(base.critic_params))
    )
    # critic_lr = 0 freezes critic (and its polyak target stays put)
    cfrozen, _, _ = _burst(
        sac, base.replace(hyperparams={**hp, "critic_lr": jnp.float32(0.0)})
    )
    _assert_bitwise(cfrozen.critic_params, base.critic_params)
    _assert_bitwise(cfrozen.target_critic_params, base.target_critic_params)
    # alpha is read from the hyperparams, not the config scalar
    _, _, m_lo = _burst(
        sac, base.replace(hyperparams={**hp, "alpha": jnp.float32(0.01)})
    )
    _, _, m_hi = _burst(
        sac, base.replace(hyperparams={**hp, "alpha": jnp.float32(5.0)})
    )
    assert float(m_lo["loss_pi"]) != float(m_hi["loss_pi"])


def test_td3_hyperparams_thread_through():
    from torch_actor_critic_tpu.models import DeterministicActor
    from torch_actor_critic_tpu.td3 import TD3

    cfg = SACConfig(algorithm="td3", hidden_sizes=(16, 16), batch_size=8)
    td3 = TD3(
        cfg,
        DeterministicActor(
            act_dim=ACT, hidden_sizes=cfg.hidden_sizes, act_limit=2.0,
            act_noise=cfg.act_noise,
        ),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        ACT,
    )
    base = td3.init_state(jax.random.key(6), jnp.zeros((OBS,)))
    hp = td3.default_hyperparams()
    assert set(hp) == {"actor_lr", "critic_lr", "target_noise"}
    plain, _, mp = _burst(td3, base)
    with_hp, _, mh = _burst(td3, base.replace(hyperparams=hp))
    _assert_bitwise(plain.actor_params, with_hp.actor_params)
    np.testing.assert_array_equal(
        np.asarray(mp["loss_q"]), np.asarray(mh["loss_q"])
    )
    _, _, m_noisy = _burst(
        td3, base.replace(hyperparams={**hp, "target_noise": jnp.float32(2.0)})
    )
    assert float(m_noisy["loss_q"]) != float(mp["loss_q"])


# ------------------------------------------------------------------- pbt


def test_pbt_step_copies_winner_and_perturbs_loser():
    cfg_over = dict(population=4, on_device=True, pbt_every=1,
                    pbt_quantile=0.25, pbt_perturb=1.25)
    sac = _sac(**cfg_over)
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 4, n_envs=2, pbt=True)
    state, _, _, _, pbt_state = pop.init(jax.random.key(7), buffer_capacity=64)
    assert state.hyperparams is not None
    # Distinct EMAs: member 0 worst, member 1 best; quantile 0.25 of 4
    # exploits exactly one member from each end.
    pbt_state = PBTState(
        return_ema=jnp.array([0.0, 10.0, 5.0, 3.0]),
        ema_count=jnp.ones(4, jnp.int32),
        rng=jax.random.key(8),
    )
    new, ps, ev = pop.pbt_step(state, pbt_state)
    exploited = np.asarray(ev["exploited"])
    src = np.asarray(ev["src"])
    np.testing.assert_array_equal(exploited, [True, False, False, False])
    assert src[0] == 1 and (src[1:] == [1, 2, 3]).all()
    # Loser got the winner's params + optimizer state, bitwise.
    for tree in ("actor_params", "critic_params", "pi_opt_state", "q_opt_state"):
        _assert_bitwise(
            jax.tree_util.tree_map(lambda x: x[0], getattr(new, tree)),
            jax.tree_util.tree_map(lambda x: x[1], getattr(state, tree)),
            f"{tree} not copied from winner",
        )
        # Winners/middle members untouched.
        _assert_bitwise(
            jax.tree_util.tree_map(lambda x: x[1:], getattr(new, tree)),
            jax.tree_util.tree_map(lambda x: x[1:], getattr(state, tree)),
            f"{tree} of non-exploited members changed",
        )
    # PRNG streams are NOT copied: the clone must diverge from its source.
    _assert_bitwise(new.rng, state.rng, "member PRNG streams must be kept")
    # Hyperparams: loser = winner's value * perturb^±1; others unchanged.
    perturb = 1.25
    for k in state.hyperparams:
        old = np.asarray(state.hyperparams[k])
        got = np.asarray(new.hyperparams[k])
        ratio = got[0] / old[1]
        assert np.isclose(ratio, perturb) or np.isclose(ratio, 1 / perturb), (
            k, ratio,
        )
        np.testing.assert_array_equal(got[1:], old[1:])
    # Loser inherits the winner's EMA (competes as its new self).
    np.testing.assert_allclose(np.asarray(ps.return_ema), [10.0, 10.0, 5.0, 3.0])


def test_pbt_step_gated_until_every_member_ranked():
    sac = _sac(population=3, on_device=True, pbt_every=1)
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 3, n_envs=2, pbt=True)
    state, _, _, _, _ = pop.init(jax.random.key(9), buffer_capacity=64)
    pbt_state = PBTState(
        return_ema=jnp.array([0.0, 5.0, 1.0]),
        ema_count=jnp.array([1, 0, 1], jnp.int32),  # member 1 unranked
        rng=jax.random.key(10),
    )
    new, ps, ev = pop.pbt_step(state, pbt_state)
    assert not bool(np.asarray(ev["ready"]))
    assert not np.asarray(ev["exploited"]).any()
    _assert_bitwise(new.actor_params, state.actor_params)


def test_update_ema_tracks_and_skips_empty_epochs():
    sac = _sac(population=2, on_device=True, pbt_every=1, pbt_ema=0.5)
    pop = PopulationOnDeviceLoop(sac, PendulumJax, 2, n_envs=2, pbt=True)
    ps = PBTState(
        return_ema=jnp.zeros(2), ema_count=jnp.zeros(2, jnp.int32),
        rng=jax.random.key(0),
    )
    # First contribution seeds the EMA outright.
    ps = pop.update_ema(
        ps, {"episodes": jnp.array([2.0, 0.0]),
             "reward": jnp.array([-100.0, jnp.nan])}
    )
    np.testing.assert_allclose(np.asarray(ps.return_ema), [-100.0, 0.0])
    np.testing.assert_array_equal(np.asarray(ps.ema_count), [1, 0])
    # Second blends at tau=0.5; the NaN no-episode member stays put.
    ps = pop.update_ema(
        ps, {"episodes": jnp.array([1.0, 0.0]),
             "reward": jnp.array([-50.0, jnp.nan])}
    )
    np.testing.assert_allclose(np.asarray(ps.return_ema), [-75.0, 0.0])
