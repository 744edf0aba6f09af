"""Numeric parity against an independent PyTorch computation.

SURVEY.md §7's minimum-slice gate: "same weights -> same losses to fp
tolerance" against the PyTorch reference semantics. We copy Flax params
into plain functional torch code (written here, independently of the
reference's nn.Module classes) implementing the same math —
torch.distributions.Normal log-probs, the tanh correction, the Bellman
backup — and require agreement to fp32 tolerance.

The stochastic paths can't be compared bit-for-bit across RNGs, so
parity is pinned where it is deterministic: the actor's deterministic
forward (mode + log-prob at the mode, exactly what the reference
computes when ``deterministic=True``, ref ``networks/linear.py:43-51``),
the critic forward, and the Bellman backup arithmetic.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

torch = pytest.importorskip("torch")

from torch_actor_critic_tpu.models import Actor, DoubleCritic  # noqa: E402

OBS_DIM, ACT_DIM = 11, 3
HIDDEN = (32, 16)
ACT_LIMIT = 2.0


def _dense_params(tree):
    """(kernel, bias) of a wrapped Dense module subtree (the single
    inner nn.Dense is named by its TP role: Dense_0/col/row)."""
    (inner,) = tree.values()
    return np.asarray(inner["kernel"]), np.asarray(inner["bias"])


def _torch_mlp(x, layer_params, relu_final):
    n = len(layer_params)
    for i, (w, b) in enumerate(layer_params):
        x = x @ torch.tensor(w) + torch.tensor(b)
        if relu_final or i < n - 1:
            x = torch.relu(x)
    return x


def test_actor_deterministic_forward_matches_torch():
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN, act_limit=ACT_LIMIT)
    obs = jax.random.normal(jax.random.key(1), (16, OBS_DIM))
    params = actor.init(jax.random.key(0), obs, jax.random.key(2))

    action_jax, logp_jax = actor.apply(
        params, obs, deterministic=True, with_logprob=True
    )

    p = params["params"]
    trunk = [
        _dense_params(p["MLP_0"][f"Dense_{i}"]) for i in range(len(HIDDEN))
    ]
    mu_w, mu_b = _dense_params(p["Dense_0"])
    ls_w, ls_b = _dense_params(p["Dense_1"])

    x = torch.tensor(np.asarray(obs))
    h = _torch_mlp(x, trunk, relu_final=True)
    mu = h @ torch.tensor(mu_w) + torch.tensor(mu_b)
    log_std = torch.clip(h @ torch.tensor(ls_w) + torch.tensor(ls_b), -20.0, 2.0)
    dist = torch.distributions.Normal(mu, torch.exp(log_std))
    u = mu  # deterministic mode
    action_t = torch.tanh(u) * ACT_LIMIT
    logp_t = dist.log_prob(u).sum(-1)
    logp_t = logp_t - (
        2.0 * (math.log(2.0) - u - torch.nn.functional.softplus(-2.0 * u))
    ).sum(-1)

    np.testing.assert_allclose(
        np.asarray(action_jax), action_t.numpy(), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(logp_jax), logp_t.numpy(), rtol=1e-4, atol=1e-5
    )


def test_double_critic_forward_matches_torch():
    critic = DoubleCritic(hidden_sizes=HIDDEN, num_qs=2)
    obs = jax.random.normal(jax.random.key(1), (16, OBS_DIM))
    act = jax.random.normal(jax.random.key(2), (16, ACT_DIM))
    params = critic.init(jax.random.key(0), obs, act)
    q_jax = np.asarray(critic.apply(params, obs, act))

    ens = params["params"]["ensemble"]["MLP_0"]
    x_in = torch.tensor(np.concatenate([np.asarray(obs), np.asarray(act)], -1))
    for member in range(2):
        layers = []
        for i in range(len(HIDDEN) + 1):
            w, b = _dense_params(
                jax.tree_util.tree_map(lambda a: a[member], ens[f"Dense_{i}"])
            )
            layers.append((w, b))
        q_t = _torch_mlp(x_in, layers, relu_final=False).squeeze(-1)
        np.testing.assert_allclose(
            q_jax[member], q_t.numpy(), rtol=1e-5, atol=1e-6
        )


def test_bellman_backup_matches_torch():
    """reward_scale*r + gamma*(1-d)*(min(q1t,q2t) - alpha*logp), as at
    ref sac/algorithm.py:60-67, over random inputs."""
    rng = np.random.default_rng(0)
    r = rng.normal(size=64).astype(np.float32)
    d = (rng.random(64) < 0.3).astype(np.float32)
    q1, q2 = rng.normal(size=(2, 64)).astype(np.float32)
    logp = rng.normal(size=64).astype(np.float32)
    alpha, gamma, scale = 0.2, 0.99, 1.5

    jb = scale * jnp.asarray(r) + gamma * (1 - jnp.asarray(d)) * (
        jnp.minimum(jnp.asarray(q1), jnp.asarray(q2)) - alpha * jnp.asarray(logp)
    )
    tb = scale * torch.tensor(r) + gamma * (1 - torch.tensor(d)) * (
        torch.minimum(torch.tensor(q1), torch.tensor(q2))
        - alpha * torch.tensor(logp)
    )
    np.testing.assert_allclose(np.asarray(jb), tb.numpy(), rtol=1e-6)


def test_adam_single_step_matches_torch():
    """optax.adam and torch.optim.Adam must produce the same first step
    given identical params/grads (lr 3e-4, torch defaults — the
    reference's optimizer config, ref main.py:93-95)."""
    import optax

    w0 = np.random.default_rng(1).normal(size=(8, 4)).astype(np.float32)
    g = np.random.default_rng(2).normal(size=(8, 4)).astype(np.float32)

    tx = optax.adam(3e-4)
    opt_state = tx.init(jnp.asarray(w0))
    updates, _ = tx.update(jnp.asarray(g), opt_state, jnp.asarray(w0))
    w_jax = np.asarray(optax.apply_updates(jnp.asarray(w0), updates))

    w_t = torch.tensor(w0.copy(), requires_grad=True)
    opt = torch.optim.Adam([w_t], lr=3e-4)
    w_t.grad = torch.tensor(g)
    opt.step()
    np.testing.assert_allclose(w_jax, w_t.detach().numpy(), rtol=1e-5, atol=1e-7)


def test_torch_visual_baseline_builds_and_updates():
    """The visual torch baseline (BASELINE.md's config 5, an
    independent reference that this file compares against:
    baselines/torch_sac.py:build_torch_visual_sac) runs a full SAC
    gradient step at a tiny geometry: actor output contracts hold and
    the update mutates parameters. 36x36 is the smallest square frame
    the hardwired Atari conv geometry (8,4,3)/(4,2,1) admits."""
    from torch_actor_critic_tpu.baselines import build_torch_visual_sac

    feat, hw, c, act_dim, batch = 6, (36, 36), 3, 4, 5
    actor, update = build_torch_visual_sac(feat, hw, c, act_dim, hidden=(16, 16))
    frames = torch.rand(batch, c, *hw) * 255.0
    feats = torch.randn(batch, feat)
    with torch.no_grad():
        a, logp = actor(feats, frames)
    assert a.shape == (batch, act_dim) and logp.shape == (batch,)
    assert bool((a.abs() <= 1.0).all())
    before = [p.detach().clone() for p in actor.parameters()]
    update(
        feats, frames, torch.tanh(torch.randn(batch, act_dim)),
        torch.randn(batch), torch.randn(batch, feat),
        torch.rand(batch, c, *hw) * 255.0, torch.zeros(batch),
    )
    after = list(actor.parameters())
    assert any(
        not torch.equal(b, a.detach()) for b, a in zip(before, after)
    )
