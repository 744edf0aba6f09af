"""Independent PyTorch SAC — the measured stand-in for the reference.

Same semantics and hyperparameter defaults as the reference run config
(ref ``main.py:147-160``: alpha=0.2 fixed, gamma=0.99, polyak=0.995,
batch 64, hidden [256,256], lr 3e-4), same squashed-Gaussian math (ref
``networks/linear.py:39-51``) and twin-critic Bellman update (ref
``sac/algorithm.py:30-74``), written functionally for the
return-parity runner (``scripts/parity_run.py``) and the torch
comparisons in ``tests/test_parity_torch.py``.

This module shares NO code with ``/root/reference`` — it is the
project's own torch implementation of the published SAC equations.
"""

from __future__ import annotations

import typing as t


_MODS = None  # (np, torch, F) — imported once, on first use


def _mods():
    """Lazy module triple: torch stays un-imported until a baseline is
    actually built (same convention as the builders), but the per-step
    hot path pays one global check instead of three sys.modules
    lookups per call."""
    global _MODS
    if _MODS is None:
        import numpy as np
        import torch
        import torch.nn.functional as F

        _MODS = (np, torch, F)
    return _MODS


def _squashed_gaussian(mu, log_std, act_limit, deterministic):
    """Shared squashed-Gaussian sample + log-prob (ref
    ``networks/linear.py:39-51`` semantics) — one copy for the flat and
    visual actors so the distribution math cannot drift."""
    np, torch, F = _mods()

    log_std = torch.clip(log_std, -20, 2)
    std = torch.exp(log_std)
    u = mu if deterministic else mu + std * torch.randn_like(mu)
    a = torch.tanh(u) * act_limit
    logp = torch.distributions.Normal(mu, std).log_prob(u).sum(-1)
    logp = logp - (2 * (np.log(2) - u - F.softplus(-2 * u))).sum(-1)
    return a, logp


def _make_sac_update(actor, critics, targets, lr, alpha, gamma, polyak):
    """Shared SAC gradient step over tuple-observations.

    ``actor(*obs)`` -> (action, logp); ``critic(*obs, a)`` -> q. The
    flat and visual builders differ ONLY in network definitions and obs
    arity — the backup, twin-Q loss, frozen-critic policy step and
    polyak averaging live here once (the package docstring's 'cannot
    drift' contract, kept after the visual twin landed).
    Returns ``update(obs_tuple, a, r, obs2_tuple, d)``.
    """
    import torch

    for c, tgt in zip(critics, targets):
        tgt.load_state_dict(c.state_dict())
        for p in tgt.parameters():
            p.requires_grad_(False)
    pi_opt = torch.optim.Adam(actor.parameters(), lr=lr)
    q_opt = torch.optim.Adam(
        [p for c in critics for p in c.parameters()], lr=lr
    )

    def update(obs, a, r, obs2, d):
        with torch.no_grad():
            a2, logp2 = actor(*obs2)
            qt = torch.min(*(tg(*obs2, a2) for tg in targets))
            backup = r + gamma * (1 - d) * (qt - alpha * logp2)
        q1, q2 = (c(*obs, a) for c in critics)
        loss_q = ((q1 - backup) ** 2).mean() + ((q2 - backup) ** 2).mean()
        q_opt.zero_grad()
        loss_q.backward()
        q_opt.step()

        for c in critics:
            for p in c.parameters():
                p.requires_grad_(False)
        pi, logp = actor(*obs)
        loss_pi = (
            alpha * logp - torch.min(*(c(*obs, pi) for c in critics))
        ).mean()
        pi_opt.zero_grad()
        loss_pi.backward()
        pi_opt.step()
        for c in critics:
            for p in c.parameters():
                p.requires_grad_(True)

        with torch.no_grad():
            for c, tgt in zip(critics, targets):
                for pc, pt in zip(c.parameters(), tgt.parameters()):
                    pt.mul_(polyak).add_((1 - polyak) * pc)

    return update


def build_torch_sac(
    obs_dim: int,
    act_dim: int,
    act_limit: float = 1.0,
    hidden: t.Sequence[int] = (256, 256),
    lr: float = 3e-4,
    alpha: float = 0.2,
    gamma: float = 0.99,
    polyak: float = 0.995,
    num_threads: int = 2,
):
    """Build actor/critics and return ``(actor_fn, update_fn)``.

    - ``actor_fn(obs_batch, deterministic=False) -> (action, logp)``
      (torch tensors, no grad context managed by the caller);
    - ``update_fn(s, a, r, s2, d)`` runs one full SAC gradient step
      (critic, policy with frozen critic, polyak) on torch tensors.

    ``torch.set_num_threads(num_threads)`` mirrors ref ``main.py:130``.
    """
    import torch
    import torch.nn as nn

    torch.set_num_threads(num_threads)

    def mlp(sizes):
        layers = []
        for a, b in zip(sizes[:-1], sizes[1:]):
            layers += [nn.Linear(a, b), nn.ReLU()]
        return nn.Sequential(*layers)

    class Actor(nn.Module):
        def __init__(self):
            super().__init__()
            self.trunk = mlp([obs_dim, *hidden])
            self.mu = nn.Linear(hidden[-1], act_dim)
            self.log_std = nn.Linear(hidden[-1], act_dim)

        def forward(self, obs, deterministic=False):
            h = self.trunk(obs)
            return _squashed_gaussian(
                self.mu(h), self.log_std(h), act_limit, deterministic
            )

    class Critic(nn.Module):
        def __init__(self):
            super().__init__()
            net = mlp([obs_dim + act_dim, *hidden])
            net.append(nn.Linear(hidden[-1], 1))
            self.net = net

        def forward(self, s, a):
            return self.net(torch.cat([s, a], -1)).squeeze(-1)

    actor = Actor()
    critics = [Critic(), Critic()]
    targets = [Critic(), Critic()]
    inner = _make_sac_update(actor, critics, targets, lr, alpha, gamma, polyak)

    def update(s, a, r, s2, d):
        inner((s,), a, r, (s2,), d)

    return actor, update


def build_torch_visual_sac(
    feature_dim: int,
    frame_hw: t.Tuple[int, int],
    frame_channels: int,
    act_dim: int,
    act_limit: float = 1.0,
    hidden: t.Sequence[int] = (256, 256),
    cnn_features: int = 1,
    lr: float = 3e-4,
    alpha: float = 0.2,
    gamma: float = 0.99,
    polyak: float = 0.995,
    num_threads: int = 2,
):
    """Visual (CNN) twin of :func:`build_torch_sac` — the measured torch
    stand-in for the reference's pixel stack (BASELINE config 5).

    Same architecture semantics as the reference visual networks
    (ref ``networks/convolutional.py:30-183``): Atari-DQN conv trunk
    (filters [32,64,64], kernels [8,4,3], strides [4,2,1], VALID
    padding) -> Dense(512) -> Dense(``cnn_features``, default 1 — the
    scalar-vision bottleneck), concatenated with the proprioceptive MLP;
    the critic ReLUs through every MLP layer including the width-1
    output then applies the final ``Linear(1+cnn_features, 1)``. NCHW
    float frames, as the reference stores them. Shares no code with
    ``/root/reference``.

    Returns ``(actor_fn, update_fn)``; ``update_fn(feat, frame, a, r,
    feat2, frame2, d)`` runs one full SAC gradient step.
    """
    import torch
    import torch.nn as nn

    torch.set_num_threads(num_threads)

    def mlp(sizes, relu_final=False):
        layers = []
        for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
            layers.append(nn.Linear(a, b))
            if relu_final or i < len(sizes) - 2:
                layers.append(nn.ReLU())
        return nn.Sequential(*layers)

    def cnn():
        h, w = frame_hw
        convs = []
        c = frame_channels
        for f, k, s in zip((32, 64, 64), (8, 4, 3), (4, 2, 1)):
            convs += [nn.Conv2d(c, f, k, s), nn.ReLU()]
            c = f
            h = (h - k) // s + 1
            w = (w - k) // s + 1
        return nn.Sequential(
            *convs, nn.Flatten(),
            nn.Linear(c * h * w, 512), nn.Linear(512, cnn_features),
        )

    class Actor(nn.Module):
        def __init__(self):
            super().__init__()
            self.trunk = mlp([feature_dim, *hidden], relu_final=True)
            self.vision = cnn()
            self.mu = nn.Linear(hidden[-1] + cnn_features, act_dim)
            self.log_std = nn.Linear(hidden[-1] + cnn_features, act_dim)

        def forward(self, feat, frame, deterministic=False):
            h = torch.cat([self.trunk(feat), self.vision(frame)], -1)
            return _squashed_gaussian(
                self.mu(h), self.log_std(h), act_limit, deterministic
            )

    class Critic(nn.Module):
        def __init__(self):
            super().__init__()
            # ReLU through every layer incl. the width-1 output — the
            # reference quirk (ref convolutional.py:156-158).
            self.trunk = mlp([feature_dim + act_dim, *hidden, 1], relu_final=True)
            self.vision = cnn()
            self.final = nn.Linear(1 + cnn_features, 1)

        def forward(self, feat, frame, act):
            x = self.trunk(torch.cat([feat, act], -1))
            x = torch.cat([x, self.vision(frame)], -1)
            return self.final(x).squeeze(-1)

    actor = Actor()
    critics = [Critic(), Critic()]
    targets = [Critic(), Critic()]
    inner = _make_sac_update(actor, critics, targets, lr, alpha, gamma, polyak)

    def update(feat, frame, a, r, feat2, frame2, d):
        inner((feat, frame), a, r, (feat2, frame2), d)

    return actor, update
