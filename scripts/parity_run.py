"""Return-parity runs: our trainer vs an independent torch SAC.

BASELINE.md's gate is "average return within ±5% of the PyTorch
baseline" at the reference run configuration. The reference itself
cannot execute in this image (it imports legacy ``gym`` and ``mpi4py``;
only gymnasium is installed), so the torch side here is an independent
PyTorch implementation of the reference's exact semantics — same
hyperparameters (ref ``main.py:147-160``: alpha=0.2 fixed, gamma=0.99,
polyak=0.995, batch 64, hidden [256,256], lr 3e-4, start_steps=
update_after=1000, update_every=50), same squashed-Gaussian math (ref
``networks/linear.py:39-51``), same per-window update burst (ref
``sac/algorithm.py:273-283``), torch-default inits (which our Flax
models also reproduce, ``models/mlp.py``).

Usage::

    python scripts/parity_run.py --impl torch --env Pendulum-v1 \
        --steps 30000 --out runs_parity/torch_pendulum.jsonl
    python scripts/parity_run.py --impl jax --env Pendulum-v1 \
        --steps 30000 --parity-pi-obs false --out ...

Each run writes one JSON line per episode (step, return) and a final
summary line; PARITY.md records the comparison.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable straight from a source checkout: scripts/ is not a package.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def episode_logger(out_path):
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    f = open(out_path, "w")

    def log(record):
        f.write(json.dumps(record) + "\n")
        f.flush()

    return log


# --------------------------------------------------------------- torch side


def run_torch(env_name: str, steps: int, seed: int, out: str):
    import gymnasium
    import numpy as np
    import torch

    from torch_actor_critic_tpu.baselines import build_torch_sac

    torch.manual_seed(seed)
    np.random.seed(seed)

    env = gymnasium.make(env_name)
    obs_dim = env.observation_space.shape[0]
    act_dim = env.action_space.shape[0]
    act_limit = float(env.action_space.high[0])
    env.action_space.seed(seed)

    actor, sac_update = build_torch_sac(obs_dim, act_dim, act_limit)

    cap = min(1_000_000, steps)
    buf = {
        "s": np.zeros((cap, obs_dim), np.float32),
        "a": np.zeros((cap, act_dim), np.float32),
        "r": np.zeros(cap, np.float32),
        "s2": np.zeros((cap, obs_dim), np.float32),
        "d": np.zeros(cap, np.float32),
    }
    ptr, size = 0, 0

    batch = 64  # remaining ref hyperparams live in build_torch_sac
    start_steps, update_after, update_every = 1000, 1000, 50
    max_ep_len = 1000

    def update():
        idx = np.random.randint(0, size, batch)
        sac_update(
            *(torch.as_tensor(buf[k][idx]) for k in ("s", "a", "r", "s2", "d"))
        )

    log = episode_logger(out)
    obs, _ = env.reset(seed=seed)
    ep_ret, ep_len, returns = 0.0, 0, []
    t0 = time.time()
    for step in range(steps):
        if step < start_steps:
            action = env.action_space.sample()
        else:
            with torch.no_grad():
                action, _ = actor(torch.as_tensor(obs, dtype=torch.float32)[None])
                action = action.numpy()[0]
        obs2, r, term, trunc, _ = env.step(action)
        ep_ret += r
        ep_len += 1
        hit_cap = ep_len >= max_ep_len
        buf["s"][ptr] = obs; buf["a"][ptr] = action; buf["r"][ptr] = r
        buf["s2"][ptr] = obs2
        buf["d"][ptr] = float(term and not hit_cap)
        ptr = (ptr + 1) % cap
        size = min(size + 1, cap)
        obs = obs2
        if term or trunc or hit_cap:
            returns.append(ep_ret)
            log({"step": step, "episode_return": ep_ret, "len": ep_len})
            obs, _ = env.reset()
            ep_ret, ep_len = 0.0, 0
        if step >= update_after and (step + 1) % update_every == 0:
            for _ in range(update_every):
                update()

    # deterministic eval, 10 episodes
    eval_returns = []
    for _ in range(10):
        o, _ = env.reset()
        ret, done, n = 0.0, False, 0
        while not done and n < max_ep_len:
            with torch.no_grad():
                a, _ = actor(
                    torch.as_tensor(o, dtype=torch.float32)[None],
                    deterministic=True,
                )
            o, r, term, trunc, _ = env.step(a.numpy()[0])
            ret += r; n += 1; done = term or trunc
        eval_returns.append(ret)
    summary = {
        "summary": True, "impl": "torch", "env": env_name, "steps": steps,
        "seed": seed,
        "train_return_last25pct": float(
            np.mean(returns[-max(1, len(returns) // 4):])
        ),
        "eval_return_mean": float(np.mean(eval_returns)),
        "eval_return_std": float(np.std(eval_returns)),
        "wall_s": round(time.time() - t0, 1),
    }
    log(summary)
    print(json.dumps(summary), flush=True)


# ----------------------------------------------------------------- jax side


def run_jax(env_name: str, steps: int, seed: int, out: str, parity_pi_obs: bool):
    import jax

    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig

    steps_per_epoch = 5000
    epochs = max(1, steps // steps_per_epoch)
    actual_steps = epochs * steps_per_epoch
    if actual_steps != steps:
        print(
            f"[parity] NOTE: --steps {steps} rounded to {actual_steps} "
            f"({epochs} epochs x {steps_per_epoch}); the summary records "
            "the ACTUAL step count.",
            file=sys.stderr,
        )
    cfg = SACConfig(
        epochs=epochs,
        steps_per_epoch=steps_per_epoch,
        parity_pi_obs=parity_pi_obs,
        max_ep_len=1000,
        buffer_size=min(1_000_000, actual_steps),
    )
    t0 = time.time()
    tr = Trainer(env_name, cfg, mesh=make_mesh(dp=1), seed=seed)
    log = episode_logger(out)

    metrics = tr.train()
    ev = tr.evaluate(episodes=10, deterministic=True)
    summary = {
        "summary": True, "impl": "jax", "env": env_name,
        "steps": actual_steps,
        "seed": seed, "parity_pi_obs": parity_pi_obs,
        "train_return_final_epoch": metrics["reward"],
        "eval_return_mean": ev["ep_ret_mean"],
        "eval_return_std": ev["ep_ret_std"],
        "grad_steps_per_sec": metrics.get("grad_steps_per_sec"),
        "env_steps_per_sec": metrics.get("env_steps_per_sec"),
        "wall_s": round(time.time() - t0, 1),
    }
    log(summary)
    tr.close()
    print(json.dumps(summary), flush=True)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--impl", choices=["torch", "jax"], required=True)
    p.add_argument("--env", default="Pendulum-v1")
    p.add_argument("--steps", type=int, default=30000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", required=True)
    p.add_argument("--parity-pi-obs", default="false",
                   choices=["true", "false"])
    args = p.parse_args()
    if args.impl == "torch":
        run_torch(args.env, args.steps, args.seed, args.out)
    else:
        run_jax(
            args.env, args.steps, args.seed, args.out,
            parity_pi_obs=args.parity_pi_obs == "true",
        )


if __name__ == "__main__":
    sys.exit(main())
