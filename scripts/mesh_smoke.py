"""End-to-end smoke of the named-mesh GSPMD substrate on forced devices.

Forces a 4-device CPU backend (``--xla_force_host_platform_device_count``,
the same shim tier-1 uses) and exercises the three scale-out paths the
PR-8 rebuild unlocked, through the real entry points:

- the data-parallel update burst on a dp=4 mesh (jit-with-sharding, no
  shard_map): params replicated across all 4 devices, finite losses,
  replica-desync canary (``param_norm_skew``) reading exactly 0.0;
- the dp+fsdp hybrid burst (dp=2 x fsdp=2, threshold forced to 0 so the
  tiny model really shards) — the path the legacy substrate version-
  gated off — matching the all-replicated burst allclose;
- ``--population 8`` member-sharded fused training END-TO-END through
  the ``train.py`` CLI on the dp=4 mesh: members spread 2 per device,
  N distinct finite curves in metrics.jsonl, and a bitwise ``--run``
  resume of the sharded population checkpoint.

The ``make mesh-smoke`` gate; ~2 min on a 2-thread CPU host.
"""

import json
import os
import sys
import tempfile
from pathlib import Path

# Must precede the first jax import anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 4
POP = 8


def fail(msg):
    print(f"[mesh-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def ok(msg):
    print(f"[mesh-smoke] {msg}", flush=True)


def _chunk(key, n_dev, per_dev, obs_dim, act_dim):
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.core.types import Batch

    ks = jax.random.split(key, 5)
    shape = (n_dev, per_dev)
    return Batch(
        states=jax.random.normal(ks[0], shape + (obs_dim,)),
        actions=jnp.tanh(jax.random.normal(ks[1], shape + (act_dim,))),
        rewards=jax.random.normal(ks[2], shape),
        next_states=jax.random.normal(ks[3], shape + (obs_dim,)),
        done=jnp.zeros(shape),
    )


def _dp(sac, mesh, **kw):
    from torch_actor_critic_tpu.parallel import DataParallelSAC

    return DataParallelSAC(sac, mesh, **kw)


def check_dp_burst():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.parallel import (
        init_sharded_buffer,
        make_mesh,
        shard_chunk,
    )
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig

    obs_dim, act_dim = 4, 2
    cfg = SACConfig(
        hidden_sizes=(32, 32), batch_size=8, diagnostics="light"
    )
    sac = SAC(
        cfg,
        Actor(act_dim=act_dim, hidden_sizes=cfg.hidden_sizes),
        DoubleCritic(hidden_sizes=cfg.hidden_sizes),
        act_dim,
    )
    dp = _dp(sac, make_mesh(dp=N_DEV))
    state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
    buf = init_sharded_buffer(
        128, jax.ShapeDtypeStruct((obs_dim,), jnp.float32), act_dim, dp.mesh
    )
    chunk = shard_chunk(
        _chunk(jax.random.key(1), N_DEV, 32, obs_dim, act_dim), dp.mesh
    )
    state, buf, m = dp.update_burst(state, buf, chunk, 4)
    if int(state.step) != 4 or not np.isfinite(float(m["loss_q"])):
        fail(f"dp burst broken: step={int(state.step)}, m={m}")
    leaf = jax.tree_util.tree_leaves(state.actor_params)[0]
    if len(leaf.sharding.device_set) != N_DEV or not leaf.sharding.is_fully_replicated:
        fail(f"params not replicated across {N_DEV} devices: {leaf.sharding}")
    if float(m["diag/param_norm_skew"]) != 0.0:
        fail(f"replica desync canary nonzero: {m['diag/param_norm_skew']}")
    ok(f"dp={N_DEV} burst: loss_q={float(m['loss_q']):.4f}, "
       "params replicated, param_norm_skew=0.0")


def check_hybrid_burst():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.parallel import (
        init_sharded_buffer,
        make_mesh,
        shard_chunk,
    )
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig

    obs_dim, act_dim = 4, 2

    if hasattr(jax, "shard_map"):
        ok("note: native jax.shard_map present; the point of this check "
           "is that the hybrid no longer needs it")

    def run(fsdp):
        cfg = SACConfig(hidden_sizes=(32, 32), batch_size=8)
        sac = SAC(
            cfg,
            Actor(act_dim=act_dim, hidden_sizes=cfg.hidden_sizes),
            DoubleCritic(hidden_sizes=cfg.hidden_sizes),
            act_dim,
        )
        dp = _dp(
            sac, make_mesh(dp=2, fsdp=fsdp), fsdp_min_bytes=0
        )
        state = dp.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
        if fsdp > 1:
            kern = state.actor_params["params"]["MLP_0"]["Dense_0"]["col"][
                "kernel"
            ]
            if kern.sharding.is_fully_replicated:
                fail("fsdp=2 kernel not actually sharded")
        buf = init_sharded_buffer(
            64, jax.ShapeDtypeStruct((obs_dim,), jnp.float32), act_dim,
            dp.mesh,
        )
        chunk = shard_chunk(
            _chunk(jax.random.key(1), 2, 16, obs_dim, act_dim), dp.mesh
        )
        state, buf, m = dp.update_burst(state, buf, chunk, 3)
        return state, m

    s_f, m_f = run(fsdp=2)
    s_r, m_r = run(fsdp=1)
    import numpy as np

    for a, b in zip(
        jax.tree_util.tree_leaves(s_f.critic_params),
        jax.tree_util.tree_leaves(s_r.critic_params),
    ):
        if not np.allclose(np.asarray(a), np.asarray(b), atol=1e-5):
            fail("dp+fsdp hybrid diverged from the replicated burst")
    ok(f"dp=2 x fsdp=2 hybrid burst (no version gate): "
       f"loss_q={float(m_f['loss_q']):.4f} == replicated "
       f"{float(m_r['loss_q']):.4f}")


def check_population_sharded():
    import jax
    import numpy as np

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    root = Path(tempfile.mkdtemp(prefix="mesh_smoke_"))
    args = [
        "--environment", "Pendulum-v1",
        "--on-device", "true",
        "--population", str(POP),
        "--telemetry", "true",
        "--runs-root", str(root),
        "--epochs", "2",
        "--steps-per-epoch", "60",
        "--update-every", "20",
        "--start-steps", "20",
        "--on-device-envs", "2",
        "--buffer-size", "3000",
        "--hidden-sizes", "16,16",
        "--batch-size", "8",
        "--save-every", "1",
        "--experiment", "mesh-smoke",
    ]
    metrics = train_main(args)
    for i in range(POP):
        v = metrics.get(f"loss_q_m{i}")
        if v is None or not np.isfinite(v):
            fail(f"member {i} curve missing/not finite: {v}")
    if len({round(metrics[f'loss_q_m{i}'], 6) for i in range(POP)}) < 2:
        fail("member curves are one curve copied N times")
    runs = list(root.glob("*/*/metrics.jsonl"))
    if not runs:
        fail(f"no metrics.jsonl under {root}")
    rows = [json.loads(line) for line in runs[0].read_text().splitlines()]
    if len(rows) < 2:
        fail(f"expected 2 epochs of metrics rows, got {len(rows)}")
    run_id = runs[0].parent.name
    ok(f"population={POP} sharded over dp={jax.device_count()} via CLI: "
       f"{len(rows)} epochs, {POP} distinct finite curves (run {run_id})")

    # Bitwise resume of the sharded population checkpoint: one more
    # epoch from the saved state must land where a fresh read of the
    # final metrics did.
    resumed = train_main([
        "--run", run_id,
        "--runs-root", str(root),
        "--experiment", "mesh-smoke",
        "--epochs", "1",
    ])
    for i in range(POP):
        v = resumed.get(f"loss_q_m{i}")
        if v is None or not np.isfinite(v):
            fail(f"resumed member {i} curve missing/not finite: {v}")
    ok(f"sharded population checkpoint resumed (run {run_id})")


def main():
    import jax

    if jax.device_count() != N_DEV:
        fail(
            f"expected {N_DEV} forced CPU devices, got {jax.device_count()} "
            "(XLA_FLAGS not honored — is jax imported before this script "
            "set the env?)"
        )
    check_dp_burst()
    check_hybrid_burst()
    check_population_sharded()
    ok("OK")


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main()
