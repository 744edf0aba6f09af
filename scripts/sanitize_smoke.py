"""End-to-end smoke of the --sanitize transfer-guard tier (docs/ANALYSIS.md).

Forces a 4-device CPU backend (the tier-1 shim) and proves the runtime
sanitizer's contract on both planes, through the real entry points:

- **train, clean**: a short ``train.py --sanitize on`` run on the dp=4
  mesh completes with finite losses, and its loss stream is BITWISE
  equal to the same seed with ``--sanitize off`` — the guard is
  behavior-neutral on a clean path (the off tier's no-op parity is
  pinned the other way round by tests/test_sanitize.py);
- **train, trip**: an injected host read — the placed chunk left as
  raw numpy so the guarded burst dispatch sees an implicit
  host->device transfer — fails the epoch loudly with the guard's
  XlaRuntimeError instead of silently taxing every window;
- **serve, clean**: a real ``serve.py --sanitize on`` subprocess
  floods 60 ``/act`` requests (deterministic and sampled) over
  loopback — every one answered, none tripped, proving the explicit
  ``device_put`` staging covers the whole request path;
- **serve, trip**: an engine handed host-numpy params under sanitize
  raises at the first forward (the per-request re-transfer tax the
  tier exists to catch).

The ``make sanitize-smoke`` gate; ~2 min on a 2-thread CPU host.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
from urllib import request as urlreq

# Must precede the first jax import anywhere.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4"
    ).strip()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_DEV = 4
OBS_DIM, ACT_DIM = 6, 2
FLOOD = 60

TINY = dict(
    hidden_sizes=(16, 16), batch_size=16, epochs=2, steps_per_epoch=120,
    start_steps=30, update_after=30, update_every=30, buffer_size=2000,
    max_ep_len=100, save_every=1000, sentinel=False,
)


def fail(msg, proc=None):
    print(f"[sanitize-smoke] FAIL: {msg}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=10)
            print(out[-3000:], file=sys.stderr)
        except subprocess.TimeoutExpired:
            proc.kill()
    sys.exit(1)


def ok(msg):
    print(f"[sanitize-smoke] {msg}", flush=True)


def check_train_clean_and_parity():
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig

    import numpy as np

    metrics = {}
    for tier in ("off", "on"):
        tr = Trainer(
            "Pendulum-v1", SACConfig(**TINY, sanitize=tier),
            mesh=make_mesh(dp=N_DEV), seed=7,
        )
        try:
            metrics[tier] = tr.train()
        finally:
            tr.close()
    for k in ("loss_q", "loss_pi", "reward"):
        a, b = metrics["off"][k], metrics["on"][k]
        if not np.isfinite(b):
            fail(f"sanitize=on {k} not finite: {b}")
        if a != b:
            fail(f"sanitize on/off diverged on {k}: {a} != {b}")
    if set(metrics["off"]) != set(metrics["on"]):
        fail("sanitize tier changed the metric schema")
    ok(
        f"dp={N_DEV} train under sanitize=on: clean, loss stream "
        f"bitwise == off (loss_q={metrics['on']['loss_q']:.4f})"
    )


def check_train_trip():
    import torch_actor_critic_tpu.sac.trainer as trmod
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tr = Trainer(
        "Pendulum-v1", SACConfig(**TINY, sanitize="on"),
        mesh=make_mesh(dp=1), seed=7,
    )
    orig = trmod.shard_chunk_from_local
    # The injected host read: leave the window's chunk as raw numpy so
    # the guarded burst dispatch must transfer implicitly.
    trmod.shard_chunk_from_local = lambda chunk, mesh, sp=1: chunk
    try:
        tr.train()
        fail("guarded burst accepted a host-resident chunk")
    except Exception as e:  # noqa: BLE001 — asserting the trip class
        if "transfer" not in repr(e).lower():
            fail(f"expected a transfer-guard trip, got {e!r}")
        ok(f"injected host read tripped the guard: {type(e).__name__}")
    finally:
        trmod.shard_chunk_from_local = orig
        tr.close()


def check_serve_flood():
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tmp = tempfile.mkdtemp(prefix="sanitize_smoke_")
    ckpt_dir = os.path.join(tmp, "ckpts")
    cfg = SACConfig(hidden_sizes=(16, 16))
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=(16, 16)),
        DoubleCritic(hidden_sizes=(16, 16)),
        ACT_DIM,
    )
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    ck = Checkpointer(ckpt_dir, save_buffer=False)
    ck.save(0, state, extra={"config": cfg.to_json()}, wait=True)
    ck.close()

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""
        ),
    )
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--ckpt-dir", ckpt_dir,
            "--obs-dim", str(OBS_DIM), "--act-dim", str(ACT_DIM),
            "--port", "0", "--max-batch", "8", "--max-wait-ms", "2",
            "--sanitize", "on",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO,
    )
    address, deadline = None, time.time() + 180
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                fail(f"server exited rc={proc.returncode} before ready", proc)
            time.sleep(0.1)
            continue
        if line.startswith("{"):
            try:
                address = json.loads(line)["serving"]
                break
            except (json.JSONDecodeError, KeyError):
                continue
    if address is None:
        fail("server never printed its address", proc)
    ok(f"sanitized server up at {address}")
    try:
        answered = 0
        for i in range(FLOOD):
            obs = [0.01 * (i + j) for j in range(OBS_DIM)]
            req = urlreq.Request(
                address + "/act",
                data=json.dumps(
                    {"obs": obs, "deterministic": i % 2 == 0}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            out = json.loads(urlreq.urlopen(req, timeout=30).read())
            if len(out["action"]) != ACT_DIM:
                fail(f"bad action on request {i}: {out}", proc)
            answered += 1
        if answered != FLOOD:
            fail(f"only {answered}/{FLOOD} answered", proc)
        ok(
            f"{FLOOD}/{FLOOD} /act requests (det + sampled) answered "
            "under the transfer guard"
        )
    except Exception as e:  # noqa: BLE001 — any failure is a smoke fail
        fail(repr(e), proc)
    finally:
        proc.terminate()
        try:
            proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def check_serve_trip():
    import jax
    import numpy as np

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve.engine import PolicyEngine

    actor = Actor(act_dim=ACT_DIM, hidden_sizes=(16, 16))
    spec = jax.ShapeDtypeStruct((OBS_DIM,), np.float32)
    params = actor.init(
        jax.random.key(0), np.zeros((1, OBS_DIM), np.float32), None,
        deterministic=True, with_logprob=False,
    )
    engine = PolicyEngine(actor, spec, max_batch=4, sanitize=True)
    np_params = jax.tree_util.tree_map(np.asarray, params)
    try:
        engine.act(
            np_params, np.zeros((2, OBS_DIM), np.float32),
            deterministic=True,
        )
        fail("sanitized engine accepted host-numpy params")
    except Exception as e:  # noqa: BLE001 — asserting the trip class
        if "transfer" not in repr(e).lower():
            fail(f"expected a transfer-guard trip, got {e!r}")
        ok(f"host-numpy params tripped the guard: {type(e).__name__}")


def main():
    import jax

    if jax.device_count() != N_DEV:
        fail(
            f"expected {N_DEV} forced CPU devices, got "
            f"{jax.device_count()} (XLA_FLAGS not honored)"
        )
    check_train_clean_and_parity()
    check_train_trip()
    check_serve_flood()
    check_serve_trip()
    ok("OK")


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    main()
