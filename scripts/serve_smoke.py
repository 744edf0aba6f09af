"""End-to-end smoke of the serving CLI: real checkpoint, real HTTP.

Writes a real TrainState checkpoint into a temp dir, launches
``python serve.py --ckpt-dir ... --port 0`` as a subprocess (the exact
operator entry point), round-trips ``/act`` and ``/healthz`` over
loopback, and exits nonzero on any failure — the `make serve-smoke`
gate. Runs on CPU in ~30s; no accelerator required.
"""

import json
import os
import subprocess
import sys
import tempfile
import time
from urllib import request as urlreq

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS_DIM, ACT_DIM = 17, 6


def fail(msg, proc=None):
    print(f"[serve-smoke] FAIL: {msg}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=10)
            print(out[-3000:], file=sys.stderr)
        except subprocess.TimeoutExpired:
            proc.kill()
    sys.exit(1)


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tmp = tempfile.mkdtemp(prefix="serve_smoke_")
    ckpt_dir = os.path.join(tmp, "ckpts")
    cfg = SACConfig(hidden_sizes=(32, 32))
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32)),
        DoubleCritic(hidden_sizes=(32, 32)),
        ACT_DIM,
    )
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    ck = Checkpointer(ckpt_dir, save_buffer=False)
    ck.save(0, state, extra={"config": cfg.to_json()}, wait=True)
    ck.close()
    print(f"[serve-smoke] checkpoint written: {ckpt_dir}")

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
            "--port", "0",  # random ephemeral port, printed at startup
            "--max-batch", "8", "--max-wait-ms", "2",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO,
    )

    # The CLI prints one JSON line {"serving": "http://...", ...} once
    # the model is loaded and every bucket is warm.
    address, deadline = None, time.time() + 120
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                fail(f"server exited rc={proc.returncode} before ready", proc)
            time.sleep(0.1)
            continue
        sys.stderr.write("[server] " + line)
        if line.startswith("{"):
            try:
                address = json.loads(line)["serving"]
                break
            except (json.JSONDecodeError, KeyError):
                continue
    if address is None:
        fail("server never printed its address", proc)
    print(f"[serve-smoke] server up at {address}")

    try:
        health = json.loads(
            urlreq.urlopen(address + "/healthz", timeout=30).read()
        )
        assert health["status"] == "ok", health
        assert health["slots"]["default"]["epoch"] == 0, health
        print(f"[serve-smoke] /healthz ok: {health['slots']}")

        obs = [0.1 * i for i in range(OBS_DIM)]
        req = urlreq.Request(
            address + "/act",
            data=json.dumps({"obs": obs, "deterministic": True}).encode(),
            headers={"Content-Type": "application/json"},
        )
        out = json.loads(urlreq.urlopen(req, timeout=30).read())
        assert len(out["action"]) == ACT_DIM, out
        assert all(abs(a) <= 1.0 for a in out["action"]), out
        assert out["generation"] == 0, out
        # determinism across the wire: same obs, same bits
        out2 = json.loads(urlreq.urlopen(req, timeout=30).read())
        assert out2["action"] == out["action"], (out, out2)
        print(f"[serve-smoke] /act ok: {out['action']}")
    except Exception as e:  # noqa: BLE001 — any failure is a smoke fail
        fail(repr(e), proc)
    finally:
        proc.terminate()
        try:
            proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()

    print("[serve-smoke] OK")
    sys.exit(0)


if __name__ == "__main__":
    main()
