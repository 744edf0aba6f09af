"""Sharded-serving smoke: 2 workers x 2 sub-mesh replicas, kill+reload.

End-to-end proof of docs/SERVING.md "Sharded serving & precision
tiers" through the REAL operator entry point — ``serve.py --devices
all --submesh 2x2 --fleet 2`` under the forced 8-device CPU shim (each
worker process carves its 8 virtual devices into two (2,2) sub-mesh
replicas; the router fronts the two workers), ~2 min:

1. **Flood + mid-flood validated hot-reload**: a closed-loop client
   herd floods the router; MID-flood a newer checkpoint epoch is
   written and ``POST /reload`` rolls it across the fleet. Asserts
   every request is answered (zero accepted-request drops), post-roll
   traffic serves the new generation, and the aggregated
   ``reload_transfer_bytes_total`` counter grew by exactly one sharded
   placement per live sub-mesh replica — the one-transfer-per-device
   contract, observed through /metrics.
2. **Mid-flood worker SIGKILL**: one worker dies under load; the
   router fails in-flight proxies over and membership ejects it —
   still zero drops, goodput continues on the surviving worker's two
   sub-meshes.
3. **Teardown**: SIGTERM drains the fleet gracefully, exit 0.

Exits nonzero on any violated invariant; prints a one-line JSON
summary for CI logs.
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path
from urllib import request as urlreq

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = str(Path(__file__).resolve().parent.parent)
sys.path.insert(0, REPO)
OBS_DIM, ACT_DIM = 17, 6


def fail(msg, proc=None):
    print(f"[shard-serve-smoke] FAIL: {msg}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=10)
            if out:
                print(out[-3000:], file=sys.stderr)
        except subprocess.TimeoutExpired:
            proc.kill()
    sys.exit(1)


def router_metrics(router):
    return json.loads(urlreq.urlopen(router + "/metrics", timeout=30).read())


def main():
    import jax
    import jax.numpy as jnp

    import numpy as np

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.serve import PolicyClient
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tmp = tempfile.mkdtemp(prefix="shard_serve_smoke_")
    ckpt_dir = os.path.join(tmp, "ckpts")
    cfg = SACConfig(hidden_sizes=(32, 32))
    sac = SAC(
        cfg,
        Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32)),
        DoubleCritic(hidden_sizes=(32, 32)),
        ACT_DIM,
    )

    def save_epoch(epoch, seed):
        ck = Checkpointer(ckpt_dir, save_buffer=False)
        try:
            ck.save(
                epoch,
                sac.init_state(jax.random.key(seed), jnp.zeros((OBS_DIM,))),
                extra={"config": cfg.to_json()}, wait=True,
            )
        finally:
            ck.close()

    save_epoch(0, seed=0)
    print(f"[shard-serve-smoke] checkpoint written: {ckpt_dir}")

    # The forced multi-device shim MUST reach the worker processes
    # before their first jax import: 8 virtual CPU devices -> two
    # (2,2) sub-mesh replicas per worker.
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
        PYTHONPATH=REPO + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""
        ),
    )
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--fleet", "2", "--port", "0",
            "--ckpt-dir", ckpt_dir,
            "--obs-dim", str(OBS_DIM), "--act-dim", str(ACT_DIM),
            "--devices", "all", "--submesh", "2x2",
            "--max-batch", "8", "--max-wait-ms", "1",
            "--poll-interval", "0",  # reload only via the explicit roll
            "--router-poll", "0.5",
        ],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, cwd=REPO,
    )

    info, deadline = None, time.time() + 300
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                fail(f"fleet exited rc={proc.returncode} before ready", proc)
            time.sleep(0.1)
            continue
        sys.stderr.write("[fleet] " + line)
        if line.startswith("{") and '"router"' in line:
            try:
                info = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if info is None:
        fail("fleet never printed its router address", proc)
    router = info["router"]
    pids = info["pids"]
    assert len(pids) == 2, info
    print(f"[shard-serve-smoke] up: router {router}, worker pids {pids}")
    threading.Thread(
        target=lambda: [None for _ in proc.stdout], daemon=True
    ).start()

    summary = {}
    try:
        # Preflight: both workers expose the sharding section.
        for name, addr in info["workers"].items():
            snap = router_metrics(addr)
            sh = snap.get("sharding")
            if not sh or sh["submesh"] != {"tp": 2, "fsdp": 2}:
                fail(f"worker {name} has no 2x2 sharding section: {sh}")
            if sh["replicas"] != 2:
                fail(f"worker {name} replicas {sh['replicas']} != 2")
        placements0 = router_metrics(router)["param_placements_total"]
        bytes0 = router_metrics(router)["reload_transfer_bytes_total"]
        if placements0 <= 0 or bytes0 <= 0:
            fail(
                f"warmup placed nothing? placements={placements0} "
                f"bytes={bytes0}"
            )

        obs = np.linspace(-1, 1, OBS_DIM).astype(np.float32)
        n_threads, per_thread = 6, 50
        reload_after, kill_after = 40, 140
        answered, errors = [0], []
        count_lock = threading.Lock()
        reloaded, killed = threading.Event(), threading.Event()
        roll_result = {}

        def do_roll():
            save_epoch(1, seed=7)
            req = urlreq.Request(
                router + "/reload", data=b"{}",
                headers={"Content-Type": "application/json"},
            )
            roll_result.update(json.loads(
                urlreq.urlopen(req, timeout=120).read()
            )["reload"])
            print(f"[shard-serve-smoke] mid-flood roll: {roll_result}")

        def flooder(i):
            client = PolicyClient(url=router, retries=3, backoff_s=0.1)
            local_obs = obs + 0.01 * i
            for _ in range(per_thread):
                try:
                    res = client.act(local_obs, timeout=60.0)
                    assert len(res.action) == ACT_DIM
                    with count_lock:
                        answered[0] += 1
                        n = answered[0]
                    if n >= reload_after and not reloaded.is_set():
                        reloaded.set()
                        threading.Thread(
                            target=do_roll, daemon=True
                        ).start()
                    if n >= kill_after and not killed.is_set():
                        killed.set()
                        os.kill(pids[0], signal.SIGKILL)
                        print(
                            f"[shard-serve-smoke] SIGKILLed worker "
                            f"{pids[0]} after {n} responses"
                        )
                except Exception as e:  # noqa: BLE001 — any client
                    # failure is an accepted-request drop: smoke fail
                    errors.append(repr(e)[:300])

        t0 = time.perf_counter()
        herd = [
            threading.Thread(target=flooder, args=(i,))
            for i in range(n_threads)
        ]
        for th in herd:
            th.start()
        for th in herd:
            th.join(timeout=600.0)
        flood_s = time.perf_counter() - t0
        offered = n_threads * per_thread
        if errors:
            fail(f"{len(errors)} dropped/errored requests: {errors[:3]}")
        if answered[0] != offered:
            fail(f"answered {answered[0]} != offered {offered}")
        if not (reloaded.is_set() and killed.is_set()):
            fail("flood ended before reload+kill fired; raise per_thread")

        deadline = time.time() + 60
        while time.time() < deadline:
            health = json.loads(
                urlreq.urlopen(router + "/healthz", timeout=30).read()
            )
            if health["admitted_workers"] == 1:
                break
            time.sleep(0.5)
        else:
            fail(f"membership never ejected the dead worker: {health}")

        # The roll runs concurrently with the flood; wait for it.
        deadline = time.time() + 120
        while time.time() < deadline and not roll_result:
            time.sleep(0.5)
        if not roll_result:
            fail("mid-flood /reload never completed")

        # Post-roll traffic serves the new generation.
        client = PolicyClient(url=router, retries=3)
        res = client.act(obs, timeout=60.0)
        if res.generation != 1:
            fail(f"post-roll generation {res.generation} != 1")
        if res.epoch != 1:
            fail(f"post-roll epoch {res.epoch} != 1")
        for _ in range(8):  # touch both surviving sub-mesh replicas
            client.act(obs, timeout=60.0)

        # One sharded placement per live sub-mesh replica for the
        # reload: the surviving worker's 2 replicas each transferred
        # once more, and each placement moved the same bytes as its
        # initial one (the aggregate only sums LIVE workers — the dead
        # one no longer reports).
        deadline = time.time() + 60
        while time.time() < deadline:
            snap = router_metrics(router)
            if snap.get("param_placements_total") == 4:
                break
            client.act(obs, timeout=60.0)
            time.sleep(0.5)
        sh = snap.get("workers", {})
        live = [w for w in sh.values() if not w.get("unreachable")]
        if len(live) != 1:
            fail(f"expected 1 live worker in /metrics, got {sh}")
        per_bytes = bytes0 // 4  # 2 workers x 2 replicas warmed equally
        got = snap["reload_transfer_bytes_total"]
        if got != 4 * per_bytes:
            fail(
                f"transfer accounting off: live-worker bytes {got} "
                f"!= 4 x {per_bytes} (2 replicas x initial+reload)"
            )
        if snap["param_placements_total"] != 4:
            fail(
                "live worker placements "
                f"{snap['param_placements_total']} != 4 "
                "(2 replicas x initial+reload)"
            )

        summary["flood"] = {
            "offered": offered,
            "answered": answered[0],
            "errors": 0,
            "goodput_rps": round(offered / flood_s, 1),
            "post_roll_generation": res.generation,
            "admitted_workers": health["admitted_workers"],
            "live_worker_placements": snap["param_placements_total"],
            "live_worker_transfer_bytes": got,
        }
        print(f"[shard-serve-smoke] flood ok: {summary['flood']}")

        # ------------------------------------------------ teardown
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            fail("fleet did not exit within 120s of SIGTERM", proc)
        if rc != 0:
            fail(f"fleet exited rc={rc} after graceful SIGTERM")
        summary["teardown"] = {"rc": rc}
    finally:
        if proc.poll() is None:
            proc.kill()

    print("SHARD-SERVE-SMOKE OK " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
