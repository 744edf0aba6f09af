"""Fleet smoke: 3 workers + router, worker kill, rolling reload.

End-to-end proof of docs/SERVING.md "Fleet" through the REAL operator
entry point (``serve.py --fleet 3`` — worker subprocesses on ephemeral
ports behind the health-gated router), on CPU, ~2 min:

1. **Flood + kill**: a closed-loop client herd (HTTP ``PolicyClient``
   with Retry-After-honoring retries) floods the router; one worker is
   SIGKILLed MID-flood. Asserts every client request is answered (the
   router fails in-flight proxies over to surviving workers; zero
   accepted-request drops), membership ejects the dead worker, and
   goodput continues after the kill.
2. **Rolling reload**: a newer checkpoint epoch appears; ``POST
   /reload`` on the router rolls it across the fleet one worker at a
   time. Asserts surviving workers reload to the new epoch and are
   re-admitted, the dead worker reports an error without aborting the
   roll, and the aggregated ``/metrics`` carries per-worker labels +
   merged latency percentiles from the survivors.
3. **Teardown**: SIGTERM to the fleet parent drains workers gracefully
   and exits 0.

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
    print(f"[fleet-smoke] FAIL: {msg}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=10)
            if out:
                print(out[-3000:], file=sys.stderr)
        except subprocess.TimeoutExpired:
            proc.kill()
    sys.exit(1)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.serve import PolicyClient
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    tmp = tempfile.mkdtemp(prefix="fleet_smoke_")
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
    print(f"[fleet-smoke] checkpoint written: {ckpt_dir}")

    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=REPO + (
            os.pathsep + os.environ["PYTHONPATH"]
            if os.environ.get("PYTHONPATH") else ""
        ),
    )
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO, "serve.py"),
            "--fleet", "3", "--port", "0",
            "--ckpt-dir", ckpt_dir,
            "--obs-dim", str(OBS_DIM), "--act-dim", str(ACT_DIM),
            "--max-batch", "8", "--max-wait-ms", "1",
            "--poll-interval", "0",  # reload only via the rolling roll
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
    assert len(pids) == 3, info
    print(f"[fleet-smoke] fleet up: router {router}, worker pids {pids}")
    threading.Thread(
        target=lambda: [None for _ in proc.stdout], daemon=True
    ).start()  # keep the parent's stdout pipe drained

    summary = {}
    try:
        obs = np.linspace(-1, 1, OBS_DIM).astype(np.float32)

        # ------------------------------------------- 1. flood + kill
        n_threads, per_thread = 6, 40
        kill_after = 60  # responses before the kill
        answered, errors = [0], []
        count_lock = threading.Lock()
        killed = threading.Event()
        t_kill_response_mark = [0]

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
                    if n >= kill_after and not killed.is_set():
                        killed.set()
                        os.kill(pids[0], signal.SIGKILL)
                        t_kill_response_mark[0] = n
                        print(
                            f"[fleet-smoke] SIGKILLed worker pid "
                            f"{pids[0]} after {n} responses"
                        )
                except Exception as e:  # noqa: BLE001 — any client
                    # failure is an accepted-request drop: a smoke fail
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
        if not killed.is_set():
            fail("flood finished before the kill fired; raise per_thread")
        post_kill = offered - t_kill_response_mark[0]
        if post_kill <= 0:
            fail("no goodput after the worker kill")

        health = json.loads(
            urlreq.urlopen(router + "/healthz", timeout=30).read()
        )
        if health["admitted_workers"] != 2:
            fail(f"membership never ejected the dead worker: {health}")
        dead = [
            n for n, w in health["workers"].items() if not w["admitted"]
        ]
        summary["flood"] = {
            "offered": offered,
            "answered": answered[0],
            "errors": 0,
            "responses_after_kill": post_kill,
            "goodput_rps": round(offered / flood_s, 1),
            "ejected": dead,
            "admitted_workers": health["admitted_workers"],
        }
        print(f"[fleet-smoke] flood ok: {summary['flood']}")

        # --------------------------------------- 2. rolling reload
        save_epoch(1, seed=7)
        req = urlreq.Request(
            router + "/reload", data=b"{}",
            headers={"Content-Type": "application/json"},
        )
        roll = json.loads(urlreq.urlopen(req, timeout=120).read())["reload"]
        ok = [
            n for n, s in roll.items()
            if s.get("readmitted")
            and s.get("reload", {}).get("default", {}).get("status") == "ok"
            and s.get("reload", {}).get("default", {}).get("epoch") == 1
        ]
        if len(ok) != 2:
            fail(f"rolling reload did not roll the 2 survivors: {roll}")
        dead_status = [s for n, s in roll.items() if n in dead]
        if not dead_status or dead_status[0].get("readmitted"):
            fail(f"dead worker resurrected by the roll?: {roll}")
        # post-roll traffic serves the NEW generation
        client = PolicyClient(url=router, retries=3)
        res = client.act(obs, timeout=60.0)
        if res.generation != 1:
            fail(f"post-roll generation {res.generation} != 1")
        metrics = json.loads(
            urlreq.urlopen(router + "/metrics", timeout=30).read()
        )
        if metrics["workers_reporting"] != 2:
            fail(f"aggregated /metrics workers: {metrics.get('workers')}")
        if not metrics.get("p50_ms"):
            fail("aggregated /metrics has no merged latency percentiles")
        summary["rolling_reload"] = {
            "rolled": ok,
            "dead_worker_status": "isolated",
            "post_roll_generation": res.generation,
            "fleet_p50_ms": metrics["p50_ms"],
            "fleet_responses_total": metrics["responses_total"],
        }
        print(f"[fleet-smoke] rolling reload ok: {summary['rolling_reload']}")

        # ------------------------------------------- 3. teardown
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

    print("FLEET-SMOKE OK " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
