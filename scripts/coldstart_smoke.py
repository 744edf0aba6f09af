"""Cold-start smoke: warm-start bundles through the real serve.py CLI.

The `make coldstart-smoke` gate for the aot/ subsystem
(docs/SERVING.md "Cold start & warm-start bundles"). Writes a real
TrainState checkpoint, builds a warm-start bundle next to it
(aot/bundle.py — jax.export programs + pre-populated persistent
compilation cache), then proves four claims against fresh
``python serve.py`` subprocesses over loopback HTTP:

1. **Cold baseline**: a worker without the bundle comes up, pays its
   compiles inside warmup (``warmup_compiles > 0``), and answers /act.
2. **Warm worker**: ``--warm-start auto`` resolves the
   checkpoint-adjacent bundle; the first /act is answered with ZERO
   serve-plane live compiles (``live_compiles == 0``,
   ``bundle_compiles > 0``, ``warmup_compiles == 0``) and the
   watchdog's three-way split shows the compiles under
   ``bundle_load`` with ``bundle_hits`` counted.
3. **Flood**: a second warm worker (its xla_cache now fully
   populated — ``cache_hits > 0``) takes a chaos-smoke-style
   closed-loop herd flood of deterministic + sampled /act requests
   and HOLDS ``live_compiles == 0`` through all of it.
4. **Tamper rejection**: a fingerprint-corrupted bundle is LOUDLY
   rejected (``bundle_rejected`` bumped), the worker falls back to a
   plain live warmup and still serves correctly.

Also reports time-to-first-act cold vs warm. Runs on CPU in ~1 min;
exits nonzero on any violated invariant.
"""

import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from urllib import request as urlreq

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS_DIM, ACT_DIM = 17, 6
MAX_BATCH = 8


def fail(msg, proc=None):
    print(f"[coldstart-smoke] FAIL: {msg}", file=sys.stderr)
    if proc is not None:
        proc.terminate()
        try:
            out, _ = proc.communicate(timeout=10)
            print(out[-3000:], file=sys.stderr)
        except subprocess.TimeoutExpired:
            proc.kill()
    sys.exit(1)


class Worker:
    """One fresh serve.py subprocess; times spawn -> ready -> first act."""

    def __init__(self, ckpt_dir, extra, label):
        self.label = label
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=REPO + (
                os.pathsep + os.environ["PYTHONPATH"]
                if os.environ.get("PYTHONPATH") else ""
            ),
        )
        self.t_spawn = time.time()
        self.proc = subprocess.Popen(
            [
                sys.executable, os.path.join(REPO, "serve.py"),
                "--ckpt-dir", ckpt_dir,
                "--obs-dim", str(OBS_DIM), "--act-dim", str(ACT_DIM),
                "--port", "0", "--max-batch", str(MAX_BATCH),
                "--max-wait-ms", "2",
            ] + extra,
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO,
        )
        self.address = None
        deadline = time.time() + 180
        while time.time() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                if self.proc.poll() is not None:
                    fail(f"{label}: worker died rc={self.proc.returncode}",
                         self.proc)
                time.sleep(0.05)
                continue
            sys.stderr.write(f"[{label}] {line}")
            if line.startswith("{"):
                try:
                    self.startup = json.loads(line)
                    self.address = self.startup["serving"]
                    break
                except (json.JSONDecodeError, KeyError):
                    continue
        if self.address is None:
            fail(f"{label}: worker never printed its address", self.proc)
        self.ready_ms = (time.time() - self.t_spawn) * 1e3
        # Keep the pipe drained so the worker never blocks on stdout.
        threading.Thread(
            target=lambda: [None for _ in self.proc.stdout], daemon=True
        ).start()

    def act(self, deterministic=True, timeout=60):
        req = urlreq.Request(
            self.address + "/act",
            data=json.dumps({
                "obs": [0.1] * OBS_DIM, "deterministic": deterministic,
            }).encode(),
            headers={"Content-Type": "application/json"},
        )
        out = json.loads(urlreq.urlopen(req, timeout=timeout).read())
        assert len(out["action"]) == ACT_DIM, out
        return out

    def metrics(self):
        return json.loads(
            urlreq.urlopen(self.address + "/metrics", timeout=30).read()
        )

    def health(self):
        return json.loads(
            urlreq.urlopen(self.address + "/healthz", timeout=30).read()
        )

    def close(self):
        self.proc.terminate()
        try:
            self.proc.communicate(timeout=15)
        except subprocess.TimeoutExpired:
            self.proc.kill()


def main():
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.aot import default_bundle_dir, emit_bundle
    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig

    summary = {}
    tmp = tempfile.mkdtemp(prefix="coldstart_smoke_")
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

    t0 = time.time()
    bundle = emit_bundle(
        ckpt_dir, sac.actor_def,
        jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32),
        jax.device_get(state.actor_params), max_batch=MAX_BATCH,
    )
    bundle_dir = str(bundle.root)
    summary["bundle_build_s"] = round(time.time() - t0, 2)
    assert bundle_dir == str(default_bundle_dir(ckpt_dir)), bundle_dir
    print(f"[coldstart-smoke] bundle built at {bundle_dir} "
          f"({summary['bundle_build_s']}s)")

    # ---------------------------------------------- 1. cold baseline
    w = Worker(ckpt_dir, [], "cold")
    try:
        w.act()
        cold_ms = (time.time() - w.t_spawn) * 1e3
        met = w.metrics()
        assert met["live_compiles"] == 0, met["live_compiles"]
        assert met["bundle_compiles"] == 0, met["bundle_compiles"]
        assert met["xla"]["warmup_compiles"] > 0, met["xla"]
        assert w.health()["slots"]["default"]["bundle_loaded"] is False
    finally:
        w.close()
    summary["cold"] = {"first_act_ms": round(cold_ms, 1)}
    print(f"[coldstart-smoke] cold worker ok: first act {cold_ms:.0f}ms")

    # -------------------------------- 2. warm worker, zero live compiles
    w = Worker(ckpt_dir, ["--warm-start", "auto"], "warm")
    try:
        w.act(deterministic=True)
        w.act(deterministic=False)
        warm_ms = (time.time() - w.t_spawn) * 1e3
        met = w.metrics()
        xla = met["xla"]
        assert met["live_compiles"] == 0, met["live_compiles"]
        assert met["bundle_compiles"] > 0, met["bundle_compiles"]
        assert xla["warmup_compiles"] == 0, xla
        assert xla["bundle_load_compiles"] > 0, xla
        assert xla["bundle_hits"] > 0, xla
        assert xla["bundle_rejected"] == 0, xla
        assert w.health()["slots"]["default"]["bundle_loaded"] is True
    finally:
        w.close()
    summary["warm"] = {
        "first_act_ms": round(warm_ms, 1),
        "bundle_compiles": met["bundle_compiles"],
        "bundle_hits": xla["bundle_hits"],
    }
    print(f"[coldstart-smoke] warm worker ok: first act {warm_ms:.0f}ms, "
          f"{met['bundle_compiles']} bundle-armed dispatches, 0 live")

    # ------------------- 3. second warm worker: cache hits, then flood
    w = Worker(ckpt_dir, ["--warm-start", "auto"], "flood")
    try:
        w.act()
        met = w.metrics()
        assert met["xla"]["cache_hits_total"] > 0, met["xla"]
        # chaos-smoke-style closed-loop herd: 8 threads x 100 requests,
        # deterministic and sampled mixed, against the warm worker.
        errors = []

        def herd(n=100):
            for i in range(n):
                try:
                    w.act(deterministic=(i % 2 == 0))
                except Exception as e:  # noqa: BLE001 — collected below
                    errors.append(repr(e))

        threads = [threading.Thread(target=herd) for _ in range(8)]
        t0 = time.time()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        flood_s = time.time() - t0
        assert not errors, errors[:3]
        met = w.metrics()
        assert met["live_compiles"] == 0, (
            f"flood paid {met['live_compiles']} live compiles"
        )
        assert met["responses_total"] >= 800, met["responses_total"]
    finally:
        w.close()
    summary["flood"] = {
        "requests": 800,
        "seconds": round(flood_s, 1),
        "live_compiles": met["live_compiles"],
        "cache_hits": met["xla"]["cache_hits_total"],
    }
    print(f"[coldstart-smoke] flood ok: 800 acts in {flood_s:.1f}s, "
          f"live_compiles still 0")

    # --------------------------- 4. tampered bundle: loud rejection
    manifest_path = os.path.join(bundle_dir, "MANIFEST.json")
    with open(manifest_path) as f:
        manifest = json.load(f)
    manifest["fingerprint"]["jaxlib"] = "0.0.0-tampered"
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    w = Worker(ckpt_dir, ["--warm-start", "auto"], "tampered")
    try:
        w.act()
        met = w.metrics()
        xla = met["xla"]
        assert xla["bundle_rejected"] >= 1, xla
        assert met["bundle_compiles"] == 0, met["bundle_compiles"]
        assert xla["warmup_compiles"] > 0, xla  # fell back to live warmup
        assert met["live_compiles"] == 0, met["live_compiles"]
        assert w.health()["slots"]["default"]["bundle_loaded"] is False
    finally:
        w.close()
    summary["tamper"] = {
        "bundle_rejected": xla["bundle_rejected"],
        "fell_back_to_warmup": True,
    }
    print("[coldstart-smoke] tampered bundle rejected loudly; "
          "worker fell back and served")

    summary["speedup"] = round(
        summary["cold"]["first_act_ms"] / summary["warm"]["first_act_ms"], 2
    )
    print("COLDSTART-SMOKE OK " + json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
