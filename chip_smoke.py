#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, end to end, through the entry points a user
calls, at the full width of the reference flagship (HalfCheetah-v5: obs
17, act 6, actor + twin critics ``(256, 256)``, batch 64, 50 updates a
window, the HBM replay ring at its real 1,000,000 slots). Only the run
length is cut; weights and data are made from ``--seed``.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the data-parallel mesh, and only it

One chip, in order, each phase a process of its own (a chip belongs to
one process at a time, so this parent never imports JAX):

1. ``train``   — ``torch_actor_critic_tpu.train``: two short epochs, several
   update bursts, a checkpoint; state and ring must sit on a TPU device.
2. ``serve``   — ``serve.py --run <id> --port 0`` on that checkpoint: all
   bucket programs warm, ``/act`` at several batch sizes (deterministic
   and sampled), ``/healthz``, ``/metrics``, SIGTERM -> exit 0.
3. ``kernels`` — flash attention forward+backward against
   ``reference_attention`` (128 and 64 lanes), the trunk's one pass ahead of
   those kernels (head norm, rotary, heads first) there and back against the
   composition it replaces, at the trunk cell's shapes, one sequence-policy update
   burst, and the fused pixel kernel bit for bit against
   ``gather_frames_reference`` — compiled, not interpreted.
4. ``fused``   — one ``--on-device true`` epoch through ``train``.

``--chips 4`` runs ``train --devices 4`` (one GSPMD program, replay
sharded over ``dp``) and the same program and seed on four virtual CPU
devices, and compares them; no other phase.

A phase that fails stops the script with a non-zero code. Earlier lines
of stdout carry each phase's facts (timings, compile counts, cache hits
and misses, HBM in use); the LAST line is the verdict and exists only
when every phase passed on a TPU:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Nothing here selects the CPU. Started under a plain ``JAX_PLATFORMS=cpu``
the script rehearses its own control flow at a cut size, with the
kernels interpreted, reports the platform it found and exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import threading
import time
from urllib import request as urlreq

HERE = os.path.dirname(os.path.abspath(__file__))
# Runs, checkpoints and logs of this script: made here from --seed on
# every run, wiped first, git-ignored, never carried to the chip.
WORK = os.path.join(HERE, ".chip_smoke")
OUT = os.path.join(HERE, "chiprun_out")
# A plain JAX_PLATFORMS=cpu from outside makes the parent rehearse
# (on-chip-measurement guide §2.1): cut sizes, interpreted kernels, and
# never a passing verdict. Children are told (--rehearsal); they do not
# guess. --reference marks the CPU side of the mesh comparison: full
# size, but a reference and not a result, so it may run off the chip.

ENV_NAME, OBS_DIM, ACT_DIM = "HalfCheetah-v5", 17, 6
# The reference flagship (utils/config.py defaults, __graft_entry__.py)
# and what a rehearsal cuts it to.
FULL = {"hidden": "256,256", "buffer": 1_000_000}
CUT = {"hidden": "32,32", "buffer": 20_000}
BATCH, UPDATE_EVERY = 64, 50
# First-burst losses, TPU against CPU at the same seed: the replay
# contents and sample indices are identical by construction, so what is
# left is the TPU's default f32 matmul (bf16 passes) compounded over the
# window's 50 updates — 6e-4 relative on loss_q and 2e-4 on loss_pi when
# measured at dp=1 on a v5e (PR 21); the bound leaves an order of
# magnitude over that.
DP_LOSS_RTOL = 1e-2
PHASE_TIMEOUT_S = 900


def say(**facts) -> None:
    """One line of facts on stdout (never the verdict's keys); the
    parent also keeps its lines under chiprun_out/, which is what comes
    back from the chip when stdout is too long for its tail."""
    line = json.dumps(facts, sort_keys=True)
    print(line, flush=True)
    if "phase" in facts:
        os.makedirs(OUT, exist_ok=True)
        with open(os.path.join(OUT, "chip_smoke.jsonl"), "a") as f:
            f.write(line + "\n")


# --------------------------------------------------------------------------
# Child phases: each runs in its own process and is the only one on the chip.
# --------------------------------------------------------------------------


def off_chip_ok(args) -> bool:
    return args.rehearsal or args.reference


def device_facts(args) -> dict:
    """The device as JAX reports it; refuses anything but a TPU unless
    this is a rehearsal or the CPU reference."""
    import jax

    t0 = time.time()
    devs = jax.devices()
    facts = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "reach_s": round(time.time() - t0, 2),
    }
    if facts["platform"] != "tpu" and not off_chip_ok(args):
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (default platform "
            f"{facts['platform']!r}, {facts['kind']!r}); nothing is reported"
        )
    return facts


def all_finite(tree) -> bool:
    import jax
    import jax.numpy as jnp

    leaves = [
        x for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    ]
    return all(bool(jnp.isfinite(x).all()) for x in leaves)


def device_names(tree) -> list:
    import jax

    devs = set()
    for x in jax.tree_util.tree_leaves(tree):
        devs |= set(x.devices())
    return sorted(f"{d.platform}:{d.id}" for d in devs)


def replicas_equal(tree) -> bool:
    """Every device's copy of each replicated leaf holds the same bytes."""
    import jax
    import numpy as np

    return all(
        len({np.asarray(s.data).tobytes() for s in x.addressable_shards}) == 1
        for x in jax.tree_util.tree_leaves(tree)
        if x.sharding.is_fully_replicated
    )


def tree_bytes(tree) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def compile_facts() -> dict:
    from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

    snap = get_watchdog().snapshot()
    return {
        "compiles": snap["compiles_total"],
        "compile_s": snap["compile_time_s"],
        "cache_hits": snap["cache_hits_total"],
        "cache_misses": snap["cache_misses_total"],
        "by_source": snap["by_source"],
    }


def memory_facts() -> list:
    import jax

    stats = {d: d.memory_stats() or {} for d in jax.local_devices()}
    return [
        {
            "device": str(d),
            "bytes_in_use": s.get("bytes_in_use"),
            "peak_bytes_in_use": s.get("peak_bytes_in_use"),
        }
        for d, s in stats.items()
    ]


def sizes(args) -> dict:
    return CUT if args.rehearsal else FULL


def train_argv(args, extra: list) -> list:
    """The flagship configuration with only the run length cut: two
    epochs, bursts from step 149 on (warm-up actions are random until
    step 200, so the first bursts see seeded data only), a checkpoint
    each epoch."""
    return [
        "--environment", ENV_NAME, "--seed", str(args.seed),
        "--runs-root", args.runs_root, "--experiment", "chip_smoke",
        "--hidden-sizes", sizes(args)["hidden"], "--batch-size", str(BATCH),
        "--update-every", str(UPDATE_EVERY),
        "--buffer-size", str(sizes(args)["buffer"]),
        "--epochs", "2", "--steps-per-epoch", "400",
        "--start-steps", "200", "--update-after", "100",
        "--save-every", "1", "--compile-cache", "true",
    ] + extra


def watch_trainer() -> dict:
    """Look at the live trainer through the normal entry point: what
    ``train.main`` builds is read after every update burst and just
    before it is closed."""
    import jax

    from torch_actor_critic_tpu.parallel.dp import DataParallelSAC
    from torch_actor_critic_tpu.sac.trainer import Trainer

    seen: dict = {"bursts": []}
    close = Trainer.close

    def close_and_look(self):
        seen.update(
            grad_steps=int(self.state.step),
            state_finite=all_finite(self.state),
            state_devices=device_names(self.state),
            ring_devices=device_names(self.buffer),
            ring_bytes=tree_bytes(self.buffer),
            ring_size=[int(n) for n in jax.device_get(self.buffer.size)],
            run_id=self.tracker.run_id,
            mesh=dict(self.mesh.shape),
            replicas_equal=replicas_equal(
                (self.state.actor_params, self.state.critic_params)
            ),
            memory=memory_facts(),
        )
        return close(self)

    Trainer.close = close_and_look
    burst = DataParallelSAC.update_burst

    def burst_and_look(self, state, buffer, chunk, num_updates):
        if not seen["bursts"]:
            seen["chunk_devices"] = device_names(chunk)
        out = burst(self, state, buffer, chunk, num_updates)
        metrics = jax.device_get(out[2])
        seen["bursts"].append({
            k: float(metrics[k])
            for k in ("loss_q", "loss_pi", "diag/param_norm_skew")
            if k in metrics
        })
        return out

    DataParallelSAC.update_burst = burst_and_look
    return seen


def epoch_losses(runs_root: str, run_id: str) -> list:
    path = os.path.join(runs_root, "chip_smoke", run_id, "metrics.jsonl")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [[row["loss_q"], row["loss_pi"]] for row in rows]


def require(cond: bool, what: str, **facts) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {what}: {json.dumps(facts)}")


def phase_train(args) -> dict:
    """The host-loop trainer (one chip), or with ``--devices N`` the
    data-parallel mesh with light diagnostics."""
    import math

    t0 = time.time()
    dev = device_facts(args)
    from torch_actor_critic_tpu import train

    mesh_run = args.devices > 1
    seen = watch_trainer()
    extra = (
        ["--devices", str(args.devices), "--diagnostics", "light"]
        if mesh_run else []
    )
    train.main(train_argv(args, extra))
    losses = epoch_losses(args.runs_root, seen["run_id"])
    ckpt = os.path.join(
        args.runs_root, "chip_smoke", seen["run_id"], "artifacts",
        "checkpoints",
    )
    facts = {
        "device": dev, "epoch_losses": losses, **seen,
        "checkpoints": sorted(os.listdir(ckpt)),
        "wall_s": round(time.time() - t0, 1), **compile_facts(),
    }
    require(
        losses and all(math.isfinite(x) for row in losses for x in row),
        "losses not finite", losses=losses,
    )
    require(
        seen["bursts"] and all(
            math.isfinite(v) for b in seen["bursts"] for v in b.values()
        ),
        "a burst reported a non-finite loss", bursts=seen["bursts"],
    )
    require(seen["state_finite"], "TrainState holds non-finite values")
    require(seen["grad_steps"] > 0, "no gradient step was taken")
    require(facts["checkpoints"], "no checkpoint was written", dir=ckpt)
    on_chip = off_chip_ok(args) or all(
        name.startswith("tpu:")
        for name in seen["state_devices"] + seen["ring_devices"]
    )
    require(
        on_chip, "TrainState or replay ring is not on a TPU device",
        state=seen["state_devices"], ring=seen["ring_devices"],
    )
    n = args.devices
    require(
        len(seen["ring_devices"]) == n and len(seen["chunk_devices"]) == n,
        f"replay ring / chunk do not span {n} distinct device(s)",
        ring=seen["ring_devices"], chunk=seen["chunk_devices"],
    )
    in_use = [m["bytes_in_use"] for m in seen["memory"]]
    require(
        off_chip_ok(args) or all(
            b is not None and b >= seen["ring_bytes"] // n for b in in_use
        ),
        "a device holds less than its replay shard",
        bytes_in_use=in_use, ring_bytes=seen["ring_bytes"],
    )
    if mesh_run:
        skews = [b["diag/param_norm_skew"] for b in seen["bursts"]]
        require(
            skews and all(s == 0.0 for s in skews) and seen["replicas_equal"],
            "replicas out of step", param_norm_skew=skews,
            replicas_equal=seen["replicas_equal"],
        )
    return facts


def phase_fused(args) -> dict:
    """One fused ``--on-device true`` epoch (acting, env twin, ring and
    updates in one device program) through the same CLI entry point."""
    import math

    t0 = time.time()
    dev = device_facts(args)
    from torch_actor_critic_tpu import train

    argv = train_argv(args, ["--on-device", "true"])
    argv[argv.index("--epochs") + 1] = "1"
    metrics = train.main(argv)
    losses = {k: float(metrics[k]) for k in ("loss_q", "loss_pi")}
    require(
        all(math.isfinite(v) for v in losses.values()),
        "fused epoch losses not finite", **losses,
    )
    memory = memory_facts()
    require(
        args.rehearsal or (memory[0]["peak_bytes_in_use"] or 0)
        > sizes(args)["buffer"] * 4 * (2 * OBS_DIM + ACT_DIM + 2),
        "the device never held the replay ring", memory=memory,
    )
    return {
        "device": dev, **losses, "memory": memory,
        "wall_s": round(time.time() - t0, 1), **compile_facts(),
    }


def phase_kernels(args) -> dict:
    """Every Pallas kernel of the train/serve paths, compiled for this
    chip and compared with its plain reference, plus the sequence
    policy's update burst (flash attention forward+backward inside the
    real loss)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    t0 = time.time()
    dev = device_facts(args)
    from torch_actor_critic_tpu.buffer import init_replay_buffer
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.models import (
        SequenceActor,
        SequenceDoubleCritic,
    )
    from torch_actor_critic_tpu.ops import pixels
    from torch_actor_critic_tpu.ops.attention import (
        flash_attention,
        qk_norm_rope,
        reference_attention,
    )
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig

    interpret = args.rehearsal
    key = jax.random.key(args.seed)
    facts: dict = {"device": dev, "flash": [], "pixel": []}

    # (causal, T, head dim, blocks, lanes): the auto path, explicit 128
    # blocks, a ragged head dim, the 512-block row, and the 64-lane layout.
    for causal, t, d, blocks, lanes in [
        (True, 256, 64, None, 128),
        (False, 256, 64, 128, 128),
        (True, 128, 48, None, 128),
        (True, 1024, 64, None, 128),
        (True, 256, 64, None, 64),
    ]:
        kq, kk, kv, kg, key = jax.random.split(key, 5)
        q, k, v, g = (
            jax.random.normal(kx, (2, 4, t, d), jnp.float32)
            for kx in (kq, kk, kv, kg)
        )
        out_f, vjp_f = jax.vjp(
            lambda q, k, v: flash_attention(
                q, k, v, causal, blocks, blocks, interpret, lanes
            ), q, k, v,
        )
        out_r, vjp_r = jax.vjp(
            lambda q, k, v: reference_attention(q, k, v, causal=causal),
            q, k, v,
        )
        np.testing.assert_allclose(
            np.asarray(out_f), np.asarray(out_r), atol=2e-2, rtol=2e-2
        )
        for a, b in zip(vjp_f(g), vjp_r(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-2, rtol=5e-2
            )
        facts["flash"].append({
            "causal": causal, "t": t, "d": d, "lanes": lanes,
            "max_abs_err": float(jnp.max(jnp.abs(out_f - out_r))),
        })

    # The one pass between q_proj and the kernels (head norm, rotary, heads
    # first), there and back, against the composition it replaces, at the
    # trunk cell's shapes: 8 x 1024 tokens, 32 query and 4 key/value heads of
    # 128. Float32 either way, so sums in another order at most.
    batch, t = (1, 64) if args.rehearsal else (8, 1024)
    pos = 3 + jnp.arange(t)
    facts["qk_rope"] = []
    for heads in (32, 4):
        ky, kw, kg, key = jax.random.split(key, 4)
        y = jax.random.normal(ky, (batch, t, heads, 128), jnp.float32)
        w = 1.0 + 0.1 * jax.random.normal(kw, (128,), jnp.float32)
        g = jax.random.normal(kg, (batch, heads, t, 128), jnp.float32)
        (out_f, vjp_f), (out_r, vjp_r) = (
            jax.vjp(lambda y, w: qk_norm_rope(y, w, pos, 1e6, 1e-6, impl), y, w)
            for impl in ("interpret" if interpret else "pallas", "xla")
        )
        gaps = [
            float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
            for a, b in zip((out_f, *vjp_f(g)), (out_r, *vjp_r(g)))
        ]
        require(max(gaps) < 1e-5, "the one pass left the composition", gaps=gaps)
        facts["qk_rope"].append({"heads": heads, "t": t, "rel_gaps": gaps})

    # The sequence policy at its shipped shape: attention sees
    # [64, 4, 8, 16] (batch 64, 4 heads, 8 steps of history, d_model 64).
    horizon = 8
    sac = SAC(
        SACConfig(batch_size=BATCH, history_len=horizon),
        SequenceActor(
            act_dim=ACT_DIM, d_model=64, num_heads=4, max_len=horizon
        ),
        SequenceDoubleCritic(d_model=64, num_heads=4, max_len=horizon),
        ACT_DIM,
    )
    state = sac.init_state(key, jnp.zeros((horizon, OBS_DIM)))
    ring = init_replay_buffer(
        2_000, jax.ShapeDtypeStruct((horizon, OBS_DIM), jnp.float32), ACT_DIM
    )
    ks = jax.random.split(key, 4)
    chunk = Batch(
        states=jax.random.normal(ks[0], (200, horizon, OBS_DIM)),
        actions=jnp.tanh(jax.random.normal(ks[1], (200, ACT_DIM))),
        rewards=jax.random.normal(ks[2], (200,)),
        next_states=jax.random.normal(ks[3], (200, horizon, OBS_DIM)),
        done=jnp.zeros((200,)),
    )
    burst = jax.jit(sac.update_burst, static_argnums=(3,))
    state, ring, metrics = burst(state, ring, chunk, 10)
    seq = {k: float(metrics[k]) for k in ("loss_q", "loss_pi")}
    require(
        all(np.isfinite(v) for v in seq.values()) and all_finite(state),
        "sequence-policy burst not finite", **seq,
    )
    facts["sequence_burst"] = seq

    # The fused pixel kernel at the wall-runner geometry, bit for bit.
    ring_shape = (256, 64, 64, 3) if args.rehearsal else (20000, 64, 64, 3)
    frames = jax.random.randint(
        ks[0], ring_shape, 0, 256, jnp.int32
    ).astype(jnp.uint8)
    for batch in ((8,) if args.rehearsal else (32, 512)):
        idx = jax.random.randint(ks[1], (batch,), 0, ring_shape[0])
        offsets = jax.random.randint(ks[2], (batch, 2), 0, 9)
        for dtype in (jnp.float32, jnp.bfloat16):
            for offs in (None, offsets):
                got, want = (
                    jax.jit(lambda r, i, o, impl=impl: pixels.fused_frame_gather(
                        r, i, o, normalize=True, out_dtype=dtype, impl=impl,
                        interpret=interpret and impl == "pallas",
                    ))(frames, idx, offs)
                    for impl in ("pallas", "xla")
                )
                same = bool(jnp.array_equal(got, want))
                facts["pixel"].append({
                    "batch": batch, "dtype": jnp.dtype(dtype).name,
                    "shift": offs is not None, "bitwise": same,
                })
                require(
                    same and got.dtype == jnp.dtype(dtype),
                    "pixel kernel differs from gather_frames_reference",
                    **facts["pixel"][-1],
                    max_abs_err=float(jnp.max(jnp.abs(
                        got.astype(jnp.float32) - want.astype(jnp.float32)
                    ))),
                )
    facts["wall_s"] = round(time.time() - t0, 1)
    return facts


PHASES = {"train": phase_train, "fused": phase_fused, "kernels": phase_kernels}


# --------------------------------------------------------------------------
# The parent: stdlib only, never on the chip.
# --------------------------------------------------------------------------

LIVE: list = []  # process groups this script started and has not reaped


def stop_everything() -> None:
    for proc in LIVE:
        if proc.poll() is None:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()


def start(cmd: list, env: dict | None = None) -> subprocess.Popen:
    proc = subprocess.Popen(
        cmd, env=env, cwd=HERE, stdout=subprocess.PIPE, text=True,
        start_new_session=True,
    )
    LIVE.append(proc)
    return proc


def run_phase(name: str, args, extra=(), env: dict | None = None) -> dict:
    """Run one child phase to its end; its last stdout line is its
    facts. A non-zero exit or a timeout stops the whole script."""
    proc = start(
        [
            sys.executable, os.path.abspath(__file__), "--phase", name,
            "--seed", str(args.seed), "--runs-root", args.runs_root,
            *(["--rehearsal"] if args.rehearsal else []), *extra,
        ],
        env,
    )
    try:
        out, _ = proc.communicate(timeout=PHASE_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise SystemExit(
            f"chip_smoke: FAILED — phase {name} exceeded {PHASE_TIMEOUT_S}s"
        ) from None
    if proc.returncode != 0:
        raise SystemExit(
            f"chip_smoke: FAILED — phase {name} exited {proc.returncode}"
        )
    return json.loads(out.strip().splitlines()[-1])


def post_act(address: str, obs, deterministic: bool) -> dict:
    req = urlreq.Request(
        address + "/act",
        data=json.dumps({"obs": obs, "deterministic": deterministic}).encode(),
        headers={"Content-Type": "application/json"},
    )
    return json.loads(urlreq.urlopen(req, timeout=60).read())


def phase_serve(args, run_id: str) -> dict:
    """``serve.py --run <id> --port 0`` as the operator starts it,
    driven over loopback from this (JAX-free) process."""
    import math
    import random

    t0 = time.time()
    proc = start([
        sys.executable, os.path.join(HERE, "serve.py"), "--run", run_id,
        "--experiment", "chip_smoke", "--runs-root", args.runs_root,
        "--port", "0", "--compile-cache",
    ])
    lines: queue.Queue = queue.Queue()
    threading.Thread(
        target=lambda: [lines.put(line) for line in proc.stdout],
        daemon=True,
    ).start()
    ready = None
    deadline = time.time() + PHASE_TIMEOUT_S
    while ready is None:
        require(proc.poll() is None, "serve.py exited before it was ready",
                rc=proc.returncode)
        require(time.time() < deadline, "serve.py never printed its address")
        try:
            line = lines.get(timeout=1.0)
        except queue.Empty:
            continue
        if line.startswith("{"):
            ready = json.loads(line)
    address, slot = ready["serving"], ready["slots"]["default"]
    ready_s = round(time.time() - t0, 1)
    warmed = [tuple(k) for k in slot["compiled"]]
    require(
        len(warmed) == 2 * len(slot["buckets"]),
        "not every (bucket, deterministic|sampled) program is warm", **slot,
    )
    require(
        args.rehearsal or ready["device"]["platform"] == "tpu",
        "serve.py is not on a TPU", device=ready["device"],
    )

    rng = random.Random(args.seed)
    acts = []
    # 1, 3, 9 and 33 rows land in different buckets; both forwards are hit.
    for rows, deterministic in [(1, True), (3, False), (9, True), (33, False)]:
        obs = [[rng.gauss(0, 1) for _ in range(OBS_DIM)] for _ in range(rows)]
        reply = post_act(address, obs if rows > 1 else obs[0], deterministic)
        action = reply["action"] if rows > 1 else [reply["action"]]
        require(
            len(action) == rows and all(
                len(a) == ACT_DIM and all(
                    math.isfinite(x) and abs(x) <= 1.0 for x in a
                ) for a in action
            ),
            "an /act reply is not a finite action batch of the asked shape",
            rows=rows, reply=reply,
        )
        acts.append({"rows": rows, "deterministic": deterministic})
    health = json.loads(urlreq.urlopen(address + "/healthz", timeout=30).read())
    require(health["status"] == "ok", "/healthz is not ok", **health)
    metrics = json.loads(urlreq.urlopen(address + "/metrics", timeout=30).read())
    xla = metrics["xla"]
    require(
        metrics["live_compiles"] == 0,
        "a request paid a live compile after warm-up",
        live_compiles=metrics["live_compiles"],
    )
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        raise SystemExit(
            "chip_smoke: FAILED — serve.py did not exit on SIGTERM"
        ) from None
    require(rc == 0, "serve.py did not exit 0 on SIGTERM", rc=rc)
    return {
        "device": ready["device"], "ready_s": ready_s,
        "buckets": slot["buckets"], "warmed_programs": len(warmed),
        "acts": acts, "epoch": slot["epoch"],
        "compiles": xla["compiles_total"], "compile_s": xla["compile_time_s"],
        "cache_hits": xla["cache_hits_total"],
        "cache_misses": xla["cache_misses_total"],
        "wall_s": round(time.time() - t0, 1),
    }


def one_chip(args) -> dict:
    train = run_phase("train", args)
    say(phase="train", **train)
    say(phase="serve", **phase_serve(args, train["run_id"]))
    say(phase="kernels", **run_phase("kernels", args))
    say(phase="fused", **run_phase("fused", args))
    return train["device"]


def four_chips(args) -> dict:
    """``train --devices 4`` on the chips, then the same program and
    seed on four virtual CPU devices (a child held to the CPU), and the
    first burst of each side by side."""
    n = str(args.chips)
    chip = run_phase("train", args, ["--devices", n])
    say(phase="dp-chip", **chip)
    cpu_env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS=(
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=" + n
        ).strip(),
    )
    cpu = run_phase("train", args, ["--devices", n, "--reference"], cpu_env)
    say(phase="dp-cpu-reference", **cpu)
    first = {
        k: (chip["bursts"][0][k], cpu["bursts"][0][k])
        for k in ("loss_q", "loss_pi")
    }
    say(phase="dp-compare", first_burst=first, rtol=DP_LOSS_RTOL)
    require(
        chip["ring_size"] == cpu["ring_size"],
        "the two runs did not store the same number of transitions",
        chip=chip["ring_size"], cpu=cpu["ring_size"],
    )
    require(
        all(
            abs(a - b) <= DP_LOSS_RTOL * max(abs(a), abs(b))
            for a, b in first.values()
        ),
        "first-burst losses differ beyond the stated tolerance", **first,
    )
    return chip["device"]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: the data-parallel mesh and its CPU "
                             "comparison, and no other phase")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    parser.add_argument("--devices", type=int, default=1, help=argparse.SUPPRESS)
    parser.add_argument("--reference", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--rehearsal", action="store_true",
                        help=argparse.SUPPRESS)
    parser.add_argument("--runs-root", default=os.path.join(WORK, "runs"),
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args.phase:  # a child: told by the parent what kind of run this is
        sys.path.insert(0, HERE)
        say(**PHASES[args.phase](args))
        return 0
    args.rehearsal = (
        os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    )

    if not os.path.isdir(os.path.join(HERE, "torch_actor_critic_tpu")):
        raise SystemExit(
            "chip_smoke: the torch_actor_critic_tpu package is not beside "
            "this script; there is nothing to run"
        )
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(args.runs_root)
    t0 = time.time()
    try:
        device = (four_chips if args.chips == 4 else one_chip)(args)
    finally:
        stop_everything()
    device = {k: device[k] for k in ("platform", "kind", "count")}
    say(phase="total", wall_s=round(time.time() - t0, 1),
        rehearsal=args.rehearsal)
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(
            f"chip_smoke: FAILED — ran on {json.dumps(device)}, not on "
            f"{args.chips} TPU chip(s); no result",
            flush=True,
        )
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
