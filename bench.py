"""Headline benchmark: SAC gradient-steps/sec on one TPU chip.

BASELINE.md: the reference publishes no numbers, so the measured
baseline is a PyTorch-CPU implementation of the same update at the
reference run configuration (alpha=0.2 fixed, gamma=0.99, polyak=0.995,
batch 64, hidden [256,256], lr 3e-4, ``torch.set_num_threads(2)`` as in
ref ``main.py:130``) on HalfCheetah-v3 dimensions (obs 17, act 6).

Prints exactly ONE JSON line on stdout:
    {"metric": "sac_grad_steps_per_sec", "value": N, "unit":
     "steps/sec", "vs_baseline": ratio_vs_torch_cpu, ...}
Extra keys: backend, device_kind, mfu, flops_per_step, sweep (batch/
width MFU scaling), visual (CNN burst at the wall-runner geometry),
on_device (fused env+update loop throughput), host_envs (worker-pool
on/off incl. the wall-runner crossover), telemetry_overhead (Trainer
throughput with telemetry off vs on), obs_overhead (run-wide obs
collector + SLO engine off vs on), diagnostics_overhead (tiered
off/light/full learning-health diagnostics cost).

Process contract (one process for each chip):
  * The parent never initialises a backend. It asks a SUBPROCESS what
    the default device is; without an accelerator there is no number —
    it says so on stderr and exits non-zero, with no JSON line (a CPU
    figure is never written under the device metric's name).
  * Every stage runs in a subprocess of its own, one after the other,
    so the chip always has one owner; host-side stages start held to
    the CPU (``JAX_PLATFORMS=cpu`` in the child's environment).
  * A stage that raises or times out is recorded under
    ``stage_errors`` and makes the exit code non-zero.

The TPU number is measured through the real training path — the fused
``update_burst`` (push + 50 sampled gradient steps per dispatch) over
the HBM replay buffer, exactly what the trainer runs.
"""

import functools
import json
import os
import subprocess
import sys
import time

OBS_DIM, ACT_DIM = 17, 6
BATCH = 64
HIDDEN = (256, 256)
BURST = 50

# Peak bf16 FLOP/s per chip generation now lives in ONE place —
# telemetry/costmodel.py (the live roofline layer shares it); bench's
# peak_flops_for() below delegates there, TAC_PEAK_FLOPS override
# included.

_PROBE_SRC = """
import json, time
t0 = time.time()
import jax, jax.numpy as jnp
devs = jax.devices()
x = jnp.ones((256, 256), jnp.float32)
assert float((x @ x)[0, 0]) == 256.0
print(json.dumps({
    "platform": devs[0].platform,
    "device_kind": devs[0].device_kind,
    "n_devices": len(devs),
    "init_seconds": round(time.time() - t0, 1),
}))
"""


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def preflight_backend():
    """Ask a subprocess (which exits before any stage starts, so the
    chip is free again) what the default device is. Raises when the
    probe fails; the caller refuses to go on without an accelerator."""
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE_SRC],
        capture_output=True, text=True, timeout=150,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"backend probe exited {proc.returncode}: {proc.stderr[-500:]}"
        )
    info = json.loads(proc.stdout.strip().splitlines()[-1])
    log(f"preflight ok: {info}")
    return info


def sac_flops_per_step(batch=BATCH, hidden=HIDDEN, obs=OBS_DIM, act=ACT_DIM):
    """Analytic FLOPs for one SAC gradient step (critic+policy update),
    dense matmul MACs x2, batch-scaled. Backward through a layer costs
    ~2x its forward; the frozen-critic pass in the policy loss only
    needs input grads (~1x forward extra). Elementwise/Adam/polyak
    terms are negligible and omitted."""
    def mlp_macs(sizes):
        return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))

    actor = mlp_macs([obs, *hidden]) + 2 * hidden[-1] * act       # trunk + mu/log_std heads
    critic = 2 * mlp_macs([obs + act, *hidden, 1])                # twin Q
    macs = (
        actor          # pi(s') for the backup (no grad)
        + critic       # target twin fwd
        + 3 * critic   # critic twin fwd+bwd
        + 3 * actor    # actor fwd+bwd (policy loss)
        + 2 * critic   # critic fwd + input-only bwd (frozen)
    )
    return 2 * batch * macs


def visual_flops_per_step(feat=168, frame=(64, 64, 3), act_dim=56,
                          batch=32, hidden=(256, 256), cnn_features=1):
    """Analytic FLOPs for one visual SAC gradient step (same fwd/bwd
    weighting as :func:`sac_flops_per_step`), dominated by the four CNN
    towers (actor + twin critic, each with its own conv trunk)."""
    def cnn_macs():
        h, w, c = frame
        macs = 0
        for f, k, s in zip((32, 64, 64), (8, 4, 3), (4, 2, 1)):
            h = (h - k) // s + 1
            w = (w - k) // s + 1
            macs += h * w * f * k * k * c
            c = f
        macs += (h * w * c) * 512 + 512 * cnn_features
        return macs

    def mlp_macs(sizes):
        return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))

    actor = (
        cnn_macs() + mlp_macs([feat, *hidden])
        + 2 * (hidden[-1] + cnn_features) * act_dim
    )
    critic_mlp = 2 * (mlp_macs([feat + act_dim, *hidden, 1]) + (1 + cnn_features))
    critic = 2 * cnn_macs() + critic_mlp  # twin, each with its own CNN tower
    macs = (
        actor          # pi(s') for the backup (no grad)
        + critic       # target twin fwd
        + 3 * critic   # critic twin fwd+bwd
        + 3 * actor    # actor fwd+bwd (policy loss)
        # frozen-critic policy step: full fwd, but the input-only
        # backward only traverses the MLP branch — the frame input is
        # constant data, so no gradient ever flows through the conv
        # towers (autograd skips them; XLA DCEs them).
        + critic + critic_mlp
    )
    return 2 * batch * macs


def _make_bench_fn(obs_dim, act_dim, hidden, batch, capacity=1_000_000,
                   compute_dtype="float32", burst_unroll=0,
                   algorithm="sac"):
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.buffer import init_replay_buffer, push
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.utils.config import SACConfig

    cfg = SACConfig(
        batch_size=batch, hidden_sizes=hidden, compute_dtype=compute_dtype,
        burst_unroll=burst_unroll, algorithm=algorithm,
    )

    class _Spec:  # the flat-obs env surface build_models dispatches on
        obs_spec = jax.ShapeDtypeStruct((obs_dim,), jnp.float32)
        act_limit = 1.0

    _Spec.act_dim = act_dim
    actor, critic = build_models(cfg, _Spec)
    sac = make_learner(cfg, actor, critic, act_dim)
    state = sac.init_state(jax.random.key(0), jnp.zeros((obs_dim,)))
    buf = init_replay_buffer(
        capacity, jax.ShapeDtypeStruct((obs_dim,), jnp.float32), act_dim
    )

    def chunk(key, n=BURST):
        ks = jax.random.split(jax.random.key(key), 5)
        return Batch(
            states=jax.random.normal(ks[0], (n, obs_dim)),
            actions=jnp.tanh(jax.random.normal(ks[1], (n, act_dim))),
            rewards=jax.random.normal(ks[2], (n,)),
            next_states=jax.random.normal(ks[3], (n, obs_dim)),
            done=jnp.zeros((n,)),
        )

    buf = jax.jit(push, donate_argnums=(0,))(buf, chunk(1, 5000))
    burst = jax.jit(sac.update_burst, static_argnums=(3,), donate_argnums=(0, 1))

    from torch_actor_critic_tpu.utils.sync import drain

    state, buf, m = burst(state, buf, chunk(2), BURST)  # compile + warmup
    drain(m["loss_q"])

    def run(n_bursts):
        # Drain with a host fetch (utils/sync.py): each burst chains
        # through the donated (state, buf), so fetching the last burst's
        # loss forces the whole sequence to execute.
        # Chunks are generated and drained BEFORE the clock starts —
        # they are test scaffolding (the trainer stages real
        # transitions), not part of the measured update path.
        nonlocal state, buf
        chunks = [chunk(10 + i) for i in range(n_bursts)]
        for c in chunks:
            # One reduced fetch per chunk that depends on EVERY leaf —
            # draining a single field would let the other arrays'
            # kernels land inside the timed region.
            drain(jax.tree_util.tree_reduce(
                lambda a, leaf: a + jnp.sum(leaf), c, jnp.float32(0.0)
            ))
        t0 = time.perf_counter()
        for c in chunks:
            state, buf, m = burst(state, buf, c, BURST)
        drain(m["loss_q"])
        return n_bursts * BURST / (time.perf_counter() - t0)

    return run


def bench_accelerator(compute_dtype="float32"):
    """Headline number: grad-steps/sec at the reference config through
    the real fused update_burst path."""
    run = _make_bench_fn(OBS_DIM, ACT_DIM, HIDDEN, BATCH,
                         compute_dtype=compute_dtype)
    run(5)  # extra warmup beyond compile
    return run(60)


def bench_td3(budget_s=300.0):
    """TD3 fused-burst throughput at the reference config — the second
    algorithm family (extension) through the same update_burst path as
    the SAC headline, for a like-for-like grad-steps/s comparison.

    Calibrates with a 2-burst probe and only buys the full 60-burst
    measurement when it fits the remaining budget (BENCH_r05 killed
    the fixed-65-burst version at the stage timeout, shipping
    nothing); the short number is noisier but always lands."""
    t0 = time.time()
    run = _make_bench_fn(OBS_DIM, ACT_DIM, HIDDEN, BATCH, algorithm="td3")
    sps = run(2)  # calibration
    n = 60
    if BURST * (5 + n) / sps < budget_s - (time.time() - t0):
        run(5)
        sps = run(n)
    return {"grad_steps_per_sec": round(sps, 1), "algorithm": "td3"}


def bench_population(budget_s=420.0):
    """Population scaling at the reference config: N independent
    learners vmapped into one burst (parallel/population.py).

    The round-4 sweep proved the chip does 70% MFU at batch 8192 while
    the product config runs ~1-2% (latency-bound at batch 64); this
    stage measures how much of that idle MXU converts into extra SEEDS:
    aggregate grad-steps/s (all members) vs the N=1 burst. Near-linear
    scaling until the member matmuls fill the MXU is the design claim.
    """
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel.population import PopulationLearner
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.sync import drain

    cfg = SACConfig(batch_size=BATCH, hidden_sizes=HIDDEN)

    class _Spec:
        obs_spec = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)
        act_limit = 1.0

    _Spec.act_dim = ACT_DIM
    actor, critic = build_models(cfg, _Spec)
    sac = make_learner(cfg, actor, critic, ACT_DIM)
    capacity = 20_000  # per member; keeps 128 members << HBM

    out = []
    t_start = time.time()
    base_sps = None
    for n_members in (1, 8, 32, 128):
        if time.time() - t_start > budget_s:
            break
        entry = {"members": n_members}
        try:
            pop = PopulationLearner(sac, n_members)
            state = pop.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
            buffer = pop.init_buffer(
                capacity, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32),
                ACT_DIM,
            )

            def chunk(seed, n=BURST):
                ks = jax.random.split(jax.random.key(seed), 5)
                shp = (n_members, n)
                return Batch(
                    states=jax.random.normal(ks[0], shp + (OBS_DIM,)),
                    actions=jnp.tanh(
                        jax.random.normal(ks[1], shp + (ACT_DIM,))
                    ),
                    rewards=jax.random.normal(ks[2], shp),
                    next_states=jax.random.normal(ks[3], shp + (OBS_DIM,)),
                    done=jnp.zeros(shp),
                )

            buffer = pop.push_chunk(buffer, chunk(1, 2000))
            state, buffer, m = pop.update_burst(state, buffer, chunk(2), BURST)
            drain(m["loss_q"])  # compile + warmup
            n_bursts = 40 if n_members <= 32 else 20
            chunks = [chunk(10 + i) for i in range(n_bursts)]
            for c in chunks:
                drain(jax.tree_util.tree_reduce(
                    lambda a, leaf: a + jnp.sum(leaf), c, jnp.float32(0.0)
                ))
            t0 = time.perf_counter()
            for c in chunks:
                state, buffer, m = pop.update_burst(state, buffer, c, BURST)
            drain(m["loss_q"])
            dt = time.perf_counter() - t0
            agg = n_bursts * BURST * n_members / dt
            entry["grad_steps_per_sec_aggregate"] = round(agg, 1)
            if n_members == 1:
                base_sps = agg
            if base_sps is not None:
                # Only ever relative to a MEASURED N=1 point; if that
                # point failed, publishing "scaling_vs_1" against some
                # other N would corrupt the scaling claim.
                entry["scaling_vs_1"] = round(agg / base_sps, 2)
        except Exception as e:  # noqa: BLE001 — per-point best effort
            entry["error"] = repr(e)[:200]
        out.append(entry)
    return out


def bench_population_fused(budget_s=420.0):
    """Population-FUSED scaling: the entire Anakin epoch — envs, replay
    rings, PRNG streams and update bursts — vmapped over N members
    (sac/ondevice.py PopulationOnDeviceLoop), so acting is included,
    not just gradient steps. Reports AGGREGATE env-steps/s and
    grad-steps/s vs N plus an estimated MFU (gradient-burst FLOPs only;
    the pendulum physics is negligible), the conversion rate of the
    measured idle MXU into whole learning curves.
    """
    import jax

    from torch_actor_critic_tpu.envs.ondevice import PendulumJax
    from torch_actor_critic_tpu.sac.ondevice import (
        PopulationOnDeviceLoop,
        _wrap_and_build,
    )
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.sync import drain

    cfg = SACConfig(batch_size=BATCH, hidden_sizes=HIDDEN)
    env_cls, sac = _wrap_and_build(PendulumJax, cfg)
    steps, n_envs = 2 * BURST, 8
    flops = sac_flops_per_step(
        batch=BATCH, hidden=HIDDEN, obs=PendulumJax.obs_dim,
        act=PendulumJax.act_dim,
    )
    try:
        peak = peak_flops_for(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001
        peak = None

    out = []
    t_start = time.time()
    base_sps = None
    for n_members in (1, 8, 32, 128):
        if time.time() - t_start > budget_s:
            break
        entry = {"members": n_members}
        try:
            loop = PopulationOnDeviceLoop(
                sac, env_cls, n_members=n_members, n_envs=n_envs
            )
            ts, buf, es, keys, _ = loop.init(
                jax.random.key(0), buffer_capacity=20_000
            )
            ts, buf, es, keys, _ = loop.epoch(
                ts, buf, es, keys, steps=BURST, update_every=BURST,
                warmup=True,
            )
            # compile the measured shape, then time a fresh dispatch
            ts, buf, es, keys, m = loop.epoch(
                ts, buf, es, keys, steps=steps, update_every=BURST
            )
            drain(m["loss_q"])
            t0 = time.perf_counter()
            ts, buf, es, keys, m = loop.epoch(
                ts, buf, es, keys, steps=steps, update_every=BURST
            )
            drain(m["loss_q"])
            dt = time.perf_counter() - t0
            agg_gs = steps * n_members / dt
            entry["grad_steps_per_sec_aggregate"] = round(agg_gs, 1)
            entry["env_steps_per_sec_aggregate"] = round(
                steps * n_envs * n_members / dt, 1
            )
            if peak:
                entry["est_mfu"] = round(agg_gs * flops / peak, 5)
            if n_members == 1:
                base_sps = agg_gs
            if base_sps is not None:
                entry["scaling_vs_1"] = round(agg_gs / base_sps, 2)
        except Exception as e:  # noqa: BLE001 — per-point best effort
            entry["error"] = repr(e)[:200]
        out.append(entry)
    return out


def bench_sharding(budget_s=420.0):
    """Named-mesh GSPMD scaling (PR 8): the jit-with-sharding dp burst
    at the headline config across mesh shapes dp x fsdp in {1x1, 2x1,
    2x2}, reporting lockstep grad-steps/s, aggregate row throughput
    and estimated PER-DEVICE MFU (each dp shard computes one
    batch-64 gradient per step; fsdp changes layout, not FLOPs), plus
    the population_fused point re-run with the member axis sharded
    P('dp') over every visible device — the two scale-out paths the
    legacy shard_map substrate blocked. On a single-device backend the
    multi-device points record a skip reason (CPU tier-1 proves them
    under the forced-device-count shim; TPU numbers are the artifact).
    """
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.parallel import (
        DataParallelSAC,
        init_sharded_buffer,
        make_mesh,
        shard_chunk,
    )
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.sync import drain

    n_avail = jax.device_count()
    flops = sac_flops_per_step()
    try:
        peak = peak_flops_for(jax.devices()[0].device_kind)
    except Exception:  # noqa: BLE001
        peak = None

    def chunk_for(n_dev, per_dev=32):
        ks = jax.random.split(jax.random.key(1), 5)
        shape = (n_dev, per_dev)
        return Batch(
            states=jax.random.normal(ks[0], shape + (OBS_DIM,)),
            actions=jnp.tanh(jax.random.normal(ks[1], shape + (ACT_DIM,))),
            rewards=jax.random.normal(ks[2], shape),
            next_states=jax.random.normal(ks[3], shape + (OBS_DIM,)),
            done=jnp.zeros(shape),
        )

    out = {"device_count": n_avail, "burst": [], }
    t_start = time.time()
    for dp, fsdp in ((1, 1), (2, 1), (2, 2)):
        entry = {"mesh": f"dp{dp}xfsdp{fsdp}"}
        out["burst"].append(entry)
        if dp * fsdp > n_avail:
            entry["skipped"] = f"needs {dp * fsdp} devices, have {n_avail}"
            continue
        if time.time() - t_start > budget_s:
            entry["skipped"] = "budget exhausted"
            continue
        try:
            cfg = SACConfig(hidden_sizes=HIDDEN, batch_size=BATCH)
            sac = SAC(
                cfg,
                Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN),
                DoubleCritic(hidden_sizes=HIDDEN),
                ACT_DIM,
            )
            learner = DataParallelSAC(sac, make_mesh(dp=dp, fsdp=fsdp))
            state = learner.init_state(
                jax.random.key(0), jnp.zeros((OBS_DIM,))
            )
            buf = init_sharded_buffer(
                100_000, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32),
                ACT_DIM, learner.mesh,
            )
            chunk = shard_chunk(chunk_for(dp), learner.mesh)
            # compile + warm, then time a fresh dispatch
            state, buf, m = learner.update_burst(state, buf, chunk, BURST)
            drain(m["loss_q"])
            t0 = time.perf_counter()
            state, buf, m = learner.update_burst(state, buf, chunk, BURST)
            drain(m["loss_q"])
            dt = time.perf_counter() - t0
            sps = BURST / dt
            entry["grad_steps_per_sec"] = round(sps, 1)
            # Every dp shard grinds one batch-64 gradient per lockstep
            # step: aggregate row throughput scales with dp.
            entry["rows_per_sec"] = round(sps * BATCH * dp, 1)
            if peak:
                entry["est_mfu_per_device"] = round(sps * flops / peak, 5)
        except Exception as e:  # noqa: BLE001 — per-point best effort
            entry["error"] = repr(e)[:200]
        log(f"sharding {entry}")

    # population_fused with the member axis sharded over dp (the PR 6
    # loop was pinned to one device; this is the unlock).
    pop = {"members": 8, "mesh_dp": n_avail}
    out["population_member_sharded"] = pop
    try:
        from torch_actor_critic_tpu.envs.ondevice import PendulumJax
        from torch_actor_critic_tpu.sac.ondevice import (
            PopulationOnDeviceLoop,
            _wrap_and_build,
        )

        if pop["members"] % n_avail:
            raise ValueError(
                f"population 8 not divisible by {n_avail} devices"
            )
        cfg = SACConfig(batch_size=BATCH, hidden_sizes=HIDDEN)
        env_cls, sac = _wrap_and_build(PendulumJax, cfg)
        p_flops = sac_flops_per_step(
            batch=BATCH, hidden=HIDDEN, obs=PendulumJax.obs_dim,
            act=PendulumJax.act_dim,
        )
        loop = PopulationOnDeviceLoop(
            sac, env_cls, n_members=pop["members"], n_envs=8,
            mesh=make_mesh() if n_avail > 1 else None,
        )
        steps = 2 * BURST
        ts, buf, es, keys, _ = loop.init(
            jax.random.key(0), buffer_capacity=20_000
        )
        ts, buf, es, keys, _ = loop.epoch(
            ts, buf, es, keys, steps=BURST, update_every=BURST, warmup=True
        )
        ts, buf, es, keys, m = loop.epoch(
            ts, buf, es, keys, steps=steps, update_every=BURST
        )
        drain(m["loss_q"])
        t0 = time.perf_counter()
        ts, buf, es, keys, m = loop.epoch(
            ts, buf, es, keys, steps=steps, update_every=BURST
        )
        drain(m["loss_q"])
        dt = time.perf_counter() - t0
        agg = steps * pop["members"] / dt
        pop["grad_steps_per_sec_aggregate"] = round(agg, 1)
        pop["env_steps_per_sec_aggregate"] = round(
            steps * 8 * pop["members"] / dt, 1
        )
        if peak:
            # Per-device MFU: each device grinds members/n_avail curves.
            pop["est_mfu_per_device"] = round(
                agg / max(n_avail, 1) * p_flops / peak, 5
            )
    except Exception as e:  # noqa: BLE001
        pop["error"] = repr(e)[:200]
    log(f"sharding population {pop}")
    return out


def bench_unroll(budget_s=300.0):
    """Burst-scan unroll tuning at the headline config: the per-step
    kernels are launch-bound at batch 64 x [256,256], so unrolling the
    50-step gradient scan trades compile time for loop overhead. The
    product default is auto (burst_unroll=0 -> 5 on TPU, from this
    stage's chip evidence); this reports the full knob curve."""
    out = []
    t_start = time.time()
    for unroll in (1, 2, 5, 10):
        if time.time() - t_start > budget_s:
            break
        entry = {"unroll": unroll}
        try:
            run = _make_bench_fn(OBS_DIM, ACT_DIM, HIDDEN, BATCH,
                                 capacity=100_000, burst_unroll=unroll)
            sps = run(2)  # calibration; buy the long run only if it fits
            if BURST * 45 / sps < budget_s - (time.time() - t_start):
                run(5)
                sps = run(40)
            entry["grad_steps_per_sec"] = round(sps, 1)
        except Exception as e:  # noqa: BLE001 — per-point best effort
            entry["error"] = repr(e)[:200]
        out.append(entry)
        log_point("burst_unroll", entry)
    return out


def bench_sweep(budget_s=600.0):
    """Batch/width MFU scaling: where the chip stops being latency-bound
    and how close the update can get to peak (VERDICT r2 missing #2).

    Spans batch 64->16384 and width 256->4096 in f32 and bf16; each
    point reports achieved FLOP/s and MFU against the device's bf16
    peak (one consistent denominator — f32 entries' MFU understates by
    ~2x on MXU hardware, which is itself the point of the bf16 rows).
    Best-effort within a time budget; truncation is logged, not silent.
    """
    import jax

    kind = jax.devices()[0].device_kind
    peak = peak_flops_for(kind)
    results = []
    t_start = time.time()
    points = [
        # The headline's batch/width/dtype — but at unroll=1 (see the
        # pinned burst_unroll below), so this row is comparable to the
        # other sweep rows, not to the auto-unroll headline value.
        (BATCH, HIDDEN, "float32"),
        (512, HIDDEN, "float32"),
        (4096, HIDDEN, "float32"),
        (8192, HIDDEN, "float32"),
        (4096, (1024, 1024), "float32"),
        (4096, (1024, 1024), "bfloat16"),
        (8192, (2048, 2048), "float32"),
        (8192, (2048, 2048), "bfloat16"),
        # MFU-ceiling probes (bf16 only: the f32 rows above already
        # show the non-MXU penalty): 4x the per-layer FLOPs, then 2x
        # the batch at the best-known width.
        (8192, (4096, 4096), "bfloat16"),
        (16384, (2048, 2048), "bfloat16"),
    ]
    for batch, hidden, dtype in points:
        if time.time() - t_start > budget_s:
            log(f"sweep budget exhausted; dropped points from "
                f"batch={batch} hidden={hidden} {dtype} onward")
            results.append({"truncated_from": [batch, list(hidden), dtype]})
            break
        entry = {"batch": batch, "hidden": list(hidden), "dtype": dtype}
        try:
            # unroll pinned to 1: the sweep measures batch/width
            # scaling, and a 5x-unrolled burst body at width 4096
            # would spend the stage budget on compiles, not points.
            run = _make_bench_fn(OBS_DIM, ACT_DIM, hidden, batch,
                                 capacity=100_000, compute_dtype=dtype,
                                 burst_unroll=1)
            sps = run(2)  # calibration; re-measure properly only if fast
            if BURST * 20 / sps < (budget_s - (time.time() - t_start)):
                sps = run(20)
            flops = sac_flops_per_step(batch=batch, hidden=hidden)
            entry.update({
                "grad_steps_per_sec": round(sps, 1),
                "examples_per_sec": round(sps * batch, 0),
                "achieved_tflops": round(sps * flops / 1e12, 3),
            })
            if peak:
                entry["mfu"] = round(sps * flops / peak, 5)
            log(f"sweep batch={batch} hidden={hidden} {dtype}: "
                f"{sps:.1f} steps/s, {entry['achieved_tflops']} TFLOP/s")
        except Exception as e:  # noqa: BLE001 — sweep is best-effort
            entry["error"] = repr(e)
        results.append(entry)
        log_point("sweep", entry)
    return results


def bench_on_device(budget_s=300.0):
    """Fused on-device env+update loop throughput (envs/ondevice.py):
    the path the host-loop reference cannot express. Best-effort."""
    out = {}
    t_start = time.time()
    try:
        from torch_actor_critic_tpu.sac.ondevice import benchmark_on_device
    except ImportError:
        return {"error": "benchmark_on_device not available"}
    # n_envs=16 matches earlier rounds; the 128-env point shows the
    # fused loop's near-free env scaling (vectorized physics shares the
    # dispatch + update cost); the history-8 point times the fused
    # long-context (causal-transformer) path — shapes the host-loop
    # reference cannot express at all.
    # The pixel point runs the fused loop with ON-CHIP frame
    # rasterization through the visual (CNN) stack — pixel training
    # with zero host involvement.
    for env_name, n_envs, hist in (
        ("pendulum", 16, 1),
        ("cheetah", 16, 1),
        ("cheetah", 128, 1),
        ("cheetah", 16, 8),
        ("pixel", 16, 1),
    ):
        key = env_name + ("" if n_envs == 16 else f"@{n_envs}")
        key += "" if hist == 1 else f"_h{hist}"
        if time.time() - t_start > budget_s:
            out[key] = {"error": "budget exhausted"}
            continue
        try:
            out[key] = benchmark_on_device(
                env_name, n_envs=n_envs, history_len=hist
            )
        except Exception as e:  # noqa: BLE001
            out[key] = {"error": repr(e)}
    return out


def bench_scenarios(budget_s=300.0):
    """Fused-loop throughput per scenarios/ family (multi-agent,
    procedural, multi-task) against the pendulum baseline measured in
    the SAME process/config — the scenario-diversity counterpart of
    `on_device`: how much env-steps/s each workload family costs
    relative to the classic single-agent physics. Best-effort."""
    out = {}
    t_start = time.time()
    try:
        from torch_actor_critic_tpu.sac.ondevice import benchmark_on_device
    except ImportError:
        return {"error": "benchmark_on_device not available"}
    for env_name in ("pendulum", "multiagent", "procedural", "multitask"):
        if time.time() - t_start > budget_s:
            out[env_name] = {"error": "budget exhausted"}
            continue
        try:
            out[env_name] = benchmark_on_device(env_name, n_envs=16)
        except Exception as e:  # noqa: BLE001
            out[env_name] = {"error": repr(e)}
    base = out.get("pendulum", {}).get("env_steps_per_sec")
    if base:
        for env_name, row in out.items():
            if isinstance(row, dict) and row.get("env_steps_per_sec"):
                row["vs_pendulum"] = round(
                    row["env_steps_per_sec"] / base, 3
                )
    return out


def bench_attention(budget_s=180.0, t=2048, block_sweep=False):
    """Flash-attention kernel throughput (the long-context extension's
    hot op): causal fwd and fwd+bwd at a long-context shape, reported
    as achieved TFLOP/s. On TPU this exercises the Pallas kernels both
    directions (auto dispatch); elsewhere the XLA blockwise path."""
    b, h, d = 4, 8, 64
    out = {"shape": [b, h, t, d]}
    t_start = time.time()
    try:
        import jax
        import jax.numpy as jnp

        from torch_actor_critic_tpu.ops.attention import attention

        ks = jax.random.split(jax.random.key(0), 4)
        q, k, v = (
            jax.random.normal(kk, (b, h, t, d), jnp.float32) for kk in ks[:3]
        )
        g = jax.random.normal(ks[3], (b, h, t, d), jnp.float32)

        # Each step folds its output back into q so iteration i+1 has a
        # data dependency on iteration i: an async/pipelining backend
        # cannot overlap the timed kernels.
        fwd = jax.jit(
            lambda q, k, v: q * 0.999 + 1e-3 * attention(q, k, v, causal=True)
        )

        def loss_vjp_blocks(q, k, v, g, attn=None):
            _, vjp = jax.vjp(
                attn or (lambda q, k, v: attention(q, k, v, causal=True)),
                q, k, v,
            )
            # Fold ALL THREE grads into the chained output (tq == tk
            # here, so shapes match) — returning only dq would let XLA
            # dead-code-eliminate the dK/dV backward kernel entirely.
            dq, dk, dv = vjp(g)
            return q * 0.999 + 1e-3 * (dq + dk + dv)

        bwd = jax.jit(loss_vjp_blocks)

        # causal: half the score matrix is live -> 0.5 * 4*b*h*t^2*d per
        # fwd; bwd recomputes probs and adds dq/dk/dv matmuls (~2.5x).
        flops_fwd = 0.5 * 4 * b * h * t * t * d
        flops_bwd = 3.5 * flops_fwd  # fwd residual recompute + 2.5x bwd
        from torch_actor_critic_tpu.utils.sync import drain

        def timed(fn, q0, *args):
            drain(fn(q0, *args))  # compile + calibrate
            t0 = time.perf_counter()
            drain(fn(q0, *args))
            once = time.perf_counter() - t0
            n = max(4, min(50, int(5.0 / max(once, 1e-4))))
            r = q0
            t0 = time.perf_counter()
            for _ in range(n):
                r = fn(r, *args)
            drain(r)
            return (time.perf_counter() - t0) / n

        dt = timed(fwd, q, k, v)
        out["fwd_ms"] = round(dt * 1e3, 2)
        out["fwd_tflops"] = round(flops_fwd / dt / 1e12, 2)

        if time.time() - t_start < budget_s:
            dt = timed(bwd, q, k, v, g)
            out["fwd_bwd_ms"] = round(dt * 1e3, 2)
            out["fwd_bwd_tflops"] = round(flops_bwd / dt / 1e12, 2)

        # bf16 operands: the kernels keep sub-f32 dtypes on the MXU
        # (f32 accumulation) — the dtype the sequence stack trains in
        # under compute_dtype=bfloat16, and the fast systolic path.
        if time.time() - t_start < budget_s:
            qb, kb, vb, gb = (
                x.astype(jnp.bfloat16) for x in (q, k, v, g)
            )
            dt = timed(fwd, qb, kb, vb)
            out["fwd_ms_bf16"] = round(dt * 1e3, 2)
            out["fwd_tflops_bf16"] = round(flops_fwd / dt / 1e12, 2)
        if time.time() - t_start < budget_s:
            dt = timed(bwd, qb, kb, vb, gb)
            out["fwd_bwd_ms_bf16"] = round(dt * 1e3, 2)
            out["fwd_bwd_tflops_bf16"] = round(flops_bwd / dt / 1e12, 2)

        # Pallas block-size tuning (TPU only — the XLA path ignores
        # block_q): fwd+bwd bf16 at a few (block_q, block_k) tilings.
        # The un-suffixed rows above run the product default (auto
        # blocks, 512-capped — chosen FROM this sweep's chip data).
        # Opt-in per call: each point pays a fresh Pallas fwd+bwd
        # compile, so the caller must budget for it.
        if block_sweep and jax.default_backend() == "tpu":
            from torch_actor_critic_tpu.ops.attention import flash_attention

            # (block_q, block_k, pad_lanes): 128 = the zero-padded
            # native lane layout; 64 keeps a d=64 head at true width
            # (half the q/k/v/o HBM traffic — the MXU is 50%-bounded
            # at d=64 either way, see SCALING.md's attention roofline).
            # Decision-relevant points first (the stage budget may
            # truncate the tail): the incumbent (512,512,128) and the
            # round-4 candidates, then the historical small blocks.
            sweep = []
            for bq, bk, lanes in (
                (512, 512, 128), (512, 512, 64),
                (1024, 1024, 128), (1024, 1024, 64),
                (512, 1024, 128), (256, 512, 128),
                (256, 256, 128), (128, 256, 128),
            ):
                if time.time() - t_start > budget_s:
                    break
                try:
                    f = jax.jit(functools.partial(
                        loss_vjp_blocks,
                        attn=functools.partial(
                            flash_attention, causal=True, block_q=bq,
                            block_k=bk, pad_lanes=lanes,
                        ),
                    ))
                    dt = timed(f, qb, kb, vb, gb)
                    sweep.append({
                        "block_q": bq, "block_k": bk, "pad_lanes": lanes,
                        "fwd_bwd_ms": round(dt * 1e3, 2),
                        "fwd_bwd_tflops": round(flops_bwd / dt / 1e12, 2),
                    })
                except Exception as e:  # noqa: BLE001 — per-point
                    sweep.append({"block_q": bq, "block_k": bk,
                                  "pad_lanes": lanes,
                                  "error": repr(e)[:200]})
            if sweep:
                out["block_sweep"] = sweep
                best = max(
                    (s for s in sweep if "fwd_bwd_tflops" in s),
                    key=lambda s: s["fwd_bwd_tflops"],
                    default=None,
                )
                if best and "fwd_bwd_tflops_bf16" in out:
                    out["best_blocks"] = [best["block_q"], best["block_k"]]
                    out["best_pad_lanes"] = best.get("pad_lanes", 128)
                    out["best_blocks_tflops"] = max(
                        best["fwd_bwd_tflops"], out["fwd_bwd_tflops_bf16"]
                    )
        # Roofline context for the numbers above (SCALING.md, attention
        # section): at d=64 both kernel matmuls run a 64-wide
        # contraction/output on the 128x128 MXU, so the achievable
        # ceiling is <=50% of nominal peak regardless of software.
        out["achievable_peak_frac_d64"] = 0.5
        log(f"attention: {out}")
    except Exception as e:  # noqa: BLE001 — best-effort section
        out["error"] = repr(e)
    return out


def bench_visual(budget_s=300.0, burst=25):
    """Visual (CNN) update_burst throughput at the real wall-runner
    geometry — BASELINE config 5's perf half (VERDICT r2 missing #4):
    168 proprioceptive features + a 64x64x3 uint8 egocentric frame,
    act_dim 56 (ref ``networks/convolutional.py:54-183``,
    ``environments/wall_runner.py``). Reports grad-steps/sec plus the
    HBM footprint of the uint8 replay shard the throughput rides on.
    The backend it ran on is recorded alongside."""
    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.buffer import init_visual_replay_buffer, push
    from torch_actor_critic_tpu.buffer.replay import estimate_buffer_bytes
    from torch_actor_critic_tpu.core.types import Batch, MultiObservation
    from torch_actor_critic_tpu.envs.wall_runner import (
        ACT_DIM, FEATURE_DIM, FRAME_SHAPE,
    )
    from torch_actor_critic_tpu.models import VisualActor, VisualDoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.sync import drain

    feat, frame, act_dim, batch = FEATURE_DIM, FRAME_SHAPE, ACT_DIM, 32
    capacity = 20_000
    out = {
        "geometry": {
            "features": feat, "frame": list(frame), "act_dim": act_dim,
            "batch": batch, "burst": burst,
        },
        "backend": jax.default_backend(),
        "buffer_capacity": capacity,
        "buffer_hbm_bytes": estimate_buffer_bytes(
            capacity,
            MultiObservation(
                features=jax.ShapeDtypeStruct((feat,), jnp.float32),
                frame=jax.ShapeDtypeStruct(frame, jnp.uint8),
            ),
            act_dim,
        ),
    }
    t_start = time.time()

    def obs(key_f, key_p, n):
        return MultiObservation(
            features=jax.random.normal(key_f, (n, feat)),
            frame=jax.random.randint(key_p, (n, *frame), 0, 256, jnp.uint8),
        )

    def chunk(seed, n=burst):
        ks = jax.random.split(jax.random.key(seed), 6)
        return Batch(
            states=obs(ks[0], ks[1], n),
            actions=jnp.tanh(jax.random.normal(ks[2], (n, act_dim))),
            rewards=jax.random.normal(ks[3], (n,)),
            next_states=obs(ks[4], ks[5], n),
            done=jnp.zeros((n,)),
        )

    def measure(bsz, compute_dtype, pipeline="reference"):
        """Build the full visual stack at one (batch, dtype, pixel
        pipeline) point and time the fused burst; returns calibrated
        grad-steps/sec."""
        cfg = SACConfig(batch_size=bsz, compute_dtype=compute_dtype,
                        pixel_pipeline=pipeline)
        dt_ = cfg.model_dtype
        sac = SAC(cfg, VisualActor(act_dim=act_dim, dtype=dt_),
                  VisualDoubleCritic(dtype=dt_), act_dim)
        state = sac.init_state(
            jax.random.key(0),
            MultiObservation(
                features=jnp.zeros((feat,)), frame=jnp.zeros(frame, jnp.uint8)
            ),
        )
        buf = init_visual_replay_buffer(capacity, feat, frame, act_dim)
        buf = jax.jit(push, donate_argnums=(0,))(buf, chunk(2, 2000))
        burst_fn = jax.jit(
            sac.update_burst, static_argnums=(3,), donate_argnums=(0, 1)
        )
        state, buf, m = burst_fn(state, buf, chunk(3), burst)  # compile
        drain(m["loss_q"])

        def run(n_bursts):
            nonlocal state, buf
            chunks = [chunk(10 + i) for i in range(n_bursts)]
            for c in chunks:
                drain(jax.tree_util.tree_reduce(
                    lambda a, leaf: a + jnp.sum(leaf, dtype=jnp.float32),
                    c, jnp.float32(0.0),
                ))
            t0 = time.perf_counter()
            for c in chunks:
                state, buf, m = burst_fn(state, buf, c, burst)
            drain(m["loss_q"])
            return n_bursts * burst / (time.perf_counter() - t0)

        sps = run(2)  # calibration
        if burst * 20 / sps < (budget_s - (time.time() - t_start)):
            sps = run(20)
        return sps

    sps = measure(batch, "float32")
    out["grad_steps_per_sec"] = round(sps, 1)
    out["examples_per_sec"] = round(sps * batch, 0)
    out.update(mfu_metrics(
        sps, jax.devices()[0].device_kind,
        flops=visual_flops_per_step(feat, frame, act_dim, batch),
    ))
    log_point("visual_points", dict(out.get("geometry", {}),
                                    dtype="float32", pipeline="reference",
                                    grad_steps_per_sec=out["grad_steps_per_sec"]))

    # The mixed-precision + fused-pixel-pipeline training path (the
    # visual-MFU tentpole, docs/SCALING.md "Mixed precision & the
    # pixel pipeline"): the same stack at compute_dtype=bfloat16, then
    # bf16 with pixel_pipeline="fused" (replay-gather -> uint8 decode
    # -> cast fused at sample time — no f32 frame batch in HBM).
    # Measured on any backend so the before/after artifact exists even
    # on the CPU fallback; the 0.2+ MFU target is a chip number.
    for variant, dtype_, pipeline in (
        ("bf16", "bfloat16", "reference"),
        ("bf16_fused", "bfloat16", "fused"),
    ):
        if time.time() - t_start > budget_s:
            out[variant] = {"error": "budget exhausted"}
            continue
        try:
            sps_v = measure(batch, dtype_, pipeline)
            out[variant] = {
                "batch": batch, "dtype": dtype_, "pipeline": pipeline,
                "grad_steps_per_sec": round(sps_v, 1),
                "examples_per_sec": round(sps_v * batch, 0),
                **mfu_metrics(
                    sps_v, jax.devices()[0].device_kind,
                    flops=visual_flops_per_step(feat, frame, act_dim, batch),
                ),
            }
            log_point("visual_points", dict(
                dtype=dtype_, pipeline=pipeline,
                grad_steps_per_sec=out[variant]["grad_steps_per_sec"],
            ))
        except Exception as e:  # noqa: BLE001 — extra point, best effort
            out[variant] = {"error": repr(e)[:200]}

    # Large-batch bf16+fused point (TPU only — a CPU fallback would
    # burn the whole budget): where the conv towers leave the
    # latency-bound regime; MFU against the CNN-aware analytic FLOPs.
    # This is the 0.18-MFU probe made the real training path.
    if jax.default_backend() == "tpu" and time.time() - t_start < budget_s:
        try:
            big = 512
            sps_big = measure(big, "bfloat16", "fused")
            out["large_batch"] = {
                "batch": big, "dtype": "bfloat16", "pipeline": "fused",
                "grad_steps_per_sec": round(sps_big, 1),
                "examples_per_sec": round(sps_big * big, 0),
                **mfu_metrics(
                    sps_big, jax.devices()[0].device_kind,
                    flops=visual_flops_per_step(feat, frame, act_dim, big),
                ),
            }
        except Exception as e:  # noqa: BLE001 — extra point, best effort
            out["large_batch"] = {"error": repr(e)[:200]}

    # Reference-style torch-CPU visual baseline at the same geometry
    # (BASELINE config 5's ratio; the flat headline has its own).
    try:
        out.update(bench_torch_visual(
            feat, frame, act_dim, batch,
            budget_s=budget_s - (time.time() - t_start) - 30,
        ))
        if out.get("torch_cpu_steps_per_sec"):
            out["vs_baseline"] = round(sps / out["torch_cpu_steps_per_sec"], 2)
    except Exception as e:  # noqa: BLE001 — ratio is best-effort
        out["torch_baseline_error"] = repr(e)
    log(f"visual burst: {out['grad_steps_per_sec']} grad-steps/s "
        f"({out['backend']}), vs torch {out.get('vs_baseline')}")
    return out


def bench_torch_visual(feat, frame, act_dim, batch, n_steps=15, budget_s=180.0):
    """Torch-CPU visual SAC gradient-step throughput at the wall-runner
    geometry (``baselines/torch_sac.py:build_torch_visual_sac`` — the
    same shared-baseline discipline as the flat headline). NCHW float
    frames, as the reference stores them. Batches are pre-generated
    OUTSIDE the clock, mirroring the JAX side's pre-drained chunks, so
    vs_baseline compares pure update cost on both sides."""
    if budget_s < 45:
        # A warmup + one timed step can take tens of seconds on a slow
        # host; starting with no budget would overrun the stage's hard
        # timeout and lose the already-measured JAX section with it.
        return {"torch_baseline_skipped": f"budget exhausted ({budget_s:.0f}s)"}

    import torch

    from torch_actor_critic_tpu.baselines import build_torch_visual_sac

    _, update = build_torch_visual_sac(feat, frame[:2], frame[2], act_dim)
    g = torch.Generator().manual_seed(0)

    def data():
        return (
            torch.randn(batch, feat, generator=g),
            torch.rand(batch, frame[2], *frame[:2], generator=g) * 255.0,
            torch.tanh(torch.randn(batch, act_dim, generator=g)),
            torch.randn(batch, generator=g),
            torch.randn(batch, feat, generator=g),
            torch.rand(batch, frame[2], *frame[:2], generator=g) * 255.0,
            torch.zeros(batch),
        )

    t_start = time.time()
    batches = [data() for _ in range(n_steps)]
    update(*data())  # warmup
    t0 = time.perf_counter()
    done = 0
    for b in batches:
        update(*b)
        done += 1
        if time.time() - t_start > budget_s:
            break
    sps = done / (time.perf_counter() - t0)
    return {"torch_cpu_steps_per_sec": round(sps, 2)}


def _measure_pool(env_name, n_envs, n_steps, parallel, warmup=None):
    """Steps/sec of one env pool configuration, plus its build time.

    Warmup steps are excluded from the clock; the pool is closed even on
    failure so worker processes never leak into later sections.
    """
    import numpy as np

    from torch_actor_critic_tpu.envs.vec_env import make_env_pool

    warmup = max(2, n_steps // 10) if warmup is None else warmup
    pool = None
    try:
        t_build = time.perf_counter()
        pool = make_env_pool(env_name, n_envs, base_seed=0, parallel=parallel)
        if parallel and type(pool).__name__ != "ParallelEnvPool":
            return {"error": "native pool unavailable"}
        pool.reset_all([10000 * i for i in range(n_envs)])
        build_s = time.perf_counter() - t_build
        rng = np.random.default_rng(0)
        actions = rng.uniform(
            -1, 1, (n_steps + warmup, n_envs, pool.act_dim)
        ).astype(np.float32)
        for a in actions[:warmup]:
            pool.step(a)
        t0 = time.perf_counter()
        for a in actions[warmup:]:
            pool.step(a)
        dt = time.perf_counter() - t0
        return {
            "n_envs": n_envs,
            "env_steps_per_sec": round(n_steps * n_envs / dt, 1),
            "ms_per_lockstep_round": round(dt / n_steps * 1e3, 2),
            "build_s": round(build_s, 1),
        }
    except Exception as e:  # noqa: BLE001 — best-effort section
        return {"error": repr(e)}
    finally:
        if pool is not None:
            pool.close()


def bench_host_envs(n_envs=4, budget_s=600.0):
    """Host env-loop throughput: native shared-memory ParallelEnvPool vs
    in-process SequentialEnvPool across the step-cost spectrum
    (VERDICT r2 missing #3 / weak #6 — the pool's target regime was
    unmeasured).

    Three regimes: sub-ms envs (Pendulum ~20us, dm cheetah ~0.12ms)
    where the ~0.7ms lockstep IPC round makes the pool LOSE — reported
    anyway, honest overhead beats a cherry-picked win; an n_envs
    scaling curve on dm cheetah showing how the loss evolves with
    worker count; and the pool's target, the composer wall-runner (ref
    ``environments/wall_runner.py:17-62``, ~175ms of physics per step),
    where workers can overlap physics — given cores to run on. The
    measured sandbox is a 1-core host, where workers physically
    serialize and the best possible outcome is parity (IPC amortized);
    ``host_cores`` is recorded and ``crossover_note`` states the
    per-core-count conclusion instead of pretending the topology away."""
    n_cores = os.cpu_count() or 1
    out = {
        "host_cores": n_cores,
        "note": (
            "pendulum/dm_cheetah are sub-ms/step so the ~0.7ms lockstep "
            "IPC round dominates and sequential wins; the wall-runner "
            "row is the pool's target regime (>~2ms physics/step). The "
            "pool needs >=2 host cores to overlap physics at all — "
            "worker processes serialize on a 1-core host."
        ),
    }
    t_start = time.time()

    def left():
        return budget_s - (time.time() - t_start)

    for env_name, env_key, n_steps in (
        ("Pendulum-v1", "pendulum", 380),
        ("dm:cheetah:run", "dm_cheetah", 100),
    ):
        for parallel in (False, True):
            name = f"{env_key}_{'parallel' if parallel else 'sequential'}"
            if left() <= 0:
                out[name] = {"error": "budget exhausted"}
                continue
            out[name] = _measure_pool(env_name, n_envs, n_steps, parallel)
            log(f"host envs {name}: {out[name]}")

    # n_envs scaling on the cheap env: per-round IPC cost vs fan-out.
    scaling = {"env": "dm:cheetah:run", "points": []}
    for n in (1, 2, 4, 8):
        if left() < 30:
            scaling["points"].append({"n_envs": n, "error": "budget exhausted"})
            continue
        scaling["points"].append({
            "n_envs": n,
            "sequential": _measure_pool("dm:cheetah:run", n, 80, False),
            "parallel": _measure_pool("dm:cheetah:run", n, 80, True),
        })
    out["scaling"] = scaling

    # The expensive-env point the pool exists for. Construction builds a
    # CMU-humanoid composer scene (~1 min per env, workers build
    # concurrently), so steps are few and the budget guard is generous.
    wall = {}
    for parallel in (True, False):
        name = "parallel" if parallel else "sequential"
        if left() < (60 if parallel else 100):
            wall[name] = {"error": "budget exhausted"}
            continue
        wall[name] = _measure_pool(
            "DeepMindWallRunner-v0", n_envs, 24, parallel, warmup=4
        )
        log(f"host envs wall_runner_{name}: {wall[name]}")
    out["wall_runner"] = wall

    seq = wall.get("sequential", {}).get("env_steps_per_sec")
    par = wall.get("parallel", {}).get("env_steps_per_sec")
    if seq and par:
        if n_cores == 1:
            # Explicit negative result (VERDICT r2 item 3): process
            # parallelism cannot beat sequential stepping without a
            # second core. On the heavy env the IPC round is fully
            # amortized (ratio ~1.0); on sub-ms envs it dominates. The
            # pool stays OFF by default (config.parallel_envs=False).
            out["crossover_note"] = (
                f"1-core host: wall-runner ({n_envs} envs) parallel {par} "
                f"vs sequential {seq} env-steps/s ({par / seq:.2f}x) — "
                "workers serialize physics, so parity-within-noise is the "
                "ceiling here (measured 0.94x-1.24x across runs); the "
                "pool targets >=2-core hosts with >~2ms/step physics, "
                "and is off by default"
            )
        else:
            out["crossover_note"] = (
                f"wall-runner ({n_envs} envs, {n_cores} cores): parallel "
                f"{par} vs sequential {seq} env-steps/s ({par / seq:.2f}x); "
                "the pool pays off once per-step physics exceeds the ~2ms "
                "IPC round, loses below it (see sub-ms rows)"
            )
    return out


def bench_serving(budget_s=180.0, n_threads=16, requests_per_thread=150):
    """Policy-serving throughput through the real serve/ stack: an
    in-process :class:`PolicyClient` fan-out of concurrent single-obs
    requests through the micro-batcher and the bucketed jitted forward
    (exactly the path the HTTP frontend parks on). Reports
    requests/sec, latency percentiles and mean batch occupancy — the
    numbers docs/SERVING.md's tuning section is about."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve import (
        MicroBatcher,
        ModelRegistry,
        PolicyClient,
    )

    t_start = time.time()
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN)
    params = actor.init(
        jax.random.key(0), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    obs_spec = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)
    registry = ModelRegistry()
    max_batch = 64
    registry.register(
        "default", actor, obs_spec, params=params, max_batch=max_batch,
    )  # warmup compiles every bucket before the clock starts
    out = {
        "obs_dim": OBS_DIM, "act_dim": ACT_DIM,
        "hidden": list(HIDDEN), "max_batch": max_batch,
        "n_client_threads": n_threads,
        "backend": jax.default_backend(),
    }
    rng = np.random.default_rng(0)
    all_obs = rng.standard_normal((n_threads, OBS_DIM)).astype(np.float32)
    errors = []

    with MicroBatcher(registry, max_batch=max_batch, max_wait_ms=2.0) as mb:
        client = PolicyClient(registry, mb)

        def worker(i):
            try:
                for _ in range(requests_per_thread):
                    client.act(all_obs[i], deterministic=True)
                    if time.time() - t_start > budget_s:
                        return
            except Exception as e:  # noqa: BLE001 — recorded, not raised
                errors.append(repr(e)[:200])

        # a short rinse so the timed window starts steady-state
        client.act(all_obs[0], deterministic=True)
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_threads)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=budget_s + 60)
        elapsed = time.perf_counter() - t0
        snap = mb.metrics.snapshot()

    done = snap["responses_total"] - 1  # minus the rinse request
    out.update({
        "requests": done,
        "requests_per_sec": round(done / elapsed, 1),
        "p50_ms": snap.get("p50_ms"),
        "p95_ms": snap.get("p95_ms"),
        "p99_ms": snap.get("p99_ms"),
        "mean_batch_occupancy": snap.get("mean_batch_occupancy"),
        "mean_rows_per_batch": snap.get("mean_rows_per_batch"),
        "batches_total": snap["batches_total"],
    })
    if errors:
        out["errors"] = errors[:5]
    log(f"serving: {out['requests_per_sec']} req/s, "
        f"p50 {out['p50_ms']}ms p99 {out['p99_ms']}ms, "
        f"occupancy {out['mean_batch_occupancy']}")
    return out


def bench_overload(budget_s=180.0, capacity=64):
    """Overload behavior at 2x capacity (docs/SERVING.md "Overload &
    degradation"): calibrate the stack's saturated service rate, then
    offer twice that for a fixed window with a bounded queue and
    per-request deadlines, and record what admission control did —
    goodput (accepted AND answered per second), shed rate and
    breakdown, queue-bound compliance, and tail latency under
    overload. The acceptance story: goodput should hold near the
    calibrated service rate while the excess is rejected with
    structured 429/503s, instead of every request getting slower
    forever (the unbounded-queue failure mode this layer replaced)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.resilience.faultinject import flood
    from torch_actor_critic_tpu.serve import (
        MicroBatcher,
        ModelRegistry,
        ShedError,
    )

    t_start = time.time()
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN)
    params = actor.init(
        jax.random.key(0), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    obs_spec = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)
    registry = ModelRegistry()
    max_batch = 64
    registry.register(
        "default", actor, obs_spec, params=params, max_batch=max_batch,
    )
    obs = np.ones((OBS_DIM,), np.float32)
    out = {
        "capacity": capacity, "max_batch": max_batch,
        "backend": jax.default_backend(),
    }

    with MicroBatcher(
        registry, max_batch=max_batch, max_wait_ms=2.0, capacity=capacity
    ) as mb:
        # Calibration: closed-loop saturation from a small herd gives
        # the achievable service rate (requests/s) for 1-row requests.
        cal_stop = threading.Event()
        cal_done = [0] * 8

        def cal_worker(i):
            while not cal_stop.is_set():
                mb.act(obs, timeout=30.0)
                cal_done[i] += 1

        cal_threads = [
            threading.Thread(target=cal_worker, args=(i,))
            for i in range(len(cal_done))
        ]
        t0 = time.perf_counter()
        for th in cal_threads:
            th.start()
        cal_window = min(10.0, budget_s / 6)
        time.sleep(cal_window)
        cal_stop.set()
        for th in cal_threads:
            th.join(timeout=30.0)
        service_rate = sum(cal_done) / (time.perf_counter() - t0)
        out["service_rate_rps"] = round(service_rate, 1)

        # Overload window: offer 2x the calibrated rate, paced
        # open-loop across a thread herd, each request carrying a
        # deadline so the infeasible/expired paths are exercised too.
        offered_rate = 2.0 * max(service_rate, 1.0)
        n_threads = 16
        window_s = min(20.0, max(5.0, budget_s - (time.time() - t_start) - 30))
        interval = n_threads / offered_rate
        futures, sheds = [], []
        flood_lock = threading.Lock()
        depth_max = [0]
        stop = threading.Event()

        def sampler():
            while not stop.is_set():
                depth_max[0] = max(depth_max[0], mb.queue_depth())
                time.sleep(0.002)

        def offer_worker(i):
            t_next = time.perf_counter() + (i / n_threads) * interval
            t_end = time.perf_counter() + window_s
            local_f, local_s = [], []
            while time.perf_counter() < t_end:
                delay = t_next - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                t_next += interval
                f, s = flood(mb.submit, obs, 1, deadline_s=0.5)
                local_f += f
                local_s += s
            with flood_lock:
                futures.extend(local_f)
                sheds.extend(local_s)

        smp = threading.Thread(target=sampler, daemon=True)
        smp.start()
        workers = [
            threading.Thread(target=offer_worker, args=(i,))
            for i in range(n_threads)
        ]
        t0 = time.perf_counter()
        for th in workers:
            th.start()
        for th in workers:
            th.join(timeout=window_s + 60)
        answered, expired = 0, 0
        for f in futures:
            try:
                f.result(timeout=60)
                answered += 1
            except ShedError:
                expired += 1
        elapsed = time.perf_counter() - t0
        stop.set()
        snap = mb.metrics.snapshot()

    offered = len(futures) + len(sheds)
    out.update({
        "offered_rate_rps": round(offered / elapsed, 1),
        "target_offered_rate_rps": round(offered_rate, 1),
        "goodput_rps": round(answered / elapsed, 1),
        "answered": answered,
        "shed_submit": len(sheds),
        "shed_expired": expired,
        "shed_fraction": round((len(sheds) + expired) / max(offered, 1), 4),
        "shed_by_reason": snap["shed_by_reason"],
        "max_queue_depth": depth_max[0],
        "queue_bound_held": depth_max[0] <= capacity,
        "p50_ms": snap.get("p50_ms"),
        "p99_ms": snap.get("p99_ms"),
    })
    registry.close()
    log(f"overload: offered {out['offered_rate_rps']} rps (2x capacity "
        f"{out['service_rate_rps']}), goodput {out['goodput_rps']} rps, "
        f"shed {out['shed_fraction'] * 100:.1f}%, max queue depth "
        f"{out['max_queue_depth']}/{capacity}")
    return out


def bench_fleet(budget_s=300.0, service_ms=8.0, replica_counts=(1, 2, 4)):
    """Fleet serving scale-out (docs/SERVING.md "Fleet"): aggregate
    goodput + tail latency vs engine-replica count through the REAL
    EngineFleet (per-device engines, least-loaded dispatch, shared
    admission), plus continuous-vs-group batching p50 at low offered
    load.

    The engine forward is pinned to a fixed simulated service time
    (``service_ms`` sleep around the real jitted forward): on the
    1-core CPU bench host real forwards cannot scale past one core, so
    the stage measures what actually matters and transfers to real
    hardware — whether the fleet's dispatch plane OVERLAPS N engines'
    service times (on a TPU host each replica's forward runs on its
    own chip; the host-side dispatch path benched here is identical).
    Scaling ~N in ``scaling_vs_1`` means the dispatcher, shared
    admission and per-replica queues add no serialization."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve import (
        EngineFleet,
        MicroBatcher,
        ModelRegistry,
        ServeMetrics,
    )

    t_start = time.time()
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN)
    params = actor.init(
        jax.random.key(0), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    obs_spec = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)
    obs = np.ones((OBS_DIM,), np.float32)
    # Small per-forward capacity (2 rows x service_ms) so ONE replica
    # saturates well below the client herd's closed-loop offer rate —
    # otherwise a single replica absorbs the whole herd and scaling
    # measures the clients, not the fleet.
    max_batch = 2
    service_s = service_ms / 1e3
    out = {
        "simulated_service_ms": service_ms,
        "max_batch": max_batch,
        "backend": jax.default_backend(),
        "local_devices": len(jax.local_devices()),
        "replicas": {},
    }

    def slow_engines(fleet):
        """Pin each replica engine's forward to the simulated service
        time (the sleep releases the GIL, so replicas overlap exactly
        as N real devices would)."""
        for rep in fleet._replicas:
            engine, _, _ = rep.registry.acquire("default")
            real_act = engine.act

            def slow_act(*a, _real=real_act, **k):
                time.sleep(service_s)
                return _real(*a, **k)

            engine.act = slow_act

    def herd_window(act_fn, n_threads, window_s):
        """Closed-loop saturation: goodput over a fixed window."""
        stop = threading.Event()
        done = [0] * n_threads
        errors = []

        def worker(i):
            while not stop.is_set():
                try:
                    act_fn(obs)
                    done[i] += 1
                except Exception as e:  # noqa: BLE001 — recorded
                    errors.append(repr(e)[:200])
                    return
        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_threads)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        time.sleep(window_s)
        stop.set()
        for th in threads:
            th.join(timeout=60.0)
        return sum(done), time.perf_counter() - t0, errors

    n_threads = 32
    window_s = min(6.0, budget_s / 8)
    goodput_by_n = {}
    for n in replica_counts:
        if n > len(jax.local_devices()):
            out["replicas"][str(n)] = {
                "skipped": f"only {len(jax.local_devices())} local devices"
            }
            continue
        registry = ModelRegistry()
        registry.register(
            "default", actor, obs_spec, params=params,
            max_batch=max_batch,
        )
        metrics = ServeMetrics()
        with EngineFleet(
            registry, devices=n, max_batch=max_batch, max_wait_ms=1.0,
            metrics=metrics, capacity=1024,
        ) as fleet:
            fleet.warmup()
            slow_engines(fleet)
            fleet.act(obs, timeout=30.0)  # rinse
            answered, elapsed, errors = herd_window(
                lambda o: fleet.act(o, timeout=30.0), n_threads, window_s
            )
            snap = metrics.snapshot()
            entry = {
                "goodput_rps": round(answered / elapsed, 1),
                "p50_ms": snap.get("p50_ms"),
                "p99_ms": snap.get("p99_ms"),
                "mean_batch_occupancy": snap.get("mean_batch_occupancy"),
                "dispatch_share": [
                    s["dispatched_total"] for s in fleet.replica_stats()
                ],
            }
            if errors:
                entry["errors"] = errors[:3]
            goodput_by_n[n] = answered / elapsed
            out["replicas"][str(n)] = entry
            log(f"fleet x{n}: {entry['goodput_rps']} rps, "
                f"p99 {entry['p99_ms']}ms, "
                f"dispatch {entry['dispatch_share']}")
        registry.close()
    if 1 in goodput_by_n:
        out["scaling_vs_1"] = {
            str(n): round(goodput_by_n[n] / goodput_by_n[1], 2)
            for n in goodput_by_n if n != 1
        }

    # Continuous vs group batching at LOW offered load (single
    # replica): group mode holds a lone request max_wait_ms hoping for
    # company; continuous dispatches it the moment the engine is free.
    # The acceptance bar is continuous p50 <= group p50 here.
    max_wait_ms = 10.0
    paced_interval = 0.025  # ~40 rps offered, far below service rate
    low_load = {}
    for mode in ("group", "continuous"):
        if time.time() - t_start > budget_s - 15:
            break
        registry = ModelRegistry()
        registry.register(
            "default", actor, obs_spec, params=params,
            max_batch=max_batch,
        )
        metrics = ServeMetrics()
        with MicroBatcher(
            registry, max_batch=max_batch, max_wait_ms=max_wait_ms,
            metrics=metrics, mode=mode,
        ) as mb:
            engine, _, _ = registry.acquire("default")
            real_act = engine.act

            def slow_act(*a, _real=real_act, **k):
                time.sleep(service_s)
                return _real(*a, **k)

            engine.act = slow_act
            mb.act(obs, timeout=30.0)  # rinse
            t_end = time.perf_counter() + min(4.0, budget_s / 10)
            while time.perf_counter() < t_end:
                mb.act(obs, timeout=30.0)
                time.sleep(paced_interval)
            low_load[mode] = metrics.snapshot().get("p50_ms")
        registry.close()
    out["low_load_p50_ms"] = dict(
        low_load, max_wait_ms=max_wait_ms,
        offered_rps=round(1.0 / paced_interval, 1),
    )
    if len(low_load) == 2:
        log(f"fleet low-load p50: group {low_load['group']}ms vs "
            f"continuous {low_load['continuous']}ms")
    return out


def bench_sharded_serving(
    budget_s=180.0,
    submeshes=((1, 1), (2, 1), (2, 2)),
    precisions=("f32", "bf16", "int8"),
):
    """Sub-mesh serving sweep (docs/SERVING.md "Sharded serving &
    precision tiers"): goodput/p99 through the REAL sub-mesh
    EngineFleet for submesh {1x1, 2x1, 2x2} x precision {f32, bf16,
    int8} on the local (forced, on CPU) devices. The CPU numbers
    measure the dispatch+placement plane — whether carving devices
    into sub-meshes or switching tiers adds host-side serialization —
    plus the per-replica reload transfer bytes each layout actually
    moves; chip MFU deltas for the tiers are TPU artifacts
    (bench.py runs on-chip pick them up via the same stage)."""
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve import (
        EngineFleet,
        ModelRegistry,
        ServeMetrics,
    )

    t_start = time.time()
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=HIDDEN)
    params = actor.init(
        jax.random.key(0), jnp.zeros((OBS_DIM,)), jax.random.key(1)
    )
    obs_spec = jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32)
    obs = np.ones((OBS_DIM,), np.float32)
    n_local = len(jax.local_devices())
    out = {
        "backend": jax.default_backend(),
        "local_devices": n_local,
        "combos": {},
    }

    def herd_window(act_fn, n_threads, window_s):
        stop = threading.Event()
        done = [0] * n_threads
        errors = []

        def worker(i):
            while not stop.is_set():
                try:
                    act_fn(obs)
                    done[i] += 1
                except Exception as e:  # noqa: BLE001 — recorded
                    errors.append(repr(e)[:200])
                    return

        threads = [
            threading.Thread(target=worker, args=(i,))
            for i in range(n_threads)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        time.sleep(window_s)
        stop.set()
        for th in threads:
            th.join(timeout=60.0)
        return sum(done), time.perf_counter() - t0, errors

    n_combos = len(submeshes) * len(precisions)
    window_s = max(1.0, min(3.0, budget_s / (n_combos * 3)))
    for tp, fsdp in submeshes:
        for precision in precisions:
            name = f"{tp}x{fsdp}_{precision}"
            if time.time() - t_start > budget_s - window_s - 5:
                out["combos"][name] = {"skipped": "stage budget"}
                continue
            per = tp * fsdp
            if per > n_local:
                out["combos"][name] = {
                    "skipped": f"needs {per} of {n_local} devices"
                }
                continue
            devices = jax.local_devices()[: (n_local // per) * per]
            registry = ModelRegistry()
            registry.register(
                "default", actor, obs_spec, params=params,
                max_batch=8, warmup=False,
            )
            metrics = ServeMetrics()
            try:
                with EngineFleet(
                    registry, devices=devices, max_batch=8,
                    metrics=metrics, submesh=(tp, fsdp),
                    precision=precision, fsdp_min_bytes=0,
                ) as fleet:
                    fleet.warmup()
                    fleet.act(obs, timeout=30.0)  # rinse
                    answered, elapsed, errors = herd_window(
                        lambda o: fleet.act(o, timeout=30.0),
                        n_threads=16, window_s=window_s,
                    )
                    snap = metrics.snapshot()
                    stats = fleet.sharding_stats()
                    entry = {
                        "replicas": fleet.n_replicas,
                        "goodput_rps": round(answered / elapsed, 1),
                        "p50_ms": snap.get("p50_ms"),
                        "p99_ms": snap.get("p99_ms"),
                        "reload_transfer_bytes_per_replica": (
                            stats["per_replica"][0]["last_transfer_bytes"]
                        ),
                    }
                    if errors:
                        entry["errors"] = errors[:3]
                    out["combos"][name] = entry
                    log(
                        f"sharded {name}: {entry['replicas']} replicas, "
                        f"{entry['goodput_rps']} rps, "
                        f"p99 {entry['p99_ms']}ms, "
                        f"{entry['reload_transfer_bytes_per_replica']}B/"
                        "replica reload"
                    )
            except Exception as e:  # noqa: BLE001 — one combo's
                # failure must not void the sweep
                out["combos"][name] = {"error": repr(e)[:200]}
            finally:
                registry.close()
    return out


def bench_telemetry_overhead(budget_s=420.0):
    """Telemetry cost (docs/OBSERVABILITY.md zero-overhead contract):
    steady-state Trainer throughput with telemetry off vs on (full
    phase spans + span ring + JSONL sink + per-epoch HBM sampling) at a
    tiny CPU config, plus a recorder microbenchmark (ns per lap). The
    acceptance bar is enabled-mode within 5% of disabled-mode."""
    import tempfile

    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.telemetry import TelemetryRecorder
    from torch_actor_critic_tpu.utils.config import SACConfig

    t_start = time.time()
    out = {}

    # Recorder microbenchmark: the per-mark cost an enabled hot loop
    # pays (monotonic read + list accumulate + ring store).
    rec = TelemetryRecorder()
    n = 200_000
    t0 = time.perf_counter()
    for _ in range(n):
        rec.lap(0)
    out["lap_ns"] = round((time.perf_counter() - t0) / n * 1e9, 1)

    from torch_actor_critic_tpu.utils.tracking import Tracker

    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=4,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
    )
    # ABBA order: slow drift (CPU frequency, cache state, background
    # load) biases a plain off-then-on comparison in whichever
    # direction the drift runs; interleaving cancels it to first order.
    rates: dict = {"off": [], "grad_off": [], "on": [], "grad_on": []}
    for mode in ("off", "on", "on", "off"):
        if time.time() - t_start > budget_s:
            break
        try:
            root = tempfile.mkdtemp(prefix="bench_tm_")
            tracker = Tracker(experiment="bench", root=root)
            telem = (
                TelemetryRecorder(run_dir=tracker.run_dir)
                if mode == "on" else None
            )
            tr = Trainer(
                "Pendulum-v1", SACConfig(**tiny), mesh=make_mesh(dp=1),
                tracker=tracker, telemetry=telem,
            )
            try:
                tr.train()
            finally:
                tr.close()
            # Post-warmup epochs only (epoch 0 pays the jit compiles);
            # the accounting fix already keeps every epoch's dt free of
            # save/sentinel time, on both sides of the comparison.
            rows = tracker.metrics()[1:]
            rates[mode].extend(r["env_steps_per_sec"] for r in rows)
            rates[f"grad_{mode}"].extend(
                r["grad_steps_per_sec"] for r in rows
            )
        except Exception as e:  # noqa: BLE001 — per-run best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    # Best observed epoch per mode: scheduler hiccups only ever slow an
    # epoch down, so the max is the least-contended estimate of each
    # mode's true rate.
    for mode in ("off", "on"):
        if rates[mode]:
            out[mode] = {
                "env_steps_per_sec": round(max(rates[mode]), 1),
                "grad_steps_per_sec": round(max(rates[f"grad_{mode}"]), 1),
                "epoch_rates": [round(r, 1) for r in rates[mode]],
            }
    off = out.get("off", {}).get("env_steps_per_sec")
    on = out.get("on", {}).get("env_steps_per_sec")
    if off and on:
        out["overhead_pct"] = round((off - on) / off * 100, 2)
    log(f"telemetry overhead: {out}")
    return out


def bench_obs_overhead(budget_s=420.0):
    """Run-wide observability cost (docs/OBSERVABILITY.md "Run-wide
    plane"): steady-state Trainer throughput with the obs collector
    off vs on (scrape thread + learner source + SLO engine + obs.jsonl
    sink + per-epoch obs/ metric columns) at a tiny CPU config. Same
    ABBA discipline and 5% acceptance bar as telemetry_overhead — the
    collector lives on its own thread, so steady-state cost should be
    the learner-source snapshot plus a dict merge per epoch."""
    import tempfile

    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.tracking import Tracker

    t_start = time.time()
    out = {}
    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=4,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
    )
    # ABBA order for the same reason as telemetry_overhead: slow drift
    # biases off-then-on; interleaving cancels it to first order.
    rates: dict = {"off": [], "grad_off": [], "on": [], "grad_on": []}
    for mode in ("off", "on", "on", "off"):
        if time.time() - t_start > budget_s:
            break
        try:
            root = tempfile.mkdtemp(prefix="bench_obs_")
            tracker = Tracker(experiment="bench", root=root)
            tr = Trainer(
                "Pendulum-v1",
                SACConfig(**tiny, obs=(mode == "on"), obs_interval_s=0.5),
                mesh=make_mesh(dp=1), tracker=tracker,
            )
            try:
                tr.train()
            finally:
                tr.close()
            rows = tracker.metrics()[1:]
            rates[mode].extend(r["env_steps_per_sec"] for r in rows)
            rates[f"grad_{mode}"].extend(
                r["grad_steps_per_sec"] for r in rows
            )
        except Exception as e:  # noqa: BLE001 — per-run best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    # Max-of-post-warmup-epochs per mode (least-contended estimate),
    # matching telemetry_overhead's accounting.
    for mode in ("off", "on"):
        if rates[mode]:
            out[mode] = {
                "env_steps_per_sec": round(max(rates[mode]), 1),
                "grad_steps_per_sec": round(max(rates[f"grad_{mode}"]), 1),
                "epoch_rates": [round(r, 1) for r in rates[mode]],
            }
    off = out.get("off", {}).get("env_steps_per_sec")
    on = out.get("on", {}).get("env_steps_per_sec")
    if off and on:
        out["overhead_pct"] = round((off - on) / off * 100, 2)
    log(f"obs overhead: {out}")
    return out


def bench_elastic(budget_s=120.0, windows=600, window_s=1.0):
    """Elastic vs fixed fleet under a diurnal load curve
    (docs/RESILIENCE.md "Elasticity"): the REAL ElasticController
    drives a simulated fleet through two compressed day/night cycles
    and is scored against a fixed mean-provisioned fleet on the three
    axes the autoscaler trades — goodput, tail latency, and
    worker-seconds paid.

    Same philosophy as bench_fleet's simulated service time: the
    decision plane under test (breach -> spawn, green streak ->
    drain) is the production code path; only the workers are modeled
    (fixed per-replica service rate, carried queue, bounded backlog
    with shed), because on the 1-core bench host real workers would
    measure the host, not the controller. Simulated clock, so the
    whole curve costs milliseconds of wall time."""
    import math

    from torch_actor_critic_tpu.elastic import (
        DecisionLog,
        ElasticController,
        ElasticPolicy,
    )

    cap = 50.0          # req/s one replica serves
    base, peak = 20.0, 150.0
    period = windows / 2  # two diurnal cycles across the run

    def offered(w):
        phase = (1.0 + math.sin(2.0 * math.pi * w / period
                                - math.pi / 2.0)) / 2.0
        return base + (peak - base) * phase

    def run_config(elastic):
        sim_now = [0.0]

        class SimFleet:
            """The modeled worker plane: replicas x cap req/s, a
            carried queue bounded at one window of fleet capacity
            (beyond that requests shed, as the real admission plane
            would 503)."""

            def __init__(self, n):
                self.n = n
                self.queue = 0.0
                self.served = 0.0
                self.shed = 0.0
                self.worker_seconds = 0.0

            def replicas(self):
                return self.n

            def queue_depth(self):
                return self.queue

            def scale_out(self, reason=""):
                self.n += 1
                return {"outcome": "spawned", "worker": f"sim{self.n}"}

            def scale_in(self, reason=""):
                self.n -= 1
                return {"outcome": "draining"}

            def step(self, load):
                capacity = self.n * cap * window_s
                backlog = self.queue + load * window_s
                done = min(backlog, capacity)
                rest = backlog - done
                allowed = capacity  # one window of headroom
                self.served += done
                self.shed += max(0.0, rest - allowed)
                self.queue = min(rest, allowed)
                self.worker_seconds += self.n * window_s
                # Latency proxy: queueing delay in front of the fleet
                # plus a fixed service floor.
                wait_s = (self.queue / (self.n * cap)) if self.n else 0.0
                return 5.0 + wait_s * 1e3

        fleet = SimFleet(2)
        controller = None
        if elastic:
            controller = ElasticController(
                fleet,
                policy=ElasticPolicy(
                    min_replicas=1, max_replicas=4,
                    scale_out_cooldown_s=5.0,
                    scale_in_cooldown_s=30.0,
                    scale_in_ok_windows=10,
                ),
                log=DecisionLog(),
                clock=lambda: sim_now[0],
            )
        lat_ms = []
        breached = False
        bad = 0
        ok = 0
        for w in range(windows):
            load = offered(w)
            lat_ms.append(fleet.step(load))
            # The goodput-floor hysteresis the obs SLO engine would
            # emit: falling behind the offered load for 2 windows
            # breaches, 2 caught-up windows recover.
            behind = fleet.queue > 0.5 * fleet.n * cap * window_s
            bad = bad + 1 if behind else 0
            ok = 0 if behind else ok + 1
            events = []
            if not breached and bad >= 2:
                breached = True
                events.append({"type": "slo_breach",
                               "rule": "goodput_floor"})
            elif breached and ok >= 2:
                breached = False
                events.append({"type": "slo_recovered",
                               "rule": "goodput_floor"})
            if controller is not None:
                controller.observe_window({"slo": {"events": events}})
            sim_now[0] += window_s
        lat_ms.sort()
        total = windows * window_s
        row = {
            "goodput_rps": round(fleet.served / total, 1),
            "p99_ms": round(lat_ms[int(0.99 * (len(lat_ms) - 1))], 1),
            "worker_seconds": round(fleet.worker_seconds, 1),
            "shed_total": round(fleet.shed, 1),
            "final_replicas": fleet.n,
        }
        if controller is not None:
            snap = controller.snapshot()
            row["scale_out_total"] = snap["scale_out_total"]
            row["scale_in_total"] = snap["scale_in_total"]
        return row

    out = {
        "windows": windows,
        "window_s": window_s,
        "replica_cap_rps": cap,
        "offered_rps": {"base": base, "peak": peak},
        "fixed": run_config(elastic=False),
        "elastic": run_config(elastic=True),
    }
    log_point("elastic", dict(out["fixed"], variant="fixed"))
    log_point("elastic", dict(out["elastic"], variant="elastic"))
    log(f"elastic bench: {out}")
    return out


def bench_replay(budget_s=300.0):
    """Tiered-replay throughput (docs/REPLAY.md): the host-side costs
    the tier stack adds around the (unchanged) device ring — waterfall
    ingest with spill, task-balanced refill sampling, disk-tier chunk
    append/sample on real files, and the ``--offline`` update burst.
    All keys are ``*_per_sec`` so ``make bench-diff`` treats drops as
    regressions."""
    import shutil
    import tempfile

    import numpy as np

    from torch_actor_critic_tpu.replay import (
        DiskTier,
        TieredReplay,
        rows_count,
    )

    t_start = time.time()
    out = {}
    obs_dim, act_dim, chunk_rows = 16, 4, 256

    def mk_rows(n, seed=0):
        rng = np.random.default_rng(seed)
        return {
            "states": rng.standard_normal((n, obs_dim)).astype(np.float32),
            "next_states": rng.standard_normal(
                (n, obs_dim)
            ).astype(np.float32),
            "actions": rng.standard_normal((n, act_dim)).astype(np.float32),
            "rewards": rng.standard_normal(n).astype(np.float32),
            "done": np.zeros(n, np.float32),
        }

    # --- waterfall ingest (HBM shadow -> host, every chunk spills) ----
    tiers = TieredReplay(hbm_capacity=1024, host_capacity=8192)
    chunk = mk_rows(chunk_rows)
    tiers.ingest_rows(chunk)  # allocate rings outside the timed region
    n_chunks, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < 2.0:
        tiers.ingest_rows(chunk)
        n_chunks += 1
    dt = time.perf_counter() - t0
    out["spill_rows_per_sec"] = round(n_chunks * chunk_rows / dt, 1)
    out["conservation_ok"] = bool(tiers.conservation_holds())

    # --- refill sampling off the host tier ----------------------------
    n_draws, t0 = 0, time.perf_counter()
    while time.perf_counter() - t0 < 2.0:
        tiers.sample_refill(chunk_rows)
        n_draws += 1
    dt = time.perf_counter() - t0
    out["refill_rows_per_sec"] = round(n_draws * chunk_rows / dt, 1)

    # --- disk tier: npz chunk append + uniform sample on real files ---
    root = tempfile.mkdtemp(prefix="bench_replay_")
    try:
        disk = DiskTier(root)
        rng = np.random.default_rng(0)
        n_app, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < 2.0 and n_app < 512:
            disk.append(chunk)
            n_app += 1
        dt = time.perf_counter() - t0
        out["disk_append_rows_per_sec"] = round(n_app * chunk_rows / dt, 1)
        n_draws, t0 = 0, time.perf_counter()
        while time.perf_counter() - t0 < 2.0:
            got = disk.sample(rng, chunk_rows)
            n_draws += 1
        dt = time.perf_counter() - t0
        assert rows_count(got) == chunk_rows
        out["disk_sample_rows_per_sec"] = round(
            n_draws * chunk_rows / dt, 1
        )
        disk.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # --- offline burst (the --offline jit program) --------------------
    if time.time() - t_start < budget_s - 30:
        try:
            import jax

            from torch_actor_critic_tpu.replay.offline import (
                OfflineLearner,
                _stack_batches,
            )
            from torch_actor_critic_tpu.utils.config import SACConfig

            cfg = SACConfig(
                hidden_sizes=(64, 64), batch_size=64, offline=True,
                offline_dataset="unused", offline_steps=100,
            )
            spec = jax.ShapeDtypeStruct((obs_dim,), np.float32)
            learner = OfflineLearner(cfg, spec, act_dim)
            state = learner.init_state(jax.random.PRNGKey(0))
            data = mk_rows(4096)
            sampler = np.random.default_rng(0)
            burst = 20
            batches = _stack_batches(data, sampler, burst, cfg.batch_size)
            state, _ = learner.burst(state, batches)  # compile
            steps, t0 = 0, time.perf_counter()
            while time.perf_counter() - t0 < 10.0:
                batches = _stack_batches(
                    data, sampler, burst, cfg.batch_size
                )
                state, metrics = learner.burst(state, batches)
                steps += burst
            jax.block_until_ready(metrics)
            dt = time.perf_counter() - t0
            out["offline_grad_steps_per_sec"] = round(steps / dt, 1)
        except Exception as e:  # noqa: BLE001 — per-section best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    log(f"replay: {out}")
    return out


def bench_sanitize_overhead(budget_s=420.0):
    """Transfer-sanitizer cost (docs/ANALYSIS.md "Runtime sanitizers"):
    steady-state Trainer throughput with --sanitize off vs on at the
    tiny CPU config. The off tier must be free by construction (one
    pointer check per guarded site); the on tier's entire cost is two
    transfer-guard context entries per update window plus the explicit
    drain fetch, so BOTH sides of the comparison are held to the same
    5% bar the telemetry/diagnostics stages use."""
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.tracking import Tracker

    import tempfile

    t_start = time.time()
    out: dict = {}
    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=4,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
        save_every=1000, sentinel=False,
    )
    # ABBA order, like the telemetry/diagnostics overhead stages: slow
    # host drift cancels to first order.
    rates: dict = {"off": [], "grad_off": [], "on": [], "grad_on": []}
    for mode in ("off", "on", "on", "off"):
        if time.time() - t_start > budget_s:
            break
        try:
            root = tempfile.mkdtemp(prefix="bench_san_")
            tracker = Tracker(experiment="bench", root=root)
            tr = Trainer(
                "Pendulum-v1", SACConfig(**tiny, sanitize=mode),
                mesh=make_mesh(dp=1), tracker=tracker,
            )
            try:
                tr.train()
            finally:
                tr.close()
            rows = tracker.metrics()[1:]  # epoch 0 pays the compiles
            rates[mode].extend(r["env_steps_per_sec"] for r in rows)
            rates[f"grad_{mode}"].extend(
                r["grad_steps_per_sec"] for r in rows
            )
        except Exception as e:  # noqa: BLE001 — per-run best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    for mode in ("off", "on"):
        if rates[mode]:
            out[mode] = {
                "env_steps_per_sec": round(max(rates[mode]), 1),
                "grad_steps_per_sec": round(max(rates[f"grad_{mode}"]), 1),
                "epoch_rates": [round(r, 1) for r in rates[mode]],
            }
    off = out.get("off", {}).get("env_steps_per_sec")
    on = out.get("on", {}).get("env_steps_per_sec")
    if off and on:
        out["overhead_pct"] = round((off - on) / off * 100, 2)
    log(f"sanitize overhead: {out}")
    return out


def bench_decoupled(budget_s=420.0, max_actor_lag=4):
    """Decoupled actor/learner cost at equal config (docs/RESILIENCE.md
    "Decoupled-plane failure modes"): steady-state env-steps/s and
    grad-steps/s of the lockstep Trainer vs the DecoupledTrainer —
    every policy action through the real registry/batcher/client stack,
    transitions through the staging gate — plus the observed staleness
    distribution against ``--max-actor-lag`` (steady-state inline lag
    is exactly one publish). The delta IS the serving-plane toll on the
    act path; bench-diff picks the throughput keys up via its existing
    ``*_per_sec`` directions."""
    from torch_actor_critic_tpu.decoupled import DecoupledTrainer
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig

    t_start = time.time()
    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=4,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
        save_every=1000, sentinel=False,
    )
    out: dict = {"config": dict(tiny, max_actor_lag=max_actor_lag)}
    # ABBA order, like the telemetry/diagnostics overhead stages: slow
    # host drift cancels to first order.
    rates: dict = {m: [] for m in (
        "lockstep", "grad_lockstep", "decoupled", "grad_decoupled",
    )}
    lag_snap = None
    for mode in ("lockstep", "decoupled", "decoupled", "lockstep"):
        if time.time() - t_start > budget_s:
            break
        try:
            if mode == "decoupled":
                cfg = SACConfig(
                    **tiny, decoupled=True, max_actor_lag=max_actor_lag
                )
                tr = DecoupledTrainer(
                    "Pendulum-v1", cfg, mesh=make_mesh(dp=1), seed=0
                )
            else:
                tr = Trainer(
                    "Pendulum-v1", SACConfig(**tiny),
                    mesh=make_mesh(dp=1), seed=0,
                )
            epoch_rates, epoch_grad = [], []
            real_hook = tr._epoch_boundary_hook

            def hook(e, ok, saved, metrics, rec, _real=real_hook):
                _real(e, ok, saved, metrics, rec)
                epoch_rates.append(metrics["env_steps_per_sec"])
                epoch_grad.append(metrics["grad_steps_per_sec"])

            tr._epoch_boundary_hook = hook
            try:
                tr.train()
                if mode == "decoupled":
                    lag_snap = tr.staging.snapshot()["actor_lag"]
            finally:
                tr.close()
            # Post-warmup epochs only (epoch 0 pays the jit compiles).
            rates[mode].extend(epoch_rates[1:])
            rates[f"grad_{mode}"].extend(epoch_grad[1:])
        except Exception as e:  # noqa: BLE001 — per-run best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    for mode in ("lockstep", "decoupled"):
        if rates[mode]:
            out[f"{mode}_env_steps_per_sec"] = round(max(rates[mode]), 1)
            out[f"{mode}_grad_steps_per_sec"] = round(
                max(rates[f"grad_{mode}"]), 1
            )
    a = out.get("lockstep_env_steps_per_sec")
    b = out.get("decoupled_env_steps_per_sec")
    if a and b:
        out["decoupling_overhead_pct"] = round((a - b) / a * 100, 2)
    if lag_snap is not None:
        out["actor_lag"] = lag_snap
        out["max_actor_lag"] = max_actor_lag
        out["lag_bounded"] = (
            lag_snap.get("actor_lag_max", 0.0) <= max_actor_lag
        )
    log(f"decoupled: {out}")
    return out


def bench_actor_fleet(budget_s=240.0, sizes=(1, 2, 4), max_actor_lag=4):
    """Actor-fleet scaling (docs/RESILIENCE.md "Decoupled-plane failure
    modes"): learner throughput and staleness as ``--actors N`` fleet
    actors feed the staging buffer over the real networked transport
    (HTTP push, per-actor seq dedup). Actors run on threads through the
    exact ``_actor_loop`` the subprocess shim runs — same wire path,
    same heartbeats, without charging each sweep point a fresh jax
    import — so the curve isolates the transport + contention cost.
    bench-diff picks up the per-size ``*_per_sec`` keys."""
    import threading

    from torch_actor_critic_tpu.decoupled import FleetTrainer
    from torch_actor_critic_tpu.decoupled.fleet import _actor_loop
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.utils.config import SACConfig

    t_start = time.time()
    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=3,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
        save_every=1000, sentinel=False,
    )
    out: dict = {
        "config": dict(tiny, max_actor_lag=max_actor_lag),
        "sizes": list(sizes),
    }

    class _ThreadProc:
        _pid = iter(range(2 ** 24, 2 ** 25))

        def __init__(self, body):
            self.pid = next(self._pid)
            self.exitcode = None
            self.stop = threading.Event()
            self._t = threading.Thread(
                target=body, args=(self.stop,), daemon=True
            )
            self._t.start()

        def is_alive(self):
            return self._t.is_alive()

        def join(self, timeout=None):
            self.stop.set()
            self._t.join(timeout)

    for n in sizes:
        if time.time() - t_start > budget_s:
            out.setdefault("skipped_sizes", []).append(n)
            log(f"actor_fleet: budget exhausted, skipping actors={n}")
            continue
        try:
            cfg = SACConfig(
                **tiny, actors=n, staging_policy="shed",
                max_actor_lag=max_actor_lag, heartbeat_timeout_s=30.0,
            )
            holder: dict = {}

            def spawn(aid, inc, _h=holder):
                return _ThreadProc(lambda stop: _actor_loop(
                    aid, inc, _h["tr"].transport.address,
                    "Pendulum-v1", 1, 3000 + 10 * aid + inc, stop,
                    options={"heartbeat_interval_s": 0.5,
                             "push_retry_s": 1.0},
                ))

            tr = FleetTrainer(
                "Pendulum-v1", cfg, mesh=make_mesh(dp=1), seed=0,
                spawn=spawn,
            )
            holder["tr"] = tr
            epoch_rates, epoch_grad = [], []
            real_hook = tr._epoch_boundary_hook

            def hook(e, ok, saved, metrics, rec, _real=real_hook):
                _real(e, ok, saved, metrics, rec)
                epoch_rates.append(metrics["env_steps_per_sec"])
                epoch_grad.append(metrics["grad_steps_per_sec"])

            tr._epoch_boundary_hook = hook
            try:
                tr.train()
                lag = tr.staging.snapshot()["actor_lag"]
                tsnap = tr.transport.snapshot()
                conserved = tr.staging.conservation_holds()
            finally:
                tr.close()
            # Post-warmup epochs only (epoch 0 pays the jit compiles).
            out[f"actors{n}_env_steps_per_sec"] = round(
                max(epoch_rates[1:] or epoch_rates), 1
            )
            out[f"actors{n}_grad_steps_per_sec"] = round(
                max(epoch_grad[1:] or epoch_grad), 1
            )
            out[f"actors{n}_lag"] = lag
            out[f"actors{n}_transport_accepted"] = tsnap[
                "accepted_total"
            ]
            out[f"actors{n}_conserved"] = bool(conserved)
        except Exception as e:  # noqa: BLE001 — per-size best effort
            out.setdefault("errors", []).append(
                f"actors={n}: {e!r}"[:200]
            )
    log(f"actor_fleet: {out}")
    return out


def bench_coldstart(budget_s=420.0, trials=2):
    """Cold-start latency (docs/SERVING.md "Cold start & warm-start
    bundles"): time-to-first-act of a FRESH ``serve.py`` worker process
    without vs with a warm-start bundle (aot/bundle.py) and its
    pre-populated persistent compilation cache. Each point spawns the
    real operator CLI against a real checkpoint and times
    spawn -> ready (startup JSON line) and spawn -> first completed
    ``/act`` round-trip; the bundle rows read ``/metrics`` back to pin
    the serve-plane compile counters (``live_compiles`` must be 0 when
    the bundle loads). The ``*_ms`` keys ride bench-diff's existing
    lower-is-better direction; ``coldstart_speedup`` and
    ``cache_hit_rate`` are higher-better."""
    import shutil
    import tempfile
    from urllib import request as urlreq

    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.aot import emit_bundle
    from torch_actor_critic_tpu.models import Actor, DoubleCritic
    from torch_actor_critic_tpu.sac import SAC
    from torch_actor_critic_tpu.utils.checkpoint import Checkpointer
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.procenv import cpu_env

    if jax.default_backend() != "cpu":
        # One process for each chip: this stage builds its checkpoint
        # and bundle in-process and then spawns serve.py workers, which
        # would need the chip this process still holds.
        raise RuntimeError(
            "the coldstart stage holds the backend while it starts "
            "serve.py workers; it runs on the CPU only (JAX_PLATFORMS="
            "cpu) until the cell matrix replaces it (ROADMAP S0)"
        )
    t_start = time.time()
    max_batch = 8
    tmp = tempfile.mkdtemp(prefix="bench_coldstart_")
    ckpt_dir = os.path.join(tmp, "ckpts")
    cfg = SACConfig(hidden_sizes=(32, 32))
    sac = SAC(cfg, Actor(act_dim=ACT_DIM, hidden_sizes=(32, 32)),
              DoubleCritic(hidden_sizes=(32, 32)), ACT_DIM)
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    ck = Checkpointer(ckpt_dir, save_buffer=False)
    ck.save(0, state, extra={"config": cfg.to_json()}, wait=True)
    ck.close()

    t0 = time.time()
    emit_bundle(
        ckpt_dir, sac.actor_def,
        jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32),
        jax.device_get(state.actor_params), max_batch=max_batch,
    )
    out: dict = {
        "config": {"hidden": [32, 32], "max_batch": max_batch,
                   "trials": trials},
        "bundle_build_s": round(time.time() - t0, 2),
    }

    repo = os.path.dirname(os.path.abspath(__file__))
    # Workers come up on the CPU like this process (see the refusal at
    # the top): the bundle fingerprint pins both to one platform.
    env = cpu_env()
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )

    def measure(extra, label):
        """Spawn one fresh worker; time ready + first /act; read the
        compile counters back; always reap the subprocess."""
        argv = [
            sys.executable, os.path.join(repo, "serve.py"),
            "--ckpt-dir", ckpt_dir,
            "--obs-dim", str(OBS_DIM), "--act-dim", str(ACT_DIM),
            "--port", "0", "--max-batch", str(max_batch),
            "--max-wait-ms", "2",
        ] + extra
        t_spawn = time.time()
        proc = subprocess.Popen(
            argv, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, cwd=repo,
        )
        try:
            address, deadline = None, time.time() + 240
            while time.time() < deadline:
                line = proc.stdout.readline()
                if not line:
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"{label}: worker died rc={proc.returncode}"
                        )
                    time.sleep(0.05)
                    continue
                if line.startswith("{"):
                    try:
                        address = json.loads(line)["serving"]
                        break
                    except (json.JSONDecodeError, KeyError):
                        continue
            if address is None:
                raise RuntimeError(f"{label}: worker never became ready")
            ready_s = time.time() - t_spawn
            req = urlreq.Request(
                address + "/act",
                data=json.dumps(
                    {"obs": [0.0] * OBS_DIM, "deterministic": True}
                ).encode(),
                headers={"Content-Type": "application/json"},
            )
            urlreq.urlopen(req, timeout=60).read()
            first_act_s = time.time() - t_spawn
            met = json.loads(
                urlreq.urlopen(address + "/metrics", timeout=30).read()
            )
            xla = met.get("xla", {})
            row = {
                "ready_ms": round(ready_s * 1e3, 1),
                "first_act_ms": round(first_act_s * 1e3, 1),
                "live_compiles": met.get("live_compiles"),
                "bundle_compiles": met.get("bundle_compiles"),
                "warmup_compiles": xla.get("warmup_compiles"),
                "bundle_load_compiles": xla.get("bundle_load_compiles"),
                "bundle_hits": xla.get("bundle_hits"),
                "bundle_rejected": xla.get("bundle_rejected"),
                "cache_hits": xla.get("cache_hits_total"),
                "cache_misses": xla.get("cache_misses_total"),
            }
            hits, misses = row["cache_hits"], row["cache_misses"]
            if hits is not None and misses is not None and hits + misses:
                row["cache_hit_rate"] = round(hits / (hits + misses), 3)
            return row
        finally:
            proc.terminate()
            try:
                proc.communicate(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()

    # ABBA order like the overhead stages: host drift (page cache,
    # thermal) cancels to first order across the cold/warm pairs.
    rows: dict = {"cold": [], "warm": []}
    for label in (["cold", "warm", "warm", "cold"] * trials)[: 2 * trials]:
        if (time.time() - t_start > budget_s
                and rows["cold"] and rows["warm"]):
            break
        extra = ["--warm-start", "auto"] if label == "warm" else []
        try:
            row = measure(extra, label)
            rows[label].append(row)
            log_point("coldstart", dict(row, variant=label))
        except Exception as e:  # noqa: BLE001 — per-trial best effort
            out.setdefault("errors", []).append(f"{label}: {e!r}"[:200])
    shutil.rmtree(tmp, ignore_errors=True)

    best_cold = best_warm = None
    if rows["cold"]:
        best_cold = min(rows["cold"], key=lambda r: r["first_act_ms"])
        out["cold"] = best_cold
        out["cold_first_act_ms"] = best_cold["first_act_ms"]
    if rows["warm"]:
        best_warm = min(rows["warm"], key=lambda r: r["first_act_ms"])
        out["warm"] = best_warm
        out["warm_first_act_ms"] = best_warm["first_act_ms"]
        out["warm_live_compiles"] = best_warm["live_compiles"]
        if best_warm.get("cache_hit_rate") is not None:
            out["cache_hit_rate"] = best_warm["cache_hit_rate"]
    if best_cold and best_warm:
        out["coldstart_speedup"] = round(
            best_cold["first_act_ms"]
            / max(best_warm["first_act_ms"], 1e-9), 2
        )
        # The acceptance pin, recorded in the artifact itself: a fresh
        # worker answering its first /act off the bundle paid ZERO live
        # compiles (and really loaded the bundle — not the fallback).
        out["zero_live_compiles_with_bundle"] = bool(
            best_warm["live_compiles"] == 0
            and (best_warm["bundle_compiles"] or 0) > 0
        )
    log(f"coldstart: {out}")
    return out


def bench_diagnostics_overhead(budget_s=540.0):
    """Learning-health diagnostics cost (docs/OBSERVABILITY.md
    "Learning-health diagnostics"): steady-state Trainer throughput at
    each tier — off (parity), light (scalar grad/Q/saturation
    reductions fused into the burst) and full (light + the on-device
    TD-error histogram) — on the tiny CPU config. Acceptance bar:
    `light` within 5% of `off` (same bar as `telemetry_overhead`)."""
    import tempfile

    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.tracking import Tracker

    t_start = time.time()
    out = {}
    tiny = dict(
        hidden_sizes=(32, 32), batch_size=32, epochs=4,
        steps_per_epoch=400, start_steps=50, update_after=50,
        update_every=50, buffer_size=5000, max_ep_len=200,
    )
    # ABBA-ordered tiers (off..full then mirrored) so slow host drift
    # cancels to first order, exactly like the telemetry stage.
    rates: dict = {
        m: [] for tier in ("off", "light", "full")
        for m in (tier, f"grad_{tier}")
    }
    for tier in ("off", "light", "full", "full", "light", "off"):
        if time.time() - t_start > budget_s:
            break
        try:
            root = tempfile.mkdtemp(prefix="bench_diag_")
            tracker = Tracker(experiment="bench", root=root)
            tr = Trainer(
                "Pendulum-v1", SACConfig(**tiny, diagnostics=tier),
                mesh=make_mesh(dp=1), tracker=tracker,
            )
            try:
                tr.train()
            finally:
                tr.close()
            rows = tracker.metrics()[1:]  # post-warmup epochs only
            rates[tier].extend(r["env_steps_per_sec"] for r in rows)
            rates[f"grad_{tier}"].extend(
                r["grad_steps_per_sec"] for r in rows
            )
        except Exception as e:  # noqa: BLE001 — per-run best effort
            out.setdefault("errors", []).append(repr(e)[:200])
    # Best observed epoch per tier (scheduler hiccups only slow epochs
    # down, so the max is the least-contended estimate).
    for tier in ("off", "light", "full"):
        if rates[tier]:
            out[tier] = {
                "env_steps_per_sec": round(max(rates[tier]), 1),
                "grad_steps_per_sec": round(max(rates[f"grad_{tier}"]), 1),
                "epoch_rates": [round(r, 1) for r in rates[tier]],
            }
    off = out.get("off", {}).get("env_steps_per_sec")
    for tier in ("light", "full"):
        on = out.get(tier, {}).get("env_steps_per_sec")
        if off and on:
            out[f"overhead_{tier}_pct"] = round((off - on) / off * 100, 2)
    log(f"diagnostics overhead: {out}")
    return out


def bench_torch_cpu(n_steps=300):
    """Reference-style torch-CPU SAC update, timed per gradient step
    incl. uniform replay sampling — the measured stand-in for the
    unpublished reference baseline. Same shared implementation as the
    return-parity runs (``baselines/torch_sac.py``), so the throughput
    and return baselines can never drift apart."""
    import torch

    from torch_actor_critic_tpu.baselines import build_torch_sac

    _, update = build_torch_sac(OBS_DIM, ACT_DIM, hidden=HIDDEN)

    n = 100_000
    data = {
        "s": torch.randn(n, OBS_DIM),
        "a": torch.tanh(torch.randn(n, ACT_DIM)),
        "r": torch.randn(n),
        "s2": torch.randn(n, OBS_DIM),
        "d": torch.zeros(n),
    }

    def step():
        idx = torch.randint(0, n, (BATCH,))
        update(*(data[k][idx] for k in ("s", "a", "r", "s2", "d")))

    for _ in range(20):  # warmup
        step()
    t0 = time.perf_counter()
    for _ in range(n_steps):
        step()
    return n_steps / (time.perf_counter() - t0)


def peak_flops_for(device_kind):
    from torch_actor_critic_tpu.telemetry.costmodel import (
        peak_flops_for as _peak_flops_for,
    )

    return _peak_flops_for(device_kind)


def mfu_metrics(acc_sps, device_kind, flops=None):
    """Achieved-FLOPs/MFU keys for a measured steps/sec number — shared
    by main() and the visual section so both compute these identically.
    ``flops`` defaults to the flat headline's analytic per-step cost."""
    flops = sac_flops_per_step() if flops is None else flops
    out = {
        "flops_per_step": flops,
        "achieved_flops_per_sec": round(acc_sps * flops, 0),
    }
    peak = peak_flops_for(device_kind)
    if peak:
        out["mfu"] = round(acc_sps * flops / peak, 5)
        out["peak_flops_assumed"] = peak
    return out


def _stage_headline():
    """Subprocess entry: headline (parity-config, float32) number."""
    return {"acc_sps": bench_accelerator()}


def _stage_headline_bf16():
    """Subprocess entry: the same burst with compute_dtype=bfloat16
    (MXU-native matmuls, f32 params/optimizer/losses). Its own stage so
    a bf16 hang cannot cost the already-measured f32 headline."""
    return {"acc_sps_bf16": bench_accelerator(compute_dtype="bfloat16")}


_STAGES = {
    "headline": _stage_headline,
    "headline_bf16": _stage_headline_bf16,
    # sweep/unroll/td3 budget-scale to the enforced stage timeout
    # (stage_budget) — the BENCH_r05 fix: a chip snapshot completes
    # inside --stage-timeout instead of shipping truncated artifacts.
    "sweep": lambda: {"sweep": bench_sweep(budget_s=stage_budget(600.0))},
    "sharding": lambda: {
        "sharding": bench_sharding(budget_s=stage_budget(420.0))
    },
    "unroll": lambda: {
        "burst_unroll": bench_unroll(budget_s=stage_budget(300.0))
    },
    "td3": lambda: {"td3": bench_td3(budget_s=stage_budget(300.0))},
    # Both population sub-stages share the one subprocess timeout
    # (720s in main()), so their internal budgets are trimmed to fit
    # alongside backend init + compiles.
    "population": lambda: {
        "population": bench_population(budget_s=300.0),
        # The fused sub-stage: whole Anakin epochs (acting included)
        # vmapped over the member axis, not just the update burst.
        "population_fused": bench_population_fused(budget_s=280.0),
    },
    "visual": lambda: {"visual": bench_visual(budget_s=stage_budget(300.0))},
    "serving": lambda: {"serving": bench_serving()},
    "overload": lambda: {"overload": bench_overload()},
    "fleet": lambda: {
        "fleet": bench_fleet(),
        # Sub-mesh serving sweep: submesh {1x1,2x1,2x2} x precision
        # {f32,bf16,int8} goodput/p99 + per-replica reload transfer
        # bytes, picked up by make bench-diff's goodput/_rps/_ms
        # directions.
        "fleet_sharded": bench_sharded_serving(
            budget_s=stage_budget(180.0)
        ),
    },
    "decoupled": lambda: {
        "decoupled": bench_decoupled(),
        # Actors-vs-throughput curves over the networked staging
        # transport (--actors {1,2,4}).
        "actor_fleet": bench_actor_fleet(
            budget_s=stage_budget(240.0)
        ),
    },
    # Time-to-first-act of a fresh serve.py worker with vs without a
    # warm-start bundle (aot/; docs/SERVING.md "Cold start &
    # warm-start bundles").
    "coldstart": lambda: {
        "coldstart": bench_coldstart(budget_s=stage_budget(420.0))
    },
    "host_envs": lambda: {"host_envs": bench_host_envs()},
    "telemetry_overhead": lambda: {
        "telemetry_overhead": bench_telemetry_overhead()
    },
    "obs_overhead": lambda: {"obs_overhead": bench_obs_overhead()},
    # Elastic vs fixed fleet over a simulated diurnal load curve
    # (the real ElasticController deciding; goodput/p99/worker-
    # seconds picked up by make bench-diff's direction rows).
    "elastic": lambda: {"elastic": bench_elastic()},
    "diagnostics_overhead": lambda: {
        "diagnostics_overhead": bench_diagnostics_overhead()
    },
    "sanitize_overhead": lambda: {
        "sanitize_overhead": bench_sanitize_overhead()
    },
    # Tiered-replay host-side costs + the --offline burst
    # (docs/REPLAY.md) — spill/refill/disk rows-per-sec and offline
    # grad-steps-per-sec for make bench-diff.
    "replay": lambda: {
        "replay": bench_replay(budget_s=stage_budget(300.0))
    },
    "on_device": lambda: {"on_device": bench_on_device()},
    # scenarios/ families (multi-agent / procedural / multi-task)
    # vs the pendulum baseline — ROADMAP item 3's perf evidence.
    "scenarios": lambda: {
        "scenarios": bench_scenarios(budget_s=stage_budget(300.0))
    },
    # Two sequence lengths: the O(block)-memory kernel's scaling story —
    # 4x the length = 16x the FLOPs at flat VMEM residency.
    "attention": lambda: {
        # 2k carries the block sweep (8 extra Pallas fwd+bwd compiles);
        # the budgets must fit the stage timeout (1200s) together.
        "attention": bench_attention(budget_s=780.0, t=2048,
                                     block_sweep=True),
        "attention_8k": bench_attention(budget_s=240.0, t=8192),
    },
}


def _run_stage_inprocess(name):
    """Child-process mode: run one stage and print one JSON line. A
    stage that raises takes the process down with it (non-zero exit);
    the parent records that as the stage's error."""
    print(json.dumps(_STAGES[name]()), flush=True)


def stage_timeout_override():
    """The per-stage hard-timeout override: ``--stage-timeout=SECS``
    on the CLI (or ``TAC_BENCH_STAGE_TIMEOUT`` in the env) replaces
    every stage's default timeout — BENCH_r05's sweep/unroll/td3
    deaths were opaque 900s strings because the knob did not exist."""
    for a in sys.argv[1:]:
        if a.startswith("--stage-timeout="):
            return float(a.split("=", 1)[1])
    env = os.environ.get("TAC_BENCH_STAGE_TIMEOUT")
    return float(env) if env else None


# Fraction of a stage's hard timeout its INTERNAL budget may use; the
# remainder covers backend init + the first compiles, which happen
# before any budget check can run.
_STAGE_BUDGET_FRAC = 0.7


def stage_budget(default_s: float) -> float:
    """A stage's internal time budget, scaled to the enforced timeout.

    BENCH_r05 shipped truncated sweep/unroll/td3 sections because the
    stages' internal budgets were fixed constants: under a smaller
    ``--stage-timeout`` (or where compiles eat the window) the
    parent's hard kill landed BEFORE the stage's own budget check,
    losing the final JSON line. The parent now exports the effective
    per-stage timeout (``TAC_BENCH_STAGE_BUDGET``, set in
    ``run_stage_subprocess``); stages budget against
    ``min(default, 0.7 * timeout)`` so they self-terminate — emitting
    their completed points — inside any enforced window.
    """
    env = os.environ.get("TAC_BENCH_STAGE_BUDGET")
    if not env:
        return default_s
    return min(default_s, _STAGE_BUDGET_FRAC * float(env))


def log_point(stage_key: str, entry):
    """Stream one completed per-point result to stderr as a structured
    ``[bench-point]`` line. If the parent's hard timeout kills the
    stage anyway, ``run_stage_subprocess`` reassembles these lines into
    a partial (but structured and diff-able) stage section instead of
    shipping opaque log tails."""
    print(
        "[bench-point] " + json.dumps({"stage": stage_key, "entry": entry}),
        file=sys.stderr, flush=True,
    )


def collect_points(streams) -> dict:
    """Parse ``[bench-point]`` lines out of a killed child's streams;
    returns ``{stage_key: [entries...]}``."""
    points: dict = {}
    for stream in streams:
        if not stream:
            continue
        text = (
            stream.decode(errors="replace")
            if isinstance(stream, bytes) else stream
        )
        for line in text.splitlines():
            marker = line.find("[bench-point] ")
            if marker < 0:
                continue
            try:
                rec = json.loads(line[marker + len("[bench-point] "):])
                points.setdefault(rec["stage"], []).append(rec["entry"])
            except (json.JSONDecodeError, KeyError, TypeError):
                continue
    return points


# Structured per-stage failure records accumulated across the run and
# published as the artifact's `stage_errors` key (satellite of the
# cost-attribution PR): each is {stage, error, elapsed_s, timeout_s,
# rc?, stderr_tail?, partial_output?}.
STAGE_ERRORS: list = []


def run_stage_subprocess(
    name, timeout_s, diagnostics, platform=None, stage_errors=None
):
    """Run a bench stage in a subprocess with a hard timeout: the chip
    has one owner at a time, and a stage that hangs costs its own
    window, not the run. ``platform="cpu"`` starts the child held to
    the CPU (host-side stages); otherwise it inherits the environment
    and takes the default device.

    Failures append a STRUCTURED record to ``stage_errors`` (stage
    name, elapsed, timeout, error, and the child's output tails — the
    per-point ``[bench]`` progress lines are the partial results a
    killed stage leaves behind) instead of the former opaque
    ``"timeout after 900s"`` strings merged from partial runs.
    """
    override = stage_timeout_override()
    if override is not None:
        timeout_s = override
    from torch_actor_critic_tpu.aot.cache import CACHE_DIR_ENV, cache_dir
    from torch_actor_critic_tpu.utils.procenv import cpu_env

    env = cpu_env() if platform == "cpu" else dict(os.environ)
    # Tell the child its hard window so stage_budget() can scale the
    # stage's internal budget to finish (and print its JSON) inside it.
    env["TAC_BENCH_STAGE_BUDGET"] = str(timeout_s)
    # Persistent compilation cache across stage subprocesses (each
    # stage re-jits the same burst shapes): the checkout's one cache
    # directory, handed down through the variable jax itself reads.
    env.setdefault(CACHE_DIR_ENV, cache_dir())

    def record(err, proc=None, partial=None):
        rec = {
            "stage": name,
            "error": err,
            "elapsed_s": round(time.time() - t0, 1),
            "timeout_s": timeout_s,
        }
        if proc is not None:
            rec["rc"] = proc.returncode
            if proc.stderr:
                rec["stderr_tail"] = proc.stderr[-500:]
        if partial:
            rec["partial_output"] = partial
        (stage_errors if stage_errors is not None else STAGE_ERRORS).append(
            rec
        )
        diagnostics.append({f"{name}_stage_error": err})

    t0 = time.time()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), f"--stage={name}"],
            capture_output=True, text=True, timeout=timeout_s, env=env,
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        if proc.returncode == 0 and line:
            return json.loads(line)
        record(f"exit code {proc.returncode} with no result line", proc=proc)
    except subprocess.TimeoutExpired as e:
        # The kill loses the child's final JSON line; its streamed
        # stderr progress ([bench] lines per completed point) is the
        # partial evidence that survives.
        partial = []
        for stream in (e.stdout, e.stderr):
            if stream:
                text = (
                    stream.decode(errors="replace")
                    if isinstance(stream, bytes) else stream
                )
                partial.extend(text.strip().splitlines()[-8:])
        record(f"timeout after {timeout_s:g}s", partial=partial or None)
        log(f"stage {name} timed out ({timeout_s:g}s)")
        # Per-point subdivision: reassemble the structured
        # [bench-point] lines the child streamed per completed point —
        # a killed sweep still contributes its finished rows to the
        # artifact (marked truncated), not just log tails.
        points = collect_points((e.stdout, e.stderr))
        if points:
            out = {}
            for key, entries in points.items():
                out[key] = entries
                out[f"{key}_truncated"] = True
            return out
    except Exception as e:  # noqa: BLE001
        record(repr(e))
    return None


def main():
    out = {
        "metric": "sac_grad_steps_per_sec",
        "value": None,
        "unit": "steps/sec",
        "vs_baseline": None,
    }
    diagnostics = []

    # 1. What is the default device? Asked of a subprocess, so this
    # parent never holds the chip its stages need. No accelerator, no
    # number: nothing is printed on stdout.
    info = preflight_backend()
    if info["platform"] == "cpu":
        log(
            f"no accelerator (default device: {info}); a CPU figure is "
            "not written under the device metric's name — nothing to "
            "report"
        )
        return 2
    platform = info["platform"]
    out["backend"] = platform
    out["device_kind"] = info["device_kind"]

    def stage(name, timeout_s, on=None):
        res = run_stage_subprocess(name, timeout_s, diagnostics, platform=on)
        if res:
            out.update(res)
        return res

    # 2. Accelerator benchmark FIRST (the number that matters).
    acc_sps = None
    res = run_stage_subprocess("headline", 600, diagnostics)
    if res:
        acc_sps = res["acc_sps"]
        out["value"] = round(acc_sps, 1)
        log(f"accelerator: {acc_sps:.1f} grad-steps/s ({platform})")
    res = run_stage_subprocess("headline_bf16", 600, diagnostics)
    if res:
        out["value_bf16"] = round(res["acc_sps_bf16"], 1)
        log(f"accelerator bf16: {out['value_bf16']} grad-steps/s")

    # 3. MFU (analytic FLOPs; negligible-elementwise approximation).
    out["flops_per_step"] = sac_flops_per_step()
    if acc_sps is not None:
        out.update(mfu_metrics(acc_sps, info["device_kind"]))

    # 4. Chip stages, one subprocess each: a hang or overrun in one
    # loses only that section's data, and each timeout covers its own
    # internal budget plus a fresh backend-init + compile. (The
    # coldstart stage is not here: it holds the backend while starting
    # workers, so it runs on the CPU only — see bench_coldstart.)
    for name, timeout_s in (
        ("sweep", 900), ("sharding", 540), ("unroll", 420), ("td3", 420),
        ("population", 720), ("on_device", 540), ("scenarios", 420),
        ("attention", 900), ("visual", 480),
        # The serving planes: batcher/queue overhead is host-side, the
        # forward rides the accelerator exactly as production serving.
        ("serving", 420), ("overload", 420), ("fleet", 420),
        ("decoupled", 900),
    ):
        stage(name, timeout_s)

    # 5. Host-side stages (env-loop throughput, instrumentation
    # overheads, tiered-replay IO): CPU work whatever the backend, so
    # each child starts held to the CPU and never touches the chip.
    for name, timeout_s in (
        ("host_envs", 900), ("telemetry_overhead", 600),
        ("obs_overhead", 600), ("elastic", 300),
        ("diagnostics_overhead", 720), ("sanitize_overhead", 600),
        ("replay", 600),
    ):
        stage(name, timeout_s, on="cpu")

    # 6. Torch-CPU baseline LAST.
    torch_sps = bench_torch_cpu()
    out["torch_cpu_steps_per_sec"] = round(torch_sps, 1)
    if acc_sps is not None:
        out["vs_baseline"] = round(acc_sps / torch_sps, 2)

    # The on-device cheetah remains a surrogate until MJX/Brax lands in
    # the image (envs/ondevice.py registry warning) — throughput
    # numbers transfer, returns do not.
    out["notes"] = {
        "on_device_cheetah": (
            "surrogate dynamics (MJX/Brax not installed); host-loop "
            "path carries return parity (PARITY.md 1M-step gate)"
        )
    }

    if diagnostics:
        out["diagnostics"] = diagnostics
    if STAGE_ERRORS:
        # Structured per-stage failures (stage, elapsed, timeout,
        # partial output) — the artifact says WHICH stage died and how
        # far it got.
        out["stage_errors"] = list(STAGE_ERRORS)
    if out["value"] is None:
        out["error"] = "no accelerator benchmark completed"

    print(json.dumps(out), flush=True)
    return 0 if out["value"] is not None and not STAGE_ERRORS else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1].startswith("--stage="):
        _run_stage_inprocess(sys.argv[1].split("=", 1)[1])
        sys.exit(0)
    sys.exit(main())
