"""Learning-health diagnostics tests (ISSUE 4 / docs/OBSERVABILITY.md).

Pins the tentpole contracts: ``off`` is a true no-op (exact historical
metric keys, diagnostics never perturb the training computation);
``light``/``full`` reductions match a NumPy reference exactly on a tiny
MLP; the suffix reduction convention holds through scan, mesh
collectives and host aggregation; dp skew catches replica state; the
drift monitor fires on scripted anomalies; and the recompilation
watchdog counts, attributes and flags compiles.
"""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torch_actor_critic_tpu.buffer import init_replay_buffer, push
from torch_actor_critic_tpu.core.types import Batch
from torch_actor_critic_tpu.diagnostics import (
    TD_HIST_GROWTH,
    TD_HIST_LO,
    DriftDetector,
    EarlyWarningMonitor,
    bucket_counts,
    get_watchdog,
    global_norm,
    make_td_histogram,
    norm_ratio,
    reduce_burst_metrics,
    reduce_metric_rows,
    reduction_for,
    replica_skew,
)
from torch_actor_critic_tpu.diagnostics.ingraph import TD_HIST_BUCKETS
from torch_actor_critic_tpu.models import Actor, DoubleCritic
from torch_actor_critic_tpu.sac import SAC, losses
from torch_actor_critic_tpu.utils.config import SACConfig

OBS_DIM, ACT_DIM = 4, 2

# The exact metric key set of a pre-diagnostics SAC update — the
# ``off``-tier parity target.
BASE_SAC_KEYS = {
    "loss_q", "loss_pi", "alpha", "q_mean", "backup_mean",
    "logp_pi", "entropy",
}


def make_sac(**overrides):
    cfg = SACConfig(hidden_sizes=(32, 32), batch_size=8, **overrides)
    actor = Actor(act_dim=ACT_DIM, hidden_sizes=cfg.hidden_sizes, act_limit=1.0)
    critic = DoubleCritic(hidden_sizes=cfg.hidden_sizes, num_qs=cfg.num_qs)
    return SAC(cfg, actor, critic, ACT_DIM)


def make_batch(key, n=8):
    ks = jax.random.split(key, 5)
    return Batch(
        states=jax.random.normal(ks[0], (n, OBS_DIM)),
        actions=jnp.tanh(jax.random.normal(ks[1], (n, ACT_DIM))),
        rewards=jax.random.normal(ks[2], (n,)),
        next_states=jax.random.normal(ks[3], (n, OBS_DIM)),
        done=(jax.random.uniform(ks[4], (n,)) < 0.2).astype(jnp.float32),
    )


# ------------------------------------------------------------- off parity


def test_config_rejects_unknown_tier():
    with pytest.raises(ValueError, match="diagnostics"):
        SACConfig(diagnostics="verbose")


def test_off_tier_keys_and_bitwise_parity_with_full():
    """`off` emits exactly the historical key set, and the diagnostics
    computation is a pure observer: the off- and full-tier updates
    produce bitwise-identical training state and common metrics from
    the same inputs."""
    sac_off = make_sac(diagnostics="off")
    sac_full = make_sac(diagnostics="full")
    state = sac_off.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))
    s_off, m_off = jax.jit(sac_off.update)(state, batch)
    s_full, m_full = jax.jit(sac_full.update)(state, batch)
    assert set(m_off) == BASE_SAC_KEYS
    assert BASE_SAC_KEYS < set(m_full)
    for k in BASE_SAC_KEYS:
        np.testing.assert_array_equal(np.asarray(m_off[k]), np.asarray(m_full[k]))
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, s_off.actor_params, s_full.actor_params
    )
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, s_off.critic_params, s_full.critic_params
    )


def test_off_tier_burst_keys_unchanged():
    sac = make_sac(diagnostics="off")
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    buf = init_replay_buffer(
        64, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM
    )
    buf = push(buf, make_batch(jax.random.key(5), n=32))
    _, _, m = jax.jit(sac.update_burst, static_argnums=(3,))(
        state, buf, make_batch(jax.random.key(6), n=10), 3
    )
    assert set(m) == BASE_SAC_KEYS
    assert all(v.shape == () for v in m.values())


# ------------------------------------------------- numpy-reference exactness


def _grads_and_key(sac, state, batch):
    """Replicate the update's internal critic grad computation (the
    frame_augment='none' parity 3-way rng split)."""
    _, key_q, _ = jax.random.split(state.rng, 3)
    grad_fn = jax.grad(losses.critic_loss, has_aux=True)
    grads, _ = grad_fn(
        state.critic_params,
        actor_apply=sac._actor_apply,
        critic_apply=sac._critic_apply,
        actor_params=state.actor_params,
        target_critic_params=state.target_critic_params,
        batch=batch,
        key=key_q,
        alpha=jnp.float32(sac.config.alpha),
        gamma=sac.config.gamma,
        reward_scale=sac.config.reward_scale,
    )
    return grads, key_q


def test_grad_norm_and_update_ratio_match_numpy():
    sac = make_sac(diagnostics="light")
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))
    _, m = sac.update(state, batch)

    q_grads, _ = _grads_and_key(sac, state, batch)
    np_norm = math.sqrt(sum(
        float(np.sum(np.square(np.asarray(x, dtype=np.float32))))
        for x in jax.tree_util.tree_leaves(q_grads)
    ))
    assert float(m["diag/grad_norm_q"]) == pytest.approx(np_norm, rel=1e-5)

    # Update-to-param ratio against a manual optax step.
    q_updates, _ = sac.q_tx.update(
        q_grads, state.q_opt_state, state.critic_params
    )
    expected = float(global_norm(q_updates)) / (
        float(global_norm(state.critic_params)) + 1e-12
    )
    assert float(m["diag/update_ratio_q"]) == pytest.approx(expected, rel=1e-5)


def test_q_stats_match_numpy():
    sac = make_sac(diagnostics="full")
    state = sac.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    batch = make_batch(jax.random.key(1))
    _, m = sac.update(state, batch)

    _, key_q = _grads_and_key(sac, state, batch)
    _, aux = losses.critic_loss(
        state.critic_params,
        actor_apply=sac._actor_apply,
        critic_apply=sac._critic_apply,
        actor_params=state.actor_params,
        target_critic_params=state.target_critic_params,
        batch=batch,
        key=key_q,
        alpha=jnp.float32(sac.config.alpha),
        gamma=sac.config.gamma,
        reward_scale=sac.config.reward_scale,
        diagnostics=True,
    )
    q = np.asarray(aux["diag_q"])            # (num_qs, B)
    backup = np.asarray(aux["diag_backup"])  # (B,)
    assert float(m["diag/q_min"]) == pytest.approx(q.min(), rel=1e-6)
    assert float(m["diag/q_max"]) == pytest.approx(q.max(), rel=1e-6)
    assert float(m["diag/q_spread"]) == pytest.approx(
        (q.max(axis=0) - q.min(axis=0)).mean(), rel=1e-5
    )
    assert float(m["diag/q_bias"]) == pytest.approx(
        q.mean() - backup.mean(), rel=1e-4, abs=1e-6
    )
    # TD-error histogram: exact float32 mirror of the device bucketing.
    abs_td = np.abs(q - backup[None, :]).astype(np.float32).ravel()
    log_lo = np.float32(math.log(TD_HIST_LO))
    log_g = np.float32(math.log(TD_HIST_GROWTH))
    idx = np.floor(
        (np.log(np.maximum(abs_td, np.float32(TD_HIST_LO * 0.5)))
         - log_lo) / log_g
    ).astype(np.int32) + 1
    idx = np.where(abs_td < TD_HIST_LO, 0, np.clip(idx, 1, TD_HIST_BUCKETS + 1))
    expected_counts = np.bincount(idx, minlength=TD_HIST_BUCKETS + 2)
    np.testing.assert_array_equal(np.asarray(m["diag/td_hist"]), expected_counts)
    assert float(m["diag/td_abs_max"]) == pytest.approx(abs_td.max(), rel=1e-6)
    assert float(m["diag/td_abs_sum"]) == pytest.approx(abs_td.sum(), rel=1e-4)


def test_td_histogram_host_merge_roundtrip():
    """Device counts merge into the telemetry histogram schema with
    exact count/total/min/max and bounded-error percentiles."""
    rng = np.random.default_rng(7)
    vals = rng.lognormal(0.0, 1.5, 20_000).astype(np.float32)
    counts = np.asarray(bucket_counts(jnp.asarray(vals)))
    hist = make_td_histogram()
    assert len(counts) == hist.n_buckets + 2
    hist.merge_counts(
        counts, total=float(vals.sum()),
        vmin=float(vals.min()), vmax=float(vals.max()),
    )
    assert hist.count == len(vals)
    assert hist.mean == pytest.approx(vals.mean(), rel=1e-4)
    assert hist.max == pytest.approx(vals.max(), rel=1e-6)
    for q in (50, 95, 99):
        assert hist.percentile(q) == pytest.approx(
            np.percentile(vals, q), rel=0.25
        ), q
    snap = hist.snapshot(prefix="td_abs_", unit="")
    assert snap["td_abs_count"] == len(vals)
    assert "td_abs_p99" in snap and "td_abs_p99_ms" not in snap
    with pytest.raises(ValueError, match="bucket spec"):
        hist.merge_counts([1, 2, 3])


def test_bucket_counts_edge_cases():
    vals = jnp.asarray(
        [0.0, TD_HIST_LO / 2, 1.0, -1.0, 1e9, jnp.nan, jnp.inf]
    )
    counts = np.asarray(bucket_counts(vals))
    assert counts.sum() == 5          # nan/inf dropped
    assert counts[0] == 2             # 0.0 and lo/2 underflow
    assert counts[-1] == 1            # 1e9 overflows


# ----------------------------------------------------- reduction convention


def test_reduction_suffix_rules():
    assert reduction_for("loss_q") == "mean"
    assert reduction_for("q_mean") == "mean"  # historical key: mean
    assert reduction_for("loss_q_max") == "max"
    assert reduction_for("diag/q_min") == "min"
    assert reduction_for("diag/td_hist") == "sum"
    assert reduction_for("diag/td_abs_sum") == "sum"

    metrics = {
        "loss_q": jnp.asarray([1.0, 3.0, 2.0]),
        "loss_q_max": jnp.asarray([1.0, 3.0, 2.0]),
        "diag/q_min": jnp.asarray([1.0, -3.0, 2.0]),
        "diag/td_hist": jnp.ones((3, 4), jnp.int32),
    }
    out = reduce_burst_metrics(metrics)
    assert float(out["loss_q"]) == 2.0
    assert float(out["loss_q_max"]) == 3.0
    assert float(out["diag/q_min"]) == -3.0
    np.testing.assert_array_equal(np.asarray(out["diag/td_hist"]), [3, 3, 3, 3])

    rows = [
        {"a_max": np.asarray(1.0), "h_hist": np.ones((2, 4))},
        {"a_max": np.asarray(5.0), "h_hist": np.ones((2, 4))},
    ]
    host = reduce_metric_rows(rows)
    assert host["a_max"] == 5.0
    # Member axis folded, bucket axis kept.
    np.testing.assert_array_equal(host["h_hist"], [4, 4, 4, 4])


def test_replica_skew_under_shard_map():
    from torch_actor_critic_tpu.parallel import make_mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(dp=4)

    def body(_):
        v = jax.lax.axis_index("dp").astype(jnp.float32)
        skew = replica_skew({"diag/param_norm": v}, ("diag/param_norm",), "dp")
        return skew["diag/param_norm_skew"]

    out = shard_map(
        body, mesh=mesh, in_specs=(P("dp"),), out_specs=P(),
        check_vma=False,
    )(jnp.zeros(4))
    assert float(out) == 3.0  # pmax(0..3) - pmin(0..3)


def test_dp_burst_skew_metrics():
    """dp=2 burst: healthy replicas show grad-norm skew > 0 (distinct
    replay shards) and param-norm skew == 0.0 exactly (pmean'd grads
    keep replicas bit-identical) — the desync canary reads clean."""
    from torch_actor_critic_tpu.parallel import (
        DataParallelSAC,
        init_sharded_buffer,
        make_mesh,
        shard_chunk,
    )

    sac = make_sac(diagnostics="light")
    dp = DataParallelSAC(sac, make_mesh(dp=2))
    state = dp.init_state(jax.random.key(0), jnp.zeros((OBS_DIM,)))
    buf = init_sharded_buffer(
        64, jax.ShapeDtypeStruct((OBS_DIM,), jnp.float32), ACT_DIM, dp.mesh
    )
    ks = jax.random.split(jax.random.key(1), 5)
    chunk = Batch(
        states=jax.random.normal(ks[0], (2, 16, OBS_DIM)),
        actions=jnp.tanh(jax.random.normal(ks[1], (2, 16, ACT_DIM))),
        rewards=jax.random.normal(ks[2], (2, 16)),
        next_states=jax.random.normal(ks[3], (2, 16, OBS_DIM)),
        done=jnp.zeros((2, 16)),
    )
    _, _, m = dp.update_burst(
        state, buf, shard_chunk(chunk, dp.mesh), 4
    )
    assert float(m["diag/param_norm_skew"]) == 0.0
    assert float(m["diag/grad_norm_q_skew"]) > 0.0
    assert float(m["diag/grad_norm_pi_skew"]) > 0.0
    assert float(m["loss_q_max"]) >= float(m["loss_q"]) - 1e-6


# --------------------------------------------------------- early warnings


def test_drift_detector_grad_spike_and_warmup():
    d = DriftDetector("grad_spike", "diag/grad_norm_q", "high", k=6, warmup=3)
    # Warmup: even a large excursion inside the first `warmup` samples
    # must not fire.
    assert d.update(1.0) is None
    assert d.update(50.0) is None
    for v in (1.0, 1.05, 0.95, 1.0):
        d.update(v)
    w = d.update(100.0)
    assert w is not None and w["kind"] == "grad_spike"
    # The clipped EMA refuses to swallow the spike: the next normal
    # value does not fire low/new baselines.
    assert d.update(1.0) is None


def test_drift_detector_directions():
    low = DriftDetector("entropy_collapse", "entropy", "low", k=6, warmup=2)
    for v in (1.0, 1.0, 1.01, 0.99, 1.0):
        assert low.update(v) is None
    assert low.update(-2.0) is not None   # collapse fires
    assert low.update(1.0) is None        # recovery (upward) never fires

    shift = DriftDetector("q_bias_drift", "diag/q_bias", "shift", k=6, warmup=2)
    for v in (-0.5, -0.5, -0.52, -0.48, -0.5):
        assert shift.update(v) is None
    assert shift.update(-8.0) is not None  # drift in either direction
    shift2 = DriftDetector("q_bias_drift", "diag/q_bias", "shift", k=6, warmup=2)
    for v in (-0.5, -0.5, -0.52, -0.48, -0.5):
        assert shift2.update(v) is None
    assert shift2.update(7.0) is not None


def test_monitor_feeds_sentinel():
    from torch_actor_critic_tpu.resilience.sentinel import DivergenceSentinel

    mon = EarlyWarningMonitor(k=6, warmup=2)
    sentinel = DivergenceSentinel()
    for _ in range(5):
        ws = mon.update({
            "diag/grad_norm_q": 1.0, "diag/grad_norm_pi": 1.0,
            "entropy": 0.5, "diag/q_bias": -0.1,
        })
        assert ws == []
    ws = mon.update({
        "diag/grad_norm_q": 500.0, "diag/grad_norm_pi": 1.0,
        "entropy": 0.5, "diag/q_bias": -0.1,
    })
    assert [w["kind"] for w in ws] == ["grad_spike"]
    for w in ws:
        sentinel.note_warning(w["kind"])
    assert sentinel.warnings_total == 1
    assert sentinel.warnings_by_kind == {"grad_spike": 1}
    assert sentinel.consecutive == 0  # no rollback budget consumed
    # Non-finite values are the sentinel's business, not the monitor's.
    assert mon.update({"diag/grad_norm_q": float("nan")}) == []


# ------------------------------------------------------------- watchdog


def test_watchdog_counts_attributes_and_flags():
    wd = get_watchdog().install()
    wd.reset()
    try:
        f = jax.jit(lambda x: x * 2.0 + 1.0)
        with wd.source("train/update_burst"):
            f(jnp.ones(7))
        snap = wd.snapshot()
        assert snap["compiles_total"] >= 1
        assert snap["by_source"].get("train/update_burst", 0) >= 1
        assert snap["post_steady_compiles"] == 0

        wd.mark_steady("train/")
        with wd.source("train/update_burst"):
            f(jnp.ones(13))  # new shape: an induced steady-state recompile
        snap = wd.snapshot()
        assert snap["post_steady_compiles"] >= 1
        assert snap["anomalies"][0]["source"] == "train/update_burst"

        # expected() (warmup inside a steady regime): counted, not flagged.
        before = wd.snapshot()["post_steady_compiles"]
        total_before = wd.snapshot()["compiles_total"]
        with wd.expected(), wd.source("train/update_burst"):
            f(jnp.ones(17))
        snap = wd.snapshot()
        assert snap["post_steady_compiles"] == before
        assert snap["compiles_total"] > total_before

        # Unattributed compiles never flag (only steady prefixes do).
        jax.jit(lambda x: x - 3.0)(jnp.ones(3))
        assert wd.snapshot()["post_steady_compiles"] == before
    finally:
        wd.reset()


def test_engine_compile_counts_warmup_vs_live():
    from torch_actor_critic_tpu.serve.engine import PolicyEngine

    actor = Actor(act_dim=2, hidden_sizes=(8, 8))
    params = actor.init(
        jax.random.key(0), jnp.zeros((3,)), jax.random.key(1)
    )
    spec = jax.ShapeDtypeStruct((3,), jnp.float32)
    eng = PolicyEngine(actor, spec, max_batch=4)
    eng.warmup(params)
    s = eng.compile_stats()
    assert s["compiles_total"] == len(eng.buckets) * 2
    assert s["live_compiles"] == 0
    assert all(
        b["warmup"] == 2 and b["live"] == 0 for b in s["buckets"].values()
    )
    # Repeat traffic adds no compiles.
    eng.act(params, np.zeros((3, 3), np.float32), deterministic=True)
    assert eng.compile_stats() == s

    # A bucket skipped at warmup shows up as a LIVE compile.
    eng2 = PolicyEngine(actor, spec, max_batch=4)
    eng2.warmup(params, buckets=[2])
    eng2.act(params, np.zeros((4, 3), np.float32), deterministic=True)
    s2 = eng2.compile_stats()
    assert s2["live_compiles"] == 1
    assert s2["buckets"]["4"] == {"warmup": 0, "live": 1, "bundle": 0}


def test_server_metrics_exposes_compiles_and_xla():
    from urllib import request as urlreq

    from torch_actor_critic_tpu.serve import ModelRegistry, PolicyServer

    actor = Actor(act_dim=2, hidden_sizes=(8, 8))
    params = actor.init(
        jax.random.key(0), jnp.zeros((3,)), jax.random.key(1)
    )
    reg = ModelRegistry()
    reg.register(
        "default", actor, jax.ShapeDtypeStruct((3,), jnp.float32),
        params=params, max_batch=2,
    )
    with PolicyServer(reg, port=0, max_batch=2) as srv:
        srv.start()
        snap = json.loads(
            urlreq.urlopen(srv.address + "/metrics", timeout=30).read()
        )
    assert snap["compiles_total"] == 2  # one bucket x (det, sampled)
    assert snap["live_compiles"] == 0
    assert snap["compiles"]["default"]["buckets"]["2"]["warmup"] == 2
    assert snap["xla"]["compiles_total"] >= 2
    assert isinstance(snap["xla"]["by_source"], dict)


# ------------------------------------------------------ trainer integration


def test_trainer_light_tier_metrics(tmp_path):
    """Light tier through the real Trainer (no telemetry): diagnostic
    scalars, early_warnings and xla_compiles land in metrics.jsonl; no
    TD-histogram keys (full-only)."""
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.utils.tracking import Tracker

    tracker = Tracker(experiment="t", root=tmp_path)
    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=16, epochs=2, steps_per_epoch=30,
        start_steps=10, update_after=10, update_every=10, buffer_size=500,
        max_ep_len=100, diagnostics="light",
    )
    tr = Trainer(
        "Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker, seed=3
    )
    try:
        metrics = tr.train()
    finally:
        tr.close()
    for key in (
        "diag/grad_norm_q", "diag/update_ratio_pi", "diag/q_bias",
        "diag/act_sat", "diag/param_norm", "loss_q_max",
        "early_warnings", "xla_compiles",
    ):
        assert key in metrics, key
        assert np.isfinite(metrics[key]), key
    assert "diag/td_abs_sum" not in metrics  # full-tier only
    assert tr.td_hist.count == 0
    rows = tracker.metrics()
    assert all("diag/grad_norm_q" in r for r in rows)
