"""Telemetry subsystem tests (ISSUE 3 / docs/OBSERVABILITY.md).

Pins the four contract points: span aggregation is exact; disabled mode
is a true no-op (identical Trainer metrics keys, zero telemetry
events); the JSONL event stream round-trips its documented schema; and
the serving ``/metrics`` snapshot carries histogram-backed latency
percentiles with bounded memory.
"""

import json

import numpy as np
import pytest

from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.sac.trainer import Trainer
from torch_actor_critic_tpu.telemetry import (
    PHASES,
    FixedBucketHistogram,
    PhaseTimer,
    SpanRing,
    TelemetryRecorder,
    json_sanitize,
    parse_profile_epochs,
)
from torch_actor_critic_tpu.utils.config import SACConfig
from torch_actor_critic_tpu.utils.tracking import Tracker

TINY = dict(
    hidden_sizes=(16, 16),
    batch_size=16,
    epochs=2,
    steps_per_epoch=40,
    start_steps=10,
    update_after=10,
    update_every=10,
    buffer_size=500,
    max_ep_len=100,
)


# ------------------------------------------------------------- primitives


def test_phase_timer_aggregation_is_exact():
    """lap(i) charges exactly now - last_mark to phase i: sums, counts
    and maxes over a scripted clock match hand computation."""
    ticks = iter([0.0, 1.0, 1.5, 4.0, 4.25, 10.25])
    t = PhaseTimer(3, clock=lambda: next(ticks))  # mark at 0.0
    assert t.lap(0) == 1.0   # 0.0 -> 1.0
    assert t.lap(1) == 0.5   # 1.0 -> 1.5
    assert t.lap(0) == 2.5   # 1.5 -> 4.0
    assert t.lap(2) == 0.25  # 4.0 -> 4.25
    t.mark()                 # 10.25: the gap is charged to nothing
    assert t.sums == [3.5, 0.5, 0.25]
    assert t.counts == [2, 1, 1]
    assert t.maxs == [2.5, 0.5, 0.25]
    stats = t.stats(("a", "b", "c"))
    assert stats["a"] == {"total_s": 3.5, "count": 2, "max_s": 2.5}


def test_span_ring_wraps_without_growing():
    ring = SpanRing(capacity=4)
    for i in range(7):
        ring.record(i % 3, float(i), 0.5)
    assert ring.total == 7
    spans = ring.spans()
    assert len(spans) == 4  # bounded
    # Oldest-first: records 3..6 survive.
    assert [s[1] for s in spans] == [3.0, 4.0, 5.0, 6.0]
    assert [s[0] for s in spans] == [0, 1, 2, 0]


def test_histogram_percentiles_bounded_error():
    """Percentile estimates land within one geometric bucket (~19%) of
    the exact values; count/mean/min/max are exact."""
    rng = np.random.default_rng(3)
    vals = rng.lognormal(mean=1.0, sigma=1.0, size=50_000)
    h = FixedBucketHistogram()
    for v in vals:
        h.record(v)
    assert h.count == len(vals)
    assert h.mean == pytest.approx(vals.mean())
    assert h.max == vals.max() and h.min == vals.min()
    for q in (50, 95, 99):
        exact = np.percentile(vals, q)
        assert h.percentile(q) == pytest.approx(exact, rel=0.19), q
    # Memory is fixed: the bucket array never grew.
    assert len(h._counts) < 120
    assert h.percentile(0) == h.min and h.percentile(100) == h.max


def test_histogram_edge_cases():
    h = FixedBucketHistogram()
    assert h.percentile(50) is None and h.mean is None
    h.record(-1.0)        # negative: clock skew, dropped
    h.record(float("nan"))
    assert h.count == 0
    h.record(0.001)       # underflow bucket -> exact min
    h.record(1e9)         # overflow bucket -> exact max
    assert h.count == 2
    assert h.percentile(1) == 0.001
    assert h.percentile(99.9) == 1e9
    bounds = h.buckets()
    assert len(bounds) == 2 and bounds[-1][0] == float("inf")


def test_parse_profile_epochs():
    assert parse_profile_epochs(None) is None
    assert parse_profile_epochs("") is None
    assert parse_profile_epochs("3:7") == (3, 7)
    assert parse_profile_epochs("4") == (4, 5)
    for bad in ("5:2", "-1:3", "a:b", "1:2:3"):
        with pytest.raises(ValueError):
            parse_profile_epochs(bad)


def test_json_sanitize_strictness():
    out = json_sanitize({
        "ok": 1.5,
        "nan": float("nan"),
        "inf": float("inf"),
        "np": np.float32(2.0),
        "nested": [float("-inf"), {"x": np.int64(3)}],
    })
    # Strict JSON round-trip (json.loads with default settings accepts
    # NaN literals, so assert on the dumped text instead).
    text = json.dumps(out, allow_nan=False)
    back = json.loads(text)
    assert back["ok"] == 1.5
    assert back["nan"] is None and back["inf"] is None
    assert back["np"] == 2.0
    assert back["nested"] == [None, {"x": 3}]


# --------------------------------------------------------------- recorder


def test_recorder_epoch_event_and_run_accumulation(tmp_path):
    ticks = iter([float(i) for i in range(100)])
    rec = TelemetryRecorder(run_dir=tmp_path, clock=lambda: next(ticks))
    rec.epoch_begin(0)
    rec.lap(0)
    rec.lap(4)
    rec.inc("env_steps", 8)
    ev = rec.epoch_end(0, extra={"step": 8})
    assert ev["phases"]["act"]["total_s"] == 1.0
    assert ev["phases"]["burst_dispatch"]["total_s"] == 1.0
    assert ev["step"] == 8 and ev["counters"] == {"env_steps": 8.0}
    # Second epoch: the epoch timer reset, the run totals accumulate.
    rec.epoch_begin(1)
    rec.lap(0)
    ev2 = rec.epoch_end(1)
    assert ev2["phases"]["act"]["count"] == 1
    snap = rec.snapshot()
    assert snap["epochs_total"] == 2
    assert snap["phases"]["act"]["count"] == 2
    assert "act" in rec.summary()
    rec.close()

    lines = (tmp_path / "telemetry.jsonl").read_text().splitlines()
    events = [json.loads(line) for line in lines]
    assert events[0]["type"] == "run_start"
    assert events[0]["phases"] == list(PHASES)
    assert [e["type"] for e in events[1:]] == ["epoch", "epoch"]


def test_recorder_without_run_dir_keeps_everything_in_memory(tmp_path):
    rec = TelemetryRecorder()  # non-coordinator / unit-test mode
    rec.epoch_begin(0)
    rec.lap(2)
    rec.event("rollback", epoch=0)  # must not raise with no sink
    rec.epoch_end(0)
    assert rec.snapshot()["epochs_total"] == 1
    assert list(tmp_path.iterdir()) == []
    rec.close()


# ------------------------------------------------------ trainer integration


@pytest.fixture(scope="module")
def off_and_on(tmp_path_factory):
    """One tiny run with telemetry disabled and one enabled, sharing
    the config; both tracked so the JSONL contract is observable."""
    results = {}
    for mode in ("off", "on"):
        root = tmp_path_factory.mktemp(f"tm_{mode}")
        tracker = Tracker(experiment="t", root=root)
        cfg = SACConfig(**TINY, telemetry=(mode == "on"))
        tr = Trainer(
            "Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker,
            seed=3,
        )
        try:
            metrics = tr.train()
        finally:
            tr.close()
        results[mode] = (tracker, metrics, tr.telemetry)
    return results


def test_disabled_mode_is_true_noop(off_and_on):
    """The tentpole contract: telemetry off produces the same metrics
    dict keys as an uninstrumented build (the phase breakdown lives in
    the telemetry stream, never the metrics dict) and ZERO telemetry
    artifacts. Telemetry ON may ADD the ``cost/`` roofline columns
    (ISSUE 7) — and nothing else."""
    tracker_off, m_off, rec_off = off_and_on["off"]
    tracker_on, m_on, rec_on = off_and_on["on"]
    assert rec_off is None
    assert rec_on is not None
    assert not any(k.startswith("cost/") for k in m_off)
    assert sorted(m_off) == sorted(
        k for k in m_on if not k.startswith("cost/")
    )
    assert not (tracker_off.run_dir / "telemetry.jsonl").exists()
    assert (tracker_on.run_dir / "telemetry.jsonl").exists()


def test_epoch_accounting_metrics_present(off_and_on):
    """Satellite: sentinel/save time are their own metrics (in BOTH
    modes — the accounting fix is not telemetry-gated), so epoch dt no
    longer leaks save time into the next epoch's throughput."""
    for mode in ("off", "on"):
        _, metrics, _ = off_and_on[mode]
        assert metrics["sentinel_s"] >= 0.0
        assert metrics["save_s"] >= 0.0
        assert metrics["env_steps_per_sec"] > 0.0


def test_jsonl_schema_roundtrip_and_phase_coverage(off_and_on):
    """Every line parses as strict JSON; epoch events carry the full
    9-phase classification with consistent aggregates, and the phase sums
    cover ~the epoch wall time (the breakdown partitions the loop)."""
    tracker_on, _, _ = off_and_on["on"]
    lines = (tracker_on.run_dir / "telemetry.jsonl").read_text().splitlines()
    events = [json.loads(line) for line in lines]  # strict parse
    assert events[0]["type"] == "run_start"
    assert events[0]["schema"] == 1
    epochs = [e for e in events if e["type"] == "epoch"]
    assert len(epochs) == TINY["epochs"]
    for ev in epochs:
        assert set(ev["phases"]) == set(PHASES)
        for p in ev["phases"].values():
            assert p["count"] > 0
            assert 0.0 <= p["max_s"] <= p["total_s"] + 1e-12
        covered = sum(p["total_s"] for p in ev["phases"].values())
        assert 0.8 * ev["wall_s"] <= covered <= 1.1 * ev["wall_s"]
        # act/env_step run every step; the window phases once per window.
        # The host actor's mirror refresh (param_sync, once after every
        # burst) interrupts an act, which is then charged in two spans;
        # a window's stage, place_chunk and burst_dispatch, opened by the
        # functions that do the work, each interrupt an env_step.
        windows = TINY["steps_per_epoch"] // TINY["update_every"]
        syncs = ev["phases"]["param_sync"]["count"]
        assert windows - 1 <= syncs <= windows
        assert ev["phases"]["env_step"]["count"] == (
            TINY["steps_per_epoch"] + 3 * windows
        )
        for name in ("stage", "place_chunk"):
            assert ev["phases"][name]["count"] == windows
        assert ev["phases"]["act"]["count"] == TINY["steps_per_epoch"] + syncs
        assert ev["phases"]["burst_dispatch"]["count"] == windows
        assert ev["env_steps"] == TINY["steps_per_epoch"]
        assert ev["phases"]["checkpoint"]["count"] == 1


def test_recorder_snapshot_matches_run(off_and_on):
    _, _, rec = off_and_on["on"]
    snap = rec.snapshot()
    assert snap["epochs_total"] == TINY["epochs"]
    assert snap["counters"]["env_steps"] == (
        TINY["epochs"] * TINY["steps_per_epoch"]
    )
    # 2 full epochs of spans accumulated at run level (an act that a
    # param_sync interrupts is two spans; an env_step that a window's
    # stage, place_chunk and burst_dispatch interrupt is four)
    windows = TINY["epochs"] * TINY["steps_per_epoch"] // TINY["update_every"]
    assert snap["phases"]["env_step"]["count"] == (
        TINY["epochs"] * TINY["steps_per_epoch"] + 3 * windows
    )
    assert snap["phases"]["act"]["count"] == (
        TINY["epochs"] * TINY["steps_per_epoch"]
        + snap["phases"]["param_sync"]["count"]
    )


# ------------------------------------------------------------ serve plane


def test_serve_metrics_percentile_fields():
    """Satellite: /metrics carries histogram-backed p50/p95/p99 plus
    the mean, alongside the existing counters, from bounded memory."""
    from torch_actor_critic_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    rng = np.random.default_rng(0)
    lats = rng.lognormal(1.5, 0.5, 5000)
    for lat in lats:
        m.record_done(float(lat))
    m.record_batch(rows=4, bucket=8)
    snap = m.snapshot()
    assert snap["responses_total"] == 5000
    for key in ("mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms"):
        assert key in snap, key
    assert snap["p50_ms"] <= snap["p95_ms"] <= snap["p99_ms"] <= snap["max_ms"]
    assert snap["p50_ms"] == pytest.approx(np.percentile(lats, 50), rel=0.19)
    assert snap["p99_ms"] == pytest.approx(np.percentile(lats, 99), rel=0.19)
    assert snap["max_ms"] == pytest.approx(lats.max(), abs=1e-3)
    assert snap["mean_batch_occupancy"] == 0.5


def test_serve_metrics_empty_snapshot_has_no_percentiles():
    from torch_actor_critic_tpu.serve.metrics import ServeMetrics

    snap = ServeMetrics().snapshot()
    assert "p50_ms" not in snap and "mean_ms" not in snap
    assert snap["responses_total"] == 0


def test_http_metrics_merges_extra_snapshot():
    """The unified-schema hook: a co-located recorder's snapshot merges
    into /metrics under `training` next to the serving keys."""
    import json as _json
    from urllib import request as urlreq

    import jax
    import jax.numpy as jnp

    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve import ModelRegistry, PolicyServer

    rec = TelemetryRecorder()
    rec.epoch_begin(0)
    rec.lap(0)
    rec.epoch_end(0)

    actor = Actor(act_dim=2, hidden_sizes=(8, 8))
    params = actor.init(
        jax.random.key(0), jnp.zeros((3,)), jax.random.key(1)
    )
    reg = ModelRegistry()
    reg.register(
        "default", actor, jax.ShapeDtypeStruct((3,), jnp.float32),
        params=params, max_batch=2,
    )
    with PolicyServer(
        reg, port=0, max_batch=2,
        extra_snapshot=lambda: {"training": rec.snapshot()},
    ) as srv:
        srv.start()
        snap = _json.loads(
            urlreq.urlopen(srv.address + "/metrics", timeout=30).read()
        )
    assert snap["training"]["epochs_total"] == 1
    assert "act" in snap["training"]["phases"]
    assert "requests_total" in snap  # serving keys intact


# ---------------------------------------------------------------- tracker


def test_tracker_jsonl_mirror_is_strict_json(tmp_path):
    """Satellite: the metrics mirror stays tail-able — non-finite
    values become null instead of NaN literals that break strict
    parsers, and rows flush per line."""
    tr = Tracker(experiment="e", root=tmp_path)
    tr.log_metrics({"a": 1.0, "bad": float("nan"), "inf": float("inf")}, 0)
    text = (tr.run_dir / "metrics.jsonl").read_text()
    assert "NaN" not in text and "Infinity" not in text
    row = json.loads(text.splitlines()[0])
    assert row["a"] == 1.0 and row["bad"] is None and row["inf"] is None
    assert tr.metrics_path == tr.run_dir / "metrics.jsonl"


def test_tracker_jsonl_survives_broken_mlflow_mirror(tmp_path):
    """The JSONL mirror is the source of truth: a raising MLflow client
    must not lose the row."""
    tr = Tracker(experiment="e", root=tmp_path)

    class _Boom:
        def log_metrics(self, *a, **k):
            raise RuntimeError("mlflow down")

    tr._mlflow = _Boom()
    tr.log_metrics({"x": 2.0}, 1)
    assert tr.metrics()[0]["x"] == 2.0
