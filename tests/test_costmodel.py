"""Compute-cost attribution tests (docs/OBSERVABILITY.md "Cost
attribution & roofline").

Pins the contract points: the cost registry is populated from real
CPU-lowered programs (the dp update burst, the serving buckets) with
hand-verifiable FLOPs; roofline classification follows the ridge
point; the Perfetto trace_event export round-trips (sorted
timestamps, paired B/E events, both planes); per-epoch ``cost``
events and ``cost/`` metric columns appear with telemetry on; and
``telemetry=None`` stays a true no-op (no cost keys, no lowering, no
registry entries from the trainer).
"""

import json
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torch_actor_critic_tpu.parallel import make_mesh
from torch_actor_critic_tpu.sac.trainer import Trainer
from torch_actor_critic_tpu.telemetry import TelemetryRecorder
from torch_actor_critic_tpu.telemetry.costmodel import (
    CostRegistry,
    Peaks,
    classify_epoch,
    get_cost_registry,
    roofline,
)
from torch_actor_critic_tpu.telemetry.traceview import (
    RequestSpanLog,
    compile_events,
    export_trace,
    serve_request_events,
    training_events,
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


# ------------------------------------------------------------- registry


def test_register_jit_populates_from_cpu_lowered_mlp():
    """FLOPs from the registry match the hand-computed cost of a known
    matmul: one (8,16)x(16,4) dot is 2*8*16*4 = 1024 FLOPs; the tanh
    adds 32 transcendentals, not FLOPs."""

    def f(x, w):
        return jnp.tanh(x @ w)

    reg = CostRegistry()
    cost = reg.register_jit(
        "test/mlp", jax.jit(f),
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 4), jnp.float32),
        compiled=False,
    )
    assert cost is not None
    assert cost["flops"] == 2 * 8 * 16 * 4
    assert cost["transcendentals"] == 8 * 4
    # bytes accessed covers at least the operands + output
    assert cost["bytes_accessed"] >= 4 * (8 * 16 + 16 * 4 + 8 * 4)
    assert reg.get("test/mlp") == cost
    assert "test/mlp" in reg.costs()


def test_register_jit_burst_program():
    """The real dp update burst lowers on CPU and registers nonzero
    FLOPs/bytes from abstract (ShapeDtypeStruct) arguments — the
    trainer's exact registration path."""
    from torch_actor_critic_tpu.core.types import Batch
    from torch_actor_critic_tpu.parallel import (
        DataParallelSAC,
        init_sharded_buffer,
        shard_chunk_from_local,
    )
    from torch_actor_critic_tpu.sac.trainer import build_models, make_learner

    cfg = SACConfig(batch_size=8, hidden_sizes=(8, 8))

    class _Spec:
        obs_spec = jax.ShapeDtypeStruct((3,), jnp.float32)
        act_limit = 1.0
        act_dim = 1

    actor, critic = build_models(cfg, _Spec)
    sac = make_learner(cfg, actor, critic, 1)
    mesh = make_mesh(dp=1)
    dp = DataParallelSAC(sac, mesh)
    state = dp.init_state(jax.random.key(0), jnp.zeros((3,)))
    buf = init_sharded_buffer(64, _Spec.obs_spec, 1, mesh)
    chunk = shard_chunk_from_local(
        Batch(
            states=np.zeros((1, 10, 3), np.float32),
            actions=np.zeros((1, 10, 1), np.float32),
            rewards=np.zeros((1, 10), np.float32),
            next_states=np.zeros((1, 10, 3), np.float32),
            done=np.zeros((1, 10), np.float32),
        ),
        mesh,
    )
    abstract = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (state, buf, chunk),
    )
    state, buf, _ = dp.update_burst(state, buf, chunk, 3)
    fn = dp.burst_jit(3)
    assert fn is not None
    reg = CostRegistry()
    cost = reg.register_jit("test/burst", fn, *abstract)
    assert cost is not None
    assert cost["flops"] > 0
    assert cost["bytes_accessed"] > 0


def test_engine_warmup_registers_bucket_costs_monotone():
    """Serving warmup registers every bucket's program under
    ``serve/forward[bN]`` in the process-wide registry, and FLOPs are
    monotone in the bucket size (a bigger padded batch costs more)."""
    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.serve.engine import PolicyEngine

    actor = Actor(act_dim=2, hidden_sizes=(8, 8))
    params = actor.init(
        jax.random.key(0), jnp.zeros((5,)), jax.random.key(1)
    )
    engine = PolicyEngine(
        actor, jax.ShapeDtypeStruct((5,), jnp.float32), max_batch=8
    )
    engine.warmup(params, deterministic_only=True)
    reg = get_cost_registry()
    flops = {}
    for bucket in (2, 4, 8):
        cost = reg.get(f"serve/forward[b{bucket}]")
        assert cost is not None, f"bucket {bucket} not registered"
        assert cost["flops"] > 0
        flops[bucket] = cost["flops"]
    assert flops[2] < flops[4] < flops[8]


# ------------------------------------------------------------- roofline


def test_roofline_classification_against_ridge():
    """AI above the ridge point (peak_flops/peak_bw) is compute-bound,
    below is memory-bound; achieved FLOP/s and MFU follow from the
    measured duration."""
    peaks = Peaks(flops=1e12, hbm_bw=1e11)  # ridge = 10 FLOPs/byte
    compute = roofline(
        {"flops": 1e9, "bytes_accessed": 1e7},  # AI = 100
        duration_s=0.01, calls=10, peaks=peaks,
    )
    assert compute["bound"] == "compute"
    assert compute["arithmetic_intensity"] == 100.0
    assert compute["achieved_flops_per_sec"] == pytest.approx(1e12, rel=1e-6)
    assert compute["mfu"] == pytest.approx(1.0)
    assert compute["ridge_flops_per_byte"] == 10.0

    memory = roofline(
        {"flops": 1e6, "bytes_accessed": 1e7},  # AI = 0.1
        duration_s=1.0, calls=1, peaks=peaks,
    )
    assert memory["bound"] == "memory"
    # Attainable ceiling for AI=0.1 at bw 1e11 is 1e10 FLOP/s, far
    # under peak — MFU must be read against the roofline, and the
    # record says so.
    assert memory["attainable_flops_per_sec"] == pytest.approx(1e10)
    assert memory["roofline_frac"] == pytest.approx(
        memory["achieved_flops_per_sec"] / 1e10, rel=1e-3
    )


def test_roofline_without_peaks_omits_classification():
    out = roofline(
        {"flops": 100.0, "bytes_accessed": 50.0}, duration_s=1.0,
        peaks=Peaks(None, None),
    )
    assert "bound" not in out and "mfu" not in out
    assert out["arithmetic_intensity"] == 2.0
    assert out["achieved_flops_per_sec"] == 100


def test_peaks_env_overrides(monkeypatch):
    monkeypatch.setenv("TAC_PEAK_FLOPS", "5e12")
    monkeypatch.setenv("TAC_PEAK_BW", "2e11")
    peaks = Peaks.detect()
    assert peaks.flops == 5e12
    assert peaks.hbm_bw == 2e11


def test_tiny_mfu_survives_rounding():
    """A compile-heavy first epoch's MFU is tiny but must not round to
    an indistinguishable-from-missing 0.0."""
    out = roofline(
        {"flops": 1e3, "bytes_accessed": 1e3}, duration_s=10.0,
        peaks=Peaks(1e15, 1e12),
    )
    assert out["mfu"] > 0.0


# ------------------------------------------------------ epoch attribution


def test_classify_epoch_planes():
    def phases(**totals):
        return {k: {"total_s": v} for k, v in totals.items()}

    dev = classify_epoch(
        phases(act=0.1, env_step=0.1, burst_dispatch=0.5, drain=0.2),
        wall_s=1.0,
    )
    assert dev["class"] == "device-bound"
    assert dev["device_busy_frac"] == pytest.approx(0.7)
    host = classify_epoch(
        phases(act=0.5, env_step=0.3, drain=0.1), wall_s=1.0
    )
    assert host["class"] == "host-bound"
    inp = classify_epoch(
        phases(stage=0.4, place_chunk=0.3, act=0.1, drain=0.1), wall_s=1.0
    )
    assert inp["class"] == "input-bound"
    # Unknown phase names are skipped, not misclassified.
    weird = classify_epoch(
        {"custom": {"total_s": 9.0}, "drain": {"total_s": 0.1}}, wall_s=1.0
    )
    assert weird["class"] == "device-bound"


# -------------------------------------------------------------- traceview


def _stack_ok(events):
    """B/E pairs obey stack discipline per (pid, tid)."""
    stacks = {}
    for e in events:
        if e["ph"] == "B":
            stacks.setdefault((e["pid"], e["tid"]), []).append(e["name"])
        elif e["ph"] == "E":
            stack = stacks.get((e["pid"], e["tid"]))
            assert stack, f"E without B: {e}"
            stack.pop()
    assert all(not s for s in stacks.values()), stacks


def test_trace_event_schema_roundtrip(tmp_path):
    """The exported trace is valid JSON with sorted timestamps and
    paired B/E events across all three planes."""
    ticks = iter(float(i) for i in range(100))
    rec = TelemetryRecorder(clock=lambda: next(ticks))
    rec.epoch_begin(0)
    rec.lap(0)
    rec.lap(4)
    rec.epoch_end(0)

    log = RequestSpanLog()
    log.record({
        "request_id": "r1", "slot": "default", "rows": 1, "bucket": 2,
        "generation": 0, "t_enq": 10.0, "t_collect": 10.1,
        "t_dispatch": 10.2, "t_forward_end": 10.5, "t_done": 10.6,
        "outcome": "ok",
    })
    log.record({  # a shed: no dispatch timestamps, still well-formed
        "request_id": "r2", "slot": "default", "rows": 0,
        "t_enq": 11.0, "t_done": 11.0, "outcome": "queue_full",
    })
    compiles = [
        {"source": "serve/forward[b2]", "time": 1000.0, "duration_s": 0.5},
    ]

    path = tmp_path / "trace.json"
    summary = export_trace(
        path,
        training_events(rec),
        serve_request_events(log.records()),
        compile_events(compiles),
    )
    assert summary["train_spans"] == 2
    assert summary["serve_spans"] == 2 + 4  # 2 requests + 4 ok stages
    assert summary["compile_spans"] == 1

    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    spans = [e for e in events if e["ph"] in ("B", "E")]
    ts = [e["ts"] for e in spans]
    assert ts == sorted(ts)
    assert sum(e["ph"] == "B" for e in spans) == sum(
        e["ph"] == "E" for e in spans
    )
    _stack_ok(spans)
    names = {e["name"] for e in spans}
    assert {"act", "burst_dispatch", "request", "queue", "forward"} <= names
    # the request args carry the correlation id + outcome
    reqs = [
        e for e in spans if e["ph"] == "B" and e["name"] == "request"
    ]
    assert {r["args"]["request_id"] for r in reqs} == {"r1", "r2"}
    assert {r["args"]["outcome"] for r in reqs} == {"ok", "queue_full"}
    # metadata names the plane lanes
    meta = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"train", "serve", "xla-compile"} <= meta


def test_request_span_log_is_bounded():
    log = RequestSpanLog(capacity=4)
    for i in range(10):
        log.record({"request_id": str(i), "t_enq": float(i)})
    recs = log.records()
    assert len(recs) == 4
    assert recs[0]["request_id"] == "6"  # newest survive


# ------------------------------------------- trainer integration + parity


@pytest.fixture(scope="module")
def cost_runs(tmp_path_factory):
    """One tiny run with telemetry off then one on, with the global
    registry reset in between observations so the off-run's
    non-registration is observable."""
    results = {}
    get_cost_registry().reset()
    for mode in ("off", "on"):
        root = tmp_path_factory.mktemp(f"cost_{mode}")
        tracker = Tracker(experiment="c", root=root)
        cfg = SACConfig(**TINY, telemetry=(mode == "on"))
        tr = Trainer(
            "Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker,
            seed=5,
        )
        try:
            metrics = tr.train()
        finally:
            tr.close()
        burst_cost = get_cost_registry().get("train/update_burst")
        results[mode] = (tracker, metrics, tr.telemetry, burst_cost)
    return results


def test_telemetry_off_registers_nothing(cost_runs):
    """telemetry=None no-op parity: the off run performs no lowering,
    registers nothing, and its metrics carry no cost keys."""
    _, m_off, rec_off, burst_cost_off = cost_runs["off"]
    assert rec_off is None
    assert burst_cost_off is None
    assert not any(k.startswith("cost/") for k in m_off)


def test_telemetry_on_adds_cost_keys_only(cost_runs):
    """The on run's metrics are the off run's keys PLUS the cost
    columns — nothing else moves."""
    _, m_off, _, _ = cost_runs["off"]
    _, m_on, _, burst_cost_on = cost_runs["on"]
    assert burst_cost_on is not None and burst_cost_on["flops"] > 0
    on_without_cost = [k for k in m_on if not k.startswith("cost/")]
    assert sorted(m_off) == sorted(on_without_cost)
    for key in (
        "cost/update_burst_gflops",
        "cost/update_burst_achieved_gflops_s",
        "cost/update_burst_ai",
    ):
        assert key in m_on, key
        assert m_on[key] > 0


def test_cost_events_in_telemetry_stream(cost_runs):
    tracker_on, _, _, _ = cost_runs["on"]
    events = [
        json.loads(line)
        for line in (tracker_on.run_dir / "telemetry.jsonl").read_text()
        .splitlines()
    ]
    cost_events = [e for e in events if e["type"] == "cost"]
    assert len(cost_events) == TINY["epochs"]
    for ev in cost_events:
        rl = ev["programs"]["train/update_burst"]
        assert rl["flops_per_call"] > 0
        assert rl["bytes_per_call"] > 0
        assert rl["calls"] > 0
        for v in rl.values():
            if isinstance(v, float):
                assert math.isfinite(v)
    # every epoch event carries the host/device/input attribution
    for ev in (e for e in events if e["type"] == "epoch"):
        attr = ev["attribution"]
        assert attr["class"] in (
            "host-bound", "device-bound", "input-bound"
        )
        assert 0.0 <= attr["device_busy_frac"] <= 1.5


def test_attribution_in_summary(cost_runs):
    _, _, rec_on, _ = cost_runs["on"]
    summary = rec_on.summary()
    assert "epoch attribution" in summary
    rolled = rec_on.attribution_summary()
    assert rolled["epochs"] == TINY["epochs"]
    assert sum(rolled["by_class"].values()) == TINY["epochs"]


# ------------------------------------------------------------ serve plane


def test_request_id_threads_through_spans_and_metrics_costs():
    """X-Request-Id round-trip: client-supplied id echoes on the
    response, lands in the request's span record, and /metrics gains a
    per-bucket costs section after traffic."""
    from urllib import request as urlreq

    from torch_actor_critic_tpu.models import Actor
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
    log = RequestSpanLog()
    with PolicyServer(reg, port=0, max_batch=2, span_log=log) as srv:
        srv.start()
        req = urlreq.Request(
            srv.address + "/act",
            data=json.dumps({"obs": [0.1, 0.2, 0.3]}).encode(),
            headers={"X-Request-Id": "rid-42"},
        )
        resp = urlreq.urlopen(req, timeout=30)
        assert resp.headers.get("X-Request-Id") == "rid-42"
        # a generated id appears when the client sends none
        resp2 = urlreq.urlopen(urlreq.Request(
            srv.address + "/act",
            data=json.dumps({"obs": [0.1, 0.2, 0.3]}).encode(),
        ), timeout=30)
        gen_rid = resp2.headers.get("X-Request-Id")
        assert gen_rid
        snap = json.loads(
            urlreq.urlopen(srv.address + "/metrics", timeout=30).read()
        )
    assert "costs" in snap
    assert "b2" in snap["costs"]
    entry = snap["costs"]["b2"]
    assert entry["flops_per_call"] > 0
    assert entry["calls"] >= 2
    rids = {r.get("request_id") for r in log.records()}
    assert {"rid-42", gen_rid} <= rids
    outcomes = {r["outcome"] for r in log.records()}
    assert outcomes == {"ok"}
