"""Compute-cost attribution smoke: cost events, roofline, one trace.

Runs, on CPU (the tier-1 environment — ``cost_analysis()`` works on
CPU-lowered programs), the whole cost-attribution contract
(docs/OBSERVABILITY.md "Cost attribution & roofline"):

1. a short host-Trainer run with telemetry on → every epoch after the
   first update epoch carries a ``cost`` event whose roofline record
   is present and finite, `cost/` columns land in metrics.jsonl, and
   epoch events carry host/device/input attribution;
2. an in-process serve round (PolicyServer + HTTP /act with an
   ``X-Request-Id``) → ``/metrics`` exposes per-bucket ``costs``
   entries, and the registered per-bucket FLOPs are MONOTONE in the
   bucket size (a bigger batch must cost more);
3. one cross-plane Perfetto export → the file loads as valid JSON,
   timestamps are sorted, and BOTH planes' spans (training phases +
   at least one serve request span) share the timeline.

The ``make cost-smoke`` gate; ~60s on a 2-thread CPU host.
"""

import json
import math
import os
import sys
import tempfile
from pathlib import Path
from urllib import request as urlreq

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fail(msg):
    print(f"[cost-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def check_finite(record, path):
    for k, v in record.items():
        if isinstance(v, dict):
            check_finite(v, f"{path}.{k}")
        elif isinstance(v, float) and not math.isfinite(v):
            fail(f"non-finite value at {path}.{k}: {v}")


def main():
    import jax

    sys.path.insert(0, REPO)
    # Pin the roofline denominators: a host CPU has no device-kind
    # entry, and the classification path must still be exercised.
    os.environ.setdefault("TAC_PEAK_FLOPS", "1e12")
    os.environ.setdefault("TAC_PEAK_BW", "1e11")

    import jax.numpy as jnp

    from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog
    from torch_actor_critic_tpu.models import Actor
    from torch_actor_critic_tpu.parallel import make_mesh
    from torch_actor_critic_tpu.sac.trainer import Trainer
    from torch_actor_critic_tpu.serve import ModelRegistry, PolicyServer
    from torch_actor_critic_tpu.telemetry import (
        RequestSpanLog,
        TelemetryRecorder,
        export_trace,
        get_cost_registry,
    )
    from torch_actor_critic_tpu.telemetry.traceview import (
        compile_events,
        serve_request_events,
        training_events,
    )
    from torch_actor_critic_tpu.utils.config import SACConfig
    from torch_actor_critic_tpu.utils.tracking import Tracker

    # --- 1. training plane: cost events + metrics columns ---
    root = Path(tempfile.mkdtemp(prefix="cost_smoke_"))
    tracker = Tracker(experiment="cost", root=root)
    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=16, epochs=2, steps_per_epoch=40,
        start_steps=10, update_after=10, update_every=10, buffer_size=500,
        max_ep_len=100, telemetry=True,
    )
    rec = TelemetryRecorder(run_dir=tracker.run_dir)
    tr = Trainer(
        "Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker,
        telemetry=rec,
    )
    try:
        tr.train()
    finally:
        tr.close()

    events = [
        json.loads(line)
        for line in (tracker.run_dir / "telemetry.jsonl").read_text()
        .splitlines()
    ]
    cost_events = [e for e in events if e["type"] == "cost"]
    if len(cost_events) != cfg.epochs:
        fail(f"expected {cfg.epochs} cost events, got {len(cost_events)}")
    for ev in cost_events:
        programs = ev.get("programs") or {}
        if "train/update_burst" not in programs:
            fail(f"cost event missing train/update_burst: {ev}")
        rl = programs["train/update_burst"]
        for key in ("flops_per_call", "bytes_per_call",
                    "achieved_flops_per_sec", "arithmetic_intensity",
                    "mfu", "bound"):
            if key not in rl:
                fail(f"cost record missing {key}: {rl}")
        if rl["flops_per_call"] <= 0 or rl["bytes_per_call"] <= 0:
            fail(f"degenerate cost record: {rl}")
        if rl["bound"] not in ("compute", "memory"):
            fail(f"bad roofline class: {rl['bound']}")
        check_finite(rl, "cost")
    epochs = [e for e in events if e["type"] == "epoch"]
    for ev in epochs:
        attr = ev.get("attribution")
        if not attr or attr["class"] not in (
            "host-bound", "device-bound", "input-bound"
        ):
            fail(f"epoch {ev['epoch']} missing/bad attribution: {attr}")
    rows = [
        json.loads(line)
        for line in (tracker.run_dir / "metrics.jsonl").read_text()
        .splitlines()
    ]
    for row in rows:
        for key in ("cost/update_burst_gflops",
                    "cost/update_burst_achieved_gflops_s",
                    "cost/update_burst_mfu"):
            if key not in row or row[key] is None or row[key] <= 0:
                fail(f"metrics row missing/bad {key}: {row}")
    print(f"[cost-smoke] training plane ok: {len(cost_events)} cost "
          f"events, attribution on {len(epochs)} epochs, cost/ columns "
          "in metrics.jsonl")

    # --- 2. serving plane: /metrics costs + FLOPs monotone in bucket ---
    actor = Actor(act_dim=2, hidden_sizes=(16, 16))
    params = actor.init(
        jax.random.key(0), jnp.zeros((3,)), jax.random.key(1)
    )
    registry = ModelRegistry()
    registry.register(
        "default", actor, jax.ShapeDtypeStruct((3,), jnp.float32),
        params=params, max_batch=8,
    )
    cost_reg = get_cost_registry()
    flops = {}
    for bucket in (2, 4, 8):
        cost = cost_reg.get(f"serve/forward[b{bucket}]")
        if cost is None or cost["flops"] <= 0:
            fail(f"no registered cost for serve/forward[b{bucket}]")
        flops[bucket] = cost["flops"]
    if not (flops[2] < flops[4] < flops[8]):
        fail(f"per-bucket FLOPs not monotone in batch size: {flops}")

    span_log = RequestSpanLog()
    with PolicyServer(
        registry, port=0, max_batch=8, span_log=span_log
    ) as srv:
        srv.start()
        for i in range(6):
            req = urlreq.Request(
                srv.address + "/act",
                data=json.dumps({"obs": [0.1, 0.2, 0.3]}).encode(),
                headers={
                    "Content-Type": "application/json",
                    "X-Request-Id": f"smoke-{i}",
                },
            )
            resp = urlreq.urlopen(req, timeout=30)
            if resp.headers.get("X-Request-Id") != f"smoke-{i}":
                fail("X-Request-Id not echoed on the response")
        snap = json.loads(
            urlreq.urlopen(srv.address + "/metrics", timeout=30).read()
        )
        costs = snap.get("costs") or {}
        if not costs:
            fail(f"/metrics has no costs section: {sorted(snap)}")
        for name, entry in costs.items():
            for key in ("flops_per_call", "achieved_flops_per_sec",
                        "mfu", "bound"):
                if key not in entry:
                    fail(f"/metrics costs[{name}] missing {key}: {entry}")
            check_finite(entry, f"costs.{name}")
    print(f"[cost-smoke] serving plane ok: /metrics costs for "
          f"{sorted(costs)}, FLOPs monotone over buckets {sorted(flops)}")

    # --- 3. cross-plane trace export ---
    trace_path = root / "trace.json"
    summary = export_trace(
        trace_path,
        training_events(rec),
        serve_request_events(span_log.records()),
        compile_events(get_watchdog().compile_log()),
    )
    trace = json.loads(trace_path.read_text())  # valid JSON or dies
    span_events = [
        e for e in trace["traceEvents"] if e.get("ph") in ("B", "E")
    ]
    ts = [e["ts"] for e in span_events]
    if ts != sorted(ts):
        fail("trace events not sorted by timestamp")
    if summary["train_spans"] == 0:
        fail("trace has no training phase spans")
    if summary["serve_spans"] == 0:
        fail("trace has no serve request spans")
    names = {e["name"] for e in span_events}
    if "request" not in names or "act" not in names:
        fail(f"expected both planes' span names in trace, got {names}")
    print(f"[cost-smoke] trace ok: {summary['train_spans']} train + "
          f"{summary['serve_spans']} serve + {summary['compile_spans']} "
          f"compile spans in {trace_path}")
    print("[cost-smoke] PASS")


if __name__ == "__main__":
    main()
