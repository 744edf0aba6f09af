"""End-to-end smoke of the learning-health diagnostics stack.

Runs a tiny full-tier CPU training job through the real CLI entry point
(``--diagnostics full --telemetry true``) and asserts the contract
docs/OBSERVABILITY.md "Learning-health diagnostics" promises:

- every post-warmup ``metrics.jsonl`` row carries the full diagnostic
  key set, with finite (non-null) values;
- ``telemetry.jsonl`` holds one strict-JSON ``diagnostics`` event per
  update epoch whose TD-histogram snapshot is internally consistent
  (count > 0, p50 <= p95 <= p99 <= max);
- epoch events carry the recompilation watchdog's ``xla_compiles``
  count, which is positive and non-decreasing.

The ``make diag-smoke`` gate; ~60s on a 2-thread CPU host.
"""

import json
import math
import os
import sys
import tempfile
from pathlib import Path

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The metric keys every full-tier update epoch must report
# (docs/OBSERVABILITY.md metric glossary).
DIAG_KEYS = (
    "diag/grad_norm_q",
    "diag/grad_norm_pi",
    "diag/update_ratio_q",
    "diag/update_ratio_pi",
    "diag/q_min",
    "diag/q_max",
    "diag/q_spread",
    "diag/q_bias",
    "diag/act_sat",
    "diag/param_norm",
    "diag/td_abs_min",
    "diag/td_abs_max",
    "diag/td_abs_sum",
    "loss_q_max",
    "loss_pi_max",
    "early_warnings",
    "xla_compiles",
)


def fail(msg):
    print(f"[diag-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    import jax

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    root = Path(tempfile.mkdtemp(prefix="diag_smoke_"))
    train_main([
        "--environment", "Pendulum-v1",
        "--devices", "1",
        "--runs-root", str(root),
        "--epochs", "3",
        "--steps-per-epoch", "60",
        "--start-steps", "20",
        "--update-after", "20",
        "--update-every", "10",
        "--batch-size", "16",
        "--buffer-size", "500",
        "--hidden-sizes", "16,16",
        "--max-ep-len", "100",
        "--diagnostics", "full",
        "--telemetry", "true",
    ])
    run_dir = next((root / "Default").iterdir())
    print(f"[diag-smoke] run dir: {run_dir}")

    # --- metrics.jsonl: full diagnostic key set, finite values ---
    rows = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    if not rows:
        fail("no metrics rows")
    for row in rows:
        for key in DIAG_KEYS:
            if key not in row:
                fail(f"metrics row (step {row.get('step')}) missing {key}")
            v = row[key]
            # The tracker maps non-finite to null; a null diagnostic
            # means the in-graph reduction produced NaN/inf.
            if v is None or not math.isfinite(float(v)):
                fail(f"{key} is non-finite in step {row.get('step')}: {v!r}")
        if not (row["diag/q_min"] <= row["diag/q_max"]):
            fail(f"q_min > q_max in step {row.get('step')}")
        if row["diag/td_abs_min"] > row["diag/td_abs_max"]:
            fail(f"td_abs_min > td_abs_max in step {row.get('step')}")
        if not 0.0 <= row["diag/act_sat"] <= 1.0:
            fail(f"act_sat outside [0,1]: {row['diag/act_sat']}")
    print(f"[diag-smoke] metrics ok: {len(rows)} rows x {len(DIAG_KEYS)} "
          "diagnostic keys, all finite")

    # --- telemetry.jsonl: diagnostics events + watchdog counts ---
    events = [
        json.loads(line)
        for line in (run_dir / "telemetry.jsonl").read_text().splitlines()
    ]
    diag_events = [e for e in events if e["type"] == "diagnostics"]
    if len(diag_events) != len(rows):
        fail(
            f"expected {len(rows)} diagnostics events, got {len(diag_events)}"
        )
    for ev in diag_events:
        hist = ev.get("td_hist")
        if not hist or hist.get("td_abs_count", 0) <= 0:
            fail(f"epoch {ev['epoch']}: empty TD histogram snapshot {hist}")
        p50, p95, p99, mx = (
            hist["td_abs_p50"], hist["td_abs_p95"],
            hist["td_abs_p99"], hist["td_abs_max"],
        )
        if not p50 <= p95 <= p99 <= mx:
            fail(f"epoch {ev['epoch']}: TD percentiles disordered {hist}")
        for key in ("diag/grad_norm_q", "diag/q_bias", "diag/act_sat"):
            if key not in ev["metrics"]:
                fail(f"diagnostics event missing metrics[{key!r}]")
    epochs = [e for e in events if e["type"] == "epoch"]
    compiles = [e.get("xla_compiles") for e in epochs]
    if any(c is None or c <= 0 for c in compiles):
        fail(f"epoch events missing positive xla_compiles: {compiles}")
    if compiles != sorted(compiles):
        fail(f"xla_compiles not non-decreasing: {compiles}")
    print(f"[diag-smoke] telemetry ok: {len(diag_events)} diagnostics "
          f"events, TD histogram consistent, xla_compiles {compiles}")
    print("[diag-smoke] PASS")


if __name__ == "__main__":
    main()
