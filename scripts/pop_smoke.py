"""End-to-end smoke of population-fused training with on-device PBT.

Runs a tiny CPU population through the real CLI entry point
(``--on-device true --population 4 --pbt-every 1 --telemetry true``)
and asserts the contract docs/SCALING.md "population" promises:

- N DISTINCT finite learning curves: every ``metrics.jsonl`` row
  carries ``loss_q_m0..N-1`` / ``reward_m0..N-1`` member curves plus
  the suffix-keyed aggregates, all finite, and the members are not one
  curve copied N times;
- at least one PBT exploit event: a schema-valid ``pbt`` record in
  ``telemetry.jsonl`` whose ``exploited`` list is non-empty, with
  per-member hyperparameters that actually diverged (explore);
- a successful ``--run`` resume of the population checkpoint (stacked
  state + member PRNG keys + per-member hyperparams).

The ``make pop-smoke`` gate; ~90s on a 2-thread CPU host.
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

N = 4
EPOCHS = 3


def fail(msg):
    print(f"[pop-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    import jax

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    root = Path(tempfile.mkdtemp(prefix="pop_smoke_"))
    # The on-device pendulum truncates at its own max_episode_steps
    # (200); sized so every env finishes an episode during epoch 1
    # (20 warmup + 2x100 steps > 200) — the exploit gate (every member
    # ranked) opens at that pbt_every boundary.
    final = train_main([
        "--environment", "Pendulum-v1",
        "--on-device", "true",
        "--population", str(N),
        "--pbt-every", "1",
        "--pbt-quantile", "0.25",
        "--telemetry", "true",
        "--devices", "1",
        "--runs-root", str(root),
        "--epochs", str(EPOCHS),
        "--steps-per-epoch", "100",
        "--update-every", "10",
        "--start-steps", "20",
        "--update-after", "0",
        "--batch-size", "16",
        "--buffer-size", "800",
        "--hidden-sizes", "16,16",
        "--on-device-envs", "2",
    ])
    run_dir = next((root / "Default").iterdir())
    print(f"[pop-smoke] run dir: {run_dir}")

    # --- N distinct finite learning curves ---
    rows = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    if len(rows) != EPOCHS:
        fail(f"expected {EPOCHS} metrics rows, got {len(rows)}")
    for row in rows:
        for base in ("loss_q", "loss_pi", "reward"):
            for i in range(N):
                key = f"{base}_m{i}"
                if key not in row:
                    fail(f"metrics row missing {key}")
                if base != "reward" and row[key] is None:
                    # tracker maps non-finite to null; reward is
                    # legitimately null for a no-episode epoch
                    fail(f"{key} is null (non-finite) in {row}")
    curves = [
        tuple(row[f"loss_q_m{i}"] for row in rows) for i in range(N)
    ]
    for i, c in enumerate(curves):
        if not all(math.isfinite(v) for v in c):
            fail(f"member {i} loss_q curve non-finite: {c}")
    if len(set(curves)) != N:
        fail(f"member curves are not distinct: {curves}")
    if any(f"loss_q_m{N}" in row for row in rows):
        fail(f"phantom member {N} in metrics")
    print(f"[pop-smoke] metrics ok: {N} distinct finite member curves "
          f"over {len(rows)} epochs")

    # --- PBT exploit events, schema-valid ---
    events = [
        json.loads(line)
        for line in (run_dir / "telemetry.jsonl").read_text().splitlines()
    ]
    pbt = [e for e in events if e.get("type") == "pbt"]
    if not pbt:
        fail("no pbt telemetry events")
    for e in pbt:
        missing = {"epoch", "exploited", "src", "ready", "return_ema",
                   "hyperparams"} - set(e)
        if missing:
            fail(f"pbt event missing {missing}: {e}")
        if len(e["src"]) != N or len(e["return_ema"]) != N:
            fail(f"pbt event arrays not member-shaped: {e}")
    exploits = [e for e in pbt if e["exploited"]]
    if not exploits:
        fail(f"no exploit fired in {len(pbt)} pbt steps "
             f"(ready={[e['ready'] for e in pbt]})")
    ev = exploits[0]
    for loser in ev["exploited"]:
        if ev["src"][loser] == loser:
            fail(f"exploited member {loser} has itself as src: {ev}")
    hp = ev["hyperparams"]
    if not hp:
        fail("pbt event carries no hyperparameters")
    for k, v in hp.items():
        if len(v) != N:
            fail(f"hyperparam {k} not per-member: {v}")
        if len(set(v)) == 1:
            fail(f"hyperparam {k} identical across members (no explore): {v}")
    print(f"[pop-smoke] pbt ok: {len(pbt)} steps, "
          f"{sum(len(e['exploited']) for e in exploits)} exploits, "
          f"hyperparams diverged: {sorted(hp)}")

    # --- resume the population checkpoint ---
    resumed = train_main(
        ["--run", run_dir.name, "--runs-root", str(root)]
    )
    for i in range(N):
        v = resumed.get(f"loss_q_m{i}")
        if v is None or not math.isfinite(float(v)):
            fail(f"resumed loss_q_m{i} non-finite: {v!r}")
    rows_after = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    if len(rows_after) <= len(rows):
        fail(f"resume logged no new epochs ({len(rows_after)} rows)")
    print(f"[pop-smoke] resume ok: {len(rows_after) - len(rows)} more "
          f"epochs, {N} members still finite")
    print(f"[pop-smoke] final: "
          f"{ {k: round(v, 3) for k, v in final.items() if k.startswith('loss_q_m')} }")
    print("[pop-smoke] PASS")


if __name__ == "__main__":
    main()
