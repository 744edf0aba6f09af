"""End-to-end smoke of the observability stack: telemetry + profiler.

Runs a tiny CPU training job through the real CLI entry point with
telemetry enabled and a ``--profile-epochs 1:2`` window, then asserts
the contract docs/OBSERVABILITY.md promises:

- ``<run_dir>/telemetry.jsonl`` exists, every line is strict JSON, and
  there is one ``epoch`` event per epoch with the full 8-phase
  classification whose per-phase sums cover ~the epoch wall time;
- ``<run_dir>/trace`` holds a TensorBoard/xprof-loadable XLA trace
  (``plugins/profile/<ts>/*``) captured over exactly the window;
- ``<run_dir>/metrics.jsonl`` rows carry the save/sentinel accounting
  metrics and parse as strict JSON.

The ``make trace-smoke`` gate; ~60s on a 2-thread CPU host.
"""

import json
import os
import sys
import tempfile
from pathlib import Path

# A CPU smoke: this process and every child it starts are held to the
# CPU (jax reads the variable when it is imported; children inherit it).
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = (
    "act", "env_step", "stage", "place_chunk", "burst_dispatch",
    "drain", "sentinel", "checkpoint", "param_sync",
)


def fail(msg):
    print(f"[trace-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def main():
    import jax

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    root = Path(tempfile.mkdtemp(prefix="trace_smoke_"))
    train_main([
        "--environment", "Pendulum-v1",
        "--devices", "1",
        "--runs-root", str(root),
        "--epochs", "2",
        "--steps-per-epoch", "60",
        "--start-steps", "20",
        "--update-after", "20",
        "--update-every", "10",
        "--batch-size", "16",
        "--buffer-size", "500",
        "--hidden-sizes", "16,16",
        "--max-ep-len", "100",
        "--telemetry", "true",
        "--profile-epochs", "1:2",
    ])
    run_dir = next((root / "Default").iterdir())
    print(f"[trace-smoke] run dir: {run_dir}")

    # --- telemetry JSONL stream ---
    tpath = run_dir / "telemetry.jsonl"
    if not tpath.exists():
        fail(f"no telemetry stream at {tpath}")
    events = [json.loads(line) for line in tpath.read_text().splitlines()]
    epochs = [e for e in events if e["type"] == "epoch"]
    if events[0]["type"] != "run_start" or events[0]["phases"] != list(PHASES):
        fail(f"bad run_start header: {events[0]}")
    if len(epochs) != 2:
        fail(f"expected 2 epoch events, got {len(epochs)}")
    for ev in epochs:
        missing = [p for p in PHASES if p not in ev["phases"]]
        if missing:
            fail(f"epoch {ev['epoch']} missing phases {missing}")
        covered = sum(p["total_s"] for p in ev["phases"].values())
        # The phases partition the epoch: their sums must cover ~the
        # wall time (scheduler noise allows a small under-run, and
        # nothing can exceed it by more than jitter).
        if not 0.8 * ev["wall_s"] <= covered <= 1.1 * ev["wall_s"]:
            fail(
                f"epoch {ev['epoch']}: phase sums {covered:.4f}s do not "
                f"cover wall_s {ev['wall_s']:.4f}s"
            )
    print(f"[trace-smoke] telemetry ok: {len(epochs)} epoch events, "
          f"phase coverage verified")

    # --- XLA trace (the --profile-epochs window) ---
    profile_dir = run_dir / "trace" / "plugins" / "profile"
    if not profile_dir.is_dir():
        fail(f"no profiler capture under {profile_dir}")
    captures = [
        f for d in profile_dir.iterdir() if d.is_dir()
        for f in d.iterdir()
    ]
    if not captures:
        fail(f"profiler capture directory {profile_dir} is empty")
    print(f"[trace-smoke] trace ok: {len(captures)} artifact(s) under "
          f"{profile_dir}")

    # --- metrics mirror carries the epoch-accounting satellites ---
    rows = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    for row in rows:
        for key in ("sentinel_s", "save_s", "env_steps_per_sec"):
            if key not in row:
                fail(f"metrics row missing {key}: {row}")
    print("[trace-smoke] metrics mirror ok "
          f"({len(rows)} rows with save/sentinel accounting)")
    print("[trace-smoke] PASS")


if __name__ == "__main__":
    main()
