"""End-to-end smoke of the tiered replay store + data flywheel.

Drives the whole docs/REPLAY.md surface through the REAL CLI entry
point (``train.py``), asserting the contracts the subsystem promises:

1. **Bitwise HBM tier** — a run with ``--replay-tiers host`` produces
   the exact same per-epoch loss stream as the tiers-off run at the
   same seed (tier 0 is today's device ring, bit for bit; the shadow
   accounting never touches the jit path), and the tiers-off run emits
   ZERO ``replay/`` metric columns (default-off means invisible).
2. **Spill → evict → refill → prefetch** — a run with the disk tier, a
   tiny disk budget (forces fifo eviction) and ``--replay-refill`` on:
   finite losses, chunks + manifest on disk, evictions counted, refills
   served with prefetch hits, and the per-tier conservation invariant
   (``replay/conservation_ok``) holding on every epoch.
3. **Offline training from the spilled dataset** — ``train.py
   --offline`` pointed at the disk tier run (2) just wrote trains CQL-
   regularized SAC end-to-end with finite losses and a saved final
   checkpoint.

The ``make replay-smoke`` gate; ~90s on a 2-thread CPU host.
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

# The loss columns the bitwise A/B comparison pins.
LOSS_KEYS = ("loss_q", "loss_pi", "avg_return")

TINY = [
    "--environment", "Pendulum-v1",
    "--devices", "1",
    "--seed", "0",
    "--epochs", "3",
    "--steps-per-epoch", "120",
    "--start-steps", "30",
    "--update-after", "30",
    "--update-every", "10",
    "--batch-size", "16",
    "--buffer-size", "200",
    "--hidden-sizes", "16,16",
    "--max-ep-len", "100",
]


def fail(msg):
    print(f"[replay-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def run(train_main, root, extra):
    train_main(TINY + ["--runs-root", str(root)] + extra)
    run_dir = next((Path(root) / "Default").iterdir())
    rows = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    if not rows:
        fail(f"no metrics rows under {run_dir}")
    return run_dir, rows


def main():
    import jax

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    scratch = Path(tempfile.mkdtemp(prefix="replay_smoke_"))

    # ---- 1. bitwise HBM tier: off vs host-tier shadow ----------------
    _, rows_off = run(train_main, scratch / "a_off", [])
    _, rows_host = run(
        train_main, scratch / "b_host", ["--replay-tiers", "host"]
    )
    if any(k.startswith("replay/") for r in rows_off for k in r):
        fail("tiers-off run leaked replay/ metric columns")
    if len(rows_off) != len(rows_host):
        fail(f"epoch counts differ: {len(rows_off)} vs {len(rows_host)}")
    for ra, rb in zip(rows_off, rows_host):
        for key in LOSS_KEYS:
            if ra.get(key) != rb.get(key):
                fail(
                    f"loss stream diverged at step {ra.get('step')}: "
                    f"{key} {ra.get(key)!r} (off) vs {rb.get(key)!r} (host)"
                )
    for r in rows_host:
        if r.get("replay/conservation_ok") != 1.0:
            fail(f"host-tier conservation broken: {r}")
        if "replay/hbm_bytes" not in r or r["replay/hbm_bytes"] <= 0:
            fail("replay/hbm_bytes missing or non-positive")
    print(
        f"[replay-smoke] bitwise ok: {len(rows_off)} epochs, loss "
        "stream identical off vs host tier; conservation holds"
    )

    # ---- 2. spill -> evict -> refill -> prefetch through the CLI ----
    replay_dir = scratch / "disk_tier"
    _, rows_disk = run(train_main, scratch / "c_disk", [
        "--replay-tiers", "disk",
        "--replay-dir", str(replay_dir),
        "--replay-host-capacity", "120",
        "--replay-disk-bytes", "8192",    # a few chunks: forces fifo evict
        "--replay-refill", "2",
        "--replay-prefetch", "true",
    ])
    last = rows_disk[-1]
    for key in LOSS_KEYS[:2]:
        v = last.get(key)
        if v is None or not math.isfinite(float(v)):
            fail(f"disk-tier run non-finite {key}: {v!r}")
    for r in rows_disk:
        if r.get("replay/conservation_ok") != 1.0:
            fail(f"disk-tier conservation broken: {r}")
    if last.get("replay/spilled_disk_total", 0) <= 0:
        fail(f"no rows spilled to disk: {last}")
    if last.get("replay/disk_evicted_rows_total", 0) <= 0:
        fail(f"disk budget never evicted: {last}")
    if last.get("replay/refills_served", 0) <= 0:
        fail(f"no refills served: {last}")
    if last.get("replay/prefetch_hit_rate", 0) <= 0:
        fail(f"prefetch never hit: {last}")
    chunks = sorted(replay_dir.glob("chunk-*.npz"))
    if not chunks or not (replay_dir / "manifest.jsonl").exists():
        fail(f"disk tier artifacts missing under {replay_dir}")
    meta = json.loads((replay_dir / "meta.json").read_text())
    if meta.get("act_dim") != 1 or "obs" not in meta:
        fail(f"disk tier meta malformed: {meta}")
    print(
        f"[replay-smoke] tier flow ok: spilled "
        f"{last['replay/spilled_disk_total']:.0f} rows, evicted "
        f"{last['replay/disk_evicted_rows_total']:.0f}, "
        f"{last['replay/refills_served']:.0f} refills (hit rate "
        f"{last['replay/prefetch_hit_rate']:.2f}), "
        f"{len(chunks)} chunks resident"
    )

    # ---- 3. --offline from the dataset run (2) just spilled ----------
    off_root = scratch / "d_offline"
    train_main([
        "--runs-root", str(off_root),
        "--hidden-sizes", "16,16",
        "--batch-size", "16",
        "--offline", "true",
        "--offline-dataset", str(replay_dir),
        "--offline-steps", "60",
        "--offline-reg", "cql",
        "--offline-reg-weight", "0.5",
        "--seed", "0",
    ])
    off_dir = next((off_root / "Default").iterdir())
    off_rows = [
        json.loads(line)
        for line in (off_dir / "metrics.jsonl").read_text().splitlines()
    ]
    if not off_rows:
        fail("offline run wrote no metrics")
    final = off_rows[-1]
    for key in ("loss_q", "loss_pi", "offline/cql_gap"):
        v = final.get(key)
        if v is None or not math.isfinite(float(v)):
            fail(f"offline non-finite {key}: {v!r}")
    if final.get("offline/steps") != 60.0:
        fail(f"offline step count wrong: {final.get('offline/steps')}")
    ckpts = list((off_dir / "artifacts" / "checkpoints").glob("*"))
    if not ckpts:
        fail(f"offline run saved no checkpoint under {off_dir}")
    print(
        f"[replay-smoke] offline ok: 60 CQL steps from "
        f"{final['offline/dataset_rows']:.0f} spilled rows, "
        f"loss_q={final['loss_q']:.3f}, cql_gap="
        f"{final['offline/cql_gap']:.3f}, checkpoint saved"
    )
    print("[replay-smoke] PASS")


if __name__ == "__main__":
    main()
