"""End-to-end smoke of the scenarios/ subsystem through the real CLI.

Trains every scenario pillar for a few epochs on CPU via
``python -m torch_actor_critic_tpu.train`` and asserts the contract
docs/SCENARIOS.md promises:

- **multi-agent** — finite losses plus per-agent reward curves
  (``reward_a0..A-1``) in metrics.jsonl;
- **procedural** — the hurdle-runner trains with finite losses and a
  finite mean return (level regeneration riding the fused loop);
- **multi-task** — schema-valid per-task metrics (``reward_t{i}`` /
  ``episodes_t{i}`` for every task, per-task episode counts summing to
  the total) from the striped-replay run, AND a **bitwise resume**: a
  population run interrupted at epoch 1 and resumed reproduces the
  uninterrupted run's member loss curves exactly (the population
  checkpoint carries env states, act keys and the striped rings).

The ``make scenario-smoke`` gate; ~2-3 min on a 2-thread CPU host.
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


def fail(msg):
    print(f"[scenario-smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


BASE_ARGS = [
    "--on-device", "true",
    "--devices", "1",
    "--steps-per-epoch", "100",
    "--update-every", "10",
    "--start-steps", "20",
    "--update-after", "0",
    "--batch-size", "15",
    "--buffer-size", "3000",
    "--hidden-sizes", "16,16",
    "--on-device-envs", "4",
    "--save-every", "1",
]


def read_rows(run_dir: Path):
    return [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]


def run_dir_of(root: Path):
    return next((root / "Default").iterdir())


def main():
    import jax

    sys.path.insert(0, REPO)
    from torch_actor_critic_tpu.train import main as train_main

    # --- multi-agent: per-agent curves under the fused loop ---
    root = Path(tempfile.mkdtemp(prefix="scen_ma_"))
    final = train_main([
        "--environment", "multi-pendulum-2",
        "--runs-root", str(root), "--epochs", "2", *BASE_ARGS,
    ])
    for key in ("loss_q", "loss_pi"):
        if not math.isfinite(final[key]):
            fail(f"multi-agent {key} non-finite: {final[key]}")
    rows = read_rows(run_dir_of(root))
    for row in rows:
        for agent in range(2):
            if f"reward_a{agent}" not in row:
                fail(f"multi-agent row missing reward_a{agent}: {sorted(row)}")
    # Episodes finish from epoch 1 (200-step episodes, 4 envs x 100
    # steps/epoch + warmup): the last row's per-agent rewards are real.
    last = rows[-1]
    for agent in range(2):
        v = last[f"reward_a{agent}"]
        if v is None or not math.isfinite(v):
            fail(f"reward_a{agent} non-finite in final epoch: {v!r}")
    print("[scenario-smoke] multi-agent ok: per-agent curves "
          f"a0={last['reward_a0']:.1f} a1={last['reward_a1']:.1f}")

    # --- procedural: fresh level per episode, fused loop ---
    root = Path(tempfile.mkdtemp(prefix="scen_proc_"))
    final = train_main([
        "--environment", "hurdle-runner",
        "--runs-root", str(root), "--epochs", "2", *BASE_ARGS,
        # Hurdle episodes truncate at 300 steps; 2 x 200 steps x 4 envs
        # finishes episodes inside the run (argparse keeps the last
        # occurrence, overriding BASE_ARGS' 100).
        "--steps-per-epoch", "200",
    ])
    if not math.isfinite(final["loss_q"]):
        fail(f"procedural loss_q non-finite: {final['loss_q']}")
    if not math.isfinite(final["reward"]):
        fail(f"procedural reward non-finite: {final['reward']}")
    print(f"[scenario-smoke] procedural ok: reward={final['reward']:.1f}")

    # --- multi-task: per-task metric schema ---
    root = Path(tempfile.mkdtemp(prefix="scen_mt_"))
    final = train_main([
        "--environment", "pendulum-multitask",
        "--runs-root", str(root), "--epochs", "3", *BASE_ARGS,
        "--on-device-envs", "8",
    ])
    n_tasks = 3
    rows = read_rows(run_dir_of(root))
    for row in rows:
        total = 0.0
        for task in range(n_tasks):
            for base in ("reward_t", "episodes_t"):
                if f"{base}{task}" not in row:
                    fail(f"multi-task row missing {base}{task}: {sorted(row)}")
            total += row[f"episodes_t{task}"]
        if total != row["episodes"]:
            fail(
                f"per-task episodes {total} != total {row['episodes']}"
            )
        if f"reward_t{n_tasks}" in row:
            fail(f"phantom task {n_tasks} in metrics: {sorted(row)}")
    # Episodes truncate at 200 steps, so not every epoch finishes one
    # (a no-episode epoch honestly reports null); SOME epoch must have
    # produced finite per-task rewards.
    finite_t = sorted({
        t for row in rows for t in range(n_tasks)
        if row[f"reward_t{t}"] is not None
        and math.isfinite(row[f"reward_t{t}"])
    })
    if not finite_t:
        fail(f"no task produced a finite reward curve: {rows}")
    print(f"[scenario-smoke] multi-task ok: schema-valid per-task "
          f"metrics, finite tasks {finite_t}")

    # --- bitwise resume: interrupted+resumed == uninterrupted ---
    # The population driver checkpoints the COMPLETE scenario state
    # (stacked learners, striped rings, env states incl. task ids,
    # act keys), so a resumed run must reproduce the uninterrupted
    # member curves exactly.
    def population_run(root, epochs):
        return train_main([
            "--environment", "pendulum-multitask",
            "--runs-root", str(root), "--epochs", str(epochs),
            "--population", "2", *BASE_ARGS,
        ])

    root_full = Path(tempfile.mkdtemp(prefix="scen_full_"))
    population_run(root_full, 3)
    rows_full = read_rows(run_dir_of(root_full))

    root_cut = Path(tempfile.mkdtemp(prefix="scen_cut_"))
    population_run(root_cut, 1)  # "interrupted" after epoch 0's save
    cut_dir = run_dir_of(root_cut)
    # Resume runs config.epochs (1) more epochs per invocation.
    for _ in range(2):
        train_main(["--run", cut_dir.name, "--runs-root", str(root_cut)])
    rows_cut = read_rows(cut_dir)
    if len(rows_cut) != len(rows_full):
        fail(
            f"resumed run logged {len(rows_cut)} epochs vs "
            f"{len(rows_full)} uninterrupted"
        )
    compare = [
        k for k in rows_full[-1]
        if k.startswith(("loss_q_m", "loss_pi_m", "reward_m", "episodes"))
    ]
    for full_row, cut_row in zip(rows_full, rows_cut):
        for k in compare:
            if full_row.get(k) != cut_row.get(k):
                fail(
                    f"resume not bitwise at epoch {full_row['step']}: "
                    f"{k} {full_row.get(k)!r} != {cut_row.get(k)!r}"
                )
    print(f"[scenario-smoke] resume ok: {len(compare)} member-metric "
          f"keys bitwise across {len(rows_full)} epochs")
    print("[scenario-smoke] PASS")


if __name__ == "__main__":
    main()
