"""Each driver rehearsed end to end on the CPU at a cut size: the whole of a
run but for the look for a chip.  A rehearsal's numbers are never printed
under a device metric's name."""

import json
import os
import subprocess
import sys

import pytest
from bench_cut import CELLS, ROOT, cut

from benchmark.harness import main, registry


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "per_layer"])
@pytest.mark.parametrize("cell_name", CELLS)
def test_rehearse_cell_on_cpu(cell_name, trace, tmp_path):
    bench, cell, config = cut(cell_name)
    result = main.run_cell(
        bench, cell, config, seed=2_500_000_001 + trace, seconds=0.5,
        trace=bool(trace), rehearsal=True, out_dir=str(tmp_path),
    )
    line = json.loads(json.dumps(result))  # the last line a run prints parses
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(result)[-1] == "comparisons"  # each number beside its limit, last in the line
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    assert line["rehearsal"] == "cpu" and line["device"]["platform"] == "cpu"
    assert line["metrics"] and all(k.startswith("cpu_rehearsal.") for k in line["metrics"])
    section = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in registry.metrics_for(bench, section, cell_name)}
    got = {k[len("cpu_rehearsal."):] for k in line["metrics"]}
    assert got <= wanted
    if not trace:
        assert got == wanted and "setup_s" in got
    assert all(limit in (0.0, 1e-4) for _, limit in line["comparisons"].values())


def test_same_seed_same_inputs():
    """The seed decides weights, ring and chunks: two set-ups of one seed
    report the same first-call losses, another seed reports others."""
    from benchmark.harness import spans

    def first_losses(seed):
        _, cell, config = cut("wallrunner_cnn_burst")
        driver = registry.load_driver(cell["driver"])(cell, config, seed, spans.Spans())
        driver.setup()
        driver.free()
        return float(driver.first["loss_q"]), float(driver.first["loss_pi"])

    a, b, c = first_losses(3_000_000_019), first_losses(3_000_000_019), first_losses(7)
    assert a == b and a != c


def test_cli_refuses_without_a_chip_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert run.returncode != 0
    assert run.stdout.strip() == "" and "No result" in run.stderr


def test_cli_refuses_an_unknown_cell():
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "no_such_cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert run.returncode != 0 and run.stdout.strip() == ""
