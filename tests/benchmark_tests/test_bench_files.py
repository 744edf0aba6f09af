"""Every piece of the benchmark is found by the name BENCHMARK.json gives it,
fits its schema, and can be added as new files plus entries."""

import json
import os
import shutil

import bench_cut
import pytest
from bench_cut import ROOT

from benchmark.harness import registry

BENCH = registry.load_benchmark()
WHOLE = registry.load_benchmark(parked=True)  # with the parked cells' entries


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert all(os.path.isdir(os.path.join(ROOT, p)) for p in BENCH["paths"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile and
    # 1200 s spare have to fit 43200 s with the full 24 cells.
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(entry):
    """Its entry and file fit; ``reduced`` names no width (a key's ending tells
    one: ``bench_cut.WIDTH_ENDINGS``) and, where it names a count of heads,
    groups or experts held, the file says of what deployment and how."""
    bench_cut.check_configuration(BENCH, entry)


@pytest.mark.parametrize("entry", WHOLE["workloads"], ids=lambda w: w["name"])
def test_workload_file_and_driver(entry):
    bench_cut.check_workload(WHOLE, entry)


@pytest.mark.parametrize(
    "metric", WHOLE["end_to_end"] + WHOLE["per_layer"], ids=lambda m: m["name"]
)
def test_metric_entry(metric):
    bench_cut.check_metric(WHOLE, metric)


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in WHOLE[section]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in WHOLE["end_to_end"] + WHOLE["per_layer"]]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in WHOLE["workloads"]]
    assert len(pairs) == len(set(pairs))


PARKED_DIR = os.path.join(ROOT, "benchmark", "parked")


@pytest.mark.parametrize("file", sorted(os.listdir(PARKED_DIR)))
def test_a_parked_cell_is_out_of_every_run_and_whole_in_its_file(file):
    """``parked/<cell>.json`` holds what BENCHMARK.json would need for the cell
    and BENCHMARK.json names it nowhere: a run of it is refused by name, and
    putting it back is adding these entries."""
    with open(os.path.join(PARKED_DIR, file)) as f:
        parked = json.load(f)
    name = file[: -len(".json")]
    assert set(parked) == {"why", "workloads", "end_to_end", "per_layer"} and parked["why"]
    assert [w["name"] for w in parked["workloads"]] == [name]
    assert name not in json.dumps(BENCH)
    with pytest.raises(registry.BenchmarkError, match="no workload"):
        registry.resolve(name)
    assert registry.resolve(name, parked=True)[1]["name"] == name
    for m in parked["end_to_end"] + parked["per_layer"]:
        assert m["workloads"] == [name]
    moved = {m["moves"] for m in parked["per_layer"]}
    assert moved <= {m["name"] for m in parked["end_to_end"]} | {"setup_s"}
    # merged, every metric of the cell is there once and lists the cell
    for section in ("end_to_end", "per_layer"):
        for m in parked[section]:
            (kept,) = [e for e in WHOLE[section] if e["name"] == m["name"]]
            assert name in kept["workloads"]


def test_a_later_pr_adds_files_and_entries_only(tmp_path):
    """A configuration, a cell, a driver and a per-layer metric, each added
    as a new file plus an entry; no file that was there is edited."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark")
    bdir = str(root / "benchmark")
    config = registry.load_config("wallrunner_cnn")
    config["sac"]["batch_size"] = 512
    (root / "benchmark/configs/wallrunner_cnn_b512.json").write_text(json.dumps(config))
    cell = registry.load_workload("wallrunner_cnn_burst")
    cell.update(config="wallrunner_cnn_b512", driver="burst_b")
    (root / "benchmark/workloads/wallrunner_cnn_burst_b512.json").write_text(json.dumps(cell))
    shutil.copy(os.path.join(bdir, "drivers/burst.py"), os.path.join(bdir, "drivers/burst_b.py"))
    (root / "benchmark/layer_metrics/ops.sample_us_per_step.py").write_text(
        "def read(ctx):\n    return None\n"
    )
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "wallrunner_cnn_b512", "source": config["source"][:200],
        "file": "benchmark/configs/wallrunner_cnn_b512.json",
        "reduced": ["buffer_size"], "why": "batch 512",
    })
    bench["workloads"].append({
        "name": "wallrunner_cnn_burst_b512", "config": "wallrunner_cnn_b512",
        "traffic": "replay_burst", "chips": 1, "why": cell["why"],
    })
    bench["per_layer"].append({
        "name": "ops.sample_us_per_step", "unit": "us", "better": "lower",
        "source": "device_trace", "layer": "kernels and device ops",
        "moves": "grad_steps_per_s", "workloads": ["wallrunner_cnn_burst_b512"],
    })
    for m in bench["end_to_end"]:
        if m["name"] == "grad_steps_per_s":
            m["workloads"].append("wallrunner_cnn_burst_b512")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    got, new_cell, new_config = registry.resolve("wallrunner_cnn_burst_b512", str(root))
    assert new_config["sac"]["batch_size"] == 512 and new_cell["driver"] == "burst_b"
    assert registry.load_driver("burst_b", bdir).__name__ == "Driver"
    names = [m["name"] for m in registry.metrics_for(got, "per_layer", "wallrunner_cnn_burst_b512")]
    assert "ops.sample_us_per_step" in names and "shell.compile_s" in names
    assert registry.load_layer_metric("ops.sample_us_per_step", bdir)(None) is None


@pytest.mark.parametrize("what", ["workload", "config", "driver", "metric"])
def test_a_missing_piece_is_named(what):
    with pytest.raises(registry.BenchmarkError, match="missing|no workload"):
        {
            "workload": lambda: registry.resolve("no_such_cell"),
            "config": lambda: registry.load_config("no_such_config"),
            "driver": lambda: registry.load_driver("no_such_driver"),
            "metric": lambda: registry.load_layer_metric("no.such_metric"),
        }[what]()
