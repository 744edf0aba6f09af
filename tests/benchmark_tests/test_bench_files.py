"""Every piece of the benchmark is found by the name BENCHMARK.json gives it,
fits its schema, and can be added as new files plus entries."""

import json
import os
import re
import shutil

import pytest
from bench_cut import ROOT

from benchmark.harness import registry

BENCH = registry.load_benchmark()
WHOLE = registry.load_benchmark(parked=True)  # with the parked cells' entries
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_ENDINGS = (  # what ``reduced`` may never name; a vocabulary's size is no width
    "hidden_size", "intermediate_size", "latent_size", "state_size", "hidden_sizes",
    "_dim", "_rank", "_width", "_per_tok", "filters", "kernel_sizes", "strides",
    "dense_size", "features",
)


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
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and len(entry["why"]) <= 200
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    cfg = registry.load_config(entry["name"])
    assert cfg["reduced"] == entry["reduced"]
    # a width is told by its key's ending (``num_hidden_layers`` is a depth, the
    # one cut every catalog model needs), a head size by the word
    assert not any(key.endswith(WIDTH_ENDINGS) or "head" in key for key in entry["reduced"])
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    assert cfg["reference_mode"] in ("highest", "bf16_operands")
    assert cfg["control"]["reference_mode"] == "fp8_operands"


@pytest.mark.parametrize("entry", WHOLE["workloads"], ids=lambda w: w["name"])
def test_workload_file_and_driver(entry):
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    _, cell, config = registry.resolve(entry["name"], parked=True)
    assert cell["why"] == entry["why"]
    assert set(cell["limits"]) == {"loss_q", "loss_pi", "adam_nu", "param_change"}
    driver = registry.load_driver(cell["driver"])
    for method in ("setup", "window", "per_window", "free", "check", "control", "at_rest_bytes"):
        assert callable(getattr(driver, method)), method
    reported = [m["name"] for m in registry.metrics_for(WHOLE, "end_to_end", entry["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert registry.metrics_for(WHOLE, "per_layer", entry["name"])


@pytest.mark.parametrize(
    "metric", WHOLE["end_to_end"] + WHOLE["per_layer"], ids=lambda m: m["name"]
)
def test_metric_entry(metric):
    end_to_end = metric in WHOLE["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"}
    )
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    cells = {w["name"] for w in WHOLE["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert callable(registry.load_layer_metric(metric["name"]))
        moved = next(m for m in WHOLE["end_to_end"] if m["name"] == metric["moves"])
        # every cell that reads this metric reports the metric it moves
        assert set(metric.get("workloads", cells)) <= set(moved.get("workloads", cells))
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


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
