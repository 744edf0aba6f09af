"""A rehearsal of what a ``model_config`` PR does: a third trunk family's
configuration, cell, limit readings and entries go into a temporary copy of
the benchmark as added files and appended entries, nothing that was there is
edited, and every rule the benchmark's tests hold the old cells to
(``bench_cut``'s, each a function of the root) holds the new one.  The same
addition, broken one way at a time, fails the rule it should.

The configuration is made up (no catalog model): its cut names the depth, the
experts held, two head counts and a per-layer list of head counts, as a stack
whose layer kinds hold different numbers of query heads would need.
"""

import copy
import json
import os
import shutil

import bench_cut
import pytest
from bench_cut import ROOT

from benchmark.harness import registry

CONFIG, CELL = "madeup_trunk", "madeup_trunk_burst"
CUT = {  # key: (published, held here, how)
    "num_hidden_layers": (30, 5, "30 -> 5: the leading dense layer and one whole period of four"),
    "num_experts": (96, 8, "96 published -> 8 held here (experts 0-7 of a layer shared twelve ways)"),
    "num_attention_heads": (24, 6, "24 published -> 6 held here (query heads 0-5)"),
    "num_key_value_heads": (8, 2, "8 published -> 2 held here (key/value heads 0-1)"),
    "num_attention_heads_per_layer": (
        [24, 36, 36, 36, 24], [6, 9, 9, 9, 6], "a layer's 24 or 36 query heads -> 6 or 9 held here",
    ),
}
READINGS = {  # number: (limit, largest sound, smallest control, separates)
    "loss_q": (0.003, 0.001, 0.0012, False),
    "loss_pi": (0.003, 0.001, 0.0009, False),
    "adam_nu": (0.2, 0.08, 0.5, True),
    "param_change": (0.01, 0.003, 0.1, True),
    "router_choices": (0.004, 0.001, 0.05, True),
}


def a_trunk_cell(bench: dict) -> str:
    """A cell that is there and limits its router's choices: what the made-up
    family's files are modelled on, found by that and not by name."""
    return next(
        w["name"] for w in bench["workloads"]
        if "router_disagree_limit" in registry.load_workload(w["name"])["traffic"]
    )


def add(root, reduced_also=(), readings=READINGS, limit_file=True, in_moved_list=True) -> dict:
    """Copy the benchmark to ``root`` and add the made-up family to the copy:
    three new files and appended entries.  Returns the new ``BENCHMARK.json``."""
    bench = registry.load_benchmark()
    bdir = root / bench["paths"][0]
    shutil.copytree(  # but for the recorded traces, which no rule reads
        os.path.join(ROOT, bench["paths"][0]), bdir,
        ignore=shutil.ignore_patterns("__pycache__", "*.pb", "*.table.json"),
    )
    like = a_trunk_cell(bench)
    cell = registry.load_workload(like)
    base = registry.load_config(cell["config"])

    config = {k: copy.deepcopy(base[k]) for k in registry.CONFIG_KEYS}
    config.update(
        source="https://example.org/made-up/trunk/config.json (no model: a rehearsal)",
        hidden_size=1536, head_dim=64, moe_intermediate_size=512, num_experts_per_tok=6,
        sliding_window=256, vocab_size=50000,
        reduced=list(CUT) + list(reduced_also),
        reduced_how={key: how for key, (_, _, how) in CUT.items()},
        deployment="twelve chips share each layer by experts, heads four ways inside each of three replicas",
        assumed={"everything": "made up for tests/benchmark_tests/test_bench_addition.py"},
        **{key: held for key, (_, held, _) in CUT.items()},
    )
    (bdir / "configs" / f"{CONFIG}.json").write_text(json.dumps(config, indent=1))

    cell = copy.deepcopy(cell)
    cell.update(
        config=CONFIG, why="a made-up third trunk family's cell: added files and entries alone",
        limits={n: READINGS[n][0] for n in bench_cut.NUMBERS},
    )
    cell["traffic"]["router_disagree_limit"] = READINGS[bench_cut.ROUTER][0]
    (bdir / "workloads" / f"{CELL}.json").write_text(json.dumps(cell, indent=1))

    if limit_file:
        entries = {
            number: {
                "limit": limit, "sound_max": sound, "sound_seeds": 12, "control_min": low,
                "control_seeds": 3, "separates": separates, "origin": "made up",
            } for number, (limit, sound, low, separates) in readings.items()
        }
        (bdir / "data" / f"limit_readings.{CELL}.json").write_text(
            json.dumps({"what": "made up", "cells": {CELL: entries}}, indent=1)
        )

    new = copy.deepcopy(bench)
    new["configs"].append({
        "name": CONFIG, "source": config["source"], "file": f"{bench['paths'][0]}/configs/{CONFIG}.json",
        "reduced": config["reduced"], "why": "a made-up stack whose layer kinds hold different head counts",
    })
    new["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "history_burst", "chips": 1, "why": cell["why"],
    })
    moved = {m["name"] for m in new["end_to_end"]}
    for metric in new["end_to_end"] + new["per_layer"]:
        # the new cell joins every list that holds the cell it is modelled on
        if like in metric.get("workloads", []) and (in_moved_list or metric["name"] not in moved):
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(new, indent=1))
    return new


def hold_to_every_rule(root) -> None:
    """What ``test_bench_files.py`` and ``test_bench_limits.py`` ask of the
    repo, asked of ``root``."""
    bench = registry.load_benchmark(str(root))
    whole = registry.load_benchmark(str(root), parked=True)
    for entry in bench["configs"]:
        bench_cut.check_configuration(bench, entry, str(root))
    for metric in whole["end_to_end"] + whole["per_layer"]:
        bench_cut.check_metric(whole, metric, str(root))
    for entry in whole["workloads"]:
        bench_cut.check_workload(whole, entry, str(root))
        bench_cut.check_control_fails_a_number(entry["name"], str(root))
    readings = bench_cut.limit_readings(str(root))
    assert set(readings) == {w["name"] for w in whole["workloads"]}
    for cell, numbers in readings.items():
        for number in numbers:
            bench_cut.check_limit(cell, number, str(root))


def test_a_third_trunk_family_is_taken_by_added_files_and_entries_alone(tmp_path):
    new = add(tmp_path)
    old = registry.load_benchmark()
    # nothing that was there is edited: the three files are new names, and every
    # old entry is as it was but for the lists the new cell joined at their end
    bdir = old["paths"][0]
    added = [f"configs/{CONFIG}.json", f"workloads/{CELL}.json", f"data/limit_readings.{CELL}.json"]
    for file in added:
        assert (tmp_path / bdir / file).is_file() and not os.path.exists(os.path.join(ROOT, bdir, file))
    for key in ("command", "paths", "run_seconds"):
        assert new[key] == old[key]
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[section], new[section]):
            if "workloads" in was:
                assert now["workloads"][: len(was["workloads"])] == was["workloads"]
                now = dict(now, workloads=was["workloads"])
            assert now == was
    grown = [len(new[s]) - len(old[s]) for s in ("configs", "workloads", "end_to_end", "per_layer")]
    assert grown == [1, 1, 0, 0]

    hold_to_every_rule(tmp_path)
    # the new cell resolves and reports what the cell it is modelled on reports;
    # its configuration's cut is by counts: a depth, experts, heads, heads by layer
    bench, cell, config = registry.resolve(CELL, str(tmp_path))
    like = a_trunk_cell(old)
    for section in ("end_to_end", "per_layer"):
        names = lambda b, c: [m["name"] for m in registry.metrics_for(b, section, c)]  # noqa: E731
        assert names(bench, CELL) == names(old, like)
    assert config["reduced"] == list(CUT) and config["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]
    assert set(bench_cut.limit_readings(str(tmp_path))[CELL]) == bench_cut.NUMBERS | {bench_cut.ROUTER}


BROKEN = {
    "reduced_names_head_dim": (dict(reduced_also=["head_dim"]), "names a width"),
    "reduced_names_hidden_size": (dict(reduced_also=["hidden_size"]), "names a width"),
    "reduced_names_a_window": (dict(reduced_also=["sliding_window"]), "names a width"),
    "no_number_its_control_fails": (
        dict(readings={n: (lim, sound, 2.5 * sound, False) for n, (lim, sound, _, _) in READINGS.items()}),
        "control fails no number",
    ),
    "no_readings_of_a_number": (
        dict(readings={n: v for n, v in READINGS.items() if n != bench_cut.ROUTER}),
        "its file limits",
    ),
    "cell_missing_from_the_limit_files": (dict(limit_file=False), "no limit_readings file names"),
    "moved_metric_does_not_list_it": (dict(in_moved_list=False), "which grad_steps_per_s does not"),
}


@pytest.mark.parametrize("how", BROKEN)
def test_a_broken_addition_fails_the_rule_it_should(how, tmp_path):
    kwargs, message = BROKEN[how]
    add(tmp_path, **kwargs)
    with pytest.raises(AssertionError, match=message):
        hold_to_every_rule(tmp_path)


def test_a_cell_with_readings_in_two_files_is_an_error(tmp_path):
    add(tmp_path)
    data = tmp_path / "benchmark" / "data"
    whole = json.loads((data / "limit_readings.json").read_text())
    extra = json.loads((data / f"limit_readings.{CELL}.json").read_text())
    (data / f"limit_readings.{CELL}.json").write_text(
        json.dumps({"cells": {**extra["cells"], **dict(list(whole["cells"].items())[:1])}})
    )
    with pytest.raises(registry.BenchmarkError, match="alone"):
        bench_cut.limit_readings(str(tmp_path))
    first = next(iter(whole["cells"]))
    (data / f"limit_readings.{CELL}.json").write_text(json.dumps(extra))
    (data / f"limit_readings.{first}.json").write_text(
        json.dumps({"cells": {first: whole["cells"][first]}})
    )
    with pytest.raises(registry.BenchmarkError, match="another file"):
        bench_cut.limit_readings(str(tmp_path))
