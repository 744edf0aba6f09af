"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives it.

A later PR adds files and entries and edits none: a configuration is
``configs/<config>.json``, a cell is ``workloads/<cell>.json``, a driver is
``drivers/<driver>.py`` and a per-layer metric is
``layer_metrics/<metric>.py``.  Nothing here names a particular one of them.

A cell the check cannot hold yet is *parked*: its files stay, and
``parked/<cell>.json`` keeps the entries ``BENCHMARK.json`` had for it, so that
a later PR puts it back by adding those entries and nothing else.  A run never
sees a parked cell; the tests rehearse it all the same (``parked=True``).
"""

from __future__ import annotations

import importlib.util
import json
import os
import typing as t

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

CONFIG_KEYS = (
    "source", "family", "precision", "reference_mode", "control", "model", "sac",
    "reduced", "assumed", "deployment",
)
WORKLOAD_KEYS = ("config", "driver", "chips", "traffic", "limits", "why")


class BenchmarkError(Exception):
    """A file of the benchmark is missing or does not fit its schema."""


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise BenchmarkError(f"{os.path.relpath(path, ROOT)} is missing")
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT, parked: bool = False) -> dict:
    """``BENCHMARK.json``; with ``parked``, the parked cells' entries merged
    into it: a metric the file still has gets the parked cell appended to its
    ``workloads`` (and keeps the file's bound), any other entry is appended."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    parked_dir = os.path.join(root, bench["paths"][0], "parked")
    if not parked or not os.path.isdir(parked_dir):
        return bench
    for name in sorted(os.listdir(parked_dir)):
        entries = _load_json(os.path.join(parked_dir, name))
        for section in ("workloads", "end_to_end", "per_layer"):
            for entry in entries.get(section, []):
                kept = next((e for e in bench[section] if e["name"] == entry["name"]), None)
                if kept is None:
                    bench[section].append(entry)
                else:
                    kept["workloads"] = kept["workloads"] + entry["workloads"]
    return bench


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise BenchmarkError(f"{os.path.relpath(path, ROOT)} is missing")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    cfg = _load_json(os.path.join(bench_dir, "configs", f"{name}.json"))
    missing = [k for k in CONFIG_KEYS if k not in cfg]
    if missing:
        raise BenchmarkError(f"configs/{name}.json lacks {missing}")
    return cfg


def load_workload(name: str, bench_dir: str = BENCH_DIR) -> dict:
    cell = _load_json(os.path.join(bench_dir, "workloads", f"{name}.json"))
    missing = [k for k in WORKLOAD_KEYS if k not in cell]
    if missing:
        raise BenchmarkError(f"workloads/{name}.json lacks {missing}")
    if cell["chips"] not in (1, 4):
        raise BenchmarkError(f"workloads/{name}.json: chips must be 1 or 4")
    return cell


def load_driver(name: str, bench_dir: str = BENCH_DIR):
    """``drivers/<name>.py`` exposes ``Driver`` (see drivers/README in
    benchmark/README.md): set-up, one window, counts, check."""
    module = _module(
        os.path.join(bench_dir, "drivers", f"{name}.py"), f"bench_driver_{name}"
    )
    if not hasattr(module, "Driver"):
        raise BenchmarkError(f"drivers/{name}.py defines no Driver")
    return module.Driver


def load_layer_metric(name: str, bench_dir: str = BENCH_DIR) -> t.Callable:
    """``layer_metrics/<name>.py`` exposes ``read(ctx)``: the metric's
    value, or ``None`` where it finds nothing to read."""
    module = _module(
        os.path.join(bench_dir, "layer_metrics", f"{name}.py"),
        "bench_metric_" + name.replace(".", "_").replace("-", "_"),
    )
    if not callable(getattr(module, "read", None)):
        raise BenchmarkError(f"layer_metrics/{name}.py defines no read(ctx)")
    return module.read


def metrics_for(bench: dict, section: str, cell: str) -> t.List[dict]:
    """The metrics of ``section`` that ``cell`` reports: those that list
    it under ``workloads``, and those with no such key."""
    return [
        m for m in bench[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def resolve(
    cell_name: str, root: str = ROOT, parked: bool = False
) -> t.Tuple[dict, dict, dict]:
    """(benchmark, workload entry merged with its file, configuration)."""
    bench = load_benchmark(root, parked)
    entry = next((w for w in bench["workloads"] if w["name"] == cell_name), None)
    if entry is None:
        raise BenchmarkError(
            f"BENCHMARK.json has no workload {cell_name!r}; it has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    bench_dir = os.path.join(root, bench["paths"][0])
    cell = load_workload(cell_name, bench_dir)
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        raise BenchmarkError(
            f"workloads/{cell_name}.json disagrees with BENCHMARK.json on "
            "config or chips"
        )
    cell = {**cell, "name": cell_name}
    config = {**load_config(cell["config"], bench_dir), "name": cell["config"]}
    return bench, cell, config
