"""What the benchmark's tests share: the rules its files are held to, each a
function of the root it reads (the repo, or a temporary copy with an addition:
``test_bench_addition.py``), and the cut sizes of the CPU rehearsals.

The rules ask by rule and never by name: a configuration, a cell, a metric
or a file of limit readings that a later PR adds is held to them with no
file here edited.

The real cells fill a quarter of a chip; a test holds a ring of a thousand
rows.  Widths are cut here and nowhere else: a number from such a run is a
rehearsal of the control flow, never a device metric.
"""

import copy
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import registry  # noqa: E402

# what BENCHMARK.json lists first, then the parked cells (benchmark/parked/): a
# run never sees those, a rehearsal keeps their files working
CELLS = [w["name"] for w in registry.load_benchmark(parked=True)["workloads"]]

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# What ``reduced`` may never name: a width, told by its key's ending.  A head's
# size is one by its ending (``head_dim``, ``mamba_head_dim``, ``v_head_dim``);
# a count of heads, groups, experts, layers or vocabulary rows is the chip's
# share or the depth and may be named (the ``model-configs`` guide, section 4).
WIDTH_ENDINGS = (
    "hidden_size", "intermediate_size", "latent_size", "state_size", "hidden_sizes",
    "_dim", "_rank", "_width", "_per_tok", "expand", "conv_kernel", "chunk_size",
    "sliding_window", "filters", "kernel_sizes", "strides", "dense_size", "features",
)
SHARED_BY_COUNT = ("head", "group", "expert")  # a count of these held is the chip's share
NUMBERS = {"loss_q", "loss_pi", "adam_nu", "param_change"}  # every cell's limits
ROUTER = "router_choices"  # a trunk cell's fifth number; its limit lies in the traffic


def bench_dir_of(root: str = ROOT) -> str:
    return os.path.join(root, registry.load_benchmark(root)["paths"][0])


def limit_readings(root: str = ROOT) -> dict:
    """``{cell: {number: readings}}`` from ``data/limit_readings.json`` and every
    ``data/limit_readings.<cell>.json`` beside it, which holds that cell alone.
    A cell in two files is an error."""
    data_dir = os.path.join(bench_dir_of(root), "data")
    merged: dict = {}
    for file in sorted(os.listdir(data_dir)):
        if not (file.startswith("limit_readings.") and file.endswith(".json")):
            continue
        with open(os.path.join(data_dir, file)) as f:
            cells = json.load(f)["cells"]
        named = file[len("limit_readings"):-len(".json")][1:]  # "" for limit_readings.json
        if named and set(cells) != {named}:
            raise registry.BenchmarkError(f"data/{file} holds {sorted(cells)}, not {named} alone")
        twice = sorted(set(cells) & set(merged))
        if twice:
            raise registry.BenchmarkError(f"data/{file}: {twice} has readings in another file too")
        merged.update(cells)
    return merged


def check_configuration(bench: dict, entry: dict, root: str = ROOT) -> dict:
    """A ``configs`` entry and its file; the configuration, for more."""
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"]) and 1 <= len(entry["why"]) <= 200
    assert entry["file"].startswith(bench["paths"][0] + "/")
    assert len(entry["reduced"]) <= 16 and all(NAME.match(key) for key in entry["reduced"])
    cfg = registry.load_config(entry["name"], bench_dir_of(root))
    assert cfg["reduced"] == entry["reduced"]
    widths = [key for key in entry["reduced"] if key.endswith(WIDTH_ENDINGS)]
    assert not widths, f"reduced names a width: {widths}"
    shared = [key for key in entry["reduced"] if any(word in key for word in SHARED_BY_COUNT)]
    if shared:  # the chip's share of a deployment: say of what, and published -> held
        assert cfg["deployment"], "reduced names a count held and the file states no deployment"
        for key in shared:
            assert "->" in cfg.get("reduced_how", {}).get(key, ""), f"reduced_how lacks {key}"
    assert any(w["config"] == entry["name"] for w in bench["workloads"])
    assert cfg["reference_mode"] in ("highest", "bf16_operands")
    assert cfg["control"]["reference_mode"] == "fp8_operands"
    return cfg


def check_workload(bench: dict, entry: dict, root: str = ROOT) -> dict:
    """A ``workloads`` entry (``bench`` with the parked cells' entries), its
    file, its driver and what it reports; the cell, for more."""
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(entry["name"]) and NAME.match(entry["traffic"])
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    _, cell, _ = registry.resolve(entry["name"], root, parked=True)
    assert cell["why"] == entry["why"]
    assert set(cell["limits"]) == NUMBERS
    driver = registry.load_driver(cell["driver"], bench_dir_of(root))
    for method in ("setup", "window", "per_window", "free", "check", "control", "at_rest_bytes"):
        assert callable(getattr(driver, method)), method
    reported = [m["name"] for m in registry.metrics_for(bench, "end_to_end", entry["name"])]
    assert "setup_s" in reported and len(reported) >= 2
    assert registry.metrics_for(bench, "per_layer", entry["name"])
    return cell


def check_metric(bench: dict, metric: dict, root: str = ROOT) -> None:
    """An ``end_to_end`` or ``per_layer`` entry (``bench`` with the parked
    cells' entries), its reader, and the cells it lists."""
    end_to_end = metric in bench["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"}
    )
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in SOURCES
    cells = {w["name"] for w in bench["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        return
    assert callable(registry.load_layer_metric(metric["name"], bench_dir_of(root)))
    moved = next(m for m in bench["end_to_end"] if m["name"] == metric["moves"])
    # every cell that reads this metric reports the metric it moves
    lost = set(metric.get("workloads", cells)) - set(moved.get("workloads", cells))
    assert not lost, f"{metric['name']} lists {sorted(lost)}, which {moved['name']} does not"
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def check_limit(cell: str, number: str, root: str = ROOT) -> None:
    """The cell file's limit stands over the largest sound reading with half
    of it to spare (a sound reading past two thirds of a limit moves it), and
    a separating number's stands under the smallest reading of the float8
    control, nearer the lower reading in ratio."""
    entry = limit_readings(root)[cell][number]
    cell_file = registry.load_workload(cell, bench_dir_of(root))
    limit = (
        cell_file["traffic"]["router_disagree_limit"] if number == ROUTER
        else cell_file["limits"][number]
    )
    assert entry["limit"] == limit
    sound, control = entry["sound_max"], entry["control_min"]
    assert entry["sound_seeds"] >= 12
    assert sound <= limit * 2 / 3, (sound, limit)
    if entry["separates"]:
        assert entry["control_seeds"] >= 3
        assert control >= 3 * sound and limit < control, (sound, limit, control)
        # more of the room above the lower reading than under the upper one
        assert limit / sound >= control / limit or limit >= 2 * sound
    else:
        assert control < 3 * sound or limit < control


def check_control_fails_a_number(cell: str, root: str = ROOT) -> None:
    """The cell has its readings, for every number its file limits (a router's
    choices among them where its traffic limits those), and the float8 control
    fails one of them."""
    readings = limit_readings(root)
    assert cell in readings, f"no limit_readings file names {cell}"
    cell_file = registry.load_workload(cell, bench_dir_of(root))
    numbers = NUMBERS | ({ROUTER} if "router_disagree_limit" in cell_file["traffic"] else set())
    assert set(readings[cell]) == numbers, (
        f"{cell}'s readings are of {sorted(readings[cell])}; its file limits {sorted(numbers)}"
    )
    assert any(
        e["separates"] and e["control_min"] > e["limit"] for e in readings[cell].values()
    ), f"the control fails no number of {cell}"


def cut(cell_name: str, chips: int | None = None):
    """(benchmark, cell, configuration) of ``cell_name`` at a size a test run
    can hold.  On the CPU the program multiplies in true float32, so the
    reference it is held to is the ``highest`` one."""
    bench, cell, config = registry.resolve(cell_name, parked=True)
    cell, config = copy.deepcopy(cell), copy.deepcopy(config)
    config["reference_mode"] = "highest"
    cell["limits"] = {k: 1e-4 for k in cell["limits"]}
    config["model"]["hidden_sizes"] = [32, 32]
    config["sac"].update(batch_size=8, update_every=10)
    if config["model"]["family"] == "visual":
        # 44x44 is the smallest frame whose last conv layer is not 1x1.
        config["model"].update(
            frame=[44, 44, 3], cnn_dense_size=64, feature_dim=12, act_dim=5
        )
        cell["traffic"].update(ring_rows=512, pool_windows=2, fill_slab_rows=100)
    else:
        config["sac"]["population"] = 3
        cell["traffic"].update(ring_rows=1000)
        if cell["driver"] == "fused":
            cell["traffic"].update(n_envs=4, steps_per_dispatch=30)
        else:
            cell["traffic"].update(steps_per_epoch=40, windows_per_call=4)
    cell["traffic"]["trace_seconds"] = 1
    if chips is not None:
        cell["chips"] = chips
    return bench, cell, config
