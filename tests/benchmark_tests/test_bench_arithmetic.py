"""The yardstick's arithmetic against hand-computed values: FLOPs and bytes
of a step, the table of peaks, percentiles, interval algebra of the trace
reduction."""

import pytest
from bench_cut import ROOT  # noqa: F401 — puts the repo on sys.path

from benchmark.harness import flops, main, peaks, registry, trace

VISUAL = registry.load_config("wallrunner_cnn")["model"]
MLP = registry.load_config("cheetah_pop32")["model"]


def test_visual_step_is_3_4_gflop_at_batch_32():
    # conv tower: 15*15*32*8*8*3 + 6*6*64*4*4*32 + 4*4*64*3*3*64 + 1024*512 + 512
    tower = 1_382_400 + 1_179_648 + 589_824 + 524_288 + 512
    assert flops.conv_tower_macs(VISUAL) == tower
    assert flops.flops_per_step(VISUAL, 32) == 3_424_024_064


def test_mlp_step_is_147_mflop_at_batch_64():
    actor = 17 * 256 + 256 * 256 + 2 * 256 * 6
    critic = 2 * (23 * 256 + 256 * 256 + 256)
    assert flops.flops_per_step(MLP, 64) == 2 * 64 * (4 * actor + 6 * critic) == 147_456_000


def test_flops_scale_with_batch_and_read_widths_from_the_file():
    assert flops.flops_per_step(VISUAL, 64) == 2 * flops.flops_per_step(VISUAL, 32)
    wider = dict(VISUAL, filters=[64, 64, 64])
    assert flops.conv_tower_macs(wider) > flops.conv_tower_macs(VISUAL)


def test_conv_flops_count_fourteen_tower_passes():
    conv = 1_382_400 + 1_179_648 + 589_824
    assert flops.conv_only_macs(VISUAL) == conv
    assert flops.conv_flops_per_step(VISUAL, 32) == 2 * 32 * conv * 14
    assert flops.conv_flops_per_step(MLP, 64) == 0
    assert flops.conv_bytes_per_step(VISUAL, 32) > 0 == flops.conv_bytes_per_step(MLP, 64)


def test_row_bytes():
    assert flops.row_bytes(VISUAL) == 2 * (12_288 + 672) + 56 * 4 + 8 == 26_152
    assert flops.row_bytes(MLP) == (2 * 17 + 6 + 2) * 4 == 168
    assert 200_000 * flops.row_bytes(VISUAL) / 2**30 == pytest.approx(4.87, abs=0.01)
    assert 32 * 1_000_000 * flops.row_bytes(MLP) / 2**30 == pytest.approx(5.01, abs=0.01)


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in registry.load_benchmark(parked=True)["workloads"]]
)
def test_every_cell_holds_four_gib_at_rest(cell_name):
    """By what the cell's driver holds on a chip between steps (its
    ``at_rest_bytes``): rings alone for three drivers, the trunk with its
    target and Adam's moments beside a ring of histories for the fourth."""
    _, cell, config = registry.resolve(cell_name, parked=True)
    at_rest = registry.load_driver(cell["driver"]).at_rest_bytes(cell, config)
    assert at_rest >= 4 * 2**30, (cell_name, at_rest)
    if config["model"]["family"] in ("mlp", "visual"):
        rows = cell["traffic"]["ring_rows"] * config["sac"].get("population", 1)
        assert at_rest == rows // cell["chips"] * flops.row_bytes(config["model"])


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e
    with pytest.raises(KeyError, match="not in the benchmark's table"):
        peaks.peaks_for("TPU v9000")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert main.percentile(values, 0.95) == 95
    assert main.percentile([5.0], 0.95) == 5.0
    assert main.percentile([1, 2, 3, 4], 0.5) == 2


def test_interval_union_and_subtraction():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.total(trace.union([(0, 2), (1, 3), (5, 6)])) == 4
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4)], [(0, 4)]) == []


def test_op_kinds_as_the_trace_names_them():
    assert trace.op_kind("%copy.1018 = u8[1,2]{1,0} copy(u8[1,2] %p)") == "copy"
    assert trace.op_kind("%bitcast_convert_fusion.2 = bf16[3] fusion(...)") == "bitcast-convert-fusion"
    assert trace.op_kind("%all-reduce.3 = f32[8] all-reduce(...)") == "all-reduce"
    assert trace.is_container("%while.5 = (s32[]) while(...)")
    assert trace.is_collective("%all-reduce.3 = f32[8] all-reduce(...)")
    summary = {"by_kind": {"copy": 2.0, "bitcast-convert-fusion": 1.0, "dynamic-update-slice": 0.5}}
    assert trace.kind_seconds(summary, "copy") == 2.0  # "convert" is no copy
    assert trace.kind_seconds(summary, "copy", "dynamic-update-slice") == 2.5


def _synthetic(devices):
    return {
        "devices": devices,
        "host": [("bench/window", 0.0, 10.0), ("bench/stage", 6.0, 4.0)],
    }


def test_reduce_busy_idle_and_gap_owner():
    summary = trace.reduce(_synthetic({0: [
        ("%while.1 = () while()", 1.0, 5.0),
        ("%fusion.1 = f32[] fusion()", 1.0, 2.0),
        ("%copy.2 = f32[] copy()", 4.0, 2.0),
    ]}))
    assert summary["window_s"] == 10.0 and summary["busy_s"] == pytest.approx(5.0)
    assert summary["by_kind"] == {"fusion": pytest.approx(2.0), "copy": pytest.approx(2.0)}
    out = trace.breakdown(summary)
    assert out["device_ops"][0][1] == pytest.approx(2.0)
    assert dict(out["idle_gaps"]) == {
        "bench/unattributed": pytest.approx(1.0), "bench/stage": pytest.approx(4.0)
    }


def test_exposed_all_reduce_is_what_no_compute_covers():
    ops = [
        ("%fusion.1 = f32[] fusion()", 0.0, 4.0),
        ("%all-reduce.1 = f32[] all-reduce()", 3.0, 3.0),  # 1 s hidden, 2 s exposed
    ]
    summary = trace.reduce(_synthetic({0: ops, 1: ops}))
    assert summary["n_devices"] == 2
    assert summary["collective_s"] == pytest.approx(3.0)
    assert summary["collective_exposed_s"] == pytest.approx(2.0)
    assert summary["busy_s"] == pytest.approx(6.0)


def test_reduction_of_the_recorded_trace():
    """A small trace recorded on a v5e chip (tools/record_small_trace.py,
    PR 23): three windows of one tiny program, spans from the harness."""
    import os

    path = os.path.join(ROOT, "benchmark", "data", "small_v5e.xplane.pb")
    raw = trace.load(path)
    assert list(raw["devices"]) == [0] and len(raw["devices"][0]) == 12
    names = sorted({n for n, _, _ in raw["host"]})
    assert names == ["bench/burst_dispatch", "bench/drain", "bench/stage", "bench/window"]
    summary = trace.reduce(raw)
    assert summary["n_devices"] == 1
    assert summary["window_s"] == pytest.approx(0.01064767, rel=1e-6)
    assert summary["busy_s"] == pytest.approx(1.4153e-05, rel=1e-4)
    assert summary["by_kind"]["fusion"] == pytest.approx(1.4107e-05, rel=1e-4)
    assert summary["collective_s"] == 0.0
    idle = 1.0 - summary["busy_s"] / summary["window_s"]
    assert idle == pytest.approx(0.99867, abs=1e-4)
    out = trace.breakdown(summary)
    assert out["device_ops"][0][0].startswith("%fusion.13 = f32[1,512,512]")
    assert out["device_ops"][0][1] == pytest.approx(9.367e-06, rel=1e-3)
    gaps = dict(out["idle_gaps"])
    assert gaps["bench/stage"] == pytest.approx(0.005802856, rel=1e-3)
    assert sum(gaps.values()) == pytest.approx(summary["window_s"] - summary["busy_s"], rel=1e-6)
