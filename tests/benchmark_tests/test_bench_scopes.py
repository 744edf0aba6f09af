"""The reduction from a trace and a scope table to seconds per scope group
(benchmark/harness/scopes.py), on the small scoped trace recorded on a v5e
(benchmark/tools/record_scoped_trace.py), and the readers built on it."""

import json
import os
import shutil
import types

import pytest
from bench_cut import ROOT

from benchmark.harness import registry, scopes, trace

DATA = os.path.join(ROOT, "benchmark", "data")
XPLANE = os.path.join(DATA, "small_v5e_scoped.xplane.pb")
NEW_METRICS = [
    m["name"] for m in registry.load_benchmark(parked=True)["per_layer"]
    if os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
    and "scopes" in open(os.path.join(ROOT, "benchmark", "layer_metrics", m["name"] + ".py")).read()
]


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(DATA, "small_v5e_scoped.table.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(trace.load(XPLANE))


@pytest.fixture(scope="module")
def loaded():
    return scopes.load(XPLANE)


def test_groups_of_the_small_scoped_trace(loaded, table, summary):
    out = scopes.reduce(loaded, table, summary["window"])
    device = out["device"]
    assert device["push"] > 0 and device["sample"] > 0 and device["compute"] > 0
    assert device["collect"] == 0.0  # an update burst has no collect scope
    assert device["compute"] > device["sample"] > device["push"]  # 2048-wide layers, batch 2048
    # the window's one operation of another program is nobody's
    assert out["unscoped_reasons"]["other_program"] > 0
    assert device[scopes.UNSCOPED] == pytest.approx(sum(out["unscoped_reasons"].values()))
    # what the compiler made is counted in its group and reported apart
    assert 0 < out["inherited"]["push"] <= device["push"]
    # proven not to be arithmetic: push and sample by their own names, and every
    # copy or slice that has no name of its own, whatever group it inherited
    named = {g: device[g] - out["inherited"][g] for g in ("push", "sample")}
    assert named["push"] + named["sample"] < out["not_compute_s"] < summary["busy_s"]
    # the identity every reader checks: the groups come to the trace's own sum of
    # leaf operations, and both reductions find the same busy union
    assert out["leaf_s"] == pytest.approx(sum(device.values()))
    assert out["leaf_s"] == pytest.approx(sum(summary["by_kind"].values()))
    assert scopes.identity_gap(out, summary) <= 1e-9
    # what the busy union has over the leaves lies under the burst's loop, between
    # one operation and the next: 0.46% here, 35 ns an operation (PERF.md, PR 28)
    assert out["busy_s"] == pytest.approx(summary["busy_s"]) == pytest.approx(0.043413201)
    assert out["container_gap_s"] == pytest.approx(summary["busy_s"] - out["leaf_s"])
    assert out["container_gap_s"] == pytest.approx(0.000200014, rel=1e-4)
    assert out["leaf_overlap_s"] == pytest.approx(0.0, abs=1e-12)  # no two leaves overlap
    assert [name for name, _ in out["container_gap"]["by_container"]] == ["while.6"]
    assert 1e9 * out["container_gap_s"] / out["container_gap"]["intervals"] == pytest.approx(45.5, abs=1)


def test_host_phases_of_the_small_scoped_trace(loaded, table, summary):
    out = scopes.reduce(loaded, table, summary["window"])
    assert out["host_spans"] == {"place_chunk": 2, "burst_dispatch": 2, "drain": 2}
    assert sum(out["host"].values()) <= out["window_s"]
    assert out["host"]["drain"] > out["host"]["burst_dispatch"]  # the host waits in drain


def test_a_missing_table_raises(loaded, summary):
    with pytest.raises(scopes.ScopeError, match="no scope table"):
        scopes.reduce(loaded, None, summary["window"])
    with pytest.raises(scopes.ScopeError, match="no scope table"):
        scopes.reduce(loaded, {"module": "jit_burst", "table": {}}, summary["window"])


def test_a_table_without_our_names_raises(loaded, table, summary):
    """What a compile cache hands back from a commit without scopes: every
    instruction there, none named.  Never read as 100% unscoped."""
    bare = {"module": table["module"], "table": {k: {"": 1} for k in table["table"]}}
    with pytest.raises(scopes.ScopeError, match="no tac/ name"):
        scopes.reduce(loaded, bare, summary["window"])


@pytest.mark.parametrize("counts,expected", [
    ({"tac/critic": 2, "tac/sample": 1}, (scopes.UNSCOPED, "two_groups")),
    ({"tac/critic": 178, "": 18, "tac/actor": 256, "tac/sample": 1}, ("compute", "")),
    ({"tac/critic": 3, "tac/optimizer": 28, "tac/polyak": 1}, ("compute", "")),
    ({"tac/sample/decode": 4, "tac/sample": 2}, ("sample", "")),
    ({"tac/collect/act": 1, "tac/collect/env_step": 5}, ("collect", "")),
    ({"tac/push~": 40}, ("push", "")),
    ({"tac/push~": 5, "tac/sample~": 5}, (scopes.UNSCOPED, "two_groups")),
    ({"": 3}, (scopes.UNSCOPED, "no_scope")),
    ({}, (scopes.UNSCOPED, "no_scope")),
], ids=["two_groups", "one_cast_in_a_conv_fusion", "compute", "sample", "collect",
        "inherited", "inherited_two_groups", "no_scope", "empty"])
def test_group_of_a_table_entry(counts, expected):
    assert scopes.group_of(counts) == expected


def test_names_and_opcodes_of_trace_events():
    name = (
        "%fusion.16 = f32[512,512]{1,0:T(8,128)S(1)} fusion(f32[512,512]{1,0:T(8,128)S(1)} "
        "%bitcast.30), kind=kOutput, calls=%fused_computation.8"
    )
    assert scopes.instruction_name(name) == "fusion.16" and scopes.opcode(name) == "fusion"
    start = (
        "%copy-start = (f32[1,512,512]{2,1,0:T(8,128)S(1)}, f32[1,512,512]{2,1,0:T(8,128)}, "
        "u32[]{:S(2)}) copy-start(f32[1,512,512]{2,1,0:T(8,128)} %args_0_.1)"
    )
    assert scopes.instruction_name(start) == "copy-start" and scopes.opcode(start) == "copy-start"
    assert scopes.opcode(start) in scopes.DATA_MOVEMENT and scopes.opcode(name) not in scopes.DATA_MOVEMENT
    remat = "%fusion.39.remat_uncompressed = f32[200000,168]{1,0:T(8,128)} copy(f32[200000,168]{0,1:T(8,128)} %x)"
    assert scopes.instruction_name(remat) == "fusion.39.remat_uncompressed"
    assert scopes.opcode(remat) == "copy"


class _Learner:
    def __init__(self, scoped):
        self._scoped = scoped

    def burst_scope_table(self):
        return self._scoped


def _ctx(summary, table, **driver):
    config = registry.load_config("wallrunner_cnn")
    return types.SimpleNamespace(
        trace=summary, cell={"name": "small_cell"}, n_windows=2,
        per_window={"grad_steps": 10, "env_steps": 0, "iterations": 10},
        config=config, device={"kind": "TPU v5 lite"},
        driver=types.SimpleNamespace(**(driver or {"dp": _Learner(table)})),
    )


@pytest.fixture
def default_trace_dir(tmp_path, monkeypatch):
    """Where the harness writes a cell's trace when given no directory, under
    a root of the test's own."""
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    path = tmp_path / ".bench_out" / "trace" / "small_cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    return path


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_readers_find_nothing_without_this_runs_trace(
    metric, summary, table, default_trace_dir
):
    """The CPU rehearsal (which passes its own directory) leaves nothing at
    the default path: every new reader answers None.  So does a trace some
    earlier run left there, and a program that has no scope table yet."""
    read = registry.load_layer_metric(metric)
    assert read(_ctx(summary, table)) is None  # no trace at the default path
    shutil.copy(XPLANE, default_trace_dir / "vm.xplane.pb")
    assert read(_ctx(None, table)) is None  # an untraced run
    stale = dict(summary, window=(summary["window"][0] + 1e-6, summary["window"][1]))
    assert read(_ctx(stale, table)) is None  # another run's trace
    assert read(_ctx(summary, table, dp=object())) is None  # the parent's learner


def test_new_readers_read_this_runs_trace(summary, table, default_trace_dir, capsys):
    shutil.copy(XPLANE, default_trace_dir / "vm.xplane.pb")
    ctx = _ctx(summary, table)
    values = {m: registry.load_layer_metric(m)(ctx) for m in NEW_METRICS}
    line = next(x for x in capsys.readouterr().out.splitlines() if x.startswith("scopes: "))
    assert json.loads(line[len("scopes: "):])["identity_gap"] <= scopes.IDENTITY
    steps = 2 * 10
    device = ctx.scope_summary["device"]
    assert values["update.push_us_per_step"] == pytest.approx(1e6 * device["push"] / steps)
    assert values["update.compute_us_per_step.host"] == pytest.approx(1e6 * device["compute"] / steps)
    assert values["fused.collect_us_per_iter"] == 0.0
    assert values["trace.unscoped_share"] == pytest.approx(
        100.0 * device[scopes.UNSCOPED] / summary["busy_s"]
    )
    assert 0.0 < values["update.compute_mfu"] < 100.0
    # the wide MLP here is not the configuration's CNN, so the figure means
    # nothing; its denominator does: busy time less what is proven not compute
    assert values["update.compute_mfu"] > registry.load_layer_metric("update.mfu")(ctx)
    assert values["host.burst_wait_share"] == pytest.approx(
        100.0 * ctx.scope_summary["host"]["drain"] / ctx.scope_summary["window_s"]
    )
    # no env_step span in this trace: nothing to divide by
    assert values["host.act_ms_per_step"] is None and values["host.env_step_ms_per_step"] is None


def test_a_reading_that_fails_the_identity_is_withheld(summary, table, default_trace_dir):
    shutil.copy(XPLANE, default_trace_dir / "vm.xplane.pb")
    ctx = _ctx(dict(summary, busy_s=1.5 * summary["busy_s"]), table)
    assert scopes.summary(ctx) is None
    assert registry.load_layer_metric("update.compute_mfu")(ctx) is None


# ---------------------------------------------------------------- made-up events
# What refused PR 27 (PERF.md section 6): the visual burst as the chip runs it,
# 600 operations a step with 35 ns between them under the burst's ``while``, and
# two ring-sized copies a window.  With the copies the time under the loop that
# no operation covers is near 1% of the busy time; take the copies out and the
# same nanoseconds are over 4% of what is left.
STEPS, OPS_A_STEP, OP_S, BETWEEN_S, COPY_S = 50, 600, 0.782e-6, 35e-9, 30.2e-3
MADE_UP_TABLE = {
    "module": "jit_burst",
    "table": {
        "fusion.1": {"tac/critic": 3}, "fusion.2": {"tac/sample": 1},
        "copy.1": {"tac/push~": 1, "tac/sample~": 1},  # a relayout between two groups
        "fusion.9": {"tac/actor": 1},
    },
}


def _made_up(copies: bool, outside_share: float = 0.0):
    """``(ops, modules, window spans)`` of one device and one run of the burst."""
    ops, t = [], 1.0
    t0 = t
    if copies:
        for _ in range(2):
            ops.append(("%copy.1 = u8[1,200000,64,64,3]{3,2,4,1,0} copy(u8[] %p)", t, COPY_S))
            t += COPY_S + BETWEEN_S
    loop_start = t
    for _ in range(STEPS):
        for i in range(OPS_A_STEP):
            name = "%fusion.2 = f32[64] fusion()" if i < 30 else "%fusion.1 = f32[64] fusion()"
            ops.append((name, t, OP_S))
            t += OP_S + BETWEEN_S
    t -= BETWEEN_S
    ops.append(("%while.6 = (s32[]) while((s32[]) %tuple)", loop_start, t - loop_start))
    modules = [("jit_burst(7)", t0, t - t0)]
    leaf_s = sum(d for n, _, d in ops if "while" not in n)
    if outside_share:  # another program's operations, after the burst's run
        ops.append(("%fusion.9 = f32[8] fusion()", t + 1e-3, leaf_s * outside_share / (1 - outside_share)))
        t = ops[-1][1] + ops[-1][2]
    return ops, modules, [("bench/window", t0 - 1e-3, t - t0 + 2e-3)]


@pytest.fixture
def made_up_ctx(monkeypatch):
    """A reader's context over made-up events: ``ctx.trace`` is the trace's own
    reduction of them, ``scopes.load`` hands the join what ``joined`` keeps."""
    def make(copies, outside_share=0.0, joined=lambda ops: ops):
        ops, modules, windows = _made_up(copies, outside_share)
        monkeypatch.setattr(scopes, "trace_path", lambda cell: "made_up.xplane.pb")
        monkeypatch.setattr(scopes, "load", lambda path: {
            "devices": {0: {"ops": joined(ops), "modules": modules}},
            "windows": windows, "host": [],
        })
        ctx = _ctx(trace.reduce({"devices": {0: ops}, "host": windows}), MADE_UP_TABLE)
        ctx.n_windows, ctx.per_window = 1, {"grad_steps": STEPS, "env_steps": 0}
        return ctx
    return make


@pytest.mark.parametrize("copies", [True, False], ids=["with_the_copies", "copies_gone"])
def test_the_time_between_operations_silences_no_reader(copies, made_up_ctx, capsys):
    """The case that refused PR 27: the program gets three times faster, the
    time under its loop that no operation covers stays what it was a step and
    becomes 4% of the busy time.  Every reader still reads, and the gap is a
    number of its own."""
    ctx = made_up_ctx(copies)
    s = scopes.summary(ctx)
    assert s is not None and s["identity_gap"] <= 1e-9
    busy = ctx.trace["busy_s"]
    between = STEPS * OPS_A_STEP - 1  # the copies run before the loop: idle, not busy, follows them
    assert s["container_gap_s"] == pytest.approx(between * BETWEEN_S, rel=1e-6)
    assert s["container_gap_us_per_step"] == pytest.approx(1e6 * between * BETWEEN_S / STEPS, rel=1e-6)
    assert s["container_gap"]["by_container"][0][0] == "while.6"
    assert s["leaf_s"] + s["container_gap_s"] == pytest.approx(busy)
    share = s["container_gap_s"] / busy
    assert (0.01 < share < scopes.IDENTITY) if copies else (share > 0.04)
    mfu = registry.load_layer_metric("update.compute_mfu")(ctx)
    assert mfu is not None and 0.0 < mfu < 100.0
    # the gap stays in the figure's denominator: it errs low, never high
    flops_s = mfu * (busy - s["not_compute_s"])
    assert flops_s / (s["device"]["compute"] or 1) > mfu
    assert registry.load_layer_metric("update.compute_us_per_step")(ctx) == pytest.approx(
        1e6 * (OPS_A_STEP - 30) * OP_S
    )
    unscoped = registry.load_layer_metric("trace.unscoped_share")(ctx)
    assert unscoped == pytest.approx(100.0 * (2 * COPY_S if copies else 0.0) / busy)
    line = next(x for x in capsys.readouterr().out.splitlines() if x.startswith("scopes: "))
    printed = json.loads(line[len("scopes: "):])
    assert printed["container_gap_s"] == s["container_gap_s"]
    assert printed["container_gap_us_per_step"] == pytest.approx(21.0, abs=0.1)


def test_a_join_that_loses_device_time_is_still_withheld(made_up_ctx):
    """What the identity was written for: a tenth of the leaf time lies outside
    every run of the program.  Booked to *unscoped* it reads; dropped by the
    join, every reader answers nothing."""
    ctx = made_up_ctx(True, outside_share=0.1)
    s = scopes.summary(ctx)
    assert s is not None
    assert s["unscoped_reasons"]["other_program"] == pytest.approx(0.1 * s["leaf_s"], rel=1e-6)
    lossy = made_up_ctx(True, outside_share=0.1, joined=lambda ops: [
        op for op in ops if not op[0].startswith("%fusion.9")
    ])
    assert scopes.summary(lossy) is None
    assert registry.load_layer_metric("update.compute_mfu")(lossy) is None
    assert registry.load_layer_metric("trace.unscoped_share")(lossy) is None
