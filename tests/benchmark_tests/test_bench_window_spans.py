"""The reduction from a trace's host spans and device runs to the window's
own account (benchmark/harness/window_spans.py): on made-up intervals, and on
the small trace recorded on a v5e by benchmark/tools/record_window_trace.py."""

import os
import shutil
import types

import pytest
from bench_cut import ROOT, cut

from benchmark.harness import registry, trace, window_spans
from benchmark.harness.window_spans import HostSpan

XPLANE = os.path.join(ROOT, "benchmark", "data", "small_v5e_windows.xplane.pb")
NEW = [
    "host.span_stage_ms", "host.span_place_chunk_ms", "host.span_burst_dispatch_ms",
    "host.exposed_stage_ms", "host.exposed_place_chunk_ms", "host.exposed_burst_dispatch_ms",
    "host.exposed_drain_ms", "host.exposed_unowned_ms", "host.launch_latency_us",
    "host.wake_latency_us", "host.drain_gap_max_ms",
]
MS = 1e-3


def midpoint_owner(gap, spans):
    """What ``trace.breakdown`` does with a gap: the whole of it to the first
    span over its midpoint."""
    mid = 0.5 * (gap[0] + gap[1])
    return next((n for n, a, b in spans if a <= mid < b), window_spans.UNOWNED)


# ------------------------------------------------------------- made-up intervals
def test_a_gap_over_three_spans_is_split_three_ways():
    """The ledger's own shape (PR 41, ``wallrunner_cnn_burst``): one idle gap of
    3.7 ms that starts in the drain's last moments and lies over a stage of
    0.77 ms, a place_chunk of 1.2 and the start of a dispatch of 2.2.  Cut at
    the edges each span owns what it covers; the midpoint rule hands all 3.7
    ms to one span that lasts 1.2."""
    t = 10.0
    spans = [
        ("drain", t - 20 * MS, t + 0.15 * MS),
        ("stage", t + 0.18 * MS, t + 0.95 * MS),
        ("place_chunk", t + 0.98 * MS, t + 2.18 * MS),
        ("burst_dispatch", t + 2.20 * MS, t + 4.40 * MS),
    ]
    gap = (t, t + 3.7 * MS)
    by = window_spans.exposed([gap], spans)
    assert by["stage"] == pytest.approx(0.77 * MS) and by["place_chunk"] == pytest.approx(1.2 * MS)
    assert by["burst_dispatch"] == pytest.approx(1.5 * MS) and by["drain"] == pytest.approx(0.15 * MS)
    assert by[window_spans.UNOWNED] == pytest.approx(0.08 * MS)  # between one span and the next
    assert sum(by.values()) == pytest.approx(3.7 * MS)
    assert all(by[name] <= b - a + 1e-12 for name, a, b in spans)  # none over its own span
    owner = midpoint_owner(gap, spans)
    assert owner == "place_chunk" and 3.7 * MS - by[owner] == pytest.approx(2.5 * MS)


def test_a_part_owns_what_it_covers_and_its_parent_the_rest():
    spans = [("place_chunk", 1.0, 2.0), ("place_chunk/transfer", 1.2, 1.5), ("place_chunk/unpack", 1.6, 1.9)]
    by = window_spans.exposed([(0.5, 1.3), (1.4, 2.5)], spans)
    assert by == pytest.approx({
        window_spans.UNOWNED: 0.5 + 0.5, "place_chunk": 0.2 + 0.1 + 0.1,
        "place_chunk/transfer": 0.1 + 0.1, "place_chunk/unpack": 0.3,
    })
    assert window_spans.longest_gap_inside([(0.5, 1.3), (1.4, 2.5)], [spans[0]]) == pytest.approx(0.6)


def _made_up(numbers=(7, 8, 9), runs_a_window=1, early=0.0, runtime_events=False):
    """Three windows of 10 ms: a dispatch of 2 ms whose run starts 1.5 ms into
    it and works 7 ms, the reduction's 10 us right after, the drain back 0.2 ms
    after that.  ``(loaded, gaps, window)`` as the reader takes them.  ``early``:
    the device's events are stamped that much before they happened;
    ``runtime_events``: the runtime says when it enqueued each program (20 us
    before it started) and when it learned of each end (0.1 ms after)."""
    spans, runs, busy, others = [], [], [], {"python3": [], "main/7": [], "tfrt/9": []}
    for k, number in enumerate(numbers):
        t = 1.0 + k * 10 * MS
        spans += [
            HostSpan("burst_dispatch", t, t + 2 * MS, number, "", "python3"),
            HostSpan("drain", t + 2.1 * MS, t + 8.71 * MS, number, "", "python3"),
            HostSpan("drain/fetch", t + 2.3 * MS, t + 8.70 * MS, number, "drain", "python3"),
        ]
        others["python3"] += [
            ("PjitFunction(burst)", t + 1e-5, t + 2 * MS - 1e-5), ("ParseArguments", t + 2e-5, t + 5e-5),
        ]
        others["main/7"].append(("ExecutePrepare", t + 1e-4, t + 6e-4))
        for r in range(runs_a_window):
            a = t + 1.5 * MS + r * 3.5 * MS
            runs.append(("jit_burst(1)", a, a + 7 * MS / runs_a_window))
        runs.append(("jit__reduce_sum(2)", t + 8.5 * MS, t + 8.51 * MS))
        busy.append((t + 1.5 * MS, t + 8.51 * MS))
    runs.sort(key=lambda r: r[1])
    if runtime_events:
        others["tfrt/9"] = [("DoEnqueueProgram", a - 2e-5, a - 1e-5) for _, a, _ in runs]
        others["main/7"] += [("tpu::System::Execute=>Done", b + 9e-5, b + 1e-4) for _, _, b in runs]
    window = (1.0, 1.0 + len(numbers) * 10 * MS)
    loaded = {
        "spans": spans, "runs": [(n, a - early, b - early) for n, a, b in runs], "n_devices": 1,
        "busy": [(a - early, b - early) for a, b in busy], "others": others,
    }
    return loaded, trace.subtract([window], loaded["busy"]), window


def test_launch_and_wake_latency_of_a_made_up_run():
    out = window_spans.reduce(*_made_up())
    assert out["n_windows"] == 3 and out["windows"] == [7, 9] and out["program"] == "jit_burst"
    assert out["launch_us"] == pytest.approx([1500.0] * 3) and out["clock_offset_us"] is None
    assert out["wake_us"] == pytest.approx([200.0] * 3)
    # the dispatch owns the idle up to its own end, the drain what follows the
    # reduction, nobody the rest of the window
    assert out["exposed_by_phase_ms"] == pytest.approx({
        "burst_dispatch": 1.5, "drain": 0.2, window_spans.UNOWNED: 1.29,
    })
    assert out["exposed_ms"]["drain/fetch"] == pytest.approx(0.19)
    assert sum(out["exposed_by_phase_ms"].values()) == pytest.approx(out["idle_ms"])
    assert out["drain_gap_max_ms"] == pytest.approx(0.2)
    assert out["span_ms"]["burst_dispatch"] == pytest.approx(2.0)
    # the runtime's events inside the dispatch, by self time, the rest Python's
    inside = out["dispatch_inside_ms"]
    assert inside["ParseArguments"] == pytest.approx(0.03)
    assert inside["PjitFunction(burst)"] == pytest.approx(2.0 - 0.02 - 0.03)
    assert inside[window_spans.PYTHON] == pytest.approx(0.02)
    assert out["dispatch_other_threads_ms"] == {"main/7": {"ExecutePrepare": pytest.approx(0.5)}}
    line = window_spans.printable(out)
    assert line["launch_us"]["median"] == pytest.approx(1500.0) and line["wake_us"]["n"] == 3


def test_the_devices_clock_is_set_from_causes_and_effects():
    """The profiler stamps the device's events 1.2 ms early (what the v5e's
    traces show): uncorrected the run starts before its dispatch is half under
    way and the drain owns the idle the dispatch exposed.  From the runtime's
    enqueues (a run cannot start before) and the ends the host learned of (it
    cannot have ended after) the reader brackets the offset, applies the lower
    bound, and reads what the true clock reads to the enqueue's 20 us."""
    true = window_spans.reduce(*_made_up())
    out = window_spans.reduce(*_made_up(early=1.2 * MS, runtime_events=True))
    offset = out["clock_offset_us"]
    assert offset["lower"] == pytest.approx(1180.0) and offset["upper"] == pytest.approx(1300.0)
    assert offset["applied"] == offset["lower"]
    assert out["launch_us"] == pytest.approx([1480.0] * 3) and out["wake_us"] == pytest.approx([220.0] * 3)
    for phase, value in true["exposed_by_phase_ms"].items():
        assert out["exposed_by_phase_ms"][phase] == pytest.approx(value, abs=0.021)
    # without the runtime's events nothing is applied, and the line says so: the
    # early stamps then hand the drain 1.2 ms more of the idle than it exposed
    raw = window_spans.reduce(*_made_up(early=1.2 * MS))
    assert raw["clock_offset_us"] is None
    assert raw["exposed_by_phase_ms"]["drain"] == pytest.approx(true["exposed_by_phase_ms"]["drain"] + 1.2)
    # an event lost from one window costs that window's bound and no more; no
    # learned end at all leaves the lower bound alone, no enqueue nothing
    loaded, gaps, window = _made_up(early=1.2 * MS, runtime_events=True)
    loaded["others"]["tfrt/9"] = loaded["others"]["tfrt/9"][2:]  # the first window's two enqueues
    assert window_spans.reduce(loaded, gaps, window)["clock_offset_us"] == pytest.approx(offset)
    loaded["others"]["main/7"] = [e for e in loaded["others"]["main/7"] if "Done" not in e[0]]
    alone = window_spans.reduce(loaded, gaps, window)["clock_offset_us"]
    assert alone["upper"] is None and alone["applied"] == pytest.approx(1180.0)
    loaded["others"]["tfrt/9"] = []
    assert window_spans.reduce(loaded, gaps, window)["clock_offset_us"] is None


@pytest.mark.parametrize("case", ["out_of_order", "a_number_missing", "two_runs_a_window", "no_spans"])
def test_a_join_that_does_not_hold_answers_nothing(case):
    if case == "out_of_order":
        args = _made_up(numbers=(7, 9, 8))
    elif case == "a_number_missing":
        args = _made_up(numbers=(7, 8, 10))
    elif case == "two_runs_a_window":
        args = _made_up(runs_a_window=2)
    else:
        args = _made_up()
        args[0]["spans"] = []  # a program that opens no span of its own: the parent's
    assert window_spans.reduce(*args) is None


def _ctx(summary, cell="small_cell"):
    return types.SimpleNamespace(
        trace=summary, cell={"name": cell}, n_windows=4,
        per_window={"grad_steps": 2, "env_steps": 0}, driver=types.SimpleNamespace(),
    )


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_find_nothing_without_a_trace(metric, tmp_path, monkeypatch):
    """An untraced run, a traced run that left nothing at the default path (the
    CPU rehearsal writes elsewhere): ``None``, and nothing raised."""
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    read = registry.load_layer_metric(metric)
    assert read(_ctx(None)) is None
    assert read(_ctx({"window": (0.0, 1.0), "gaps": [], "window_s": 1.0})) is None
    entry = next(m for m in registry.load_benchmark()["per_layer"] if m["name"] == metric)
    assert (entry["layer"], entry["moves"], entry["source"]) == ("host loop", "grad_steps_per_s", "program_span")
    source = open(os.path.join(ROOT, "benchmark", "layer_metrics", metric + ".py")).read()
    assert "scopes" not in source  # test_bench_scopes.py sweeps files by that word


# ------------------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    summary = trace.reduce(trace.load(XPLANE))
    return summary, window_spans.load(XPLANE)


def test_the_recorded_windows_join_and_their_idle_has_owners(recorded):
    summary, loaded = recorded
    out = window_spans.reduce(loaded, summary["gaps"], summary["window"])
    assert out is not None and out["n_windows"] == 4 and out["program"] == "jit_burst"
    assert out["windows"][1] - out["windows"][0] == 3
    # the pieces are the trace's own idle, to 1%
    idle_s = summary["window_s"] - summary["busy_s"]
    assert 1e-3 * 4 * sum(out["exposed_by_phase_ms"].values()) == pytest.approx(idle_s, rel=0.01)
    assert out["idle_ms"] == pytest.approx(1e3 * idle_s / 4, rel=0.01)
    # every window's four spans are there, the parts inside their parents
    for name in ("stage", "place_chunk", "place_chunk/transfer", "place_chunk/unpack",
                 "burst_dispatch", "drain", "drain/reduce", "drain/fetch"):
        assert out["span_ms"][name] > 0, name
    assert out["span_ms"]["place_chunk/transfer"] + out["span_ms"]["place_chunk/unpack"] < out["span_ms"]["place_chunk"]
    # no phase owns more idle than it lasts; the host's work is exposed here
    for phase in ("stage", "place_chunk", "burst_dispatch", "drain"):
        parts = sum(v for k, v in out["span_ms"].items() if k == phase)
        assert out["exposed_by_phase_ms"].get(phase, 0.0) <= parts + 1e-9, phase
    assert out["exposed_by_phase_ms"]["stage"] > 0.5 * out["span_ms"]["stage"]
    assert out["exposed_by_phase_ms"].get(window_spans.UNOWNED, 0.0) < 0.2 * out["idle_ms"]
    # the profiler stamped the device 1.2 ms early here (a run before its own
    # dispatch span); set from the runtime's enqueues, the run starts inside
    # or right after its dispatch and the drain is back a millisecond after
    # the reduction
    offset = out["clock_offset_us"]
    assert 1100 < offset["lower"] == offset["applied"] < offset["upper"] < 1800
    assert len(out["launch_us"]) == len(out["wake_us"]) == 4
    assert all(0 < x < 1e3 * out["span_ms"]["burst_dispatch"] * 2 for x in out["launch_us"])
    assert all(0 < x < 2000 for x in out["wake_us"])
    assert 1e3 * out["drain_gap_max_ms"] >= max(out["wake_us"]) - 1
    raw = window_spans.reduce(dict(loaded, others={}), summary["gaps"], summary["window"])
    assert raw is None  # as stamped, a run starts before its dispatch: no join
    # the runtime's own events inside the dispatch are in the trace at the
    # harness's options, so the call has an inside
    assert any(k.startswith("PjitFunction") for k in out["dispatch_inside_ms"])
    assert 0 <= out["dispatch_inside_ms"][window_spans.PYTHON] < out["span_ms"]["burst_dispatch"]


def test_the_midpoint_rule_books_the_recorded_idle_elsewhere(recorded):
    """What ``trace.breakdown`` still does (PERF.md section 7 b), on the very
    gaps of this trace: the device idles through every ``stage`` span, and the
    rule books ``stage`` next to nothing (a few nanoseconds between two
    operations), because no long gap's middle lies there."""
    summary, loaded = recorded
    spans = [(s.name, s.start, s.end) for s in loaded["spans"] if "/" not in s.name]
    booked, lasted = {}, {}
    for gap in summary["gaps"]:
        owner = midpoint_owner(gap, spans)
        booked[owner] = booked.get(owner, 0.0) + gap[1] - gap[0]
    for name, a, b in spans:
        lasted[name] = lasted.get(name, 0.0) + b - a
    cut = window_spans.exposed(summary["gaps"], spans)
    assert sum(booked.values()) == pytest.approx(sum(cut.values()))
    assert booked.get("stage", 0.0) < 1e-6 and lasted["stage"] > 5e-4
    assert cut["stage"] == pytest.approx(lasted["stage"], rel=0.02)  # but for an operation of 4 us
    assert all(cut.get(n, 0.0) <= lasted[n] + 1e-12 for n in lasted)


def test_new_readers_read_this_runs_trace_and_no_others(recorded, tmp_path, monkeypatch, capsys):
    summary, _ = recorded
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    path = tmp_path / ".bench_out" / "trace" / "small_cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    shutil.copy(XPLANE, path / "vm.xplane.pb")
    ctx = _ctx(summary)
    values = {m: registry.load_layer_metric(m)(ctx) for m in NEW}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert capsys.readouterr().out.count("window_spans: ") == 1  # once a run
    exposed = sum(v for k, v in values.items() if k.startswith("host.exposed_"))
    assert exposed == pytest.approx(1e3 * (summary["window_s"] - summary["busy_s"]) / 4, rel=0.01)
    for phase in ("stage", "place_chunk", "burst_dispatch"):
        assert values[f"host.exposed_{phase}_ms"] <= values[f"host.span_{phase}_ms"]
    stale = dict(summary, window=(summary["window"][0] + 1e-6, summary["window"][1]))
    assert all(registry.load_layer_metric(m)(_ctx(stale)) is None for m in NEW)  # another run's trace


# ------------------------------------------------------------- the specimen jar
def _waits_tool():
    return registry._module(os.path.join(ROOT, "benchmark", "tools", "waits.py"), "bench_tool_waits")


def test_a_window_that_waited_is_told_from_its_records():
    from torch_actor_critic_tpu.telemetry.recorder import SPAN_NAMES, SpanRecord

    tool = _waits_tool()
    ids = {name: i for i, name in enumerate(SPAN_NAMES)}
    records = []
    for w in range(12):
        t, wait = 0.03 * w, 0.150 if w == 7 else 0.020
        records += [
            SpanRecord(ids["env_step"], -1, w, t - 1e-4, 1e-4, None, None),  # no part of a window
            SpanRecord(ids["stage"], -1, w, t, 1e-3, None, None),
            SpanRecord(ids["burst_dispatch"], -1, w, t + 1e-3, 2e-3, None, None),
            SpanRecord(ids["drain/fetch"], ids["drain"], w, t + 3.1e-3, wait - 2e-4, 1e-4, 0.1 if w == 7 else 0.0),
            SpanRecord(ids["drain"], -1, w, t + 3e-3, wait, None, None),
        ]
    windows = tool.windows_of(records, SPAN_NAMES)
    median, long_ = tool.long_windows(windows, last=10)  # the first two are set-up's
    assert median == pytest.approx(23.0)
    (waited,) = long_
    assert waited["window"] == 7 and waited["ms"] == pytest.approx(153.0)
    assert waited["spans_ms"]["drain"] == pytest.approx(150.0) and "env_step" not in waited["spans_ms"]
    assert waited["fetch_thread_cpu_ms"] == pytest.approx(0.1)
    assert waited["fetch_runq_wait_ms"] == pytest.approx(100.0)  # the machine did not run the thread


def test_the_tool_runs_a_cell_with_a_recorder_of_its_own(tmp_path):
    from torch_actor_critic_tpu.telemetry import recorder

    bench, cell, config = cut("wallrunner_cnn_burst")
    out = _waits_tool().waits(
        bench, cell, config, seed=4_200_000_001, seconds=0.5, rehearsal=True, out_dir=str(tmp_path)
    )
    assert recorder.current() is None  # taken out again
    assert out["correct"] is True and out["windows"] >= 1 and out["median_ms"] > 0
    assert all(w["ms"] > 1.5 * out["median_ms"] for w in out["long"])


def test_a_trace_that_cannot_be_read_fails_no_run(recorded, tmp_path, monkeypatch, capsys):
    summary, _ = recorded
    monkeypatch.setattr(registry, "ROOT", str(tmp_path))
    path = tmp_path / ".bench_out" / "trace" / "small_cell" / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    (path / "vm.xplane.pb").write_bytes(b"not a trace")
    assert registry.load_layer_metric("host.span_stage_ms")(_ctx(summary)) is None
    assert "Traceback" in capsys.readouterr().err
