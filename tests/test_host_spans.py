"""The device window times itself (telemetry/recorder.py::span): stage,
place_chunk, burst_dispatch and drain are spans of the functions that do the
work, whoever calls them.

Pinned here: with no recorder installed a span is its annotation and nothing
more; with one installed a window's spans share its number, the parts name
their parent, and what the ring keeps adds up to what the phases were charged;
a span opened under a phase of the Trainer's hands back to it; the Trainer's
stream keeps its phases and gains the parts beside them; the parked host
cell's driver still reads the ring.
"""

import json
import math
import types

import jax
import numpy as np
import pytest

from torch_actor_critic_tpu.parallel import (
    DataParallelSAC,
    init_sharded_buffer,
    make_mesh,
    shard_chunk_from_local,
)
from torch_actor_critic_tpu.sac.trainer import Trainer, build_models, make_learner
from torch_actor_critic_tpu.telemetry import PHASES, SpanRing, TelemetryRecorder
from torch_actor_critic_tpu.telemetry import recorder as spans
from torch_actor_critic_tpu.utils.config import SACConfig
from torch_actor_critic_tpu.utils.sync import drain
from torch_actor_critic_tpu.utils.tracking import Tracker

OBS, ACT, WINDOW = 5, 2, 4
# The four functions that open a window's spans, by the span each opens,
# with the parts it has on this path (a chunk staged as one block).
FUNCTIONS = {
    "stage": (spans.STAGE, ()),
    "place_chunk": (spans.PLACE_CHUNK, (spans.PLACE_TRANSFER, spans.PLACE_UNPACK)),
    "burst_dispatch": (spans.BURST_DISPATCH, ()),
    "drain": (spans.DRAIN, (spans.DRAIN_REDUCE, spans.DRAIN_FETCH)),
}


class _Env:
    obs_spec = jax.ShapeDtypeStruct((OBS,), np.float32)
    act_dim, act_limit = ACT, 1.0


class _Window:
    """One learner and the four calls of a window on it, each alone."""

    def __init__(self):
        cfg = SACConfig(hidden_sizes=(8, 8), batch_size=4, buffer_size=64, update_every=WINDOW)
        self.mesh = make_mesh(dp=1)
        self.dp = DataParallelSAC(make_learner(cfg, *build_models(cfg, _Env), ACT), self.mesh)
        self.state = self.dp.init_state(jax.random.key(0), np.zeros((OBS,), np.float32))
        self.buffer = init_sharded_buffer(cfg.buffer_size, _Env.obs_spec, ACT, self.mesh)
        rng = np.random.default_rng(0)
        rows = lambda *shape: rng.standard_normal((1,) + shape).astype(np.float32)  # noqa: E731
        self.staged = [
            (rows(OBS), rows(ACT), rows(), rows(OBS), np.zeros((1,), np.float32))
            for _ in range(WINDOW)
        ]
        self.loss = None
        for name in FUNCTIONS:  # builds the programs, outside every test's eye
            self.call(name)

    def call(self, name):
        if name == "stage":
            self.local = Trainer._build_chunk(None, self.staged)
        elif name == "place_chunk":
            # a block is written once: a chunk that crossed is staged anew
            self.chunk = shard_chunk_from_local(
                Trainer._build_chunk(None, self.staged), self.mesh, sp=1
            )
        elif name == "burst_dispatch":
            chunk = shard_chunk_from_local(self.local, self.mesh, sp=1)
            self.state, self.buffer, m = self.dp.update_burst(
                self.state, self.buffer, chunk, WINDOW
            )
            self.loss = m["loss_q"]
        else:
            drain(self.loss)


@pytest.fixture(scope="module")
def window():
    return _Window()


@pytest.fixture
def installed():
    rec = TelemetryRecorder()
    previous = spans.install(rec)
    try:
        yield rec
    finally:
        spans.install(previous)


def _of(rec, phase):
    return [r for r in rec.ring.records() if r.phase == phase]


@pytest.mark.parametrize("name", FUNCTIONS)
def test_without_a_recorder_a_span_is_its_annotation_alone(name, window, monkeypatch):
    """Nothing installed: the function allocates no ring, leaves no phase
    open, and only a dispatch moves the window's number."""
    made = []
    init = SpanRing.__init__
    monkeypatch.setattr(SpanRing, "__init__", lambda self, *a, **k: (made.append(1), init(self, *a, **k))[1])
    spans.install(None)  # whatever another test left
    before = spans.window()
    window.call(name)
    assert made == [] and spans.current() is None
    assert spans._open == -1 and spans._annotation is not None
    assert spans.window() - before == (name == "burst_dispatch")


@pytest.mark.parametrize("name", FUNCTIONS)
def test_an_installed_recorder_keeps_the_span_and_its_parts(name, window, installed):
    phase, parts = FUNCTIONS[name]
    number = spans.window()
    window.call(name)
    records = installed.ring.records()
    if name in ("place_chunk", "burst_dispatch"):  # these stage or place first
        records = [r for r in records if r.phase in (phase,) + parts]
    assert [r.phase for r in records if r.phase == phase] == [phase]
    (own,) = _of(installed, phase)
    # drain waits for the last window dispatched; the others make the next ready
    assert own.window == (number - 1 if name == "drain" else number)
    assert own.parent == -1 and own.duration > 0
    for part in parts:
        (child,) = _of(installed, part)
        assert child.parent == phase and child.window == own.window
        assert own.start <= child.start and child.start + child.duration <= own.start + own.duration
    # what the ring keeps of a span is what its sum, count and maximum were charged
    timer = installed.timer
    for span_id in (phase,) + parts:
        kept = [r.duration for r in _of(installed, span_id)]
        assert timer.counts[span_id] == len(kept) == 1
        assert timer.sums[span_id] == pytest.approx(sum(kept), abs=1e-12)
        assert timer.maxs[span_id] == pytest.approx(max(kept), abs=1e-12)
    # the thread's own account is kept for the wait and for nothing else
    assert all(
        r.thread_cpu_s is None and r.runq_wait_s is None
        for r in installed.ring.records() if r.phase != spans.DRAIN_FETCH
    )
    # spans() stays the partition's laps as triples: no part, no new field
    assert all(len(s) == 3 and s[0] < len(PHASES) for s in installed.ring.spans())


def test_a_windows_four_spans_share_its_number(window, installed):
    for _ in range(2):
        number = spans.window()
        for name in ("stage", "burst_dispatch", "drain"):  # the dispatch places its chunk itself
            window.call(name)
        by = {SPAN: [r.window for r in _of(installed, SPAN)] for SPAN, _ in FUNCTIONS.values()}
        assert {w[-1] for w in by.values()} == {number}
        assert spans.window() == number + 1
    assert [r.window for r in _of(installed, spans.DRAIN_FETCH)] == [number - 1, number]


def test_a_span_under_a_phase_names_it_and_hands_back(window, installed):
    """As ``param_sync`` does: the phase that was open is charged up to the
    span, the span its own time, and the phase goes on after it, so the laps
    still partition the stretch."""
    rec = installed
    rec.epoch_begin(3)
    rec.begin(spans.ENV_STEP)
    t0 = rec.timer._t_mark
    window.call("stage")
    assert rec.open_phase == spans.ENV_STEP
    window.call("drain")
    rec.end()
    laps = [(r.phase, r.parent) for r in rec.ring.records() if r.phase < len(PHASES)]
    assert laps == [
        (spans.ENV_STEP, -1), (spans.STAGE, spans.ENV_STEP), (spans.ENV_STEP, -1),
        (spans.DRAIN, spans.ENV_STEP), (spans.ENV_STEP, -1),
    ]
    covered = sum(rec.timer.sums[: len(PHASES)])
    assert covered == pytest.approx(rec.timer._t_mark - t0, abs=1e-9)
    event = rec.epoch_end(3)
    assert set(event["phases"]) == {"env_step", "stage", "drain"}
    assert set(event["children"]) == {"drain/reduce", "drain/fetch"}
    assert event["phases"]["env_step"]["count"] == 3
    wait = event["longest_wait"]
    assert wait["span"] == "drain/fetch" and wait["s"] == event["children"]["drain/fetch"]["max_s"]
    assert rec.epoch_end(4).get("longest_wait") is None  # an epoch's own, not the run's


def test_begin_and_span_make_one_annotation(monkeypatch, installed):
    """A phase the Trainer opens and a span a function opens cannot disagree
    on the annotation's name, window or epoch: both go through one function."""
    seen = []
    annotate = spans._annotate
    monkeypatch.setattr(spans, "_annotate", lambda *a: (seen.append(a), annotate(*a))[1])
    installed.epoch_begin(7)
    installed.begin(spans.ACT)
    with spans.span(spans.STAGE):
        with spans.span(spans.PLACE_TRANSFER):
            pass
    installed.end()
    assert seen == [
        (spans.ACT, -1), (spans.STAGE, spans.ACT), (spans.PLACE_TRANSFER, spans.STAGE),
        (spans.ACT, -1),
    ]
    assert spans._epoch == 7


def test_the_os_fields_are_empty_where_the_platform_has_none(monkeypatch, installed):
    monkeypatch.setattr(spans, "_thread_os_times", lambda: (math.nan, math.nan))
    with spans.span(spans.DRAIN):
        with spans.span(spans.DRAIN_FETCH, os_wait=True):
            pass
    (fetch,) = _of(installed, spans.DRAIN_FETCH)
    assert fetch.thread_cpu_s is None and fetch.runq_wait_s is None
    assert "longest_wait" not in installed.epoch_end(0)


def test_the_ring_wraps_with_every_field():
    ring = SpanRing(capacity=3, n_phases=len(PHASES))
    for i in range(5):
        ring.record(spans.DRAIN_FETCH if i % 2 else spans.DRAIN, float(i), 0.5, spans.DRAIN, i, 0.1 * i, 0.2 * i)
    assert [r.window for r in ring.records()] == [2, 3, 4]
    assert [r.thread_cpu_s for r in ring.records()] == pytest.approx([0.2, 0.3, 0.4])
    assert ring.spans() == [(spans.DRAIN, 2.0, 0.5), (spans.DRAIN, 4.0, 0.5)]  # the part is left out


def test_the_parked_host_cells_driver_still_reads_the_ring(installed):
    """``benchmark/drivers/hostloop.py::host_spans`` names every triple of
    ``ring.spans()`` by ``recorder.phases``: a part among them would be an
    index past its end."""
    from benchmark.harness import registry

    _, cell, _ = registry.resolve("cheetah_pop32_host", parked=True)
    driver = registry.load_driver(cell["driver"])
    installed.begin(spans.ACT)
    with spans.span(spans.PLACE_CHUNK):
        with spans.span(spans.PLACE_TRANSFER):
            pass
    installed.end()
    read = driver.host_spans(types.SimpleNamespace(recorder=installed))
    assert [name for name, _, _ in read] == ["act", "place_chunk", "act"]


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    tracker = Tracker(experiment="t", root=tmp_path_factory.mktemp("host_spans"))
    cfg = SACConfig(
        hidden_sizes=(16, 16), batch_size=16, epochs=2, steps_per_epoch=40, start_steps=10,
        update_after=10, update_every=10, buffer_size=500, max_ep_len=100, telemetry=True,
    )
    tr = Trainer("Pendulum-v1", cfg, mesh=make_mesh(dp=1), tracker=tracker, seed=3)
    try:
        assert spans.current() is tr.telemetry
        tr.train()
    finally:
        tr.close()
    assert spans.current() is None
    lines = (tracker.run_dir / "telemetry.jsonl").read_text().splitlines()
    return cfg, tr.telemetry, [json.loads(line) for line in lines]


def test_the_trainers_stream_keeps_its_phases_and_gains_the_parts(trained):
    cfg, _, events = trained
    assert events[0]["phases"] == list(PHASES)
    epochs = [e for e in events if e["type"] == "epoch"]
    windows = cfg.steps_per_epoch // cfg.update_every
    for ev in epochs:
        assert set(ev["phases"]) == set(PHASES)  # the same keys as before: no part among them
        assert {k: v["count"] for k, v in ev["children"].items()} == {
            "place_chunk/transfer": windows, "place_chunk/unpack": windows,
            "drain/reduce": 1, "drain/fetch": 1,
        }
        for part, stats in ev["children"].items():
            assert stats["total_s"] <= ev["phases"][part.split("/")[0]]["total_s"] + 1e-6
        assert ev["longest_wait"]["span"] == "drain/fetch"


def test_the_trainers_windows_carry_one_number_each(trained):
    cfg, rec, _ = trained
    by_window = {}
    for r in rec.ring.records():
        if r.phase in (spans.STAGE, spans.PLACE_CHUNK, spans.BURST_DISPATCH):
            by_window.setdefault(r.window, []).append(r.phase)
            assert r.parent == spans.ENV_STEP
    numbers = sorted(by_window)
    assert len(numbers) == cfg.epochs * cfg.steps_per_epoch // cfg.update_every
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    assert all(v == [spans.STAGE, spans.PLACE_CHUNK, spans.BURST_DISPATCH] for v in by_window.values())
    # an epoch's drain waits for its last window
    fetches = [r.window for r in rec.ring.records() if r.phase == spans.DRAIN_FETCH]
    per_epoch = cfg.steps_per_epoch // cfg.update_every
    assert fetches == [numbers[per_epoch - 1], numbers[-1]]
