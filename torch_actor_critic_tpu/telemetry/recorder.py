"""Phase span/counter recorder for the training hot loop.

Design constraints (the tentpole contract, docs/OBSERVABILITY.md):

- **Next to no code when disabled.** The Trainer stores
  ``telemetry=None`` and every instrumentation point of its own is
  ``if rec is not None: rec.begin(i)`` over a loop-local — one
  always-false predicted branch per phase mark, no calls, no allocation,
  no events. The device window's four spans (below) are then their
  trace annotations and nothing more: a TraceMe no-op each while no
  trace is taken, no ring, no clock read. Disabled-mode metrics are
  byte-identical to an uninstrumented build (pinned by
  tests/test_telemetry.py).
- **No host<->device syncs when enabled.** Every measurement is a
  ``time.perf_counter()`` read; nothing here fetches a device value, so
  ``burst_dispatch`` measures exactly what it says — async dispatch
  cost — and the queued device work it dispatched surfaces later under
  ``drain``. Reading allocator watermarks (:mod:`memory`) is likewise
  a host-side query.
- **No per-step allocation when enabled.** Laps accumulate into
  preallocated per-phase lists and a preallocated :class:`SpanRing`
  (fixed numpy arrays, wrapping cursor). Events (which do allocate)
  are emitted once per epoch, off the step path.

The lap model: phases *partition* the instrumented region. ``lap(i)``
charges everything since the previous lap (or :meth:`mark`) to phase
``i``, so the per-epoch phase sums add up to ~the epoch wall time and
the breakdown answers "where did the time go" without leaving gaps
(the acceptance check ``make trace-smoke`` asserts the coverage).

The Trainer names a phase of its own where it starts: ``begin(i)`` laps
the phase that was open and opens ``i``, also as a
``jax.profiler.TraceAnnotation`` named ``tac/host/<phase>`` with
``window=``, ``parent=`` and ``epoch=``: the same partition on the
profiler's clock, beside the device operations, in whatever trace is
being taken (``--profile-epochs``, the benchmark's). With no trace
active an annotation is a TraceMe no-op.

The device window times itself: ``stage``, ``place_chunk``,
``burst_dispatch`` and ``drain`` are opened by the functions that do the
work (``Trainer._build_chunk``, ``shard_chunk_from_local`` /
``PopulationLearner.place_chunk``, ``update_burst`` / ``push_chunk`` /
the fused loops' ``epoch``, ``utils.sync.drain``) through :class:`span`,
whoever calls them. A span always is the annotation; it is also a lap
of the recorder that is installed as the process's current one
(:func:`install`: the Trainer installs its own), and nothing more where
none is. A span opened while a phase is open names it as its ``parent``
and hands back to it when it closes, so the phases still partition the
epoch. :data:`CHILDREN` are the parts of a span, nested inside it and
timed by their own clock reads; they are no part of the partition.

The window number is this module's own counter, advanced when a
``burst_dispatch`` span closes: ``stage``, ``place_chunk`` and
``burst_dispatch`` carry the number of the window being made ready,
``drain`` and its parts the number of the last window dispatched, the
one they wait for. The open phase, the window and the epoch are the
learner thread's: the instrumented functions are called from no other.
"""

from __future__ import annotations

import logging
import time
import typing as t

import jax
import numpy as np

from torch_actor_critic_tpu.telemetry.costmodel import classify_epoch
from torch_actor_critic_tpu.telemetry.memory import device_memory_watermarks
from torch_actor_critic_tpu.telemetry.profiler import ProfilerWindow
from torch_actor_critic_tpu.telemetry.scopes import HOST_PREFIX
from torch_actor_critic_tpu.telemetry.sinks import JsonlSink, format_summary

logger = logging.getLogger(__name__)

__all__ = [
    "CHILDREN", "PHASES", "PhaseTimer", "SpanRecord", "SpanRing",
    "TelemetryRecorder", "current", "install", "span", "uninstall", "window",
]

# The Trainer step classification (ISSUE 3 / docs/OBSERVABILITY.md): indices
# are the lap() argument — integer phase ids keep the hot path free of
# dict lookups.
PHASES: t.Tuple[str, ...] = (
    "act",            # policy forward (host mirror or device RPC)
    "env_step",       # pool.step + normalize + episode bookkeeping
    "stage",          # staging-list -> chunk stacking (_build_chunk)
    "place_chunk",    # host->device transfer / resharding of the chunk
    "burst_dispatch", # async dispatch of push/update_burst
    "drain",          # epoch-end device-queue drain (true burst cost)
    "sentinel",       # divergence check (+ rollback when it fires)
    "checkpoint",     # Orbax save dispatch
    "param_sync",     # device->host actor mirror: waits for the burst
)
# The parts of a phase span, named under it. They nest inside their
# parent and are no part of the partition: an epoch event reports them
# under ``children``, never under ``phases``.
CHILDREN: t.Tuple[str, ...] = (
    "place_chunk/transfer",  # the device_put of the block, or the leaf-by-leaf puts
    "place_chunk/unpack",    # dispatch of the program that takes the leaves out of the block
    "drain/reduce",          # dispatch of the scalar reduction
    "drain/fetch",           # the device_get of that scalar: the wait
)
SPAN_NAMES = PHASES + CHILDREN
# The ids :class:`span` and ``begin`` take: an index into SPAN_NAMES.
(
    ACT, ENV_STEP, STAGE, PLACE_CHUNK, BURST_DISPATCH, DRAIN, SENTINEL,
    CHECKPOINT, PARAM_SYNC,
    PLACE_TRANSFER, PLACE_UNPACK, DRAIN_REDUCE, DRAIN_FETCH,
) = range(len(SPAN_NAMES))
_N_PHASES = len(PHASES)  # ids below it partition; the parts come after
_HOST_NAMES = tuple(HOST_PREFIX + name for name in SPAN_NAMES)
# These wait for the last window dispatched and carry its number.
_WAITS = frozenset((DRAIN, DRAIN_REDUCE, DRAIN_FETCH))
SCHEMA_VERSION = 1
_NAN = float("nan")

# The learner thread's state (module docstring): the installed recorder,
# the window being made ready, the epoch, and the phase that is open
# with its parent and its annotation.
_current: "TelemetryRecorder | None" = None
_window = 0
_epoch = 0
_open = -1
_open_parent = -1
_annotation: t.Any = None


def install(recorder: "TelemetryRecorder | None") -> "TelemetryRecorder | None":
    """Make ``recorder`` the one the spans of this process are charged
    to (``None``: none). Returns the one that was installed."""
    global _current
    previous, _current = _current, recorder
    return previous


def uninstall(recorder: "TelemetryRecorder") -> None:
    """Take ``recorder`` out if it is the installed one."""
    global _current
    if _current is recorder:
        _current = None


def current() -> "TelemetryRecorder | None":
    return _current


def window() -> int:
    """The number of the window being made ready."""
    return _window


def _window_of(phase: int) -> int:
    return _window - 1 if phase in _WAITS else _window


def _annotate(phase: int, parent: int):
    """The one place a phase or span becomes a trace annotation, entered:
    its name, its window, the phase it was opened under, the epoch."""
    annotation = jax.profiler.TraceAnnotation(
        _HOST_NAMES[phase],
        window=_window_of(phase),
        parent=SPAN_NAMES[parent] if parent >= 0 else "",
        epoch=_epoch,
    )
    annotation.__enter__()
    return annotation


def _switch(
    phase: int, parent: int, recorder: "TelemetryRecorder | None",
    dispatched: bool = False,
) -> int:
    """Close the open phase, charged to ``recorder`` as a lap where
    there is one, and open ``phase`` (``-1``: none) under ``parent``.
    ``dispatched``: the phase that closes was a window's dispatch, and
    what opens belongs to the next window. Returns what was open."""
    global _open, _open_parent, _annotation, _window
    prev = _open
    if prev >= 0:
        if recorder is not None:
            recorder.lap(prev, _open_parent)
        _annotation.__exit__(None, None, None)
    elif recorder is not None:
        recorder.timer.mark()
    if dispatched:
        _window += 1
    _open, _open_parent = phase, parent
    if phase >= 0:
        _annotation = _annotate(phase, parent)
    return prev


def _thread_os_times() -> t.Tuple[float, float]:
    """What the operating system says of the calling thread: the CPU
    seconds it has run, and the seconds it has stood runnable on a run
    queue (``/proc/thread-self/schedstat``, second field). NaN where the
    platform does not say."""
    try:
        cpu = time.thread_time()
    except (AttributeError, OSError):
        cpu = _NAN
    try:
        with open("/proc/thread-self/schedstat", "rb") as f:
            runq = 1e-9 * int(f.read().split()[1])
    except (OSError, ValueError, IndexError):
        runq = _NAN
    return cpu, runq


class span:
    """One host span of the device window, opened where the work happens:
    ``with span(STAGE): ...``.

    Always the ``tac/host/<name>`` annotation with ``window=``,
    ``parent=`` and ``epoch=`` (a TraceMe no-op while no trace is being
    taken). With a recorder installed also one record of its
    :class:`SpanRing` and a charge to the span's sum, count and maximum.

    A phase (an id of :data:`PHASES`) closes the phase that is open,
    names it as its parent and hands back to it on the way out, as the
    Trainer's ``param_sync`` does. A part (an id of :data:`CHILDREN`)
    nests inside whatever is open and leaves it open. ``os_wait``: a
    part the thread waits in; with a recorder installed it also keeps
    the thread's CPU time and run-queue delay over the span.
    """

    __slots__ = ("phase", "os_wait", "_back", "_annotation", "_t0", "_os0")

    def __init__(self, phase: int, os_wait: bool = False):
        self.phase = phase
        self.os_wait = os_wait

    def __enter__(self) -> "span":
        phase = self.phase
        if phase < _N_PHASES:
            self._back = (_open, _open_parent)
            _switch(phase, _open, _current)
            return self
        self._annotation = _annotate(phase, _open)
        self._t0 = None
        recorder = _current
        if recorder is not None:
            self._os0 = _thread_os_times() if self.os_wait else None
            self._t0 = recorder._clock()
        return self

    def tag(self, **metadata) -> None:
        """Say more of the open span on its annotation (``packed=1``,
        ``build=1``): what is known only once the work is under way."""
        # a phase that handed over to another and back is annotated anew
        open_ = _annotation if self.phase < _N_PHASES else self._annotation
        open_.set_metadata(**metadata)

    def __exit__(self, *exc) -> None:
        phase = self.phase
        if phase < _N_PHASES:
            _switch(*self._back, _current, dispatched=phase == BURST_DISPATCH)
            return
        recorder = _current
        if recorder is not None and self._t0 is not None:
            dur = recorder._clock() - self._t0
            cpu = runq = _NAN
            if self._os0 is not None:
                cpu, runq = _thread_os_times()
                cpu, runq = cpu - self._os0[0], runq - self._os0[1]
            recorder.charge(phase, _open, self._t0, dur, cpu, runq)
        self._annotation.__exit__(None, None, None)


class PhaseTimer:
    """Monotonic lap timer over a fixed phase set.

    ``lap(i)`` charges ``now - last_mark`` to phase ``i`` and advances
    the mark; ``mark()`` advances it without charging (used at region
    entry). Plain Python float/list arithmetic: ~0.5us per lap, no
    allocation beyond float boxing.
    """

    __slots__ = ("n", "sums", "counts", "maxs", "_t_mark", "_clock")

    def __init__(self, n_phases: int, clock: t.Callable[[], float] = time.perf_counter):
        self.n = n_phases
        self._clock = clock
        self.sums = [0.0] * n_phases
        self.counts = [0] * n_phases
        self.maxs = [0.0] * n_phases
        self._t_mark = clock()

    def mark(self) -> float:
        self._t_mark = t0 = self._clock()
        return t0

    def lap(self, phase: int) -> float:
        now = self._clock()
        dt = now - self._t_mark
        self._t_mark = now
        self.sums[phase] += dt
        self.counts[phase] += 1
        if dt > self.maxs[phase]:
            self.maxs[phase] = dt
        return dt

    def reset(self) -> None:
        for i in range(self.n):
            self.sums[i] = 0.0
            self.counts[i] = 0
            self.maxs[i] = 0.0
        self._t_mark = self._clock()

    def stats(self, names: t.Sequence[str]) -> dict:
        return {
            names[i]: {
                "total_s": self.sums[i],
                "count": self.counts[i],
                "max_s": self.maxs[i],
            }
            for i in range(self.n)
            if self.counts[i]
        }


class SpanRecord(t.NamedTuple):
    """One retained span with everything the ring keeps of it. ``parent``
    is the id of the phase it was opened under (``-1``: none);
    ``thread_cpu_s`` and ``runq_wait_s`` are ``None`` but for a span that
    asked for them (:class:`span`, ``os_wait``) on a platform that says."""

    phase: int
    parent: int
    window: int
    start: float
    duration: float
    thread_cpu_s: float | None
    runq_wait_s: float | None


class SpanRing:
    """Preallocated ring of the most recent spans.

    Fixed numpy arrays (phase id, start time, duration; the phase it was
    opened under, its window, and for a span that waits the thread's CPU
    time and run-queue delay) and a wrapping cursor: recording is a few
    scalar stores, reading materializes only on demand. This is the
    drill-down companion to the per-epoch aggregates, "which individual
    step stalled", without ever growing.

    :meth:`spans` hands back the phases' laps, the partition, as
    ``(phase, start, duration)``: ids below ``n_phases`` where that is
    given. :meth:`records` hands back every retained span, parts
    included, with all its fields.
    """

    def __init__(self, capacity: int = 4096, n_phases: int | None = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.n_phases = n_phases
        self._phase = np.zeros(capacity, np.int16)
        self._t0 = np.zeros(capacity, np.float64)
        self._dur = np.zeros(capacity, np.float64)
        self._parent = np.full(capacity, -1, np.int16)
        self._window = np.zeros(capacity, np.int64)
        self._cpu = np.full(capacity, _NAN, np.float64)
        self._runq = np.full(capacity, _NAN, np.float64)
        self._cursor = 0
        self.total = 0

    def record(
        self, phase: int, t0: float, dur: float, parent: int = -1,
        window: int = 0, cpu: float = _NAN, runq: float = _NAN,
    ) -> None:
        i = self._cursor
        self._phase[i] = phase
        self._t0[i] = t0
        self._dur[i] = dur
        self._parent[i] = parent
        self._window[i] = window
        self._cpu[i] = cpu
        self._runq[i] = runq
        self._cursor = (i + 1) % self.capacity
        self.total += 1

    def _retained(self) -> t.Iterable[int]:
        n = min(self.total, self.capacity)
        if n < self.capacity:
            return range(n)
        return [(self._cursor + k) % self.capacity for k in range(n)]

    def spans(self) -> t.List[t.Tuple[int, float, float]]:
        """Retained laps of the phases, oldest first."""
        bound = self.n_phases
        return [
            (int(self._phase[i]), float(self._t0[i]), float(self._dur[i]))
            for i in self._retained()
            if bound is None or self._phase[i] < bound
        ]

    def records(self) -> t.List[SpanRecord]:
        """Every retained span, oldest first."""
        def told(x):
            return None if x != x else float(x)

        return [
            SpanRecord(
                int(self._phase[i]), int(self._parent[i]), int(self._window[i]),
                float(self._t0[i]), float(self._dur[i]),
                told(self._cpu[i]), told(self._runq[i]),
            )
            for i in self._retained()
        ]


class TelemetryRecorder:
    """The Trainer-facing facade: phase timer + span ring + counters +
    HBM watermarks + profiler window + JSONL sink.

    ``run_dir=None`` keeps everything in memory (non-coordinator hosts,
    unit tests); otherwise events stream to ``<run_dir>/telemetry.jsonl``
    and the ``--profile-epochs`` trace to ``<run_dir>/trace``.
    """

    def __init__(
        self,
        run_dir: t.Any | None = None,
        ring_capacity: int = 4096,
        profile_epochs: t.Optional[t.Tuple[int, int]] = None,
        clock: t.Callable[[], float] = time.perf_counter,
        sink_max_bytes: int = 0,
    ):
        # The partition; the timer also keeps a row for each of CHILDREN
        # behind it (the ids :class:`span` takes).
        self.phases = PHASES
        self._clock = clock
        self.timer = PhaseTimer(len(SPAN_NAMES), clock)
        self.ring = SpanRing(ring_capacity, n_phases=_N_PHASES)
        self.counters: t.Dict[str, float] = {}
        self.epochs_recorded = 0
        # Run-level accumulation (summary()/snapshot() aggregate the
        # whole run even though the timer resets per epoch).
        self._run_sums = [0.0] * len(self.phases)
        self._run_counts = [0] * len(self.phases)
        self._run_maxs = [0.0] * len(self.phases)
        self._t_epoch: float | None = None
        # The longest span of the epoch that kept the thread's CPU time
        # and run-queue delay (a ``drain/fetch``): what a window that
        # waited leaves behind in the epoch event.
        self._longest_wait: dict | None = None
        self.last_memory: dict | None = None
        # Host/device/input epoch attribution (costmodel.classify_epoch)
        # — rolling counts per class plus frac sums, surfaced by
        # summary() and carried on every epoch event.
        self.last_attribution: dict | None = None
        self._attr_counts: t.Dict[str, int] = {}
        self._attr_frac_sums = {"device": 0.0, "host": 0.0, "input": 0.0}

        self.sink = (
            JsonlSink(
                str(run_dir) + "/telemetry.jsonl",
                max_bytes=sink_max_bytes,
            )
            if run_dir is not None else None
        )
        self.profiler = ProfilerWindow(
            profile_epochs,
            (str(run_dir) + "/trace") if run_dir is not None else None,
        )
        if self.sink is not None:
            self.sink.write({
                "type": "run_start",
                "schema": SCHEMA_VERSION,
                "time": time.time(),
                "phases": list(self.phases),
                "profile_epochs": (
                    list(profile_epochs) if profile_epochs else None
                ),
            })

    # -------------------------------------------------- hot-path recording

    def mark(self) -> None:
        """Advance the lap mark without charging a phase (region entry)."""
        self.timer.mark()

    def lap(self, phase: int, parent: int = -1) -> None:
        """Charge time since the previous lap/mark to ``phase``, opened
        under ``parent``.

        Inlined timer + ring update (same-module peers): this runs up
        to a few times per Trainer step, and the flattened body saves
        two method dispatches over ``timer.lap`` + ``ring.record``.
        """
        timer = self.timer
        now = timer._clock()
        t0 = timer._t_mark
        dt = now - t0
        timer._t_mark = now
        timer.sums[phase] += dt
        timer.counts[phase] += 1
        if dt > timer.maxs[phase]:
            timer.maxs[phase] = dt
        ring = self.ring
        i = ring._cursor
        ring._phase[i] = phase
        ring._t0[i] = t0
        ring._dur[i] = dt
        ring._parent[i] = parent
        ring._window[i] = _window_of(phase)
        ring._cpu[i] = _NAN
        ring._runq[i] = _NAN
        ring._cursor = (i + 1) % ring.capacity
        ring.total += 1

    def charge(
        self, phase: int, parent: int, t0: float, dur: float,
        cpu: float = _NAN, runq: float = _NAN,
    ) -> None:
        """A part of a span (an id of :data:`CHILDREN`) that ran from
        ``t0`` for ``dur`` under ``parent``: its sum, count and maximum,
        and one record of the ring. The lap mark stays where it is."""
        timer = self.timer
        timer.sums[phase] += dur
        timer.counts[phase] += 1
        if dur > timer.maxs[phase]:
            timer.maxs[phase] = dur
        window = _window_of(phase)
        self.ring.record(phase, t0, dur, parent, window, cpu, runq)
        if cpu == cpu or runq == runq:  # the platform said either
            longest = self._longest_wait
            if longest is None or dur > longest["s"]:
                self._longest_wait = {
                    "span": SPAN_NAMES[phase], "window": window, "s": dur,
                    "thread_cpu_s": cpu, "runq_wait_s": runq,
                }

    def inc(self, name: str, value: float = 1.0) -> None:
        """Bump a named counter (epoch-granularity: not for the step
        path — counters allocate on first use)."""
        self.counters[name] = self.counters.get(name, 0.0) + value

    @property
    def open_phase(self) -> int:
        """The phase that is open (``-1``: none): the Trainer's own or a
        span's, whichever was opened last."""
        return _open

    @property
    def window(self) -> int:
        """The number of the window being made ready (the module's)."""
        return _window

    @window.setter
    def window(self, value: int) -> None:
        # benchmark/tools/record_scoped_trace.py still advances it by hand
        global _window
        _window = int(value)

    def begin(self, phase: int) -> int:
        """Close the open phase (charged as :meth:`lap` charges it) and
        open ``phase``; ``-1`` opens none. Returns the phase that was
        open, so that a phase nested in another can hand back to it."""
        return _switch(phase, -1, self)

    def end(self) -> None:
        """Close the open phase; what follows is charged to nothing."""
        _switch(-1, -1, self)

    # ------------------------------------------------------ epoch boundary

    def epoch_begin(self, epoch: int) -> None:
        global _epoch
        self.profiler.epoch_begin(epoch)
        if _open >= 0:  # an epoch that raised left it open
            _switch(-1, -1, None)
        _epoch = int(epoch)
        self._t_epoch = self.timer.mark()

    def epoch_end(self, epoch: int, extra: t.Mapping[str, t.Any] | None = None) -> dict:
        """Fold the epoch's laps into the run totals, sample HBM
        watermarks, emit the epoch event, stop an expiring profiler
        window, and reset the epoch timer. Returns the event dict."""
        now = self._clock()
        wall_s = now - self._t_epoch if self._t_epoch is not None else 0.0
        stats = self.timer.stats(SPAN_NAMES)
        phases = {k: v for k, v in stats.items() if k in self.phases}
        children = {k: v for k, v in stats.items() if k in CHILDREN}
        for i in range(len(self.phases)):
            self._run_sums[i] += self.timer.sums[i]
            self._run_counts[i] += self.timer.counts[i]
            if self.timer.maxs[i] > self._run_maxs[i]:
                self._run_maxs[i] = self.timer.maxs[i]
        self.last_memory = device_memory_watermarks()
        self.epochs_recorded += 1
        event: dict = {
            "type": "epoch",
            "epoch": int(epoch),
            "time": time.time(),
            "wall_s": round(wall_s, 6),
            "phases": {
                k: {
                    "total_s": round(v["total_s"], 6),
                    "count": v["count"],
                    "max_s": round(v["max_s"], 6),
                }
                for k, v in phases.items()
            },
        }
        # The parts of the spans, beside the partition and never in it,
        # and what the epoch's longest wait leaves behind.
        if children:
            event["children"] = {
                k: {
                    "total_s": round(v["total_s"], 6),
                    "count": v["count"],
                    "max_s": round(v["max_s"], 6),
                }
                for k, v in children.items()
            }
        if self._longest_wait is not None:
            event["longest_wait"] = {
                k: round(v, 6) if isinstance(v, float) else v
                for k, v in self._longest_wait.items()
            }
            self._longest_wait = None
        # Host/device/input attribution rides the epoch event.
        if wall_s > 0 and phases:
            attr = classify_epoch(phases, wall_s)
            event["attribution"] = attr
            self.last_attribution = attr
            self._attr_counts[attr["class"]] = (
                self._attr_counts.get(attr["class"], 0) + 1
            )
            self._attr_frac_sums["device"] += attr["device_busy_frac"]
            self._attr_frac_sums["host"] += attr["host_frac"]
            self._attr_frac_sums["input"] += attr["input_frac"]
        if extra:
            event.update({k: v for k, v in extra.items()})
        if self.counters:
            event["counters"] = dict(self.counters)
        if self.last_memory is not None:
            event["memory"] = self.last_memory
        if self.sink is not None:
            self.sink.write(event)
        self.profiler.epoch_end(epoch)
        self.timer.reset()
        return event

    def event(self, type_: str, **fields) -> None:
        """Emit an ad-hoc event (rollbacks, preemption, reloads)."""
        if self.sink is not None:
            self.sink.write({"type": type_, "time": time.time(), **fields})

    # ------------------------------------------------------------- reports

    def run_stats(self) -> dict:
        return {
            self.phases[i]: {
                "total_s": self._run_sums[i],
                "count": self._run_counts[i],
                "max_s": self._run_maxs[i],
            }
            for i in range(len(self.phases))
            if self._run_counts[i]
        }

    def snapshot(self) -> dict:
        """``/metrics``-style dict (the serving plane merges this under
        a ``training`` key — one schema across both planes)."""
        phases = {}
        for name, p in self.run_stats().items():
            phases[name] = {
                "total_s": round(p["total_s"], 6),
                "count": p["count"],
                "mean_ms": round(1e3 * p["total_s"] / p["count"], 3),
                "max_ms": round(1e3 * p["max_s"], 3),
            }
        out: dict = {
            "epochs_total": self.epochs_recorded,
            "spans_total": self.ring.total,
            "phases": phases,
        }
        if self.counters:
            out["counters"] = dict(self.counters)
        if self.last_memory is not None:
            out["memory"] = self.last_memory
        if self.sink is not None:
            out["events_written"] = self.sink.events_written
            if self.sink.rotations:
                out["sink_rotations_total"] = self.sink.rotations
        return out

    def attribution_summary(self) -> dict | None:
        """Rolling host/device/input attribution over the recorded
        epochs: per-class epoch counts and mean plane fractions, or
        None before the first attributed epoch."""
        n = sum(self._attr_counts.values())
        if not n:
            return None
        return {
            "epochs": n,
            "by_class": dict(self._attr_counts),
            "mean_device_busy_frac": round(
                self._attr_frac_sums["device"] / n, 4
            ),
            "mean_host_frac": round(self._attr_frac_sums["host"] / n, 4),
            "mean_input_frac": round(self._attr_frac_sums["input"] / n, 4),
        }

    def summary(self) -> str:
        """Human phase-breakdown table over the whole run, plus the
        rolling host/device/input attribution when recorded."""
        out = format_summary(self.run_stats(), self.counters)
        attr = self.attribution_summary()
        if attr is not None:
            classes = ", ".join(
                f"{k} x{v}" for k, v in sorted(attr["by_class"].items())
            )
            out += (
                f"\nepoch attribution: {classes} | mean fracs: device "
                f"{attr['mean_device_busy_frac']:.0%}, host "
                f"{attr['mean_host_frac']:.0%}, input "
                f"{attr['mean_input_frac']:.0%}"
            )
        return out

    def close(self) -> None:
        self.profiler.close()
        if self.sink is not None:
            self.sink.close()
