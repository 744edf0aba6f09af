"""The device window by the program's own host spans: the reduction behind
the ``host.span_*``, ``host.exposed_*``, ``host.launch_latency_us``,
``host.wake_latency_us`` and ``host.drain_gap_max_ms`` metrics.

The functions that do a window's work open ``tac/host/<phase>`` annotations
themselves (``telemetry/recorder.py::span``: ``stage``, ``place_chunk``,
``burst_dispatch``, ``drain`` and their parts), each with the number of its
``window`` and the phase it was opened under (``parent``), on the profiler's
clock.  The device's side of the same window is one
event on the ``XLA Modules`` line: a run of the cell's program, which is told
here as the module that takes most of the window's device time.  The k-th run
inside the traced window is joined to the k-th ``tac/host/burst_dispatch``
span, and the join is refused (``None``) unless every dispatch has exactly one
run that starts after it and before the next, and the spans' window numbers
are consecutive.

**The two clocks.**  The profiler puts the device's events on the host's
clock by an offset it takes itself, and on the v5e that offset stands off by
0.35 to 1.7 ms from one trace to the next (my chip runs, PR 42: a run of the
burst is stamped 1.2 ms *before* the runtime enqueues it).  A millisecond is a
quarter of the visual cell's idle time, so the reader sets the device's clock
itself, from causes and effects the same trace holds, a window at a time.  The
runtime writes a ``DoEnqueueProgram`` event for every program it enqueues and
a ``tpu::System::Execute=>Done`` for every program the host learns has ended.
The last enqueue between a dispatch span's start and its drain's start (its
own end, where no drain follows) is the burst's own or an earlier program's: the run cannot have started before it, so
the device's stamps are early by at least ``enqueue start - run start``.  The
first learned end after the run's stamped end is the burst's own or a later
program's: the run cannot have ended after it, so by at most ``done end - run
end``.  The largest lower and the smallest upper bound over the windows
bracket the offset; a run enqueued on an idle device starts at once, so the
lower bound is applied to every device time before anything is read
(``clock_offset_us`` in the line: ``lower``, ``upper``, ``applied``).  Where
the trace has no such events, or the bounds cross, nothing is applied and the
line says ``null``.

From the join, a window at a time: *launch latency*, from the start of the
dispatch span to the start of its run; *wake latency*, from
the end of the last run that ended inside the window's ``drain`` span (the
reduction's) to the end of that span.  From the device's idle gaps (what
``device.idle_share`` is made of, on the clock as set) *exposed time by
owner*: each gap is cut at every span's edges and each piece goes to the
innermost span that covers it, ``unowned`` where none does.  No midpoint: a
gap that lies over three spans is split three ways.

Inside a dispatch Python has no span, but the runtime writes its own events
into the same trace: those of the dispatch spans' own thread that lie inside
them are listed by name with their self time a window, the remainder as
``python``; other threads' events inside the same intervals beside them.  All
of it is printed once a traced run in a line ``window_spans: {...}``.

``summary(ctx)`` answers ``None`` where there is nothing to read: an untraced
run, no trace at the default path, a trace that is not this run's, a trace
without a device plane (a CPU rehearsal), a program that opens no such span
(one that predates them), a join that does not hold; and where the trace
cannot be read at all it prints the traceback and answers ``None`` too: these
are a builder's readings, and a run is not to fail for them.
"""

from __future__ import annotations

import bisect
import json
import statistics
import traceback
import typing as t

from benchmark.harness import scopes as scopes_mod
from benchmark.harness import trace as trace_mod

HOST_PREFIX = scopes_mod.HOST_PREFIX
DISPATCH = "burst_dispatch"
DRAIN = "drain"
UNOWNED = "unowned"
PYTHON = "python"
# The runtime's own host events (libtpu's names): one a program enqueued, one
# a program the host learns has ended.
ENQUEUED = "DoEnqueueProgram"
ENDED = "tpu::System::Execute=>Done"
EARLY = 5e-3  # no stamp is taken to stand earlier than this

Span = t.Tuple[str, float, float]  # name, start, end


class HostSpan(t.NamedTuple):
    name: str  # without the prefix: "place_chunk/transfer"
    start: float
    end: float
    window: int | None
    parent: str
    line: str


def load(path: str) -> dict:
    """One pass over the ``.xplane.pb``: the ``bench/window`` spans, our host
    spans with their ``window`` and ``parent``, every other host event by
    thread, and of the first device the programs' runs and the union of its
    operations."""
    from jax.profiler import ProfileData

    windows: t.List[t.Tuple[str, float, float]] = []
    spans: t.List[HostSpan] = []
    others: t.Dict[str, t.List[Span]] = {}
    devices: t.Dict[int, dict] = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"runs": [], "ops": []})
            for line in plane.lines:
                if line.name == scopes_mod.MODULES_LINE:
                    dev["runs"] += [
                        (ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
                elif line.name == trace_mod.OPS_LINE:
                    dev["ops"] += [
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    b = a + ev.duration_ns * 1e-9
                    if ev.name == "bench/window":
                        windows.append((ev.name, a, b - a))
                    elif ev.name.startswith(HOST_PREFIX):
                        stats = dict(ev.stats)
                        window = stats.get("window")
                        spans.append(HostSpan(
                            ev.name[len(HOST_PREFIX):], a, b,
                            None if window is None else int(window),
                            str(stats.get("parent", "")), line.name,
                        ))
                    elif not ev.name.startswith("bench/"):
                        others.setdefault(line.name, []).append((ev.name, a, b))
    first = devices[min(devices)] if devices else {"runs": [], "ops": []}
    return {
        "windows": windows, "spans": sorted(spans, key=lambda s: (s.start, -s.end)),
        "others": others, "runs": sorted(first["runs"], key=lambda r: r[1]),
        "busy": trace_mod.union(first["ops"]), "n_devices": len(devices),
    }


def clock_offset(
    loaded: dict, dispatches: t.Sequence[HostSpan], drains: t.Mapping[int, HostSpan],
    runs: t.Sequence[Span],
) -> dict | None:
    """How early the device's events are stamped in this trace, in seconds,
    from the runtime's own events (module docstring): ``dispatches`` and the
    ``runs`` joined to them by order, each with its window's drain.  ``upper``
    is ``None`` where no learned end follows a run; the whole is ``None``
    where no enqueue lies in any window's dispatch or the bounds cross."""
    events = [e for line in loaded["others"].values() for e in line]
    enqueued = sorted(a for name, a, _ in events if name == ENQUEUED)
    ended = sorted(b for name, _, b in events if name == ENDED)
    lower, upper = [], []
    following = [d.start for d in dispatches[1:]] + [float("inf")]
    for d, (_, a, b), nxt in zip(dispatches, runs, following):
        drain = drains.get(d.window)  # what is dispatched later is dispatched after d.end
        until = drain.start if drain is not None and d.end <= drain.start < nxt else d.end
        i = bisect.bisect_left(enqueued, until) - 1
        if i >= 0 and enqueued[i] >= d.start:
            lower.append(enqueued[i] - a)
        j = bisect.bisect_left(ended, b)
        if j < len(ended) and ended[j] < nxt:
            upper.append(ended[j] - b)
    if not lower or (upper and max(lower) > min(upper)):
        return None
    return {"lower": max(lower), "upper": min(upper) if upper else None}


def shifted(loaded: dict, by: float) -> dict:
    """``loaded`` with every device time moved ``by`` seconds later."""
    return dict(
        loaded, runs=[(n, a + by, b + by) for n, a, b in loaded["runs"]],
        busy=[(a + by, b + by) for a, b in loaded["busy"]],
    )


def owners(spans: t.Sequence[Span]) -> t.List[Span]:
    """The timeline the spans cover, as disjoint ``(owner, start, end)``
    pieces in order: every piece belongs to the innermost span over it, the
    one that started last (of two that started together, the shorter)."""
    edges = sorted({x for _, a, b in spans for x in (a, b)})
    by_start = sorted(spans, key=lambda s: (s[1], -s[2]))
    out: t.List[Span] = []
    open_: t.List[Span] = []
    j = 0
    for lo, hi in zip(edges, edges[1:]):
        while j < len(by_start) and by_start[j][1] <= lo:
            open_.append(by_start[j])
            j += 1
        open_ = [s for s in open_ if s[2] > lo]
        if open_:
            name = max(open_, key=lambda s: (s[1], -s[2]))[0]
            if out and out[-1][0] == name and out[-1][2] == lo:
                out[-1] = (name, out[-1][1], hi)
            else:
                out.append((name, lo, hi))
    return out


def exposed(gaps: t.Sequence[trace_mod.Interval], spans: t.Sequence[Span]) -> t.Dict[str, float]:
    """Seconds of the idle ``gaps`` by owner: each gap cut at the spans' edges,
    each piece to the innermost span over it, :data:`UNOWNED` where none is.
    The pieces of a gap sum to the gap."""
    pieces = owners(spans)
    starts = [a for _, a, _ in pieces]
    out: t.Dict[str, float] = {}
    for lo, hi in gaps:
        covered = 0.0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][1] < hi:
            name, a, b = pieces[i]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            i += 1
        if hi - lo > covered:
            out[UNOWNED] = out.get(UNOWNED, 0.0) + (hi - lo) - covered
    return out


def longest_gap_inside(
    gaps: t.Sequence[trace_mod.Interval], spans: t.Sequence[Span]
) -> float:
    """The longest single stretch of one idle gap inside one of ``spans``."""
    starts = [a for a, _ in gaps]
    longest = 0.0
    for _, lo, hi in spans:
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(gaps) and gaps[i][0] < hi:
            longest = max(longest, min(gaps[i][1], hi) - max(gaps[i][0], lo))
            i += 1
    return longest


def program_of(runs: t.Sequence[Span]) -> str | None:
    """The module that took most device time: the cell's program (its runs
    are named ``<module>(<fingerprint>)``)."""
    by: t.Dict[str, float] = {}
    for name, a, b in runs:
        module = name.split("(")[0]
        by[module] = by.get(module, 0.0) + (b - a)
    return max(by, key=by.get) if by else None


def join(dispatches: t.Sequence[HostSpan], runs: t.Sequence[Span]) -> t.List[Span] | None:
    """The run of each dispatch span, in order; ``None`` unless the window
    numbers are consecutive and every dispatch has exactly one run that
    starts after it and before the next dispatch."""
    numbers = [d.window for d in dispatches]
    if not dispatches or len(runs) != len(dispatches) or None in numbers:
        return None
    if numbers != list(range(numbers[0], numbers[0] + len(numbers))):
        return None
    following = [d.start for d in dispatches[1:]] + [float("inf")]
    held = all(d.start <= run[1] < nxt for d, run, nxt in zip(dispatches, runs, following))
    return list(runs) if held else None


def self_times(events: t.Sequence[Span]) -> t.Tuple[t.Dict[str, float], float]:
    """Self seconds by name of one thread's nested events (an event's time
    less its direct children's), and the seconds the outermost ones cover."""
    out: t.Dict[str, float] = {}
    stack: t.List[list] = []  # name, end, self seconds so far
    top = 0.0

    def close():
        name, _, own = stack.pop()
        out[name] = out.get(name, 0.0) + max(own, 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            close()
        if stack:
            stack[-1][2] -= b - a
        else:
            top += b - a
        stack.append([name, b, b - a])
    while stack:
        close()
    return out, top


def inside(intervals: t.Sequence[trace_mod.Interval], events: t.Sequence[Span]) -> t.List[Span]:
    """The events that lie wholly inside one of the sorted, disjoint ``intervals``."""
    starts = [a for a, _ in intervals]
    out = []
    for name, a, b in events:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= intervals[i][1]:
            out.append((name, a, b))
    return out


def reduce(loaded: dict, gaps: t.Sequence[trace_mod.Interval], window: trace_mod.Interval) -> dict | None:
    """Everything the readers need, or ``None`` where the join does not hold.
    ``gaps`` are the trace's own idle intervals inside ``window``, used as they
    are where the device's clock cannot be set."""
    lo, hi = window
    spans = [s for s in loaded["spans"] if s.end > lo and s.start < hi]
    dispatches = [s for s in spans if s.name == DISPATCH and lo <= s.start < hi]
    drains = {}
    for s in spans:
        if s.name == DRAIN and s.window is not None:
            drains[s.window] = s  # of two in one window, the one opened last: the function's own
    # as stamped, a window's run may stand before the window: the device was
    # idle for far longer than this before the first one
    stamped = [r for r in loaded["runs"] if lo - EARLY <= r[1] < hi]
    program = program_of(stamped)
    if not dispatches or program is None:
        return None
    stamped = [r for r in stamped if r[0].split("(")[0] == program]
    if len(stamped) != len(dispatches):
        return None
    offset = clock_offset(loaded, dispatches, drains, stamped)
    early = 0.0 if offset is None else offset["lower"]
    if offset is not None:
        loaded = shifted(loaded, early)
        gaps = trace_mod.subtract([(lo, hi)], loaded["busy"])
    runs = join(dispatches, [(n, a + early, b + early) for n, a, b in stamped])
    if runs is None:
        return None
    n = len(dispatches)

    launch = [a - d.start for d, (_, a, _) in zip(dispatches, runs)]
    ends = sorted(b for _, _, b in loaded["runs"])  # any program's: the reduction's is the last before a drain's end
    wake = []
    for d in dispatches:
        s = drains.get(d.window)
        i = bisect.bisect_right(ends, s.end) - 1 if s is not None else -1
        if i >= 0 and ends[i] >= s.start:
            wake.append(s.end - ends[i])

    clipped = [(s.name, max(s.start, lo), min(s.end, hi)) for s in spans]
    by_owner = exposed(gaps, clipped)
    by_phase: t.Dict[str, float] = {}
    for name, v in by_owner.items():
        phase = name.split("/")[0]
        by_phase[phase] = by_phase.get(phase, 0.0) + v
    span_s: t.Dict[str, float] = {}
    for name, a, b in clipped:
        span_s[name] = span_s.get(name, 0.0) + (b - a)

    intervals = trace_mod.union((d.start, d.end) for d in dispatches)
    line = dispatches[0].line
    own, top = self_times(inside(intervals, loaded["others"].get(line, [])))
    own[PYTHON] = span_s[DISPATCH] - top
    others = {
        other: self_times(found)[0]
        for other, events in loaded["others"].items() if other != line
        for found in [inside(intervals, events)] if found
    }
    return {
        "n_windows": n, "windows": [dispatches[0].window, dispatches[-1].window],
        "program": program,
        "clock_offset_us": offset and {
            "lower": 1e6 * offset["lower"],
            "upper": None if offset["upper"] is None else 1e6 * offset["upper"],
            "applied": 1e6 * offset["lower"],
        },
        "span_ms": {k: 1e3 * v / n for k, v in span_s.items()},
        "exposed_ms": {k: 1e3 * v / n for k, v in by_owner.items()},
        "exposed_by_phase_ms": {k: 1e3 * v / n for k, v in by_phase.items()},
        "idle_ms": 1e3 * trace_mod.total(gaps) / n,
        "launch_us": [1e6 * x for x in launch], "wake_us": [1e6 * x for x in wake],
        "drain_gap_max_ms": 1e3 * longest_gap_inside(
            sorted(gaps), [c for c in clipped if c[0] == DRAIN]
        ),
        "dispatch_inside_ms": {k: 1e3 * v / n for k, v in own.items()},
        "dispatch_other_threads_ms": {
            other: {k: 1e3 * v / n for k, v in by.items()} for other, by in others.items()
        },
    }


def summary(ctx) -> dict | None:
    """:func:`reduce` of this run's trace, once for all readers of a run."""
    if not hasattr(ctx, "window_span_summary"):
        ctx.window_span_summary = None  # a run that cannot be read is not asked twice
        try:
            ctx.window_span_summary = _summary(ctx)
        except Exception:  # noqa: BLE001: a builder's-eye reading never fails the run it reads
            traceback.print_exc()
    return ctx.window_span_summary


def _summary(ctx) -> dict | None:
    if getattr(ctx, "trace", None) is None:
        return None
    path = scopes_mod.trace_path(ctx.cell["name"])
    if path is None:
        return None
    loaded = load(path)
    window = trace_mod.window_of({"host": loaded["windows"], "devices": {}})
    if not loaded["windows"] or tuple(window) != tuple(ctx.trace["window"]):
        return None  # a trace some earlier run left at the default path
    out = reduce(loaded, ctx.trace["gaps"], window)
    if out is not None:
        print("window_spans: " + json.dumps(printable(out)), flush=True)
    return out


def _spread(values: t.Sequence[float]) -> dict:
    ordered = sorted(values)
    return {
        "n": len(ordered), "median": statistics.median(ordered),
        "p95": ordered[max(-(-95 * len(ordered) // 100) - 1, 0)], "max": ordered[-1],
    } if ordered else {"n": 0}


def printable(out: dict, top: int = 12) -> dict:
    """The summary as the ``window_spans:`` line prints it: the latencies as
    their spread, the runtime's events the ``top`` largest a thread."""
    def largest(by):
        return dict(sorted(by.items(), key=lambda kv: -kv[1])[:top])

    line = {k: v for k, v in out.items() if k not in ("launch_us", "wake_us")}
    line["launch_us"], line["wake_us"] = _spread(out["launch_us"]), _spread(out["wake_us"])
    line["dispatch_inside_ms"] = largest(out["dispatch_inside_ms"])
    line["dispatch_other_threads_ms"] = {
        k: largest(v) for k, v in out["dispatch_other_threads_ms"].items()
    }
    return line


# ------------------------------------------------------------ what a reader calls
def span_ms(ctx, name: str) -> float | None:
    """Mean milliseconds a traced window spent under ``tac/host/<name>``."""
    s = summary(ctx)
    return None if s is None else s["span_ms"].get(name)


def exposed_ms(ctx, phase: str) -> float | None:
    """Mean idle milliseconds a traced window that ``phase`` and its parts
    own; nothing where the trace holds no span of that phase
    (:data:`UNOWNED` is always read)."""
    s = summary(ctx)
    if s is None or (phase != UNOWNED and phase not in s["span_ms"]):
        return None
    return s["exposed_by_phase_ms"].get(phase, 0.0)


def latency_us(ctx, which: str) -> float | None:
    """Median over the traced windows of ``launch`` or ``wake`` latency."""
    s = summary(ctx)
    return statistics.median(s[which + "_us"]) if s is not None and s[which + "_us"] else None
