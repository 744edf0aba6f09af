"""Device time by the program's named scopes, host time by the Trainer's
phases: the reduction behind the per-layer metrics that read inside the program.

The program wraps its parts in ``jax.named_scope`` under ``tac/`` and its
Trainer opens a ``tac/host/<phase>`` trace annotation for every phase.  The
harness's trace holds the annotations as they are; a device operation carries
no scope (the trace is taken without the HLO proto), so it is joined to the
scope table of the compiled program (``<learner>.burst_scope_table()`` /
``epoch_scope_table()``: instruction name -> scopes) by instruction name, and
only inside the runs of that program on the ``XLA Modules`` line.

An entry of the table whose scopes end in ``~`` is an instruction the compiler
made (a layout copy, a loop a scatter was expanded into): it carries no name
of ours, and the table gives it the scopes of its nearest scoped neighbours
along the data flow.  Such time counts towards its group and is also reported
apart, as *inherited*.

Groups: *push*, *sample* (the index draw, the gathers, the pixel decode),
*collect* (acting and env step inside a fused epoch), *compute* (critic,
actor, alpha, optimizer, polyak, gradient averaging: one group, because the
compiler fuses the kernel-gradient convolution with Adam) and *unscoped*: an
operation of another program, one the table does not know, one with no
``tac/`` name, or a fusion that spans two groups (no group holds nine tenths
of its scoped instructions).

The groups partition the *leaf* time: the sum of the operations that are no
containers.  The trace's ``busy_s`` is more than that: it is the union of all
events, and a container (the burst's ``while``) is busy from its first
operation to its last, the few tens of nanoseconds between two operations
included.  That remainder, ``container_gap_s``, is nobody's: it is booked to no
group, it is reported on its own, and it is a fixed cost an operation, so its
share of ``busy_s`` grows when a program sheds its long operations.  Readers
that take a share of ``busy_s`` (``trace.unscoped_share``, ``update.mfu``,
``update.compute_mfu``, ``trunk.mfu``) keep it in their denominator and say so.

``summary(ctx)`` is what a reader calls.  It answers ``None`` where there is
nothing to read: no trace, a trace at the default path that is not this run's,
a program that has no scope table yet; and where the join lost or doubled
device time (``identity_gap``).  A table without a single ``tac/`` name is an
error (a compile cache that handed back another commit's program), never a
reading of 100% unscoped.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import time
import typing as t

from benchmark.harness import registry
from benchmark.harness import trace as trace_mod

GROUPS = (  # scope prefix -> group, first match wins
    ("tac/push", "push"),
    ("tac/sample", "sample"),
    ("tac/collect", "collect"),
    ("tac/", "compute"),
)
UNSCOPED = "unscoped"
INHERITED = "~"  # the table's mark on a scope taken from an instruction's neighbours
HOST_PREFIX = "tac/host/"
MODULES_LINE = "XLA Modules"
# Opcodes that move data and compute nothing: where one of these has no scope
# of its own, its time is still known not to be the model's arithmetic.
DATA_MOVEMENT = frozenset({
    "copy", "copy-start", "copy-done", "slice-start", "slice-done", "gather",
    "scatter", "dynamic-slice", "dynamic-update-slice", "reshape", "transpose",
    "bitcast", "slice", "concatenate", "pad", "broadcast",
})
# How far this reduction may stand from the trace's own (harness/trace.py), on
# the two quantities both take from the same events: the groups' sum against
# the trace's sum by operation kind, the busy union against the trace's.
IDENTITY = 0.02
# A fusion belongs to a group that holds this share of its scoped instructions:
# the compiler folds one cast of the sampled batch into a 400-instruction
# convolution fusion, which stays compute; a gather fused half and half with
# the model's first layer is nobody's.
DOMINANT = 0.9

_NAME = re.compile(r"^%?([\w.\-]+)")
_OPCODE = re.compile(r" = .*?\s([a-z][a-z0-9\-]*)\(")


class ScopeError(Exception):
    """The scope table cannot be what the traced program ran."""


def group_of_scope(scope: str) -> str:
    return next(g for prefix, g in GROUPS if scope.startswith(prefix))


def group_of(counts: t.Mapping[str, int]) -> t.Tuple[str, str]:
    """``(group, reason)`` of one table entry: the group that holds at least
    ``DOMINANT`` of its scoped instructions, else unscoped with why."""
    groups: t.Dict[str, int] = {}
    for scope, count in counts.items():
        if scope:
            g = group_of_scope(scope)
            groups[g] = groups.get(g, 0) + count
    if not groups:
        return UNSCOPED, "no_scope"
    top = max(groups, key=groups.get)
    if groups[top] >= DOMINANT * sum(groups.values()):
        return top, ""
    return UNSCOPED, "two_groups"


def instruction_name(event_name: str) -> str:
    return _NAME.match(event_name).group(1)


def opcode(event_name: str) -> str:
    m = _OPCODE.search(event_name)
    return m.group(1) if m else trace_mod.op_kind(event_name)


def check_table(scoped: dict | None) -> dict:
    if not scoped or not scoped.get("table"):
        raise ScopeError("no scope table for the traced program")
    if not any(s for counts in scoped["table"].values() for s in counts):
        raise ScopeError(
            f"the scope table of {scoped.get('module')!r} has no tac/ name: the "
            "compiled text it was read from is not this commit's program"
        )
    return scoped


def load(path: str) -> dict:
    """One pass over the ``.xplane.pb``: per device the ``XLA Ops`` events and
    the ``XLA Modules`` runs, and from the host plane the ``bench/window``
    spans and the ``tac/host/`` annotations."""
    from jax.profiler import ProfileData

    devices: t.Dict[int, dict] = {}
    windows, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {trace_mod.OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] += [
                        (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    span = (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                    if ev.name == "bench/window":
                        windows.append(span)
                    elif ev.name.startswith(HOST_PREFIX):
                        host.append(span)
    return {"devices": devices, "windows": windows, "host": host}


def container_gaps(
    leaves: t.Sequence[trace_mod.Interval],
    containers: t.Sequence[t.Tuple[float, float, str]],
) -> dict:
    """One device's events, clipped to the window: ``leaves`` (the operations
    that are no containers) and ``containers`` ``(start, end, name)``.  The
    busy union, the union of the leaves, and what lies between the two by the
    innermost container over each gap's middle."""
    covered = trace_mod.union(leaves)
    containers = sorted(containers)
    busy = trace_mod.union(covered + [(a, b) for a, b, _ in containers])
    by: t.Dict[str, float] = {}
    gaps = trace_mod.subtract(busy, covered)
    open_, j = [], 0
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while j < len(containers) and containers[j][0] <= mid:
            open_.append(containers[j])
            j += 1
        while open_ and open_[-1][1] <= mid:
            open_.pop()
        owner = open_[-1][2] if open_ else "no_container"
        by[owner] = by.get(owner, 0.0) + (b - a)
    return {
        "busy_s": trace_mod.total(busy), "leaf_union_s": trace_mod.total(covered),
        "intervals": len(gaps), "by_container": by,
    }


def reduce(loaded: dict, scoped: dict | None, window: t.Tuple[float, float]) -> dict:
    """Seconds by group (averaged over chips), what the unscoped time is made
    of, the busy time no leaf operation covers, and the host's seconds and
    spans by phase, all inside ``window``."""
    scoped = check_table(scoped)
    table, module = scoped["table"], scoped["module"] + "("
    lo, hi = window
    n = max(len(loaded["devices"]), 1)
    device = {g: 0.0 for _, g in GROUPS}
    device[UNSCOPED] = 0.0
    inherited = dict(device)
    by_scope: t.Dict[str, float] = {}
    unscoped_ops: t.Dict[str, float] = {}
    reasons: t.Dict[str, float] = {}
    not_compute = 0.0
    busy_s = leaf_union_s = 0.0
    gap_intervals = 0
    gap_by: t.Dict[str, float] = {}
    known: t.Dict[str, t.Any] = {}  # an event's name -> what the name alone says of it

    def classify(name: str):
        if trace_mod.is_container(name):
            return None
        counts = table.get(instruction_name(name))
        group, reason = (UNSCOPED, "not_in_table") if counts is None else group_of(counts)
        named = group != UNSCOPED and not any(k.endswith(INHERITED) for k in counts)
        scope = None if group == UNSCOPED else max((s_ for s_ in counts if s_), key=counts.get)
        return group, reason, named, scope, opcode(name) in DATA_MOVEMENT

    for dev in loaded["devices"].values():
        runs = sorted((s, s + d) for name, s, d in dev["modules"] if name.startswith(module))
        starts = [ra for ra, _ in runs]
        leaves, containers = [], []
        for name, s, d in dev["ops"]:
            a, b = max(s, lo), min(s + d, hi)
            if b <= a:
                continue
            if name not in known:
                known[name] = classify(name)
            what = known[name]
            if what is None:
                containers.append((a, b, name))
                continue
            leaves.append((a, b))
            group, reason, named, scope, moves_data = what
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                group, reason, named = UNSCOPED, "other_program", False
            device[group] += (b - a) / n
            if group == UNSCOPED:
                short = name[:96]
                unscoped_ops[short] = unscoped_ops.get(short, 0.0) + (b - a) / n
                reasons[reason] = reasons.get(reason, 0.0) + (b - a) / n
            else:
                by_scope[scope] = by_scope.get(scope, 0.0) + (b - a) / n
                if not named:
                    inherited[group] += (b - a) / n
            if (named and group in ("push", "sample")) or (not named and moves_data):
                not_compute += (b - a) / n
        gaps = container_gaps(leaves, containers)
        busy_s += gaps["busy_s"] / n
        leaf_union_s += gaps["leaf_union_s"] / n
        gap_intervals += gaps["intervals"]
        for owner, v in gaps["by_container"].items():
            owner = instruction_name(owner)
            gap_by[owner] = gap_by.get(owner, 0.0) + v / n
    host: t.Dict[str, float] = {}
    host_spans: t.Dict[str, int] = {}
    for name, s, d in loaded["host"]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            phase = name[len(HOST_PREFIX):]
            host[phase] = host.get(phase, 0.0) + (b - a)
            host_spans[phase] = host_spans.get(phase, 0) + 1
    leaf_s = sum(device.values())
    return {
        "device": device, "inherited": inherited, "leaf_s": leaf_s,
        "busy_s": busy_s, "container_gap_s": busy_s - leaf_union_s,
        "leaf_overlap_s": leaf_s - leaf_union_s,
        "container_gap": {
            "intervals": gap_intervals,
            "by_container": sorted(gap_by.items(), key=lambda kv: -kv[1])[:6],
        },
        "by_scope": by_scope, "not_compute_s": not_compute, "unscoped_reasons": reasons,
        "unscoped_ops": sorted(unscoped_ops.items(), key=lambda kv: -kv[1])[:12],
        "host": host, "host_spans": host_spans, "window_s": hi - lo,
    }


def identity_gap(summary: dict, trace_summary: dict) -> float:
    """How far the join stands from the trace's own reduction, as a share: the
    groups' sum against the trace's sum of leaf operations (the quantity the
    groups partition), and this reduction's busy union against the trace's.
    A join that loses or doubles device time (events outside the program's
    runs dropped, another clipping at the window's edges, two devices averaged
    otherwise) fails the first, a trace that is not the one reduced the second.
    The time under a container that no leaf covers is in neither."""
    pairs = (
        (summary["leaf_s"], sum(trace_summary["by_kind"].values())),
        (summary["busy_s"], trace_summary["busy_s"]),
    )
    return max((abs(got - ref) / ref if ref else 0.0) for got, ref in pairs)


def trace_path(cell_name: str) -> str | None:
    """The trace the harness writes where it is given no directory."""
    return trace_mod.find_xplane(
        os.path.join(registry.ROOT, ".bench_out", "trace", cell_name)
    )


def table_of(driver) -> t.Callable[[], dict] | None:
    """The scope table of the program the cell's window runs, from the learner
    the driver still holds after ``free()``; ``None`` where the program has
    no such table (it predates the scopes)."""
    for path in (("dp",), ("trainer", "dp"), ("loop",)):
        learner = driver
        for attr in path:
            learner = getattr(learner, attr, None)
        if hasattr(learner, "burst_scope_table"):
            return learner.burst_scope_table
        if hasattr(learner, "epoch_scope_table"):
            return lambda: learner.epoch_scope_table(driver.steps, driver.every)
    return None


def summary(ctx) -> dict | None:
    """:func:`reduce` of this run's trace, once for all readers of a run."""
    if not hasattr(ctx, "scope_summary"):
        ctx.scope_summary = None  # a run that cannot be read is not asked twice
        ctx.scope_summary = _summary(ctx)
    return ctx.scope_summary


def _summary(ctx) -> dict | None:
    make_table = table_of(ctx.driver)
    path = trace_path(ctx.cell["name"])
    if ctx.trace is None or make_table is None or path is None:
        return None
    loaded = load(path)
    window = trace_mod.window_of({"host": loaded["windows"], "devices": {}})
    if not loaded["windows"] or tuple(window) != tuple(ctx.trace["window"]):
        return None  # a trace some earlier run left at the default path
    t0 = time.perf_counter()
    scoped = make_table()
    out = reduce(loaded, scoped, window)
    out["table_compile_s"] = time.perf_counter() - t0
    out["identity_gap"] = identity_gap(out, ctx.trace)
    # per unit of work as the cell's readers count it: a scan iteration of a
    # fused epoch, else a gradient step
    per = ctx.n_windows * (ctx.per_window.get("iterations") or ctx.per_window["grad_steps"])
    out["container_gap_us_per_step"] = 1e6 * out["container_gap_s"] / per if per else None
    print("scopes: " + json.dumps({
        k: out[k] for k in (
            "device", "inherited", "by_scope", "unscoped_reasons", "not_compute_s",
            "unscoped_ops", "host", "host_spans", "table_compile_s", "identity_gap",
            "leaf_s", "busy_s", "container_gap_s", "container_gap_us_per_step",
            "container_gap", "leaf_overlap_s",
        )
    }), flush=True)
    return out if out["identity_gap"] <= IDENTITY else None


def group_us(ctx, group: str, per: float) -> float | None:
    """Device microseconds of ``group`` over ``per`` units of work: leaf
    operations alone, so the groups of a run sum to ``leaf_s``, which is
    ``busy_s`` less ``container_gap_s`` where no two operations overlap."""
    s = summary(ctx)
    return 1e6 * s["device"][group] / per if s is not None and per else None
