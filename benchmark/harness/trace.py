"""Reduction from a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone.  A device is a plane named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event for each executed
HLO operation, with start and duration in nanoseconds.  Container operations
(``while``, ``conditional``, ``call``) span their bodies' events on the same
line, so they count towards the busy union and never towards a sum by
operation.  Host spans are the ``bench/...`` trace annotations the harness
opens, found on the host plane's lines on the same clock.
"""

from __future__ import annotations

import functools
import glob
import os
import re
import typing as t

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")

Interval = t.Tuple[float, float]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


@functools.lru_cache(maxsize=1 << 16)  # a trace names each instruction once a step
def op_kind(name: str) -> str:
    """``%convolution.12`` -> ``convolution``; fusions keep their flavour
    (``copy_fusion``, ``convolution_fusion``) as the trace names it."""
    base = name.lstrip("%").split(" ")[0]
    base = re.sub(r"[.\d]+$", "", base)
    return base.replace("_", "-") if base else "unknown"


def load(path: str) -> dict:
    """``{"devices": {index: [(name, start_s, dur_s)]}, "host": [(name,
    start_s, dur_s)]}`` with times in seconds on the trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: t.Dict[int, list] = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench/"):
                        host.append((ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    return {"devices": devices, "host": host}


def union(intervals: t.Iterable[Interval]) -> t.List[Interval]:
    out: t.List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: t.Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: t.List[Interval], b: t.List[Interval]) -> t.List[Interval]:
    """The parts of the merged intervals ``a`` that no interval of the merged
    ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def is_container(name: str) -> bool:
    return op_kind(name) in CONTAINERS


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(tag in kind for tag in ("all-reduce", "all-gather", "reduce-scatter", "collective", "all-to-all"))


def window_of(trace: dict) -> Interval:
    """The traced window: from the first ``bench/window`` span's start to the
    last one's end; without them, the extent of the device operations."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == "bench/window"]
    if not spans:
        spans = [(s, s + d) for ops in trace["devices"].values() for _, s, d in ops]
    if not spans:
        return (0.0, 0.0)
    return (min(a for a, _ in spans), max(b for _, b in spans))


def reduce(trace: dict) -> dict:
    """Everything the per-layer readers and the breakdown need."""
    lo, hi = window_of(trace)
    window_s = hi - lo
    per_device = []
    by_op: t.Dict[str, float] = {}
    by_kind: t.Dict[str, float] = {}
    gaps: t.List[Interval] = []
    exposed = 0.0
    collective_s = 0.0
    for index in sorted(trace["devices"]):
        ops = [(n, max(s, lo), min(s + d, hi)) for n, s, d in trace["devices"][index]
               if min(s + d, hi) > max(s, lo)]
        busy = union((a, b) for _, a, b in ops)
        per_device.append(total(busy))
        leaf = [(n, a, b) for n, a, b in ops if not is_container(n)]
        for n, a, b in leaf:
            by_op[n] = by_op.get(n, 0.0) + (b - a)
            k = op_kind(n)
            by_kind[k] = by_kind.get(k, 0.0) + (b - a)
        coll = union((a, b) for n, a, b in leaf if is_collective(n))
        rest = union((a, b) for n, a, b in leaf if not is_collective(n))
        collective_s += total(coll)
        exposed += total(subtract(coll, rest))
        if index == min(trace["devices"]):
            gaps = subtract([(lo, hi)], busy)
    n = max(len(per_device), 1)
    return {
        "window_s": window_s, "window": (lo, hi),
        "busy_s": sum(per_device) / n, "busy_s_per_device": per_device,
        "n_devices": len(per_device),
        "by_op": {k: v / n for k, v in by_op.items()},
        "by_kind": {k: v / n for k, v in by_kind.items()},
        "collective_s": collective_s / n, "collective_exposed_s": exposed / n,
        "gaps": gaps, "host": trace["host"],
    }


def kind_seconds(summary: dict, *tags: str) -> float:
    """Device seconds (averaged over chips) of the operations whose kind
    contains one of ``tags``."""
    return sum(
        v for k, v in summary["by_kind"].items()
        if any(k == tag or k.startswith(tag + "-") or k.endswith("-" + tag) for tag in tags)
    )


def breakdown(summary: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took most
    time, and the longest idle gaps by the host span that covered them."""
    ops = sorted(summary["by_op"].items(), key=lambda kv: -kv[1])[:top]
    host = [(n, s, s + d) for n, s, d in summary["host"] if n != "bench/window"]
    named: t.Dict[str, float] = {}
    for a, b in summary["gaps"]:
        mid = 0.5 * (a + b)
        owner = next((n for n, s, e in host if s <= mid < e), "bench/unattributed")
        named[owner] = named.get(owner, 0.0) + (b - a)
    gaps = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[n, s] for n, s in gaps],
    }
