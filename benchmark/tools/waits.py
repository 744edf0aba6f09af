"""The windows of a run that waited, with what they left behind: a builder's
tool (the driver reads none of it).

Installs a ``TelemetryRecorder`` as the process's current one, so that the
spans the window's own functions open are kept in its ring, runs the cell
untraced for each seed as ``benchmark/run.py`` would (``harness.main.run_cell``,
the same ``--seconds``), and prints, for every window longer than 1.5 times the
run's median, its spans on the host clock and what the operating system says of
the thread over its ``drain/fetch``: the CPU seconds it ran and the seconds it
stood runnable on a run queue.  A wait with both near nothing was spent asleep
on the runtime; one with the queue's share high was the machine's scheduler.
Run on the chip:

    python3 benchmark/tools/waits.py --workload wallrunner_cnn_burst --seeds 1 2 3
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

LONG = 1.5  # times the run's median


def windows_of(records, names) -> dict:
    """``{window number: {"start", "end", "spans": {name: ms}, "fetch": record}}``
    from the ring's records: a window is what carries one number."""
    out: dict = {}
    for r in records:
        name = names[r.phase]
        if name.split("/")[0] not in ("stage", "place_chunk", "burst_dispatch", "drain"):
            continue
        w = out.setdefault(r.window, {"start": r.start, "end": r.start, "spans": {}})
        w["start"] = min(w["start"], r.start)
        w["end"] = max(w["end"], r.start + r.duration)
        w["spans"][name] = w["spans"].get(name, 0.0) + 1e3 * r.duration
        if name == "drain/fetch":
            w["fetch"] = r
    return out


def long_windows(windows: dict, last: int) -> tuple:
    """The ``last`` windows (the timed ones; set-up's come before them), their
    median length and those over :data:`LONG` times it."""
    timed = dict(sorted(windows.items())[-last:])
    lengths = {n: 1e3 * (w["end"] - w["start"]) for n, w in timed.items()}
    median = statistics.median(lengths.values())
    return median, [
        {
            "window": n, "ms": lengths[n], "spans_ms": timed[n]["spans"],
            "fetch_thread_cpu_ms": _ms(timed[n].get("fetch"), "thread_cpu_s"),
            "fetch_runq_wait_ms": _ms(timed[n].get("fetch"), "runq_wait_s"),
        }
        for n in timed if lengths[n] > LONG * median
    ]


def _ms(record, field):
    value = getattr(record, field, None)
    return None if value is None else 1e3 * value


def waits(bench, cell, config, seed: int, seconds: float, **run_cell) -> dict:
    """One untraced run of the cell with a recorder installed, and its
    windows that waited."""
    from benchmark.harness import main as harness
    from torch_actor_critic_tpu.telemetry import recorder

    rec = recorder.TelemetryRecorder(run_dir=None, ring_capacity=1 << 17)
    previous = recorder.install(rec)
    try:
        result = harness.run_cell(
            bench, cell, config, seed=seed, seconds=seconds, trace=False,
            t_process=time.time(), **run_cell,
        )
    finally:
        recorder.install(previous)
    windows = windows_of(rec.ring.records(), recorder.SPAN_NAMES)
    median, long_ = long_windows(windows, result["attempted"])
    return {
        "workload": cell["name"], "seed": seed, "windows": result["attempted"],
        "median_ms": median, "long": long_, "window_ms": result["window_ms"],
        "correct": result["correct"],
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--seconds", type=float, default=30.0)
    args = parser.parse_args(argv)

    from benchmark.harness import registry

    bench, cell, config = registry.resolve(args.workload)
    for seed in args.seeds:
        print("waits: " + json.dumps(waits(bench, cell, config, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
