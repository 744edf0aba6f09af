"""One run of one cell: set-up, a measured window, the check, one JSON line.

``run_cell`` is the whole of a run but for the look for a chip, so that a test
can drive it on the CPU at a cut size (``rehearsal=True``: every metric is then
printed under ``cpu_rehearsal.<name>``, never under a device metric's name).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time
import types
import typing as t

from benchmark.harness import registry

T_PROCESS = time.time()


def percentile(values: t.Sequence[float], q: float) -> float:
    """Nearest-rank percentile."""
    ordered = sorted(values)
    return ordered[max(math.ceil(q * len(ordered)) - 1, 0)]


def window_account(windows, spans) -> dict:
    """For a builder's eye (the driver reads none of it): where a window's
    time went by the harness's own spans, how the windows' lengths were
    spread, and what the longest window spent where."""
    lengths = [1e3 * (b - a) for a, b in windows]
    n = len(lengths)
    longest = max(range(n), key=lengths.__getitem__)
    t_a, t_b = windows[longest]
    tenths = (lengths[i * n // 10:(i + 1) * n // 10] for i in range(10))
    return {
        "window_spans_ms": {
            k: 1e3 * v / n for k, v in spans.totals().items() if k != "window"
        },
        "window_ms": {
            "p50": percentile(lengths, 0.5), "p95": percentile(lengths, 0.95),
            "p99": percentile(lengths, 0.99), "max": lengths[longest],
            # the mean length in each tenth of the run: a level that moves inside
            # a run is the machine's, one that differs from run to run the process's
            "tenths": [sum(part) / len(part) for part in tenths if part],
        },
        "window_longest": {
            "index": longest, "of": n,
            "spans_ms": {
                name: 1e3 * d for name, t0, d in spans.records
                if name != "window" and t_a <= t0 <= t_b
            },
        },
    }


def end_to_end(bench, cell, windows, per_window, setup_s) -> dict:
    """The cell's end-to-end metrics over all the work and all the time of
    the window: from the first window's start to the last one's end."""
    elapsed = windows[-1][1] - windows[0][0]
    n = len(windows)
    values = {
        "setup_s": setup_s,
        "grad_steps_per_s": n * per_window["grad_steps"] / elapsed,
        "env_steps_per_s": n * per_window["env_steps"] / elapsed,
        "window_ms.p95": 1e3 * percentile([b - a for a, b in windows], 0.95),
    }
    return {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in registry.metrics_for(bench, "end_to_end", cell["name"])
    }


def device_object(devices, trace_summary=None) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    out = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": int(max(peaks)),
    }
    if trace_summary is not None:
        out["busy_s"] = trace_summary["busy_s"]
        out["window_s"] = trace_summary["window_s"]
    return out


def run_cell(
    bench: dict, cell: dict, config: dict, *, seed: int, seconds: float,
    trace: bool, rehearsal: bool = False, out_dir: str | None = None,
    bench_dir: str = registry.BENCH_DIR, t_process: float = T_PROCESS,
) -> dict:
    import jax

    from benchmark.harness import spans as spans_mod
    from benchmark.harness.check import Comparison
    from benchmark.harness import trace as trace_mod
    from torch_actor_critic_tpu.aot.cache import enable_persistent_cache
    from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

    devices = jax.devices()[: cell["chips"]]
    if not rehearsal:
        enable_persistent_cache()
    watchdog = get_watchdog().install()
    spans = spans_mod.Spans(annotate=trace)
    driver = registry.load_driver(cell["driver"], bench_dir)(
        cell, config, seed, spans, {"rehearsal": rehearsal}
    )
    driver.setup()
    wd_setup = watchdog.snapshot()
    setup_spans = {k: round(v, 3) for k, v in spans.totals().items()}
    spans.clear()
    setup_s = time.time() - t_process

    trace_dir = None
    if trace:
        seconds = min(seconds, cell["traffic"].get("trace_seconds", 4))
        trace_dir = os.path.join(out_dir or os.path.join(registry.ROOT, ".bench_out"),
                                 "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the device's lines and our spans are wanted
        options.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    windows = []
    t_start = time.perf_counter()
    try:
        while time.perf_counter() - t_start < seconds:
            t_a = time.perf_counter()
            with spans.span("window"):
                driver.window()
            windows.append((t_a, time.perf_counter()))
    finally:
        if trace:
            jax.profiler.stop_trace()
    wd_window = watchdog.snapshot()
    per_window = driver.per_window()
    summary = None
    if trace:
        path = trace_mod.find_xplane(trace_dir)
        if path is not None:
            summary = trace_mod.reduce(trace_mod.load(path))
    if summary is not None and hasattr(driver, "host_spans") and summary["host"]:
        # The program's own host spans are on perf_counter; the first
        # bench/window span is on both clocks and gives the offset.
        first = min(s for n, s, _ in summary["host"] if n == "bench/window")
        offset = first - windows[0][0]
        summary["host"] += [
            ("trainer/" + n, t0 + offset, d) for n, t0, d in driver.host_spans()
        ]
    device = device_object(devices, summary)
    driver.free()

    comparisons = [
        Comparison(
            "compiles_in_window",
            float(wd_window["compiles_total"] - wd_setup["compiles_total"]), 0.0, "exact",
        )
    ] + list(driver.check(config["reference_mode"]))
    for c in comparisons:
        print(c.line(), flush=True)
    correct = all(c.ok for c in comparisons)

    if trace:
        ctx = types.SimpleNamespace(  # what a per-layer reader may read
            trace=summary, watchdog=wd_setup, spans=spans, windows=windows,
            per_window=per_window, cell=cell, config=config, device=device,
            driver=driver, n_windows=len(windows),
        )
        metrics = {}
        for m in registry.metrics_for(bench, "per_layer", cell["name"]):
            value = registry.load_layer_metric(m["name"], bench_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        metrics = end_to_end(bench, cell, windows, per_window, setup_s)
    if rehearsal:
        metrics = {"cpu_rehearsal." + k: v for k, v in metrics.items()}
    result = {
        "correct": bool(correct), "attempted": len(windows),
        "failed": 0 if correct else len(windows), "metrics": metrics,
        "device": device, "workload": cell["name"], "seed": seed,
    }
    if rehearsal:
        result["rehearsal"] = "cpu"
    result["setup_spans_s"] = setup_spans
    result.update(window_account(windows, spans))
    if trace and summary is not None:
        result["breakdown"] = trace_mod.breakdown(summary)
        result["device_kinds_s"] = sorted(
            summary["by_kind"].items(), key=lambda kv: -kv[1]
        )[:25]
    # last in the line: each number compared, beside its limit
    result["comparisons"] = {c.name: [c.value, c.limit] for c in comparisons}
    return result


def main(argv: t.Sequence[str] | None = None, t_process: float = T_PROCESS) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        bench, cell, config = registry.resolve(args.workload)
        import torch_actor_critic_tpu  # noqa: F401 — the system under test
    except (registry.BenchmarkError, ImportError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"benchmark: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"jax found {len(devices)} x {devices[0].platform}. No result.",
            file=sys.stderr,
        )
        return 3
    from benchmark.harness.peaks import peaks_for

    peaks_for(devices[0].device_kind)  # a device not in the table is an error
    result = run_cell(
        bench, cell, config, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_process=t_process,
    )
    emit(result)
    return 0


def emit(result: dict) -> None:
    """The end of a run, correct or not: the result as the last line of
    standard output, then each number compared beside its limit as the last
    lines of standard error (what the driver's record keeps of a run at fault)."""
    print(json.dumps(result), flush=True)
    for name, (value, limit) in result["comparisons"].items():
        print(f"check {name}: {value!r} against {limit!r}", file=sys.stderr, flush=True)
