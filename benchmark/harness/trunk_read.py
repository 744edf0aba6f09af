"""What the ``trunk.*`` per-layer readers share: device seconds by the trunk's
scopes (from ``scopes.summary``) and by kernel (from the trace's operation
kinds), the step's counted assignments, and the steps of the window.

Every function answers ``None`` where there is nothing to read: an untraced
run, a program without the trunk's scopes or counters (the parent's)."""

from __future__ import annotations

from benchmark.harness import peaks, scopes, trace as trace_mod

GROUPED_PRODUCT = "ragged-dot-none"  # XLA:TPU's name for a lowered ragged_dot
FLASH = "attention"  # the Pallas kernels, named for the function that calls them


def steps(ctx) -> int:
    return ctx.n_windows * ctx.per_window["grad_steps"]


def scope_us_per_step(ctx, prefix: str) -> float | None:
    """Device microseconds a step under scopes that start with ``prefix``
    (an inherited scope, marked ``~``, counts with its name)."""
    s = scopes.summary(ctx)
    if s is None or not steps(ctx):
        return None
    found = [v for k, v in s["by_scope"].items() if k.rstrip(scopes.INHERITED).startswith(prefix)]
    return 1e6 * sum(found) / steps(ctx) if found else None


def kernel_seconds(ctx, kind: str) -> float | None:
    if ctx.trace is None:
        return None
    return trace_mod.kind_seconds(ctx.trace, kind) or None


def counters(ctx) -> dict | None:
    read = getattr(ctx.driver, "trunk_counters", None)
    return read() if read is not None else None


def assignments(ctx) -> tuple[float, float] | None:
    """(online, target) assignments on held experts a step, over layers."""
    c = counters(ctx)
    if not c:
        return None
    return c["trunk/held_assignments"], c["trunk/held_assignments_target"]


def model(ctx) -> dict:
    return getattr(ctx.driver, "model", ctx.config["model"])


def peak(ctx) -> dict:
    return peaks.peaks_for(ctx.device["kind"])
