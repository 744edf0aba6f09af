"""What the ``trunk.*`` per-layer readers share: device seconds by the trunk's
scopes (from ``scopes.summary``) and by kernel (from the trace's operation
kinds), the step's counted assignments, and the steps of the window.

Every function answers ``None`` where there is nothing to read: an untraced
run, a program without the trunk's scopes or counters (the parent's)."""

from __future__ import annotations

from benchmark.harness import peaks, scopes, trace as trace_mod

GROUPED_PRODUCT = "ragged-dot-none"  # XLA:TPU's name for a lowered ragged_dot
EXPERT_PRODUCTS = "tac/trunk/moe/experts/products"  # where ``ops/moe.py`` calls them
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


def grouped_product_seconds(ctx) -> float | None:
    """Device seconds of the routed experts' grouped products over the traced
    window, whatever implements them: the operations under the scope
    ``EXPERT_PRODUCTS`` and XLA:TPU's own grouped-product kernels, which it
    puts in the place of a ``ragged_dot`` under a name of its own and without
    the program's scope (my chip run, PR 40: no instruction of the compiled
    burst carries the scope; a kernel of our own would, and is then read
    here with no reader edited)."""
    if ctx.trace is None:
        return None
    scoped_us = scope_us_per_step(ctx, EXPERT_PRODUCTS) or 0.0
    spent = 1e-6 * scoped_us * steps(ctx) + (kernel_seconds(ctx, GROUPED_PRODUCT) or 0.0)
    return spent or None


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
