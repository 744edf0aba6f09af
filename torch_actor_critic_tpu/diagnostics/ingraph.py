"""In-graph learning-health reductions (the tentpole's device half).

Everything here is a pure jnp function designed to run INSIDE the
compiled update step/burst — the Podracer discipline (arXiv:2104.06272)
of keeping all per-step computation in the compiled program, applied to
diagnostics: a gradient global-norm or TD-error histogram costs a few
fused reductions over values the update already materialized, and the
host sees only the per-burst reduced scalars it was already fetching.
Zero extra host<->device syncs, by construction.

Metric-key reduction convention
-------------------------------

Diagnostic metrics flow through three reduction stages (scan steps
within a burst, replicas across the dp mesh, bursts within an epoch)
and each stage picks its reduction FROM THE KEY SUFFIX, so a metric's
aggregation semantics live in its name and every stage agrees:

==========  ==============================  =====================
suffix       in-graph / host reduce          cross-replica
==========  ==============================  =====================
``_max``     ``max``                         ``lax.pmax``
``_min``     ``min``                         ``lax.pmin``
``_sum``     ``sum``                         ``lax.psum``
``_hist``    ``sum`` (bucket axis kept)      ``lax.psum``
(default)    ``mean``                        ``lax.pmean``
==========  ==============================  =====================

None of the pre-existing metric keys (``loss_q``, ``q_mean``, ...)
match a special suffix, so the default-``mean`` path reproduces the
historical burst reduction bit-for-bit — the ``diagnostics="off"``
parity guarantee rests on that.

The TD-error histogram buckets |TD| with the SAME geometric bucket
spec as :class:`~torch_actor_critic_tpu.telemetry.histogram.
FixedBucketHistogram` (lo/growth/count shared via
:func:`~torch_actor_critic_tpu.telemetry.histogram.geometric_bucket_count`),
so the host merges the device counts straight into the telemetry
schema with :meth:`FixedBucketHistogram.merge_counts`.
"""

from __future__ import annotations

import math
import typing as t

import jax
import jax.numpy as jnp
import numpy as np

from torch_actor_critic_tpu.telemetry.histogram import (
    FixedBucketHistogram,
    geometric_bucket_count,
)

__all__ = [
    "TD_HIST_GROWTH",
    "TD_HIST_HI",
    "TD_HIST_LO",
    "bucket_counts",
    "cross_replica_reduce",
    "global_norm",
    "make_td_histogram",
    "norm_ratio",
    "reduce_burst_metrics",
    "reduce_metric_rows",
    "reduction_for",
    "replica_skew",
    "saturation_fraction",
    "split_member_metrics",
    "split_scenario_metrics",
]

# TD-error magnitude bucket spec: |TD| from 1e-3 to 1e4 at the same
# ~19%-wide geometric buckets the latency histogram uses. Rewards in
# the supported envs are O(1e-2)..O(1e3), so early-training TD errors
# land comfortably inside; the under/overflow buckets catch the rest
# with exact min/max side stats.
TD_HIST_LO = 1e-3
TD_HIST_HI = 1e4
TD_HIST_GROWTH = 2 ** 0.25
TD_HIST_BUCKETS = geometric_bucket_count(TD_HIST_LO, TD_HIST_HI, TD_HIST_GROWTH)


def make_td_histogram() -> FixedBucketHistogram:
    """Host-side merge target matching :func:`bucket_counts`' spec."""
    return FixedBucketHistogram(
        lo=TD_HIST_LO, hi=TD_HIST_HI, growth=TD_HIST_GROWTH
    )


# ------------------------------------------------------------- reductions


def reduction_for(key: str) -> str:
    """Reduction kind (``mean``/``max``/``min``/``sum``) for a metric
    key, per the suffix convention in the module docstring."""
    if key.endswith("_max"):
        return "max"
    if key.endswith("_min"):
        return "min"
    if key.endswith("_sum") or key.endswith("_hist"):
        return "sum"
    if key.endswith("_first"):
        return "first"
    return "mean"


def reduce_burst_metrics(metrics: t.Dict[str, jax.Array]) -> t.Dict[str, jax.Array]:
    """Reduce scan-stacked burst metrics (leading axis = update step)
    by key suffix. ``_hist`` keys keep their trailing bucket axis; all
    default-``mean`` keys reproduce the historical
    ``tree_map(jnp.mean, metrics)`` exactly."""
    out = {}
    for k, v in metrics.items():
        r = reduction_for(k)
        if k.endswith("_hist"):
            out[k] = jnp.sum(v, axis=0)
        elif r == "sum":
            out[k] = jnp.sum(v, axis=0)
        elif r == "max":
            out[k] = jnp.max(v, axis=0)
        elif r == "min":
            out[k] = jnp.min(v, axis=0)
        elif r == "first":  # the burst's first update alone
            out[k] = v[0]
        else:
            out[k] = jnp.mean(v, axis=0)
    return out


def cross_replica_reduce(
    metrics: t.Dict[str, jax.Array], axes
) -> t.Dict[str, jax.Array]:
    """Suffix-aware collective reduction across mesh replicas: the
    dp-parallel analogue of :func:`reduce_burst_metrics` (a per-burst
    max must stay a max across devices, histogram counts must add)."""
    out = {}
    for k, v in metrics.items():
        r = reduction_for(k)
        if r == "sum":
            out[k] = jax.lax.psum(v, axes)
        elif r == "max":
            out[k] = jax.lax.pmax(v, axes)
        elif r == "min":
            out[k] = jax.lax.pmin(v, axes)
        else:
            out[k] = jax.lax.pmean(v, axes)
    return out


def replica_skew(
    metrics: t.Dict[str, jax.Array],
    keys: t.Sequence[str],
    axis: str = "dp",
) -> t.Dict[str, jax.Array]:
    """Per-replica spread (``pmax - pmin``) of selected per-device
    metrics — the replica-desync leading indicator: replicated params
    kept bit-identical by pmean'd grads must show ``param_norm`` skew
    of exactly 0.0; any positive value means the replicas have drifted
    (ICI fault, nondeterministic kernel) and will eventually hand the
    divergence sentinel a NaN. Grad-norm skew is naturally nonzero
    (each device samples its own replay shard); its MAGNITUDE is the
    signal — see docs/OBSERVABILITY.md for interpretation."""
    return {
        k + "_skew": jax.lax.pmax(metrics[k], axis) - jax.lax.pmin(metrics[k], axis)
        for k in keys
        if k in metrics
    }


def split_member_metrics(metrics: t.Mapping[str, t.Any]) -> dict:
    """Per-member metric layout for population training (host-side).

    A population epoch reports every metric with a leading member axis
    — N real learning curves, not one averaged one. This expands each
    ``(N,)`` value into ``{key}_m{i}`` scalars (the layout the
    trainer's ``reward_m{i}`` keys established; see
    docs/OBSERVABILITY.md) AND keeps a population aggregate under the
    base key, reduced per the suffix convention above over the FINITE
    members only (a member with no finished episodes reports NaN
    ``reward``; averaging that away would blank the aggregate curve).
    Scalars pass through; ``_hist`` keys sum their member axis and keep
    the bucket axis.
    """
    out: dict = {}
    for k, v in metrics.items():
        arr = np.asarray(v)
        if arr.ndim == 0:
            out[k] = float(arr)
            continue
        if k.endswith("_hist"):
            out[k] = arr.reshape(-1, arr.shape[-1]).sum(axis=0)
            continue
        for i, x in enumerate(arr.reshape(arr.shape[0], -1).mean(axis=1)):
            out[f"{k}_m{i}"] = float(x)
        finite = arr[np.isfinite(arr)]
        if finite.size == 0:
            out[k] = float("nan")
            continue
        r = reduction_for(k)
        out[k] = float(
            finite.sum() if r == "sum"
            else finite.max() if r == "max"
            else finite.min() if r == "min"
            else finite.mean()
        )
    return out


# Scenario metric axes (scenarios/, docs/SCENARIOS.md): an in-graph
# metric key ending `_per_<axis>` carries one value per agent/task;
# the host expands it with the matching short suffix — the `_m{i}`
# member convention applied to the scenario axes (`reward_per_task`
# (T,) -> `reward_t0..T-1`).
_SCENARIO_AXES = {"agent": "a", "task": "t"}


def split_scenario_metrics(metrics: t.Mapping[str, t.Any]) -> dict:
    """Host-side scenario metric layout for the fused-loop drivers.

    Scalars become plain floats — on a classic single-agent run this
    is EXACTLY the historical ``{k: float(v)}`` (pinned by tests).
    ``{base}_per_agent``/``{base}_per_task`` vectors expand to
    ``{base}_a{i}`` / ``{base}_t{i}`` scalars; any other vector metric
    falls back to ``{key}_{i}`` indexing so nothing is silently
    dropped.
    """
    out: dict = {}
    for k, v in metrics.items():
        arr = np.asarray(v)
        if arr.ndim == 0:
            out[k] = float(arr)
            continue
        for axis, short in _SCENARIO_AXES.items():
            suffix = f"_per_{axis}"
            if k.endswith(suffix):
                base = k[: -len(suffix)]
                for i, x in enumerate(arr.ravel()):
                    out[f"{base}_{short}{i}"] = float(x)
                break
        else:
            for i, x in enumerate(arr.ravel()):
                out[f"{k}_{i}"] = float(x)
    return out


def reduce_metric_rows(rows: t.Sequence[t.Mapping[str, t.Any]]) -> dict:
    """Host-side epoch aggregation over per-burst metric rows (numpy):
    same suffix rules, reducing over every axis (bursts, and the member
    axis under population training) except a ``_hist`` key's trailing
    bucket axis."""
    out: dict = {}
    for k in rows[0]:
        arr = np.stack([np.asarray(r[k]) for r in rows])
        r = reduction_for(k)
        if k.endswith("_hist"):
            out[k] = arr.reshape(-1, arr.shape[-1]).sum(axis=0)
        elif r == "sum":
            out[k] = arr.sum()
        elif r == "max":
            out[k] = arr.max()
        elif r == "min":
            out[k] = arr.min()
        else:
            out[k] = arr.mean()
    return out


# ----------------------------------------------------------- primitives


def global_norm(*trees: t.Any) -> jax.Array:
    """Fused L2 global norm over every inexact leaf of the given
    pytrees — one sqrt over a sum of per-leaf square-sums, the standard
    gradient-explosion monitor (float32 accumulation regardless of
    compute dtype)."""
    leaves = [
        x
        for tree in trees
        for x in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact)
    ]
    if not leaves:
        return jnp.float32(0.0)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def norm_ratio(updates: t.Any, params: t.Any) -> jax.Array:
    """Update-to-param ratio ``||updates|| / ||params||`` — the
    step-size health signal (healthy Adam training sits around 1e-3;
    orders-of-magnitude excursions flag lr/loss-scale trouble)."""
    return global_norm(updates) / (global_norm(params) + 1e-12)


def saturation_fraction(
    actions: jax.Array, act_limit: float, threshold: float = 0.99
) -> jax.Array:
    """Fraction of action components pinned against the tanh squash
    (``|a| > threshold * act_limit``): a saturated policy has vanishing
    tanh gradients and logp spikes — a classic silent SAC failure."""
    return jnp.mean(
        (jnp.abs(actions) > threshold * act_limit).astype(jnp.float32)
    )


def bucket_counts(
    values: jax.Array,
    lo: float = TD_HIST_LO,
    growth: float = TD_HIST_GROWTH,
    n_buckets: int = TD_HIST_BUCKETS,
) -> jax.Array:
    """On-device fixed-bucket histogram of ``|values|``: an int32
    ``(n_buckets + 2,)`` counts vector (underflow + geometric interior
    + overflow) under the same bucket indexing as
    ``FixedBucketHistogram.record`` — one scatter-add per reduction,
    constant memory at any sample count. Non-finite samples are
    dropped (a non-finite TD error is the divergence sentinel's
    business, not the histogram's)."""
    v = jnp.abs(values.astype(jnp.float32)).ravel()
    valid = jnp.isfinite(v)
    log_lo = math.log(lo)
    log_growth = math.log(growth)
    # Compute the log on a value clamped away from 0 — the underflow
    # branch of the where() masks the result for v < lo anyway, and the
    # clamp keeps log(0) = -inf out of the int cast.
    idx = (
        jnp.floor(
            (jnp.log(jnp.maximum(v, lo * 0.5)) - log_lo) / log_growth
        ).astype(jnp.int32)
        + 1
    )
    idx = jnp.where(v < lo, 0, jnp.clip(idx, 1, n_buckets + 1))
    # Invalid samples scatter weight 0 into bucket 0.
    idx = jnp.where(valid, idx, 0)
    return jnp.zeros(n_buckets + 2, jnp.int32).at[idx].add(
        valid.astype(jnp.int32)
    )
