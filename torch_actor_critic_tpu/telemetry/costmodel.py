"""Per-program compute-cost attribution: FLOPs, roofline, MFU.

The realistic workloads use a small share of the chip's arithmetic
(PERF.md section 5), and a run needs to say WHICH program eats the
gap, and whether it is compute- or memory-bound. This module turns
"MFU is low" into "program X is memory-bound at 0.4 FLOPs/byte":

- :class:`CostRegistry` — a process-wide registry (one per process,
  like the recompilation watchdog) where every jit entry point
  registers its XLA cost analysis (FLOPs, bytes accessed, output
  bytes) under the SAME source names the watchdog already uses
  (``train/update_burst``, ``serve/forward[bN]``,
  ``train/ondevice_epoch``, ...). Registration happens once per
  compiled program, off the hot path (trainer first-dispatch, serving
  warmup), and ONLY when cost accounting is enabled — the
  ``telemetry=None`` zero-overhead contract is untouched.
- :func:`roofline` — combine a program's static cost with a measured
  span duration into achieved FLOP/s, arithmetic intensity, MFU and a
  compute-/memory-bound classification against configurable peaks
  (:class:`Peaks`: device-kind defaults, ``TAC_PEAK_FLOPS`` /
  ``TAC_PEAK_BW`` overrides — CPU runs stay provable by pinning the
  knobs).
- :func:`classify_epoch` — host/device/input attribution of one host
  Trainer epoch from its phase spans (device-busy fraction =
  burst+drain time over wall time).

``cost_analysis()`` works on CPU-lowered programs, so the whole layer
is CI-provable under ``JAX_PLATFORMS=cpu`` (``make cost-smoke``).
"""

from __future__ import annotations

import logging
import os
import threading
import typing as t

logger = logging.getLogger(__name__)

__all__ = [
    "CostRegistry",
    "Peaks",
    "classify_epoch",
    "get_cost_registry",
    "peak_flops_for",
    "peak_hbm_bw_for",
    "roofline",
]

# Peak dense bf16 FLOP/s and HBM bandwidth (bytes/s) per chip
# generation — public figures, the MFU/roofline denominators. Matched
# by substring against ``device.device_kind``; overridable via
# TAC_PEAK_FLOPS / TAC_PEAK_BW (the CPU-CI path pins these, since a
# host CPU has no meaningful entry here).
PEAK_FLOPS_BY_KIND: t.Tuple[t.Tuple[str, float], ...] = (
    ("v6", 918e12),
    ("trillium", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)
PEAK_HBM_BW_BY_KIND: t.Tuple[t.Tuple[str, float], ...] = (
    ("v6", 1640e9),
    ("trillium", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def peak_flops_for(device_kind: str | None) -> float | None:
    """Peak FLOP/s for a device kind (env ``TAC_PEAK_FLOPS`` wins)."""
    env = os.environ.get("TAC_PEAK_FLOPS")
    if env:
        return float(env)
    kind = (device_kind or "").lower()
    for tag, peak in PEAK_FLOPS_BY_KIND:
        if tag in kind:
            return peak
    return None


def peak_hbm_bw_for(device_kind: str | None) -> float | None:
    """Peak HBM bytes/s for a device kind (env ``TAC_PEAK_BW`` wins)."""
    env = os.environ.get("TAC_PEAK_BW")
    if env:
        return float(env)
    kind = (device_kind or "").lower()
    for tag, bw in PEAK_HBM_BW_BY_KIND:
        if tag in kind:
            return bw
    return None


class Peaks(t.NamedTuple):
    """The roofline denominators. ``flops`` in FLOP/s, ``hbm_bw`` in
    bytes/s; either may be None (the dependent metrics are omitted)."""

    flops: float | None
    hbm_bw: float | None
    device_kind: str | None = None

    @classmethod
    def detect(cls) -> "Peaks":
        """Peaks for the default backend's first device (env overrides
        honored). A host CPU has no entry and gets None peaks (its
        dependent metrics are omitted); an accelerator that is not in
        the tables is an error, not a default."""
        import jax

        dev = jax.devices()[0]
        kind = dev.device_kind
        peaks = cls(peak_flops_for(kind), peak_hbm_bw_for(kind), kind)
        if dev.platform != "cpu" and None in (peaks.flops, peaks.hbm_bw):
            raise ValueError(
                f"no peak FLOP/s / HBM bandwidth known for device kind "
                f"{kind!r}: add it to PEAK_FLOPS_BY_KIND / "
                "PEAK_HBM_BW_BY_KIND (telemetry/costmodel.py) with its "
                "source, or set TAC_PEAK_FLOPS and TAC_PEAK_BW"
            )
        return peaks


def _extract_costs(analysis: t.Any) -> dict | None:
    """Normalize ``cost_analysis()`` output (dict, or list of dicts —
    one per computation — depending on jax version/backend) into
    ``{flops, bytes_accessed, output_bytes, transcendentals}``."""
    if analysis is None:
        return None
    if isinstance(analysis, (list, tuple)):
        dicts = [a for a in analysis if isinstance(a, dict)]
        if not dicts:
            return None
        merged: t.Dict[str, float] = {}
        for d in dicts:
            for k, v in d.items():
                if isinstance(v, (int, float)):
                    merged[k] = merged.get(k, 0.0) + float(v)
        analysis = merged
    if not isinstance(analysis, dict):
        return None
    return {
        "flops": float(analysis.get("flops", 0.0)),
        "bytes_accessed": float(analysis.get("bytes accessed", 0.0)),
        "output_bytes": float(analysis.get("bytes accessedout{}", 0.0)),
        "transcendentals": float(analysis.get("transcendentals", 0.0)),
    }


def roofline(
    cost: t.Mapping[str, float],
    duration_s: float,
    calls: int = 1,
    peaks: Peaks | None = None,
    compute_dtype: str | None = None,
) -> dict:
    """One program's live roofline position.

    ``cost`` is a registry entry (static per-call FLOPs/bytes);
    ``duration_s`` is the measured wall time ``calls`` executions took
    (for the trainer: the burst+drain span sum of an epoch). Returns
    achieved FLOP/s, arithmetic intensity (FLOPs per HBM byte), and —
    when peaks are known — MFU, the ridge point, and the
    ``compute``/``memory`` bound classification: a program whose
    intensity sits left of ``peak_flops / peak_bw`` cannot reach peak
    FLOP/s no matter how well it schedules; its ceiling is bandwidth.

    ``compute_dtype`` stamps the program's matmul precision policy
    (``SACConfig.compute_dtype``) onto the record: an MFU read against
    the bf16 peak means something different for an f32 program (which
    cannot reach it on MXU hardware), so ``cost`` events carry the
    dtype explicitly rather than leaving readers to guess.
    """
    def sig(x, digits=4):
        # Significant-digit rounding: fixed-decimal rounding truncates
        # legitimately tiny ratios (a compile-heavy first epoch's MFU)
        # to an indistinguishable-from-missing 0.0.
        return float(f"{float(x):.{digits}g}")

    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes_accessed", 0.0))
    out = {
        "flops_per_call": flops,
        "bytes_per_call": bytes_,
        "calls": int(calls),
        "duration_s": round(float(duration_s), 6),
    }
    if compute_dtype is not None:
        out["compute_dtype"] = str(compute_dtype)
    if duration_s > 0 and calls > 0:
        out["achieved_flops_per_sec"] = flops * calls / duration_s
        out["achieved_bytes_per_sec"] = bytes_ * calls / duration_s
    ai = flops / bytes_ if bytes_ > 0 else None
    if ai is not None:
        out["arithmetic_intensity"] = sig(ai)
    if peaks is None:
        peaks = Peaks(None, None)
    if peaks.flops and "achieved_flops_per_sec" in out:
        out["mfu"] = sig(out["achieved_flops_per_sec"] / peaks.flops)
        out["peak_flops"] = peaks.flops
    if peaks.hbm_bw and "achieved_bytes_per_sec" in out:
        out["hbm_util"] = sig(
            out["achieved_bytes_per_sec"] / peaks.hbm_bw
        )
        out["peak_hbm_bw"] = peaks.hbm_bw
    if peaks.flops and peaks.hbm_bw and ai is not None:
        ridge = peaks.flops / peaks.hbm_bw
        out["ridge_flops_per_byte"] = sig(ridge)
        out["bound"] = "compute" if ai >= ridge else "memory"
        # The ceiling this program can actually reach at its intensity:
        # min(peak, ai * bw) — MFU should be read against this, not
        # against nominal peak, for memory-bound programs.
        attainable = min(peaks.flops, ai * peaks.hbm_bw)
        out["attainable_flops_per_sec"] = attainable
        if "achieved_flops_per_sec" in out and attainable > 0:
            out["roofline_frac"] = sig(
                out["achieved_flops_per_sec"] / attainable
            )
    if "achieved_flops_per_sec" in out:
        out["achieved_flops_per_sec"] = round(out["achieved_flops_per_sec"])
        out["achieved_bytes_per_sec"] = round(out["achieved_bytes_per_sec"])
    return out


class CostRegistry:
    """Process-wide registry of per-program XLA cost analyses.

    Keys are the watchdog source names; values are
    ``{flops, bytes_accessed, output_bytes, transcendentals}`` per
    call of the compiled program. Thread-safe (serving warmup and the
    trainer may register concurrently in one process)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._costs: t.Dict[str, dict] = {}  # guarded-by: _lock
        self._errors: t.Dict[str, str] = {}  # guarded-by: _lock

    def register(self, name: str, cost: t.Mapping[str, float]) -> None:
        with self._lock:
            self._costs[name] = dict(cost)

    def register_jit(
        self,
        name: str,
        jit_fn,
        *args,
        compiled: bool = True,
        devices: int = 1,
        **kwargs,
    ) -> dict | None:
        """Lower ``jit_fn`` at ``args`` (arrays or ShapeDtypeStructs)
        and register its cost analysis under ``name``.

        ``compiled=True`` (the default) analyzes the post-optimization
        executable — honest byte counts (fusion eliminates the
        intermediate reads a pre-optimization analysis double-counts)
        at the price of one extra backend compile, paid once per
        program and only when cost accounting is on; the compile is
        marked ``expected`` to the recompilation watchdog so it never
        reads as a steady-state anomaly. ``compiled=False`` falls back
        to the pre-optimization (lowered) analysis — FLOPs stay
        accurate, bytes are an overestimate.

        ``devices`` is the participating mesh size of a GSPMD-sharded
        program: the analysis covers the whole logical program, so its
        FLOPs/bytes are divided by ``devices`` to register PER-DEVICE
        cost — ``roofline``/MFU compare against a single chip's peak,
        and a dp=8 burst must not read as 8x one chip's work. Errors
        are swallowed and recorded (cost accounting must never take
        training or serving down); returns the registered cost dict or
        None."""
        try:
            from torch_actor_critic_tpu.diagnostics.watchdog import (
                get_watchdog,
            )

            lowered = jit_fn.lower(*args, **kwargs)
            analysis = None
            if compiled:
                try:
                    with get_watchdog().expected():
                        analysis = lowered.compile().cost_analysis()
                except Exception as e:  # noqa: BLE001 — fall through to
                    # the lowered analysis below
                    logger.debug(
                        "compiled cost analysis for %s failed (%r); "
                        "using lowered analysis", name, e,
                    )
            if analysis is None:
                analysis = lowered.cost_analysis()
            cost = _extract_costs(analysis)
            if cost is None:
                raise ValueError(f"no cost analysis available: {analysis!r}")
            if devices > 1:
                cost = {k: v / devices for k, v in cost.items()}
                cost["devices"] = devices
            self.register(name, cost)
            logger.info(
                "cost registry: %s = %.3g GFLOPs, %.3g MB accessed "
                "per call%s", name, cost["flops"] / 1e9,
                cost["bytes_accessed"] / 1e6,
                f" per device (mesh of {devices})" if devices > 1 else "",
            )
            return cost
        except Exception as e:  # noqa: BLE001 — observability must not
            # break the program it observes
            with self._lock:
                self._errors[name] = repr(e)[:200]
            logger.warning("cost registration for %s failed: %r", name, e)
            return None

    def get(self, name: str) -> dict | None:
        with self._lock:
            c = self._costs.get(name)
        return dict(c) if c is not None else None

    def costs(self) -> t.Dict[str, dict]:
        """Snapshot of every registered program's static costs (plus
        registration errors under ``_errors`` when any)."""
        with self._lock:
            out = {k: dict(v) for k, v in self._costs.items()}
            if self._errors:
                out["_errors"] = dict(self._errors)
        return out

    def reset(self) -> None:
        """Test isolation."""
        with self._lock:
            self._costs.clear()
            self._errors.clear()


_REGISTRY: CostRegistry | None = None
_SINGLETON_LOCK = threading.Lock()


def get_cost_registry() -> CostRegistry:
    """The process-wide cost registry (lazy, like the watchdog)."""
    global _REGISTRY
    with _SINGLETON_LOCK:
        if _REGISTRY is None:
            _REGISTRY = CostRegistry()
        return _REGISTRY


# ------------------------------------------------- host/device attribution

# Which side of the host/device boundary each Trainer phase's time
# belongs to. Dispatch is async, so queued device execution surfaces
# under `drain`; `burst_dispatch` itself is dispatch overhead but is
# charged to the device plane because it scales with device-work
# submission, not host computation.
PHASE_PLANES: t.Mapping[str, str] = {
    "act": "host",
    "env_step": "host",
    "stage": "input",
    "place_chunk": "input",
    "burst_dispatch": "device",
    "drain": "device",
    "sentinel": "host",
    "checkpoint": "host",
    # The host waits here while the device runs the burst whose
    # parameters the actor mirror needs.
    "param_sync": "device",
}


def classify_epoch(
    phases: t.Mapping[str, t.Mapping[str, float]], wall_s: float
) -> dict:
    """Host/device/input attribution of one epoch from its phase
    stats (``{name: {"total_s": ...}}``, the recorder's epoch event
    shape). The device-busy fraction is burst+drain span time over
    epoch wall time; the epoch is classified by its largest plane
    (``host-bound`` / ``device-bound`` / ``input-bound``)."""
    sums = {"host": 0.0, "device": 0.0, "input": 0.0}
    for name, stats in phases.items():
        plane = PHASE_PLANES.get(name)
        if plane is not None:
            sums[plane] += float(stats.get("total_s", 0.0))
    wall = max(float(wall_s), 1e-12)
    fracs = {k: round(v / wall, 4) for k, v in sums.items()}
    bound = max(sums, key=sums.get)
    return {
        "class": f"{bound}-bound",
        "device_busy_frac": fracs["device"],
        "host_frac": fracs["host"],
        "input_frac": fracs["input"],
    }
