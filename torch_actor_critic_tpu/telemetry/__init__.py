"""Zero-overhead-when-off observability for training and serving.

The run-time monitoring layer TorchBeast treats as core platform
infrastructure (arXiv:1910.03552) and Podracer uses to justify its
actor/learner timing splits (arXiv:2104.06272), built for the
host<->TPU boundary:

- :mod:`recorder` — monotonic-clock phase timers over a preallocated
  span ring, aggregated per epoch. No host<->device syncs and no
  per-step allocation when enabled; when disabled the Trainer holds
  ``telemetry=None`` and the hot path degenerates to one predicted
  pointer comparison per phase mark (docs/OBSERVABILITY.md). The
  device window's spans (stage, place_chunk, burst_dispatch, drain) are
  opened by the functions that do the work (``recorder.span``) and
  charged to the recorder installed as the process's current one.
- :mod:`histogram` — fixed-bucket latency histogram (bounded memory),
  shared with :mod:`~torch_actor_critic_tpu.serve.metrics` so training
  and serving percentiles come from one estimator.
- :mod:`memory` — per-epoch device HBM watermarks via
  ``device.memory_stats()`` (None-safe on CPU).
- :mod:`profiler` — ``jax.profiler`` integration: named trace
  annotations and the ``--profile-epochs A:B`` capture window.
- :mod:`sinks` — JSONL event stream under the Tracker run dir, a human
  ``summary()`` table, and the ``/metrics``-style snapshot schema.
- :mod:`costmodel` — per-program XLA cost registry (FLOPs/bytes keyed
  by the watchdog's source names), live roofline/MFU accounting, and
  host/device/input epoch attribution.
- :mod:`traceview` — cross-plane Perfetto (``chrome://tracing``)
  export merging training phase spans, serving per-request spans and
  XLA compile events onto one timeline (``--trace-export``).
"""

from torch_actor_critic_tpu.telemetry.costmodel import (
    CostRegistry,
    Peaks,
    classify_epoch,
    get_cost_registry,
    roofline,
)
from torch_actor_critic_tpu.telemetry.histogram import FixedBucketHistogram
from torch_actor_critic_tpu.telemetry.memory import device_memory_watermarks
from torch_actor_critic_tpu.telemetry.profiler import (
    ProfilerWindow,
    parse_profile_epochs,
)
from torch_actor_critic_tpu.telemetry.recorder import (
    PHASES,
    PhaseTimer,
    SpanRing,
    TelemetryRecorder,
)
from torch_actor_critic_tpu.telemetry.sinks import (
    JsonlSink,
    format_summary,
    json_sanitize,
)
from torch_actor_critic_tpu.telemetry.traceview import (
    RequestSpanLog,
    export_trace,
)

__all__ = [
    "PHASES",
    "CostRegistry",
    "FixedBucketHistogram",
    "JsonlSink",
    "Peaks",
    "PhaseTimer",
    "ProfilerWindow",
    "RequestSpanLog",
    "SpanRing",
    "TelemetryRecorder",
    "classify_epoch",
    "device_memory_watermarks",
    "export_trace",
    "format_summary",
    "get_cost_registry",
    "json_sanitize",
    "parse_profile_epochs",
    "roofline",
]
