"""Peak rates of the chips the benchmark may run on, keyed by
``device_kind`` as jax reports it.  A device that is not here is an error,
never a default.

Copied from ``torch_actor_critic_tpu/telemetry/costmodel.py`` (the v5e row),
which stays the program's own; the yardstick keeps its own copy so that no
program PR can move it.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at
# 819 GB/s per chip, 1,600 Gbit/s inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
    "TPU v5e": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' system architecture",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's table of "
            f"peaks ({sorted(PEAKS)}); add it with its source, do not guess"
        )
    return PEAKS[device_kind]
