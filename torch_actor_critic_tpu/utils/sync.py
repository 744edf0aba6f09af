"""Device-queue drain: the timing barrier.

JAX dispatches asynchronously, so a wall-clock taken without waiting
for the device measures the enqueue. :func:`drain` waits by a host
*fetch* of one scalar that data-depends on the work — the bytes cannot
arrive before the producer ran — and hands the value back, so the same
call doubles as a checksum of what was timed.

Every timing site in the framework (the trainer's per-epoch steps/sec
metrics, the fused on-device loop's, ``benchmark/``'s windows) drains
through :func:`drain`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["drain"]


def drain(x) -> float:
    """Force execution of everything ``x`` depends on; return a float.

    ``x`` may be any array (it is reduced to one scalar on device, so
    only a few bytes cross the wire) or an already-scalar value. The
    returned float is the reduced value — usable as a checksum, but the
    point is the side effect: when this returns, the producer chain has
    executed.
    """
    if isinstance(x, jax.Array):
        if not x.is_fully_addressable:
            # Multi-host sharded array: a global reduce would need a
            # collective outside jit. Fetching this process's first
            # local shard drains the local device queue, which is all a
            # local wall-clock needs.
            shard = x.addressable_shards[0].data
            return float(jax.device_get(jnp.sum(shard, dtype=jnp.float32)))
        # Reduce in f32: summing in x's own dtype would overflow bf16
        # (max ~3.4e38 but 8-bit mantissa loses integer exactness past
        # 256) or wrap small ints, making the checksum claim false.
        # The fetch is an EXPLICIT jax.device_get: the drain is the
        # hot path's one intentional device->host transfer, so it must
        # stay legal under the --sanitize transfer guard
        # (docs/ANALYSIS.md "Runtime sanitizers").
        return float(jax.device_get(jnp.sum(x, dtype=jnp.float32)))
    return float(x)
