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

from torch_actor_critic_tpu.telemetry import recorder as spans

__all__ = ["drain"]


def drain(x) -> float:
    """Force execution of everything ``x`` depends on; return a float.

    ``x`` may be any array (it is reduced to one scalar on device, so
    only a few bytes cross the wire) or an already-scalar value. The
    returned float is the reduced value — usable as a checksum, but the
    point is the side effect: when this returns, the producer chain has
    executed. The window's ``drain`` span, with the dispatch of the
    reduction (``drain/reduce``) and the fetch (``drain/fetch``) as its
    parts.
    """
    with spans.span(spans.DRAIN):
        if not isinstance(x, jax.Array):
            return float(x)
        if not x.is_fully_addressable:
            # Multi-host sharded array: a global reduce would need a
            # collective outside jit. Fetching this process's first
            # local shard drains the local device queue, which is all a
            # local wall-clock needs.
            x = x.addressable_shards[0].data
        # Reduce in f32: summing in x's own dtype would overflow bf16
        # (max ~3.4e38 but 8-bit mantissa loses integer exactness past
        # 256) or wrap small ints, making the checksum claim false.
        with spans.span(spans.DRAIN_REDUCE):
            total = jnp.sum(x, dtype=jnp.float32)
        # The fetch is an EXPLICIT jax.device_get: the drain is the
        # hot path's one intentional device->host transfer, so it must
        # stay legal under the --sanitize transfer guard
        # (docs/ANALYSIS.md "Runtime sanitizers"). It is the wait.
        with spans.span(spans.DRAIN_FETCH, os_wait=True):
            return float(jax.device_get(total))
