"""Async double-buffered host→HBM refill for the tiered store.

The refill half of the waterfall (docs/REPLAY.md): a background thread
samples the host tier into ready-to-push ``(n_envs, refill_window)``
numpy chunks and parks them in a depth-2 queue, so when the train loop
reaches a window boundary the host→device copy is already staged and
rides the same async dispatch stream as the update burst — the copy
hides behind the burst instead of serializing after it (the
``ops/pixels.py`` scalar-prefetch gather is the in-kernel analogue of
the same idea, one level down).

The device push is its OWN jitted program (``replay/prefetch_push`` in
the checked ENTRY_POINTS table): ``jax.vmap`` of the single-ring
``push`` over the device axis, exactly like
:meth:`~torch_actor_critic_tpu.parallel.dp.DataParallelSAC.push_chunk`
but compiled for the refill chunk's shapes — reusing the warmup push's
cache entry would interleave two chunk geometries through one
dispatch site and re-trace on every boundary. Dispatch runs under the
recompilation watchdog's source scope and the program registers its
XLA cost analysis like every other entry point
(analysis/contracts.py).

With ``replay_prefetch=False`` the sampler runs synchronously at the
boundary (the stall the async path exists to hide; not measured on
the chip). Either way the TRAIN loop
performs the actual device push; the thread only ever touches host
memory.
"""

from __future__ import annotations

import queue
import threading
import time
import typing as t

import numpy as np

from torch_actor_critic_tpu.core.types import Batch, BufferState

if t.TYPE_CHECKING:
    from torch_actor_critic_tpu.replay.tiers import TieredReplay

__all__ = ["RefillPrefetcher"]


class RefillPrefetcher:
    """Samples the host tier into refill chunks ahead of the loop.

    ``refill_rows`` is rows per env per window (config
    ``replay_refill``); a refill chunk therefore has leading axes
    ``(n_envs, refill_rows)`` — same layout contract as the trainer's
    env chunk, so :func:`~torch_actor_critic_tpu.parallel.dp.
    shard_chunk_from_local` places it identically.
    """

    # The cost-registry/watchdog source name of the refill push program
    # (checked ENTRY_POINTS + contract tables, analysis/).
    push_cost_name = "replay/prefetch_push"

    def __init__(
        self,
        tiered: "TieredReplay",
        n_envs: int,
        refill_rows: int,
        async_prefetch: bool = True,
        depth: int = 2,
        idle_sleep_s: float = 0.005,
    ):
        if refill_rows < 1:
            raise ValueError(
                f"refill_rows must be >= 1, got {refill_rows}"
            )
        self.tiered = tiered
        self.n_envs = int(n_envs)
        self.refill_rows = int(refill_rows)
        self.async_prefetch = bool(async_prefetch)
        self._idle_sleep_s = float(idle_sleep_s)
        self._q: "queue.Queue[Batch]" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._push = None
        self._cost_registered = False
        self.refills_served = 0
        self.stalls_total = 0
        self.requests_total = 0
        if self.async_prefetch:
            self._thread = threading.Thread(
                target=self._run, name="replay-prefetch", daemon=True
            )
            self._thread.start()

    # ------------------------------------------------------------ sampling

    def _sample_local_chunk(self) -> Batch | None:
        """One ``(n_envs, refill_rows)`` numpy chunk off the host tier,
        or ``None`` while it is still empty."""
        import jax

        from torch_actor_critic_tpu.replay.diskstore import rows_to_batch

        rows = self.tiered.sample_refill(self.n_envs * self.refill_rows)
        if rows is None:
            return None
        flat = rows_to_batch(rows)
        lead = (self.n_envs, self.refill_rows)
        return jax.tree_util.tree_map(
            lambda x: np.asarray(x).reshape(lead + x.shape[1:]), flat
        )

    def _run(self) -> None:
        while not self._stop.is_set():
            if self._q.full():
                time.sleep(self._idle_sleep_s)
                continue
            chunk = self._sample_local_chunk()
            if chunk is None:
                time.sleep(self._idle_sleep_s)
                continue
            try:
                self._q.put(chunk, timeout=0.1)
            except queue.Full:
                pass

    def poll_local_chunk(self) -> Batch | None:
        """The train loop's boundary call: the staged chunk if one is
        ready. Synchronous mode samples on demand (the measured stall);
        async mode never blocks — an empty queue after the host tier
        warmed up counts a prefetch stall and skips this boundary."""
        self.requests_total += 1
        if not self.async_prefetch:
            return self._sample_local_chunk()
        try:
            chunk = self._q.get_nowait()
        except queue.Empty:
            if self.tiered.host.size > 0:
                self.stalls_total += 1
            return None
        return chunk

    # -------------------------------------------------------- device push

    def _build_push(self, buf_shardings=None, chunk_shardings=None):
        """The ``replay/prefetch_push`` jit program: vmapped single-ring
        push over the device axis, donating the ring (in-place update,
        exactly the warmup-push donation contract)."""
        import jax

        from torch_actor_critic_tpu.buffer.replay import push

        def _vpush(buffer: BufferState, chunk: Batch) -> BufferState:
            return jax.vmap(push)(buffer, chunk)

        if buf_shardings is not None:
            return jax.jit(
                _vpush,
                donate_argnums=(0,),
                in_shardings=(buf_shardings, chunk_shardings),
                out_shardings=buf_shardings,
            )
        return jax.jit(_vpush, donate_argnums=(0,))

    def push_into(
        self,
        buffer: BufferState,
        chunk: Batch,
        buf_shardings=None,
        chunk_shardings=None,
    ) -> BufferState:
        """Push a placed refill chunk into the sharded ring under the
        watchdog's source scope (compiles here are attributed to
        ``replay/prefetch_push``; post-steady ones are anomalies)."""
        from torch_actor_critic_tpu.diagnostics.watchdog import get_watchdog

        if self._push is None:
            self._push = self._build_push(buf_shardings, chunk_shardings)
        with get_watchdog().source(self.push_cost_name):
            out = self._push(buffer, chunk)
        self.refills_served += 1
        return out

    def maybe_register_cost(self, buffer_abstract, chunk_abstract,
                            devices: int = 1) -> None:
        """Register the push program's XLA cost analysis once (contract
        table: ``replay/prefetch_push`` cost registration). Abstract
        args only — the real buffers were donated."""
        if self._cost_registered or self._push is None:
            return
        self._cost_registered = True
        from torch_actor_critic_tpu.telemetry.costmodel import (
            get_cost_registry,
        )

        get_cost_registry().register_jit(
            self.push_cost_name, self._push, buffer_abstract,
            chunk_abstract, devices=devices,
        )

    # ------------------------------------------------------- observability

    def metrics(self) -> dict:
        served = max(self.requests_total, 1)
        return {
            "replay/refills_served": float(self.refills_served),
            "replay/prefetch_stalls_total": float(self.stalls_total),
            "replay/prefetch_hit_rate": float(
                1.0 - self.stalls_total / served
            ),
        }

    def close(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None
