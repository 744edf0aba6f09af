"""Host spans on the host clock, mirrored into the profiler's trace.

The harness's own spans, from outside the program: set-up's phases, each
measured window (``bench/window``: the traced window's edges) and the calls a
driver makes in it, which ``main.window_account`` and ``trace.breakdown``
read.  No per-layer metric reads them: the host loop is read by the program's
own ``tac/host/`` spans (``window_spans.py``).  ``Spans`` keeps
``(name, start, duration)`` in memory on ``time.perf_counter`` and, while a
trace is being taken, opens a ``jax.profiler.TraceAnnotation`` of the same
name, so that the reduction finds the span on the device trace's clock.
"""

from __future__ import annotations

import contextlib
import time
import typing as t


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: t.List[t.Tuple[str, float, float]] = []
        self._mark = time.perf_counter()

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench/" + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.records.append((name, t0, time.perf_counter() - t0))
            if ann is not None:
                ann.__exit__(None, None, None)

    def lap(self, name: str) -> None:
        """Charge the time since the last lap (or since this object was made)
        to ``name``: for the phases of set-up, which follow one another."""
        now = time.perf_counter()
        self.records.append((name, self._mark, now - self._mark))
        self._mark = now

    def clear(self) -> None:
        self.records.clear()

    def totals(self) -> t.Dict[str, float]:
        out: t.Dict[str, float] = {}
        for name, _, dur in self.records:
            out[name] = out.get(name, 0.0) + dur
        return out

