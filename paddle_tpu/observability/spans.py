"""Spans and counts inside the serving step, on the profiler's clock.

One :class:`SpanTotals` belongs to each engine instance (an in-process
cluster holds several) and comes out in ``engine.stats()`` /
``ServingScheduler.stats()``. A span does two things:

- it enters ``jax.profiler.TraceAnnotation("paddle_tpu.<name>",
  **fields)``, so inside a ``jax.profiler`` session the span sits on the
  Python thread's line of ``/host:CPU`` on the same time base as the
  device's ``XLA Ops`` line. The profiler's session is the only switch
  there is: outside one the annotation is a flag test;
- it adds to two monotonic totals for its name, a count and nanoseconds
  by ``time.perf_counter_ns``. Spans of different names nest, so a
  parent's self time is its total less its children's.

There is no sink, no exporter and no fence, and a span is never opened
per row or per request: the spans of a step follow the programs it
launches. Fields are those known on entry.
"""
from __future__ import annotations

import time
from typing import Dict, List

from jax.profiler import TraceAnnotation

PREFIX = "paddle_tpu."


class _Span:
    __slots__ = ("_cell", "_ann", "_t0")

    def __init__(self, cell: List[int], ann: TraceAnnotation):
        self._cell, self._ann = cell, ann

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        cell = self._cell
        cell[0] += 1
        cell[1] += time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        return False


class SpanTotals:
    """Per-name span totals (count, nanoseconds) and plain counters."""

    def __init__(self):
        self._spans: Dict[str, List[int]] = {}
        self._counters: Dict[str, int] = {}

    def span(self, name: str, **fields) -> _Span:
        """Context manager: ``paddle_tpu.<name>`` in the profiler's
        trace, and one more count and its nanoseconds under ``name``."""
        cell = self._spans.get(name)
        if cell is None:
            cell = self._spans[name] = [0, 0]
        return _Span(cell, TraceAnnotation(PREFIX + name, **fields))

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name``."""
        self._counters[name] = self._counters.get(name, 0) + int(n)

    def ns(self, name: str) -> int:
        """Nanoseconds spent so far in the spans called ``name``."""
        cell = self._spans.get(name)
        return cell[1] if cell is not None else 0

    def snapshot(self) -> Dict:
        """A copy for ``stats()``: the counters by their own names and
        ``spans`` as ``{name: {"count": n, "ns": t}}``."""
        out: Dict = dict(self._counters)
        out["spans"] = {name: {"count": c, "ns": t}
                        for name, (c, t) in self._spans.items()}
        return out
