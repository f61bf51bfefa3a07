"""Spans and counts inside the serving step, on the profiler's clock.

One :class:`SpanTotals` belongs to each engine instance (an in-process
cluster holds several) and comes out in ``engine.stats()`` /
``ServingScheduler.stats()``. A span does two things:

- it enters ``jax.profiler.TraceAnnotation("paddle_tpu.<name>",
  **fields)``, so inside a ``jax.profiler`` session the span sits on the
  Python thread's line of ``/host:CPU`` on the same time base as the
  device's ``XLA Ops`` line. The profiler's session is the only switch
  there is: outside one the annotation is a flag test;
- it adds to three monotonic totals for its name: a count, nanoseconds by
  ``time.perf_counter_ns`` and the longest instance (``max_ns``). A span
  entered with ``kind=`` adds the same to ``"<name>/<kind>"``, so a name's
  kinds sum to the name exactly. Spans of different names nest, so a
  parent's self time is its total less its children's.

A span inside a scheduler's step whose own time passes :data:`STALL_NS` is
a **stall**: one record in ``stats()["stalls"]`` (the newest
:data:`STALLS_KEPT`), ``stalls_total`` / ``stall_ns_total``, and one
warning on the ``paddle_tpu.serving`` logger. Own time is the span's less
what stalls and ``engine.build_program`` spans inside it already account
for, so a pause is recorded once, by the innermost span that holds it, and
a compile is never one. Outside a step nothing is judged: the engine's own
synchronous calls queue a prompt's chunks and read the last, and a wait
that long is the device's queue, not a pause. A record carries
the thread's CPU time and context switches since the last sample of them
(a step's entry at most :data:`SAMPLE_NS` and a step before the stall, or
an earlier stall's exit; ``sampled_ns`` is the wall time they cover: an
upper bound some milliseconds loose), which tells three causes apart: CPU
about equal to wall, the host computed; CPU far under wall outside
``engine.wait``, the thread was off its core; CPU far under wall in
``engine.wait``, the read was blocked (runtime, transfer or device).

There is no sink, no exporter and no fence, and a span is never opened
per row or per request: the spans of a step follow the programs it
launches. Fields are those known on entry. One thread drives an engine.
"""
from __future__ import annotations

import collections
import logging
import resource
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

PREFIX = "paddle_tpu."
#: a span's own time above this is a stall (the longest ordinary step of
#: any benchmark cell is a 41 ms chunk step)
STALL_NS = 100_000_000
STALLS_KEPT = 32
#: the thread's clock and switches are read at a step's entry where the
#: last reading is older than this: two system calls, 0.7 us on a plain
#: Linux host and 12 us on the benchmark's sealed one, so not every step
SAMPLE_NS = 50_000_000
#: marks a compile or a cache load already: never a stall, and taken out
#: of the spans around it
BUILD = "engine.build_program"

log = logging.getLogger("paddle_tpu.serving")


def _thread_sample() -> Tuple[int, int, int]:
    """The calling thread's CPU nanoseconds and its voluntary and
    involuntary context switches so far."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return time.thread_time_ns(), ru.ru_nvcsw, ru.ru_nivcsw


class _Span:
    __slots__ = ("_totals", "_cell", "_kind_cell", "_ann", "_t0",
                 "_seen0")

    def __init__(self, totals: "SpanTotals", cell: List,
                 kind_cell: Optional[List], ann: TraceAnnotation):
        self._totals, self._cell, self._kind_cell = totals, cell, kind_cell
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        self._seen0 = self._totals._seen
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        totals = self._totals
        totals._last_exit = now = time.perf_counter_ns()
        dt = now - self._t0
        leaf = cell = self._cell
        cell[0] += 1
        cell[1] += dt
        if dt > cell[2]:
            cell[2] = dt
        cell = self._kind_cell
        if cell is not None:
            leaf = cell
            cell[0] += 1
            cell[1] += dt
            if dt > cell[2]:
                cell[2] = dt
        if dt > STALL_NS or leaf[5]:
            totals._long(leaf, self._t0, dt, self._seen0)
        self._ann.__exit__(*exc)
        return False


class SpanTotals:
    """Per-name span totals (count, nanoseconds, longest), plain counters
    and the stall records."""

    def __init__(self):
        # a cell: [count, ns, max_ns, its key, {kind: that kind's cell},
        # whether it is a build's]
        self._spans: Dict[str, List] = {}
        self._counters: Dict[str, int] = {"stalls_total": 0,
                                          "stall_ns_total": 0}
        self._stalls = collections.deque(maxlen=STALLS_KEPT)
        self._seen = 0          # ns that stalls and builds account for
        self._step = -1         # the scheduler's step, for the records
        self._last_exit = 0     # when the newest span closed
        self._more = False      # the last step returned with work left
        self._in_step = False   # between step_begins and step_ends
        self._mark, self._mark_at = _thread_sample(), time.perf_counter_ns()

    def _new_cell(self, key: str) -> List:
        cell = self._spans[key] = [0, 0, 0, key, {}, key.startswith(BUILD)]
        return cell

    def span(self, name: str, **fields) -> _Span:
        """Context manager: ``paddle_tpu.<name>`` in the profiler's
        trace, and one more count and its nanoseconds under ``name`` and,
        with ``kind=``, under ``<name>/<kind>``."""
        cell = self._spans.get(name)
        if cell is None:
            cell = self._new_cell(name)
        kind = fields.get("kind")
        kind_cell = None
        if kind is not None:
            kind_cell = cell[4].get(kind)
            if kind_cell is None:
                kind_cell = cell[4][kind] = self._new_cell(f"{name}/{kind}")
        return _Span(self, cell, kind_cell,
                     TraceAnnotation(PREFIX + name, **fields))

    def count(self, name: str, n: int) -> None:
        """Add ``n`` to the counter ``name``."""
        self._counters[name] = self._counters.get(name, 0) + int(n)

    def ns(self, name: str) -> int:
        """Nanoseconds spent so far in the spans called ``name``."""
        cell = self._spans.get(name)
        return cell[1] if cell is not None else 0

    def calls(self, name: str) -> int:
        """How many spans called ``name`` have closed so far."""
        cell = self._spans.get(name)
        return cell[0] if cell is not None else 0

    def step_begins(self, step: int) -> None:
        """The scheduler's step number for the records that follow, and
        a sample of the thread's clock and switches where the last is
        older than :data:`SAMPLE_NS`. Where :meth:`step_ends` said that
        work remained, the time since the newest span closed is judged
        like a span's, as ``between_steps``: a pause can fall in the
        caller's loop."""
        now = time.perf_counter_ns()
        if self._more and 0 < self._last_exit < now - STALL_NS:
            self._stall("between_steps", self._last_exit,
                        now - self._last_exit, now)
        elif now - self._mark_at > SAMPLE_NS:
            self._mark, self._mark_at = _thread_sample(), now
        self._more, self._in_step = False, True
        self._step = step

    def step_ends(self, more: bool) -> None:
        """``more``: the step returned with work left, so its caller is
        expected back at once."""
        self._more, self._in_step = more, False

    def _long(self, cell: List, t0: int, dt: int, seen0: int) -> None:
        """A span over the threshold, or a build: a stall if its own time
        is over it too; either way its whole time is accounted for."""
        own = dt - (self._seen - seen0)
        if cell[5]:     # a compile's CPU is not the next record's
            self._mark, self._mark_at = _thread_sample(), t0 + dt
        elif own > STALL_NS and self._in_step:
            self._stall(cell[3], t0, own, t0 + dt)
        self._seen = seen0 + dt

    def _stall(self, key: str, t0: int, wall: int, t1: int) -> None:
        """One record, total and warning: ``wall`` of its own time in the
        span ``key`` that ran from ``t0`` to ``t1``."""
        name, _, kind = key.partition("/")
        sample = _thread_sample()
        cpu, vol, invol = (b - a for a, b in zip(self._mark, sample))
        sampled = t1 - self._mark_at
        self._mark, self._mark_at = sample, t1
        self._counters["stalls_total"] += 1
        self._counters["stall_ns_total"] += wall
        self._stalls.append({
            "span": name, "kind": kind or None, "step": self._step,
            "start_ns": t0, "wall_ns": wall, "cpu_ns": cpu,
            "sampled_ns": sampled, "voluntary_switches": vol,
            "involuntary_switches": invol})
        log.warning(
            "stall in %s%s at step %d: wall %.1f ms, thread CPU %.1f ms "
            "(over %.1f ms), context switches %d voluntary %d involuntary",
            name, f" ({kind})" if kind else "", self._step, wall / 1e6,
            cpu / 1e6, sampled / 1e6, vol, invol)

    def snapshot(self) -> Dict:
        """A copy for ``stats()``: the counters by their own names,
        ``spans`` as ``{name: {"count": n, "ns": t, "max_ns": m}}`` and
        ``stalls``, the newest records."""
        out: Dict = dict(self._counters)
        out["spans"] = {key: {"count": c[0], "ns": c[1], "max_ns": c[2]}
                        for key, c in self._spans.items()}
        out["stalls"] = list(self._stalls)
        return out
