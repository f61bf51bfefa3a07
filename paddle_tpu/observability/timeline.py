"""Step-timeline aggregation: profiler spans -> per-phase summary dict.

Merges the host spans collected by the profiler's ``_Collector`` (the
HostTracer analog) with the metrics registry snapshot into ONE
structured dict, so a single ``Profiler`` run yields a chrome trace AND
a machine-readable per-phase breakdown. Host clock, fenced: the
benchmark reads ``observability/spans.py`` on the profiler's clock
instead (PERF.md section 3).

Phase mapping: the reference Model-Summary event types (Forward /
Backward / Optimization / DataLoader) plus the serving phases carried in
span names (``Generate.prefill`` / ``Generate.decode`` /
``Predictor.run``), the pipeline engine's spans (``PP.*``) and watchdog
firings. (Collectives contribute counters/bytes to the ``metrics``
snapshot, not spans — they execute inside compiled programs.)
"""
from __future__ import annotations

from typing import Dict, List, Optional

# event_type -> phase bucket (the reference Model Summary split)
_TYPE_PHASE = {
    "Forward": "forward",
    "Backward": "backward",
    "Optimization": "optimizer",
    "DataLoader": "dataloader",
    "Watchdog": "watchdog",
}

# name prefix -> phase bucket; FIRST match wins, checked before the
# event-type mapping so serving/pipeline spans land in their own buckets
_NAME_PHASE = (
    ("Generate.prefill", "prefill"),
    ("Generate.decode", "decode"),
    ("Predictor.run", "inference"),
    ("PP.forward", "forward"),
    ("PP.backward", "backward"),
    ("PP.spmd", "pp_spmd"),
    ("PP.", "pipeline"),
    ("Optimizer.step", "optimizer"),
    ("DataLoader.", "dataloader"),
    ("Train.step", "train_step"),
    ("Watchdog.", "watchdog"),
)


def phase_of(name: str, event_type: str) -> str:
    for prefix, phase in _NAME_PHASE:
        if name.startswith(prefix):
            return phase
    return _TYPE_PHASE.get(event_type, "other")


def phase_summary(events, step_times: Optional[List[float]] = None,
                  include_metrics: bool = True) -> dict:
    """Aggregate spans into ``{"phases": {...}, "window_ms": ...}``.

    Each phase bucket: calls, total_ms, avg_ms, max_ms and share (of the
    step window when step times exist, else of the summed span time).
    ``metrics`` carries the registry JSON snapshot so counters (tokens,
    collective bytes, watchdog firings) ride along with the timings.
    """
    phases: Dict[str, dict] = {}
    total_span_ns = 0.0
    for e in events:
        ph = phase_of(e.name, e.event_type)
        d = phases.setdefault(ph, {"calls": 0, "total_ms": 0.0,
                                   "max_ms": 0.0})
        dur = e.end - e.start
        d["calls"] += 1
        d["total_ms"] += dur / 1e6
        d["max_ms"] = max(d["max_ms"], dur / 1e6)
        total_span_ns += dur
    window_ms = (sum(step_times) * 1e3 if step_times
                 else total_span_ns / 1e6)
    for d in phases.values():
        d["avg_ms"] = round(d["total_ms"] / d["calls"], 6)
        d["share"] = round(d["total_ms"] / window_ms, 6) if window_ms \
            else 0.0
        d["total_ms"] = round(d["total_ms"], 6)
        d["max_ms"] = round(d["max_ms"], 6)
    out = {
        "phases": phases,
        "window_ms": round(window_ms, 6),
        "steps": len(step_times or ()),
    }
    if include_metrics:
        from . import metrics as _m
        snap = _m.REGISTRY.to_json()
        if snap:
            out["metrics"] = snap
    return out


def chrome_trace(rows, pid_names: Optional[Dict[int, str]] = None,
                 tid_names: Optional[Dict[int, str]] = None) -> dict:
    """Rows -> a Chrome trace-event dict (``chrome://tracing`` /
    Perfetto's legacy JSON format). Each row: {name, cat, start_ns,
    dur_ns, pid, tid, args?}.

    Two properties every exporter in the tree routes through here for
    (ISSUE 16 bugfix — the old ``Profiler._export_chrome`` emitted one
    ``os.getpid()`` row, so cluster traces interleaved into a single
    unreadable lane):

    - DISTINCT pid/tid rows: callers map replica -> pid and slot ->
      tid (``pid_names``/``tid_names`` become process_name /
      thread_name metadata events), so a 2-replica handoff renders as
      two labeled process groups instead of one shredded row.
    - SORT-STABLE output: events are ordered by (pid, tid, ts, dur,
      name) and metadata precedes them, so two exports of the same
      spans serialize byte-identically — golden tests diff the bytes.
    """
    meta = []
    for pid, label in sorted((pid_names or {}).items()):
        meta.append({"ph": "M", "name": "process_name", "pid": pid,
                     "tid": 0, "args": {"name": label}})
    for tid, label in sorted((tid_names or {}).items()):
        meta.append({"ph": "M", "name": "thread_name", "pid": 0,
                     "tid": tid, "args": {"name": label}})
    events = []
    for r in rows:
        ev = {"ph": "X", "name": r["name"], "cat": r.get("cat", ""),
              "pid": int(r.get("pid", 0)), "tid": int(r.get("tid", 0)),
              "ts": r["start_ns"] / 1e3,        # chrome wants microsecs
              "dur": r.get("dur_ns", 0) / 1e3}
        if r.get("args"):
            ev["args"] = r["args"]
        events.append(ev)
    events.sort(key=lambda e: (e["pid"], e["tid"], e["ts"], e["dur"],
                               e["name"]))
    return {"traceEvents": meta + events}


class StepTimeline:
    """Incremental aggregator over a live profiler run.

    ``merge(profiler)`` folds the profiler's collected spans (draining
    the native ring through ``_Collector.drain``) and its step times
    into this timeline; ``summary()`` emits the combined per-phase
    dict. Lets a long job merge several RECORD windows into one
    breakdown."""

    def __init__(self):
        self._events = []
        self._step_times: List[float] = []

    def merge(self, prof) -> "StepTimeline":
        self._events.extend(prof.events())
        self._step_times.extend(getattr(prof, "_step_times", ()))
        return self

    def add_events(self, events) -> "StepTimeline":
        self._events.extend(events)
        return self

    def summary(self, include_metrics: bool = True) -> dict:
        return phase_summary(self._events, self._step_times,
                             include_metrics=include_metrics)
