"""Summary statistics tables (reference: python/paddle/profiler/
profiler_statistic.py — ~1.5k LoC of per-event aggregation + formatted
report: Overview / Model / Operator / Kernel / UserDefined summaries).

TPU-native split: the HOST tier aggregates RecordEvent spans (with
exclusive "self" time computed from span nesting per thread, like the
reference's HostStatisticNode tree); the DEVICE tier parses the XLA
trace (``jax.profiler`` xplane via ``jax.profiler.ProfileData``) into a
ranked per-op table plus op-category shares — the reference's Kernel
Summary, with categories chosen for the TPU roofline (MXU matmuls vs
vector/elementwise vs collectives vs copies) so the table shows where
the time that is not matmul goes.
"""
from __future__ import annotations

import collections
import enum
import glob
import os
import re
from typing import Dict, List, Optional


class SortedKeys(enum.Enum):
    """reference: profiler_statistic.py SortedKeys."""
    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


_UNITS = {"s": 1e-9, "ms": 1e-6, "us": 1e-3, "ns": 1.0}

_SORT_FIELD = {
    SortedKeys.CPUTotal: lambda d: -d["total"],
    SortedKeys.CPUAvg: lambda d: -(d["total"] / max(d["calls"], 1)),
    SortedKeys.CPUMax: lambda d: -d["max"],
    SortedKeys.CPUMin: lambda d: d["min"],
    SortedKeys.GPUTotal: lambda d: -d["total"],
    SortedKeys.GPUAvg: lambda d: -(d["total"] / max(d["calls"], 1)),
    SortedKeys.GPUMax: lambda d: -d["max"],
    SortedKeys.GPUMin: lambda d: d["min"],
}


def _agg(items):
    """items: iterable of (name, duration[, self_duration]) -> stats."""
    agg = collections.OrderedDict()
    for it in items:
        name, dur = it[0], it[1]
        self_dur = it[2] if len(it) > 2 else dur
        d = agg.setdefault(name, {"calls": 0, "total": 0.0, "self": 0.0,
                                  "max": 0.0, "min": float("inf")})
        d["calls"] += 1
        d["total"] += dur
        d["self"] += self_dur
        d["max"] = max(d["max"], dur)
        d["min"] = min(d["min"], dur)
    return agg


def _self_times(events) -> List[float]:
    """Exclusive time per event (total minus DIRECT same-thread nested
    children) — the reference's HostStatisticNode tree, computed with a
    sort + stack sweep instead of building the tree."""
    out = [e.end - e.start for e in events]
    by_tid = collections.defaultdict(list)
    for i, e in enumerate(events):
        by_tid[e.tid].append(i)
    for idxs in by_tid.values():
        idxs.sort(key=lambda i: (events[i].start,
                                 -(events[i].end - events[i].start)))
        stack: List[int] = []          # open spans, innermost on top
        for i in idxs:
            e = events[i]
            while stack and events[stack[-1]].end <= e.start:
                stack.pop()
            if stack and e.end <= events[stack[-1]].end:
                # nested: charge this span to its DIRECT parent only
                out[stack[-1]] -= (e.end - e.start)
            stack.append(i)
    return [max(s, 0.0) for s in out]


def _table(title, header_cols, rows) -> str:
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              if rows else len(str(h))
              for i, h in enumerate(header_cols)]
    sep = "-" * (sum(widths) + 2 * len(widths))
    lines = [sep, title, sep,
             "".join(f"{str(h):>{w + 2}}" for h, w in
                     zip(header_cols, widths))]
    for r in rows:
        lines.append("".join(f"{str(c):>{w + 2}}" for c, w in
                             zip(r, widths)))
    lines.append(sep)
    return "\n".join(lines)


# ---- device tier ----

# TPU roofline categories: where the time goes decides which residual
# (MXU util, HBM bandwidth, ICI, host) to attack next
_DEVICE_CATEGORIES = (
    ("matmul (MXU)", re.compile(r"dot|conv|einsum|gemm|matmul", re.I)),
    ("attention kernel", re.compile(r"flash|attention|pallas", re.I)),
    ("collective (ICI)", re.compile(
        r"all-reduce|all-gather|reduce-scatter|collective|all-to-all|"
        r"permute", re.I)),
    ("copy/transpose", re.compile(r"copy|transpose|bitcast", re.I)),
    ("fusion/elementwise", re.compile(
        r"fusion|add|mul|tanh|exp|rsqrt|select|compare|broadcast|"
        r"convert|reduce|wrapped|reshape", re.I)),
)


_INFRA = re.compile(
    r"ThunkExecutor|PythonRefManager|ThreadpoolListener|StartRegion|"
    r"EndRegion|^end: ")


def _categorize(name: str) -> str:
    for cat, rx in _DEVICE_CATEGORIES:
        if rx.search(name):
            return cat
    return "other"


class DeviceStatistics:
    """Per-op device statistics from a ``jax.profiler`` trace directory
    (the reference's Kernel Summary, over XLA ops instead of CUDA
    kernels)."""

    def __init__(self, ops: Dict[str, dict]):
        self.ops = ops

    @classmethod
    def from_trace_dir(cls, trace_dir) -> Optional["DeviceStatistics"]:
        files = sorted(glob.glob(os.path.join(
            str(trace_dir), "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        if not files:
            return None
        return cls.from_xplane(files[-1])

    @classmethod
    def from_xplane(cls, path: str) -> Optional["DeviceStatistics"]:
        try:
            from jax.profiler import ProfileData
            pd = ProfileData.from_file(str(path))
        except Exception:
            return None
        items = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                lines = list(plane.lines)
            elif plane.name == "/host:CPU":
                # CPU backend: XLA ops run on the PjRt client threadpool
                # lines; python lines belong to the host tier
                lines = [ln for ln in plane.lines
                         if "PjRtCpuClient" in ln.name or
                         "XLA" in ln.name]
            else:
                continue
            for line in lines:
                for e in line.events:
                    name = e.name
                    if _INFRA.search(name):
                        continue   # runtime scaffolding, not ops
                    dur = float(e.duration_ns or 0.0)
                    if dur <= 0:
                        continue
                    items.append((name, dur))
        if not items:
            return None
        return cls(_agg(items))

    def category_shares(self) -> Dict[str, float]:
        shares = collections.defaultdict(float)
        for name, d in self.ops.items():
            shares[_categorize(name)] += d["total"]
        return dict(shares)

    def report(self, time_unit="ms", max_rows=25) -> str:
        scale = _UNITS[time_unit]
        total = sum(d["total"] for d in self.ops.values()) or 1.0
        rows = []
        for name, d in sorted(self.ops.items(),
                              key=lambda kv: -kv[1]["total"])[:max_rows]:
            rows.append((
                name[:48], d["calls"],
                f"{d['total'] * scale:.4f}",
                f"{d['total'] / d['calls'] * scale:.4f}",
                f"{d['max'] * scale:.4f}",
                f"{100 * d['total'] / total:.1f}%"))
        tbl = _table(
            "Device Op Summary (XLA ops, from jax.profiler trace)",
            ("Name", "Calls", f"Total({time_unit})", f"Avg({time_unit})",
             f"Max({time_unit})", "Ratio"), rows)
        cats = sorted(self.category_shares().items(),
                      key=lambda kv: -kv[1])
        crows = [(c, f"{v * scale:.4f}", f"{100 * v / total:.1f}%")
                 for c, v in cats]
        ctbl = _table(
            "Device Category Summary (TPU roofline accounting)",
            ("Category", f"Total({time_unit})", "Ratio"), crows)
        return tbl + "\n\n" + ctbl


# ---- host tier ----

_MODEL_PHASES = ("DataLoader", "Forward", "Backward", "Optimization")


class StatisticData:
    """Aggregated host statistics + optional device tier.

    ``events``: RecordEvent spans (name, start, end, tid, event_type).
    ``step_times``: per-step wall seconds from Profiler.step().
    ``device``: DeviceStatistics or None.
    """

    def __init__(self, events, step_times=None, device=None):
        self.events = list(events)
        self.step_times = step_times or []
        self.device = device

    # retained for callers of the old single-table API
    def aggregate(self):
        return _agg((e.name, e.duration) for e in self.events)

    def _host_rows(self, agg, scale, time_unit, sorted_by, max_rows=None):
        key = _SORT_FIELD.get(sorted_by, _SORT_FIELD[SortedKeys.CPUTotal])
        total = sum(d["total"] for d in agg.values()) or 1.0
        rows = []
        for name, d in sorted(agg.items(),
                              key=lambda kv: key(kv[1]))[:max_rows]:
            rows.append((
                name[:48], d["calls"],
                f"{d['total'] * scale:.4f}",
                f"{d['self'] * scale:.4f}",
                f"{d['total'] / d['calls'] * scale:.4f}",
                f"{d['max'] * scale:.4f}",
                f"{d['min'] * scale:.4f}",
                f"{100 * d['total'] / total:.1f}%"))
        return rows

    def report(self, time_unit="ms", sorted_by=None, op_detail=True,
               thread_sep=False, max_rows=30) -> str:
        scale = _UNITS[time_unit]
        blocks = []

        # -- overview: step timing
        if self.step_times:
            import statistics as st
            n = len(self.step_times)
            mean = st.mean(self.step_times)
            blocks.append(
                f"steps: {n}  avg: {mean * 1e3:.3f} ms  "
                f"min: {min(self.step_times) * 1e3:.3f} ms  "
                f"max: {max(self.step_times) * 1e3:.3f} ms  "
                f"throughput: {1.0 / mean:.2f} steps/s")

        selfs = _self_times(self.events)
        by_type = _agg((e.event_type, e.duration, selfs[i])
                       for i, e in enumerate(self.events))
        if by_type:
            window_ns = max(
                sum(self.step_times) * 1e9 if self.step_times else
                sum(d["self"] for d in by_type.values()), 1.0)
            rows = [(t, d["calls"], f"{d['total'] * scale:.4f}",
                     f"{d['self'] * scale:.4f}",
                     f"{100 * d['self'] / window_ns:.1f}%")
                    for t, d in sorted(by_type.items(),
                                       key=lambda kv: -kv[1]["self"])]
            blocks.append(_table(
                "Overview Summary (host spans by type)",
                ("Type", "Calls", f"Total({time_unit})",
                 f"Self({time_unit})", "Window%"), rows))

        # -- model summary: training-phase shares (reference: Model
        # Summary's DataLoader/Forward/Backward/Optimization split)
        phase = {p: by_type[p] for p in _MODEL_PHASES if p in by_type}
        if phase and self.step_times:
            window_ns = max(sum(self.step_times) * 1e9, 1.0)
            accounted = sum(d["self"] for d in phase.values())
            rows = [(p, d["calls"], f"{d['self'] * scale:.4f}",
                     f"{100 * d['self'] / window_ns:.1f}%")
                    for p, d in phase.items()]
            rows.append(
                ("Others", "-", f"{(window_ns - accounted) * scale:.4f}",
                 f"{100 * (window_ns - accounted) / window_ns:.1f}%"))
            blocks.append(_table(
                "Model Summary (step-phase shares)",
                ("Phase", "Calls", f"Self({time_unit})", "Step%"), rows))

        # -- ranked per-name tables
        hdr = ("Name", "Calls", f"Total({time_unit})",
               f"Self({time_unit})", f"Avg({time_unit})",
               f"Max({time_unit})", f"Min({time_unit})", "Ratio")
        if op_detail:
            if thread_sep:
                by_tid = collections.defaultdict(list)
                for i, e in enumerate(self.events):
                    by_tid[e.tid].append((e.name, e.duration, selfs[i]))
                for tid, items in sorted(by_tid.items()):
                    blocks.append(_table(
                        f"Host Event Summary (thread {tid})", hdr,
                        self._host_rows(_agg(items), scale, time_unit,
                                        sorted_by, max_rows)))
            else:
                agg = _agg((e.name, e.duration, selfs[i])
                           for i, e in enumerate(self.events))
                if agg:
                    blocks.append(_table(
                        "Host Event Summary (ranked)", hdr,
                        self._host_rows(agg, scale, time_unit, sorted_by,
                                        max_rows)))

        # -- device tier
        if self.device is not None:
            blocks.append(self.device.report(time_unit=time_unit))

        return "\n\n".join(blocks) if blocks else "(no profiler events)"
