"""Profiler core (reference: python/paddle/profiler/profiler.py)."""
from __future__ import annotations

import contextlib
import enum
import json
import os
import threading
import time
from typing import Callable, Iterable, List, Optional

import jax


class ProfilerState(enum.Enum):
    """reference: profiler.py:89 ProfilerState."""
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class ProfilerTarget(enum.Enum):
    """reference: profiler.py ProfilerTarget (CPU/GPU/XPU/CUSTOM_DEVICE);
    TPU-native adds the device target as TPU."""
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class _Event:
    __slots__ = ("name", "start", "end", "tid", "event_type")

    def __init__(self, name, start, end, tid, event_type="UserDefined"):
        self.name = name
        self.start = start
        self.end = end
        self.tid = tid
        self.event_type = event_type

    @property
    def duration(self):
        return self.end - self.start


class _Collector:
    """Host event buffer — the HostTracer analog. Spans land in the NATIVE
    ring (_native/hosttracer.cpp: one mutex'd 32-byte append, no Python
    allocator on the hot path, like the reference's host_tracer.cc) when
    the toolchain built it; pure-python list otherwise."""

    def __init__(self):
        self.events: List[_Event] = []
        self.enabled = False
        self.lock = threading.Lock()
        self._names: dict = {}        # name -> int32 id
        self._names_rev: list = []
        self._types: dict = {}
        self._types_rev: list = []
        self._native = None           # resolved lazily at first enable

    def _lib(self):
        if self._native is None:
            from .. import _native
            self._native = (_native.load(), )
        return self._native[0]

    def _intern(self, table, rev, s):
        i = table.get(s)
        if i is None:
            i = table[s] = len(rev)
            rev.append(s)
        return i

    def native_start(self, capacity=1 << 20):
        lib = self._lib()
        if lib is not None:
            # preserve earlier record windows: drain the ring into the
            # python list BEFORE enable resets it, and restart the intern
            # tables together with the ring (ids restart from 0)
            self.drain()
            with self.lock:
                self._names.clear()
                self._names_rev.clear()
                self._types.clear()
                self._types_rev.clear()
            lib.pt_trace_enable(capacity)

    def native_stop(self):
        lib = self._lib()
        if lib is not None:
            lib.pt_trace_disable()

    def add(self, ev: _Event):
        lib = self._lib()
        if lib is not None:
            with self.lock:
                nid = self._intern(self._names, self._names_rev, ev.name)
                tid_ = self._intern(self._types, self._types_rev,
                                    ev.event_type)
            lib.pt_trace_record(nid, tid_, ev.start, ev.end, ev.tid)
            return
        with self.lock:
            self.events.append(ev)

    def drain(self) -> List[_Event]:
        """events list + everything recorded natively (converted back).
        Atomic against concurrent recording (pt_trace_drain removes only
        what it copied) and serialized against concurrent drains."""
        lib = self._lib()
        if lib is None:
            with self.lock:
                return list(self.events)
        import ctypes
        import struct
        with self.lock:
            n = lib.pt_trace_count()
            if n:
                buf = (ctypes.c_int64 * (n * 4))()  # 32-byte records
                got = lib.pt_trace_drain(ctypes.cast(
                    buf, ctypes.c_void_p), n)
                raw = memoryview(buf).cast("b")[:got * 32]
                for i in range(got):
                    s, e, t, nid, tyid = struct.unpack_from(
                        "<qqqii", raw, i * 32)
                    self.events.append(_Event(
                        self._names_rev[nid], s, e, t,
                        self._types_rev[tyid]))
            dropped = lib.pt_trace_dropped()
            if dropped:
                import warnings
                warnings.warn(
                    f"profiler: native ring capacity reached — {dropped} "
                    f"span(s) dropped; raise the window capacity or "
                    f"shorten the RECORD window")
                lib.pt_trace_clear()  # resets the drop counter
            return list(self.events)


_collector = _Collector()


class RecordEvent:
    """Span instrumentation (reference: paddle/phi/api/profiler/
    event_tracing.h:32 RecordEvent; python/paddle/profiler/utils.py
    RecordEvent). Usable as context manager or begin()/end()."""

    def __init__(self, name: str, event_type: str = "UserDefined"):
        self.name = name
        self.event_type = event_type
        self._start = None

    def begin(self):
        self._start = time.perf_counter_ns()

    def end(self):
        if self._start is None or not _collector.enabled:
            return
        _collector.add(_Event(self.name, self._start,
                              time.perf_counter_ns(),
                              threading.get_ident(), self.event_type))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0) -> Callable[[int], ProfilerState]:
    """reference: profiler.py make_scheduler — step-indexed state machine."""
    cycle = closed + ready + record

    def schedule(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return schedule


def export_chrome_tracing(dir_name: str, worker_name: Optional[str] = None
                          ) -> Callable:
    """reference: profiler.py export_chrome_tracing — on_trace_ready
    callback writing chrome://tracing JSON."""
    os.makedirs(dir_name, exist_ok=True)

    def handler(prof: "Profiler"):
        name = worker_name or f"host_{os.getpid()}"
        path = os.path.join(
            dir_name, f"{name}_time_{int(time.time())}.paddle_trace.json")
        prof._export_chrome(path)

    return handler


def load_profiler_result(filename: str):
    with open(filename) as f:
        return json.load(f)


class Profiler:
    """reference: profiler.py:358. Collects host RecordEvent spans and
    (optionally) a jax.profiler device trace per RECORD window."""

    def __init__(self, *, targets: Optional[Iterable] = None,
                 scheduler=None, on_trace_ready: Optional[Callable] = None,
                 record_shapes: bool = False, profile_memory: bool = False,
                 timer_only: bool = False, emit_nvtx: bool = False,
                 custom_device_types: Optional[list] = None):
        self._scheduler = scheduler or (lambda step: ProfilerState.RECORD)
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = lambda step: (
                ProfilerState.RECORD if lo <= step < hi
                else ProfilerState.CLOSED)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self.current_state = ProfilerState.CLOSED
        self._step = 0
        self._device_trace_dir = None
        self._device_tracing = False
        self._step_times: List[float] = []
        self._last_step_t = None

    # ---- lifecycle ----
    def start(self):
        from . import timer as _timer
        _timer.benchmark().begin()
        self.current_state = self._scheduler(self._step)
        self._apply_state()
        self._last_step_t = time.perf_counter()

    def stop(self):
        from . import timer as _timer
        _timer.benchmark().end()
        if self._device_tracing:
            jax.profiler.stop_trace()
            self._device_tracing = False
        _collector.enabled = False
        _collector.native_stop()
        if self._on_trace_ready:
            self._on_trace_ready(self)

    def step(self, num_samples: Optional[int] = None):
        from . import timer as _timer
        now = time.perf_counter()
        # count only RECORD-window steps: events exist only for those, so
        # a summary over all steps would understate every Window%/Step%
        if self._last_step_t is not None and self.current_state in (
                ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        _timer.benchmark().step(num_samples)
        old = self.current_state
        self._step += 1
        self.current_state = self._scheduler(self._step)
        if old in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN) \
                and old is ProfilerState.RECORD_AND_RETURN \
                and self._on_trace_ready:
            self._on_trace_ready(self)
        self._apply_state()

    def _apply_state(self):
        rec = self.current_state in (ProfilerState.RECORD,
                                     ProfilerState.RECORD_AND_RETURN)
        was = _collector.enabled
        _collector.enabled = rec and not self._timer_only
        if _collector.enabled and not was:
            # transition edge only: pt_trace_enable resets the ring
            _collector.native_start()
        if rec and not self._timer_only and not self._device_tracing and \
                os.environ.get("PADDLE_TPU_DEVICE_TRACE"):
            self._device_trace_dir = os.environ.get(
                "PADDLE_TPU_DEVICE_TRACE_DIR", "/tmp/paddle_tpu_trace")
            try:
                jax.profiler.start_trace(self._device_trace_dir)
                self._device_tracing = True
            except Exception:
                pass

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    # ---- results ----
    def events(self) -> List[_Event]:
        return _collector.drain()

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit='ms'):
        """reference: profiler.py summary -> profiler_statistic tables
        (Overview / Model / ranked host events / device op + category).
        The device tier appears when a jax.profiler trace was captured
        (PADDLE_TPU_DEVICE_TRACE=1 during a RECORD window)."""
        from .profiler_statistic import DeviceStatistics, StatisticData
        device = None
        if self._device_trace_dir:
            device = DeviceStatistics.from_trace_dir(
                self._device_trace_dir)
        return StatisticData(self.events(), self._step_times,
                             device=device).report(
            time_unit=time_unit, sorted_by=sorted_by,
            op_detail=op_detail, thread_sep=thread_sep)

    def phase_summary(self) -> dict:
        """Structured per-phase breakdown of the collected spans —
        forward/backward/optimizer/dataloader plus the serving phases
        (prefill/decode/inference) and pipeline buckets — merged with
        the metrics-registry snapshot (observability.timeline). The
        machine-readable counterpart of :meth:`summary`."""
        from ..observability.timeline import phase_summary
        return phase_summary(self.events(), self._step_times)

    def export(self, path: str, format: str = "json"):
        self._export_chrome(path)

    def _export_chrome(self, path: str):
        # route through the shared sort-stable exporter (ISSUE 16):
        # distinct pid/tid rows + deterministic ordering, so exports of
        # the same spans are byte-identical and cluster traces never
        # interleave into one lane
        from ..observability.timeline import chrome_trace
        pid = os.getpid()
        rows = [{"name": e.name, "cat": e.event_type,
                 "start_ns": e.start, "dur_ns": e.duration,
                 "pid": pid, "tid": e.tid}
                for e in _collector.drain()]
        doc = chrome_trace(rows, pid_names={pid: f"host {pid}"})
        with open(path, "w") as f:
            json.dump(doc, f, sort_keys=True, separators=(",", ":"))
