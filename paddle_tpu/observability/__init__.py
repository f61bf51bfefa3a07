"""Runtime telemetry: metrics registry + hot-path spans + step timeline.

The reference stack pairs its HostTracer/CUPTI profiler with
instrumentation woven through the runtime
(paddle/fluid/platform/profiler/); this package is that layer for the
TPU build:

- :mod:`metrics` — process-global, thread-safe Counters / Gauges /
  Histograms with labels, exportable as Prometheus text
  (``REGISTRY.to_prometheus()``) and JSON (``REGISTRY.to_json()``).
- :mod:`hooks` — the emitters the hot paths call (pipeline engine,
  predictor, generate, dataloader, collectives, watchdog). Near-zero
  cost when disabled: one module-flag read per call site, no
  allocation (``hooks.span`` hands back a shared nullcontext).
- :mod:`timeline` — merges profiler spans + metrics into one per-phase
  summary dict (``Profiler.phase_summary()``) + the shared sort-stable
  Chrome trace exporter (``chrome_trace``).
- :mod:`tracing` — request-scoped distributed tracing for the serving
  plane: a trace minted at submission rides the request handle through
  queue/prefill/handoff/swap/decode/recovery, stitching cross-replica
  hops into one trace; per-request TTFT breakdowns; Chrome export.
  Independent switch (``tracing.enable(clock_ns=...)``), zero-cost
  when off.
- :mod:`flight` — the crash flight recorder: per-supervisor ring of
  scheduler ticks + request-trace tails, dumped as a CRC-framed
  ``flight-<ts>.json`` black box on EngineDead / step exceptions / on
  demand.

Usage::

    import paddle_tpu.observability as obs
    obs.enable()                       # or PADDLE_TPU_METRICS=1
    ... run training / serving ...
    print(obs.REGISTRY.to_prometheus())   # scrape payload
    obs.disable()
"""
from . import metrics  # noqa: F401
from . import hooks  # noqa: F401
from . import timeline  # noqa: F401
from . import tracing  # noqa: F401
from . import flight  # noqa: F401
from .metrics import (  # noqa: F401
    REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
    counter, gauge, histogram,
)
from .hooks import enable, disable, metrics_enabled, span  # noqa: F401
from .timeline import (  # noqa: F401
    StepTimeline, chrome_trace, phase_summary,
)
from .tracing import RequestTrace, Tracer  # noqa: F401
from .flight import FlightRecorder  # noqa: F401
