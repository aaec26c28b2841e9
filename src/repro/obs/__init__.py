"""``repro.obs`` — unified telemetry for the O-POPE substrate.

The paper's headline claim is *measured* utilization (99.97% FPU busy); a
reproduction aiming at production scale needs the same discipline about its
own numbers. This package is the one place runtime observability lives:

* :mod:`~repro.obs.metrics` — thread-safe Counter/Gauge/Histogram registry,
  ``snapshot()`` (nested dict), JSON + Prometheus-text exporters, and the
  ``REPRO_METRICS=0`` hard-off switch. All instrumentation in the repo is
  host-side Python (trace-time inside ``jit``), so telemetry adds **zero
  ops to compiled HLO** on or off — asserted on a jitted decode step by
  ``tests/test_obs.py``.
* :mod:`~repro.obs.spans` — ``span(name)``: a ``jax.profiler.TraceAnnotation``
  on the profiler's clock (the serving engine's ``serve.*`` phases) and a
  wall-clock histogram.
* :mod:`~repro.obs.logging` — structured launch-script logging
  (``REPRO_LOG=text|json``) and the JSONL event log (``REPRO_EVENTS``,
  ``repro-stats tail``) the train loop's per-step records flow through.
* :mod:`~repro.obs.attr` — live utilization attribution: captured GEMM
  workloads costed with :mod:`repro.core.roofline`, measured step time
  attributed per shape bucket (``gemm.achieved_gflops`` /
  ``gemm.roofline_fraction``; ``repro-stats top``), feeding the
  ``ops.on_util_gap`` drift-retune seam.
* :mod:`~repro.obs.tracing` — request-scoped lifecycle tracing for the
  serving engine (``Request.uid``-keyed phase chains: queue → prefix-attach
  → chunk-prefill → decode, chunk-tick slices, token instants), exported
  as Chrome trace-event JSON (``repro-stats trace`` → Perfetto).
* :mod:`~repro.obs.http` — live scrape surface (``REPRO_METRICS_PORT``):
  ``/metrics`` (Prometheus text), ``/requests`` (in-flight phase ages),
  ``/trace`` (Chrome-trace JSON) on a stdlib ``http.server`` thread.
* :mod:`~repro.obs.audit` — shadow numerics auditor: ``REPRO_AUDIT=N``
  samples quantized-family GEMMs for fp re-execution on the
  ``grad_backend`` (``numerics.abs_err``/``rel_err``, NaN/Inf sentinels,
  ``numerics_drift`` events against per-family policies).

Instrumented layers: ``kernels.ops`` (per-call GEMM counters by
backend/family/tile/fusion source, degradation events, tile-cache hit/miss
+ the ``on_miss_streak`` auto-retune seam), ``serve.continuous``
(per-request lifecycle -> TTFT/ITL histograms, queue/occupancy gauges),
``train.loop`` (per-step wall/tokens-s/roofline events). The ``repro-stats``
CLI (``repro.launch.stats``) surfaces all of it.
"""

from . import attr, audit, http, tracing
from .logging import (
    Logger,
    clear_events,
    event,
    event_log_path,
    follow_events,
    get_logger,
    log_mode,
    read_events,
    recent_events,
    set_event_log,
)
from .metrics import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    Registry,
    counter,
    enabled,
    gauge,
    histogram,
    percentile,
    prometheus_text,
    reset,
    set_enabled,
    snapshot,
    to_json,
)
from .spans import span

__all__ = [
    "attr",
    "audit",
    "http",
    "tracing",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "DEFAULT_BUCKETS",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "reset",
    "to_json",
    "prometheus_text",
    "percentile",
    "enabled",
    "set_enabled",
    "span",
    "Logger",
    "get_logger",
    "log_mode",
    "event",
    "clear_events",
    "set_event_log",
    "event_log_path",
    "recent_events",
    "read_events",
    "follow_events",
]
