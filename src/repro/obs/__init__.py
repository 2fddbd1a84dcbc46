"""Unified run-log & tracing plane (DESIGN.md §12, docs/OBSERVABILITY.md).

One dependency-free (stdlib-only) event spine threaded through train,
numerics, kernels, checkpoint, serve, and analysis:

  * `events`  — typed, versioned `Event` records; the `Recorder` hub with
                an *injected* clock (tests stay deterministic) and
                no-op-when-disabled emission;
  * `sinks`   — JSONL run-log with size-based rotation, Prometheus
                textfile exposition, in-memory sink for tests;
  * `metrics` — counters / gauges / histograms with label support;
  * `trace`   — nestable span context manager that times jitted work
                correctly via an injected `block_until_ready` and puts
                itself on the profiler's clock via an injected
                `annotate`, plus the shared benchmark timer `time_fn`.

Every instrumented component takes an optional `recorder=` and defaults
to the shared no-op `NULL_RECORDER`: with all sinks disabled the
instrumented paths are bit-identical to uninstrumented ones (emission is
host-side, outside jit) and cost one truthiness check. The public
surface below is snapshotted by tools/check_api.py (CI `api-surface`
job) — extend `__all__` and refresh with `check_api.py --update`.
"""
from repro.obs.events import (KINDS, SCHEMA_VERSION, Clock, Event,
                              ManualClock, NULL_RECORDER, Recorder,
                              SystemClock)
from repro.obs.metrics import (DEFAULT_BUCKETS, Metric, MetricsRegistry)
from repro.obs.sinks import (JSONLSink, MemorySink, PrometheusTextfileSink,
                             Sink)
from repro.obs.trace import Span, time_fn

__all__ = [
    "Clock",
    "DEFAULT_BUCKETS",
    "Event",
    "JSONLSink",
    "KINDS",
    "ManualClock",
    "MemorySink",
    "Metric",
    "MetricsRegistry",
    "NULL_RECORDER",
    "PrometheusTextfileSink",
    "Recorder",
    "SCHEMA_VERSION",
    "Sink",
    "Span",
    "SystemClock",
    "time_fn",
]
