"""Zero-dependency observability for the sparse serving stack (DESIGN §12).

Three layers, threaded through the whole pipeline:

* ``trace``   — nested span tracer (thread-safe, ~no-op when disabled)
  with Perfetto/Chrome ``trace_event`` and JSONL exporters, span
  coverage analysis, and the shared per-phase breakdown schema.
* ``metrics`` — counters / gauges / log-bucket histograms with labels,
  dict snapshots, Prometheus text format, and the streaming-quantile
  summaries that replaced the full-sort percentile path.
* ``profile`` — kernel launch profiling (warmup discard, best/p50/p95,
  effective GB/s vs the dense roofline) consumed by both benches.

Second layer (DESIGN §14), request-scoped and always-on:

* ``flightrec``  — bounded ring of recent request/fault events every
  engine feeds unconditionally; the fault ladder dumps it to
  ``FLIGHT_*.json`` so post-mortems never require a traced re-run.
* ``timeline``   — reconstructs per-request lifecycles (queued →
  prefill chunks → decode ticks → terminal state) from a live tracer,
  a Chrome trace, or a JSONL event log.
* ``regression`` — noise-aware perf-regression sentinel (exact vs
  windowed one-sided tolerance bands) gated by CI via
  ``benchmarks/bench_history.py``.
"""
from repro.telemetry.flightrec import (FlightRecorder,  # noqa: F401
                                       get_recorder, set_recorder)
from repro.telemetry.metrics import (Counter, Gauge, Histogram,  # noqa: F401
                                     LATENCY_BUCKETS_S,
                                     REQUIRED_SERVE_METRICS, Registry,
                                     THROUGHPUT_BUCKETS, US_BUCKETS,
                                     log_buckets, validate_snapshot)
from repro.telemetry.profile import LaunchTiming, time_launch  # noqa: F401
from repro.telemetry.regression import (MetricSpec,  # noqa: F401
                                        PerfRegressionError,
                                        assert_no_regression, compare,
                                        format_findings)
from repro.telemetry.timeline import (RequestTimeline, Segment,  # noqa: F401
                                      build_timelines, check_timelines,
                                      format_timeline,
                                      timelines_from_chrome,
                                      timelines_from_jsonl,
                                      timelines_from_tracer)
from repro.telemetry.trace import (BREAKDOWN_SCHEMA_KEYS,  # noqa: F401
                                   NULL_TRACER, Span, Tracer, get_tracer,
                                   phase_breakdown, set_tracer,
                                   span_coverage, validate_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "log_buckets",
    "LATENCY_BUCKETS_S", "THROUGHPUT_BUCKETS", "US_BUCKETS",
    "REQUIRED_SERVE_METRICS", "validate_snapshot",
    "LaunchTiming", "time_launch",
    "Span", "Tracer", "NULL_TRACER", "get_tracer", "set_tracer",
    "span_coverage", "phase_breakdown", "validate_chrome_trace",
    "BREAKDOWN_SCHEMA_KEYS",
    "FlightRecorder", "get_recorder", "set_recorder",
    "Segment", "RequestTimeline", "build_timelines",
    "timelines_from_tracer", "timelines_from_chrome",
    "timelines_from_jsonl", "check_timelines", "format_timeline",
    "MetricSpec", "PerfRegressionError", "compare",
    "assert_no_regression", "format_findings",
]
