"""Unified telemetry subsystem (ISSUE 3).

The cross-cutting observability layer the reference spreads over
``deepspeed/monitor``, ``utils/timer.py``, the flops profiler and the
comms logger, redesigned for JAX's async-dispatch execution model:

  * :mod:`registry`  — counters / gauges / fixed-bucket latency histograms
    with p50/p95/p99 snapshots; process-global default registry plus
    :func:`record_event` for discrete occurrences (checkpoint saves,
    corruption fallbacks, elastic restarts).
  * :mod:`sink`      — structured JSONL sink (one record per line) that
    also plugs into :class:`~deepspeed_tpu.monitor.monitor.MonitorMaster`
    as its fourth writer; render with ``scripts/telemetry_report.py``.
  * :mod:`trace`     — ``telemetry.trace(path)`` Perfetto/XPlane capture
    around any block, with the hot loops' named scopes inside.
  * :mod:`mfu`       — PaLM-sense model-flops-utilization against the
    accelerator layer's per-chip peak table.
  * :mod:`compile_log` — what start-up cost, from inside: JAX's trace,
    lowering, compile and cache events by program name as ``entry/*``
    counters, and the phases of set-up (ISSUE 42).
  * :mod:`host_watch` — what the host process was doing when a step took
    too long: a stall rule at the phase edges of both step loops and one
    ``gc.callbacks`` entry, as ``host/*`` counters, ``host_stall`` events,
    spans and one warning line a stall (ISSUE 57).

Instrumentation points: ``runtime/engine.py`` (per-step wall/device time,
tokens/sec, MFU, grad-norm, fp16 skip counters, device memory) and
``serving/engine.py`` (queue-wait/TTFT/TPOT histograms, slot occupancy,
recompile counter, finished-requests/sec). What it costs on the chip:
PERF.md section 6, PR 57 (the host watch over the registry moved no
end-to-end metric of gpt2-large's two cells; an armed tracer 0.4 to 2.0% of
``itl_p95_ms``; a run with no registry at all has not been measured).
"""

from deepspeed_tpu.telemetry.compile_log import (CompileLog, SetupPhase,
                                                 compile_log)
from deepspeed_tpu.telemetry.config import TelemetryConfig, get_telemetry_config
from deepspeed_tpu.telemetry.flight_recorder import FlightRecorder
from deepspeed_tpu.telemetry.mfu import mfu, peak_flops_per_sec
from deepspeed_tpu.telemetry.registry import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    metric_label,
    record_event,
    reset_registry,
    sanitize_metric_name,
)
from deepspeed_tpu.telemetry.sink import JsonlSink, read_jsonl
from deepspeed_tpu.telemetry.slo import (DEFAULT_SLO_CONFIG, SLI, BurnRateRule,
                                         SLOAlert, SLOConfigError, SLOEngine,
                                         parse_slo_config, validate_slo_config)
from deepspeed_tpu.telemetry.tenants import DEFAULT_TENANT, TenantLedger
from deepspeed_tpu.telemetry.spans import (PHASE_OF_SPAN, PHASES, Span,
                                           SpanTracer, aggregate_phase_stats,
                                           phase_breakdown, trace_summaries)
from deepspeed_tpu.telemetry.trace import annotate, trace

__all__ = [
    "BurnRateRule",
    "CompileLog",
    "DEFAULT_LATENCY_BUCKETS_MS",
    "DEFAULT_SLO_CONFIG",
    "DEFAULT_TENANT",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "JsonlSink",
    "MetricsRegistry",
    "PHASES",
    "PHASE_OF_SPAN",
    "SLI",
    "SLOAlert",
    "SLOConfigError",
    "SLOEngine",
    "SetupPhase",
    "Span",
    "SpanTracer",
    "TelemetryConfig",
    "TenantLedger",
    "aggregate_phase_stats",
    "annotate",
    "compile_log",
    "get_registry",
    "get_telemetry_config",
    "metric_label",
    "mfu",
    "parse_slo_config",
    "peak_flops_per_sec",
    "phase_breakdown",
    "read_jsonl",
    "record_event",
    "reset_registry",
    "sanitize_metric_name",
    "trace",
    "trace_summaries",
    "validate_slo_config",
]
