"""Telemetry config section (``"telemetry": {...}`` in the DeepSpeed JSON).

Keys:
  enabled        — master switch for engine/serving instrumentation
                   (default true; the registry ops it gates are a few
                   dict updates a step).
  jsonl_path     — when non-empty, a JsonlSink is attached to the global
                   registry and periodic snapshots + events stream there
                   (render with scripts/telemetry_report.py).
  sync_interval  — every N global steps the engine fences device work
                   (block_until_ready) to read honest device-time step
                   latency, memory gauges, grad-norm/overflow/MFU. 0
                   disables fencing (async dispatch never perturbed;
                   device-time metrics then unavailable).
  cost_analysis  — allow a one-time XLA cost_analysis of the compiled
                   train step for MFU flops (an extra lower+compile at the
                   first fence; analytic model flops are the fallback).
  spans          — arm the span-graph tracer (ISSUE 11): step-window,
                   sentinel-check, recovery and checkpoint spans stamped
                   host-side at the fences that already exist (zero extra
                   device syncs; default off).
  spans_path     — JSONL file for span records; empty reuses jsonl_path's
                   sink (spans interleave with snapshots/events in one
                   file — telemetry_report.py renders both).
  flight_recorder — arm the crash-safe flight recorder (ISSUE 13): a
                   bounded ring of recent spans/events/snapshots teed
                   off the JSONL stream, dumped as one postmortem JSON
                   when the training sentinel hits an actionable
                   anomaly (default off).
  flight_dir     — directory for flight-recorder dump artifacts
                   (``flight_<NNN>_<reason>.json``); empty records
                   triggers without writing files.
"""

from __future__ import annotations

from deepspeed_tpu.runtime.config_utils import DeepSpeedConfigModel


class TelemetryConfig(DeepSpeedConfigModel):
    enabled: bool = True
    jsonl_path: str = ""
    sync_interval: int = 50
    cost_analysis: bool = True
    spans: bool = False
    spans_path: str = ""
    flight_recorder: bool = False
    flight_dir: str = ""


def get_telemetry_config(param_dict: dict) -> TelemetryConfig:
    return TelemetryConfig(**param_dict.get("telemetry", {}))
