"""Span-graph tracer unit tests (ISSUE 11), set-up spans (ISSUE 42).

Pure-host coverage of the tentpole's building blocks: deterministic
trace/span ids and parent links, closed-span stamping, JSONL streaming,
Chrome-trace export validity, per-trace phase breakdown / critical-path
aggregation, the Prometheus text exposition (satellite, round-tripped),
the metric-name drift lint (satellite), the telemetry_report ``spans``
section with the set-up spans in it, and the TRAINING engine's span
points (step windows, sentinel fence, checkpoint save/load) plus the
phases of its set-up.
"""

import importlib.util
import json
import os
import re

import numpy as np
import pytest

from deepspeed_tpu.telemetry import (JsonlSink, MetricsRegistry, SpanTracer,
                                     aggregate_phase_stats, phase_breakdown,
                                     read_jsonl, trace_summaries)

pytestmark = [pytest.mark.tracing, pytest.mark.observability,
              pytest.mark.quick]

SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ----------------------------------------------------------------- tracer
def test_tracer_ids_deterministic_and_linked():
    tr = SpanTracer(time_fn=lambda: 0.0)
    root = tr.begin("request", t=0.0, rid=7)
    child = tr.record("queue_wait", 0.0, 1.0, trace_id=root.trace_id,
                      parent_id=root.span_id)
    tr.end(root, t=2.0, finish_reason="eos")
    assert root.trace_id == "t00000000"
    assert root.span_id == "s00000000" and child.span_id == "s00000001"
    assert child.parent_id == root.span_id
    assert child.trace_id == root.trace_id
    # finished order: child committed first (record), root on end()
    assert [s.name for s in tr.spans] == ["queue_wait", "request"]
    assert root.duration == 2.0
    # a second tracer replays the same id sequence (chaos determinism)
    tr2 = SpanTracer(time_fn=lambda: 0.0)
    assert tr2.begin("request", t=0.0).trace_id == "t00000000"


def test_tracer_end_is_idempotent_and_none_safe():
    tr = SpanTracer(time_fn=lambda: 0.0)
    assert tr.end(None) is None
    s = tr.begin("x", t=1.0)
    tr.end(s, t=2.0)
    tr.end(s, t=99.0)          # second end ignored
    assert s.end == 2.0 and len(tr.spans) == 1
    # out-of-order virtual stamps clamp, never negative durations
    s2 = tr.begin("y", t=5.0)
    tr.end(s2, t=4.0)
    assert s2.duration == 0.0


def test_tracer_max_spans_bounds_memory():
    tr = SpanTracer(time_fn=lambda: 0.0, max_spans=3)
    for i in range(5):
        tr.record("s", 0.0, 1.0)
    assert len(tr.spans) == 3 and tr.dropped == 2


def test_spans_stream_to_jsonl_sink(tmp_path):
    path = str(tmp_path / "spans.jsonl")
    tr = SpanTracer(time_fn=lambda: 0.0, sink=JsonlSink(path))
    root = tr.begin("request", t=0.0, rid=1)
    tr.record("queue_wait", 0.0, 0.5, trace_id=root.trace_id,
              parent_id=root.span_id)
    tr.end(root, t=1.0, finish_reason="eos")
    tr.sink.close()
    recs = read_jsonl(path)
    assert [r["kind"] for r in recs] == ["span", "span"]
    by_name = {r["name"]: r for r in recs}
    assert by_name["queue_wait"]["parent"] == root.span_id
    assert by_name["request"]["attrs"]["finish_reason"] == "eos"
    assert by_name["queue_wait"]["dur_ms"] == pytest.approx(500.0)


def test_chrome_trace_export_valid_json(tmp_path):
    tr = SpanTracer(time_fn=lambda: 0.0)
    a = tr.begin("request", t=0.0)
    tr.record("decode_segment", 0.2, 0.9, trace_id=a.trace_id,
              parent_id=a.span_id, slot=3)
    tr.end(a, t=1.0)
    b = tr.begin("request", t=0.5)
    tr.end(b, t=0.7)
    path = tr.export_chrome_trace(str(tmp_path / "trace.json"))
    with open(path) as f:
        doc = json.load(f)          # must be VALID json
    events = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(events) == 3
    # one tid track per trace; µs timestamps
    assert {e["tid"] for e in events} == {0, 1}
    seg = [e for e in events if e["name"] == "decode_segment"][0]
    assert seg["ts"] == pytest.approx(0.2e6)
    assert seg["dur"] == pytest.approx(0.7e6)
    assert seg["args"]["slot"] == 3
    # open spans are excluded, metadata rows name the tracks
    assert any(e["ph"] == "M" for e in doc["traceEvents"])


# -------------------------------------------------------- phase breakdown
def _synthetic_request_trace(tr, t0, queue, prefill, decode, swapped=0.0):
    root = tr.begin("request", t=t0)
    t = t0
    tr.record("queue_wait", t, t + queue, trace_id=root.trace_id,
              parent_id=root.span_id)
    t += queue
    tr.record("prefill_chunk", t, t + prefill, trace_id=root.trace_id,
              parent_id=root.span_id)
    t += prefill
    if swapped:
        tr.record("swapped", t, t + swapped, trace_id=root.trace_id,
                  parent_id=root.span_id)
        t += swapped
    tr.record("decode_segment", t, t + decode, trace_id=root.trace_id,
              parent_id=root.span_id)
    t += decode
    tr.end(root, t=t, finish_reason="length")
    return root.trace_id


def test_phase_breakdown_and_critical_path_aggregation():
    tr = SpanTracer(time_fn=lambda: 0.0)
    _synthetic_request_trace(tr, 0.0, queue=0.5, prefill=0.1, decode=0.4)
    _synthetic_request_trace(tr, 1.0, queue=0.1, prefill=0.1, decode=0.3,
                             swapped=0.5)
    ph = phase_breakdown(tr.spans_for("t00000000"))
    assert ph["queue"] == pytest.approx(0.5)
    assert ph["decode"] == pytest.approx(0.4)
    assert ph["failover"] == 0.0
    sums = trace_summaries(tr.spans)
    assert len(sums) == 2
    s0 = [s for s in sums if s["trace"] == "t00000000"][0]
    assert s0["total_s"] == pytest.approx(1.0)
    assert s0["fractions"]["queue"] == pytest.approx(0.5)
    agg = aggregate_phase_stats(sums)
    assert agg["n_requests"] == 2
    assert set(agg) >= {"queue", "prefill", "decode", "swapped"}
    # the swapped request spent half its life parked
    s1 = [s for s in sums if s["trace"] != "t00000000"][0]
    assert s1["fractions"]["swapped"] == pytest.approx(0.5)


# ------------------------------------------------------------- prometheus
def test_prometheus_text_exposition_round_trip():
    reg = MetricsRegistry()
    reg.counter("serving/finished_requests").inc(7)
    reg.gauge("train/mfu").set(0.466)
    h = reg.histogram("serving/ttft_ms", buckets=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 5.0, 50.0, 500.0):
        h.observe(v)
    text = reg.to_prometheus()
    # well-formed: TYPE lines + samples, sanitized names
    assert "# TYPE dstpu_serving_finished_requests_total counter" in text
    assert "dstpu_serving_finished_requests_total 7" in text
    assert "dstpu_train_mfu 0.466" in text
    # cumulative buckets + +Inf + sum/count
    lines = dict(
        re.match(r"(\S+(?:\{[^}]*\})?) (\S+)$", ln).groups()
        for ln in text.splitlines() if not ln.startswith("#"))
    assert lines['dstpu_serving_ttft_ms_bucket{le="1.0"}'] == "1"
    assert lines['dstpu_serving_ttft_ms_bucket{le="10.0"}'] == "3"
    assert lines['dstpu_serving_ttft_ms_bucket{le="100.0"}'] == "4"
    assert lines['dstpu_serving_ttft_ms_bucket{le="+Inf"}'] == "5"
    assert float(lines["dstpu_serving_ttft_ms_sum"]) == pytest.approx(560.5)
    assert lines["dstpu_serving_ttft_ms_count"] == "5"
    # round trip: the parsed exposition reproduces the registry state
    snap = reg.snapshot()
    assert int(lines["dstpu_serving_finished_requests_total"]) == \
        snap["counters"]["serving/finished_requests"]
    assert float(lines["dstpu_train_mfu"]) == snap["gauges"]["train/mfu"]
    assert int(lines["dstpu_serving_ttft_ms_count"]) == \
        snap["histograms"]["serving/ttft_ms"]["count"]


def test_prometheus_empty_registry():
    assert MetricsRegistry().to_prometheus() == ""


# -------------------------------------------------------- metric-name lint
def test_metric_name_lint_passes_on_this_tree():
    """The satellite's contract: README metric docs exactly cover the
    telemetry call sites — a name added to either side alone fails
    tier-1."""
    mod = _load_script("check_metric_names")
    assert mod.main([]) == 0


def test_metric_name_lint_detects_drift(tmp_path):
    root = tmp_path / "repo"
    pkg = root / "deepspeed_tpu"
    pkg.mkdir(parents=True)
    (pkg / "m.py").write_text(
        "def f(reg, c):\n"
        "    reg.counter(\"serving/undocumented_thing\").inc()\n"
        "    reg.gauge(f\"fabric/replica_load/{c}\").set(1.0)\n")
    (root / "README.md").write_text(
        "docs: `fabric/replica_load/<name>` and `train/ghost_metric`\n")
    mod = _load_script("check_metric_names")
    code = mod.code_names(str(pkg))
    assert "serving/undocumented_thing" in code
    assert "fabric/replica_load/*" in code          # f-string -> wildcard
    docs = mod.readme_names(str(root / "README.md"))
    assert "fabric/replica_load/*" in docs          # <name> -> wildcard
    assert mod.main(["--root", str(root)]) == 1     # both drift kinds


# -------------------------------------------------- report spans section
def test_report_spans_section_with_setup_spans(tmp_path):
    path = str(tmp_path / "run.jsonl")
    tr = SpanTracer(time_fn=lambda: 0.0, sink=JsonlSink(path))
    _synthetic_request_trace(tr, 0.0, queue=0.6, prefill=0.1, decode=0.3)
    _synthetic_request_trace(tr, 0.0, queue=0.2, prefill=0.2, decode=0.6)
    _synthetic_request_trace(tr, 0.0, queue=0.2, prefill=0.2, decode=0.6)
    # a start-up and one recompile in service, on the engine's trace
    engine_trace = tr.new_trace()
    tr.record("setup_weights", 0.0, 1.5, trace_id=engine_trace)
    tr.record("setup_cache", 1.5, 1.75, trace_id=engine_trace)
    warm = tr.record("setup_warmup", 2.0, 5.0, trace_id=engine_trace)
    for i, (t0, t1) in enumerate(((2.0, 4.0), (4.0, 5.0))):
        tr.record("warmup_pass", t0, t1, trace_id=engine_trace,
                  parent_id=warm.span_id, **{"pass": i})
    for stage, t0, t1 in (("trace", 6.0, 6.25), ("lower", 6.25, 6.5)):
        tr.record("compile", t0, t1, trace_id=engine_trace,
                  program="prefill_512", stage=stage)
    tr.sink.close()
    mod = _load_script("telemetry_report")
    records, n_bad = mod.load_records(path)
    assert n_bad == 0
    agg = mod.aggregate(records)
    spans = agg["spans"]
    assert spans["n_requests"] == 3
    assert spans["span_counts"]["request"] == 3
    assert spans["queue"]["frac_p50"] == pytest.approx(0.2, abs=1e-6)
    assert spans["queue"]["frac_p95"] == pytest.approx(0.6, abs=1e-6)
    assert spans["decode"]["ms_p95"] == pytest.approx(600.0)
    assert "attribution" not in agg
    assert spans["setup_ms"] == {
        "setup_weights": 1500.0, "setup_cache": 250.0,
        "setup_warmup": 3000.0, "warmup_pass": 3000.0, "compile": 500.0}
    assert spans["span_counts"]["warmup_pass"] == 2
    rendered = mod.render(agg)
    assert "spans" in rendered and "setup_ms" in rendered
    assert "setup_warmup=3,000" in rendered and "decode" in rendered


def test_report_without_spans_keeps_sections_empty(tmp_path):
    path = tmp_path / "run.jsonl"
    path.write_text(json.dumps(
        {"kind": "snapshot", "step": 1,
         "metrics": {"counters": {}, "gauges": {}, "histograms": {}}})
        + "\n")
    mod = _load_script("telemetry_report")
    records, _ = mod.load_records(str(path))
    agg = mod.aggregate(records)
    assert agg["spans"] == {} and "attribution" not in agg


# --------------------------------------------------- training engine spans
def test_training_engine_spans_and_setup_phases(tmp_path, monkeypatch):
    """telemetry.spans arms the training tracer: fence step-windows,
    checkpoint save/load spans (zero extra device syncs — they stamp
    at fences the engine already pays), the spans JSONL stream, and
    the phases of set-up as counters, spans and annotations beside
    ``dstpu/train_step``."""
    import jax

    annotated = []
    real_annotation = jax.profiler.TraceAnnotation

    def recording(name, *a, **kw):
        annotated.append(name)
        return real_annotation(name, *a, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", recording)
    import deepspeed_tpu
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.utils import groups

    groups.reset()
    telemetry.reset_registry()
    cfg = GPT2Config(vocab_size=256, max_seq_len=32, num_layers=1,
                     hidden_size=32, num_heads=2)
    jsonl = str(tmp_path / "run.jsonl")
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg), config={
            "train_batch_size": 8, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 0,
            "telemetry": {"enabled": True, "jsonl_path": jsonl,
                          "sync_interval": 2, "spans": True},
        })
    assert engine.tracer is not None
    rng = np.random.RandomState(0)

    def mb():
        ids = rng.randint(0, cfg.vocab_size,
                          size=(1, 8, 17)).astype(np.int32)
        return {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]}

    for _ in range(5):
        engine.train_batch_from_stacked(mb())
    engine.save_checkpoint(str(tmp_path / "ck"))
    engine.load_checkpoint(str(tmp_path / "ck"))
    counters = telemetry.get_registry().snapshot()["counters"]
    assert counters["entry/setup_weights_ms"] > 0
    assert counters["entry/setup_first_step_ms"] > 0
    assert {"dstpu/setup_weights", "dstpu/setup_first_step",
            "dstpu/train_step"} <= set(annotated)
    assert annotated.count("dstpu/setup_first_step") == 1
    assert annotated.count("dstpu/train_step") == 5
    engine.destroy()
    recs = read_jsonl(jsonl)
    names = [r["name"] for r in recs if r["kind"] == "span"]
    assert "step_window" in names
    assert "checkpoint_save" in names and "checkpoint_load" in names
    # step windows carry step/token accounting on one train trace
    wins = [r for r in recs
            if r["kind"] == "span" and r["name"] == "step_window"]
    assert all(w["trace"] == wins[0]["trace"] for w in wins)
    # fences at steps 1/2/4 -> windows of 1 + 2 steps before the save
    assert sum(w["attrs"]["steps"] for w in wins) >= 3
    # the set-up phases reached the same JSONL, on the train trace, the
    # first step closed at the step-1 fence the engine already pays
    setup = {r["name"]: r for r in recs if r["kind"] == "span"
             and r["name"].startswith("setup_")}
    assert set(setup) == {"setup_weights", "setup_first_step"}
    assert setup["setup_first_step"]["attrs"] == {"fenced": True}
    assert all(r["trace"] == wins[0]["trace"] and r["dur_ms"] > 0
               for r in setup.values())
