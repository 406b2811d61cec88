"""SLO control plane (ISSUE 13): burn-rate engine, tenant ledger,
flight recorder, dropped-data accounting, config lint, report sections.

Host-only half of the acceptance (the serving/fabric chaos pin lives in
tests/unit/serving/test_slo_plane.py). Pinned here:

  * windowed burn-rate math over cumulative registry samples (latency
    bucket counting, availability counter ratios, gauge floors);
  * multi-window multi-burn-rate discipline: a short-window spike with
    a healthy long window never fires; both breached fires ONCE;
    recovery resolves — and the whole alert timeline is bit-identical
    across two replays of the same scripted virtual-clock sequence;
  * the alert-callback seam (ReplicaSupervisor.on_slo_alert included)
    and the flight-recorder page trigger;
  * config validation: every documented error class, via the library
    AND the scripts/check_slo_rules.py CLI;
  * tenant ledger arithmetic + metric_label sanitization shared with
    to_prometheus (arbitrary tenant strings scrape cleanly);
  * flight recorder: ring bounds/eviction accounting, tee-through
    capture, dump schema, trigger cooldown, completeness verdict wired
    to the new telemetry/spans_dropped / telemetry/events_dropped
    counters (satellite);
  * telemetry_report: slo/tenants/postmortem sections, incl. degrade
    paths — empty JSONL, torn mid-record stream, streams missing each
    section's records entirely (satellite);
  * the training engine's flight-recorder trigger on a sentinel
    anomaly.
"""

import importlib.util
import json
import os

import pytest

from deepspeed_tpu.telemetry import (DEFAULT_SLO_CONFIG, FlightRecorder,
                                     JsonlSink, MetricsRegistry, SLOConfigError,
                                     SLOEngine, TenantLedger, get_registry,
                                     metric_label, parse_slo_config,
                                     validate_slo_config)
from deepspeed_tpu.telemetry.spans import SpanTracer

pytestmark = [pytest.mark.sloplane, pytest.mark.observability,
              pytest.mark.quick]

_SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ttft_config(threshold_ms=100.0, objective=0.9, burn=2.0,
                 short_s=10.0, long_s=60.0, min_events=5,
                 severity="page"):
    return {
        "slis": [{"name": "ttft", "kind": "latency",
                  "metric": "serving/ttft_ms",
                  "threshold_ms": threshold_ms, "objective": objective}],
        "rules": [{"sli": "ttft", "short_s": short_s, "long_s": long_s,
                   "burn": burn, "min_events": min_events,
                   "severity": severity}],
    }


# ------------------------------------------------------------- burn math
def test_latency_sli_window_math():
    """bad fraction = observations above threshold inside the window;
    burn = bad_fraction / (1 - objective)."""
    reg = MetricsRegistry()
    slo = SLOEngine(_ttft_config(), registry=reg, eval_interval_s=0.0)
    h = reg.histogram("serving/ttft_ms")
    for _ in range(90):
        h.observe(10.0)
    for _ in range(10):
        h.observe(500.0)        # 10% bad
    slo.evaluate(0.0)
    st = slo.slis["ttft"]
    bad, total = slo._window(st, 0.0, 60.0)
    assert total == 100
    assert bad == pytest.approx(0.10)
    # budget = 0.1 -> burn exactly 1.0 over the lifetime window
    assert slo.budget_consumed("ttft") == pytest.approx(1.0)
    # windowing: 100 more GOOD events later -> trailing-window bad
    # fraction halves while the lifetime consumption stays put
    for _ in range(100):
        h.observe(10.0)
    slo.evaluate(30.0)
    bad30, total30 = slo._window(slo.slis["ttft"], 30.0, 25.0)
    assert total30 == 100 and bad30 == pytest.approx(0.0)


def test_multiwindow_rule_needs_both_windows_and_resolves():
    """A short-window spike with a healthy long window stays silent;
    short AND long breached fires once; recovery resolves."""
    cfg = _ttft_config(burn=2.0, short_s=10.0, long_s=40.0, min_events=4)
    reg = MetricsRegistry()
    slo = SLOEngine(cfg, registry=reg, eval_interval_s=0.0)
    h = reg.histogram("serving/ttft_ms")
    # long healthy history
    for t in range(40):
        h.observe(1.0)
        slo.evaluate(float(t))
    # short spike: 6 bad events in the last 10s, but the 40s window
    # has 40 good + 6 bad = 13% bad -> burn 1.3 < 2.0 -> silent
    for _ in range(6):
        h.observe(900.0)
    assert slo.evaluate(41.0) == []
    assert slo.firing() == []
    # sustained badness: the long window breaches too -> exactly one
    # "fired" transition, held (no re-fire) while it stays bad
    for t in range(42, 90):
        h.observe(900.0)
        slo.evaluate(float(t))
    fired = [a for a in slo.alerts if a.kind == "fired"]
    assert len(fired) == 1
    assert fired[0].severity == "page"
    assert slo.firing() == [fired[0].rule]
    # recovery: enough good traffic drains both windows -> resolved
    for t in range(90, 200):
        for _ in range(5):
            h.observe(1.0)
        slo.evaluate(float(t))
    assert slo.firing() == []
    kinds = [a.kind for a in slo.alerts]
    assert kinds == ["fired", "resolved"]


def test_min_events_gates_early_pages():
    """A near-empty service cannot page off its first bad request."""
    cfg = _ttft_config(burn=2.0, min_events=50)
    reg = MetricsRegistry()
    slo = SLOEngine(cfg, registry=reg, eval_interval_s=0.0)
    h = reg.histogram("serving/ttft_ms")
    for _ in range(10):
        h.observe(900.0)        # 100% bad, but only 10 events
    slo.evaluate(1.0)
    assert slo.firing() == []


def test_availability_sli_with_bad_counter_list():
    cfg = {
        "slis": [{"name": "avail", "kind": "availability",
                  "good": "fabric/completed_requests",
                  "bad": ["fabric/failed_requests",
                          "fabric/rejected_requests"],
                  "objective": 0.9}],
        "rules": [{"sli": "avail", "short_s": 5.0, "long_s": 20.0,
                   "burn": 2.0, "min_events": 5}],
    }
    reg = MetricsRegistry()
    slo = SLOEngine(cfg, registry=reg, eval_interval_s=0.0)
    reg.counter("fabric/completed_requests").inc(60)
    reg.counter("fabric/failed_requests").inc(30)
    reg.counter("fabric/rejected_requests").inc(10)
    slo.evaluate(0.0)
    bad, total = slo._window(slo.slis["avail"], 0.0, 20.0)
    assert total == 100 and bad == pytest.approx(0.4)
    assert slo.firing() == ["avail:page:2x"]   # burn 4 >= 2 both windows


def test_gauge_floor_sli_samples_per_evaluation():
    cfg = {
        "slis": [{"name": "mfu", "kind": "gauge_floor",
                  "metric": "train/mfu", "floor": 0.4,
                  "objective": 0.5}],
        "rules": [{"sli": "mfu", "short_s": 4.0, "long_s": 16.0,
                   "burn": 1.5, "min_events": 4}],
    }
    reg = MetricsRegistry()
    slo = SLOEngine(cfg, registry=reg, eval_interval_s=0.0)
    g = reg.gauge("train/mfu")
    for t in range(8):
        g.set(0.45)             # above floor: good samples
        slo.evaluate(float(t))
    assert slo.firing() == []
    for t in range(8, 40):
        g.set(0.1)              # sustained floor breach
        slo.evaluate(float(t))
    assert slo.firing() == ["mfu:page:1.5x"]


def test_alert_timeline_deterministic_replay():
    """The acceptance's determinism half: the same scripted sequence
    yields a bit-identical (rule, kind, t) alert timeline."""
    def run_once():
        reg = MetricsRegistry()
        slo = SLOEngine(_ttft_config(burn=1.5, short_s=5.0, long_s=20.0,
                                     min_events=3),
                        registry=reg, eval_interval_s=0.0)
        h = reg.histogram("serving/ttft_ms")
        for t in range(60):
            h.observe(1.0 if (t < 20 or t > 45) else 900.0)
            slo.evaluate(t * 0.5)
        return [(a.rule, a.kind, a.t) for a in slo.alerts]

    t1, t2 = run_once(), run_once()
    assert t1 == t2
    assert [k for _, k, _ in t1] == ["fired", "resolved"]


def test_callback_seam_and_supervisor_subscription():
    from deepspeed_tpu.serving.fabric.supervisor import ReplicaSupervisor

    reg = MetricsRegistry()
    slo = SLOEngine(_ttft_config(burn=1.0, min_events=1),
                    registry=reg, eval_interval_s=0.0)
    sup = ReplicaSupervisor()
    slo.set_alert_callback(sup.on_slo_alert)
    h = reg.histogram("serving/ttft_ms")
    for _ in range(10):
        h.observe(900.0)
    slo.evaluate(100.0)
    assert len(sup.slo_alerts) == 1
    assert sup.slo_alerts[0].kind == "fired"
    assert sup.slo_alerts[0].sli == "ttft"
    # a broken subscriber must not take down evaluation
    slo.set_alert_callback(lambda a: 1 / 0)
    for _ in range(200):
        h.observe(1.0)
    for t in range(101, 160):
        slo.evaluate(float(t))       # resolves through the raising cb
    assert slo.firing() == []
    # alert events reached the registry
    snap = reg.snapshot()["counters"]
    assert snap["slo/alert_fired"] == 1
    assert snap["slo/alert_resolved"] == 1


# ------------------------------------------------------------ validation
def test_validate_config_error_classes():
    errors = validate_slo_config({
        "slis": [
            {"name": "a", "kind": "latency", "metric": "m",
             "threshold_ms": 10, "objective": 0.99},
            {"name": "a", "kind": "nope", "objective": 2.0},
            {"kind": "latency"},
            {"name": "g", "kind": "gauge_floor", "objective": 0.5},
            {"name": "av", "kind": "availability", "objective": 0.5},
            {"name": "ok", "kind": "latency", "metric": "m2",
             "threshold_ms": 10, "objective": 0.99},
        ],
        "rules": [
            {"sli": "zzz", "short_s": 5, "long_s": 10, "burn": 1},
            {"sli": "ok", "short_s": 60, "long_s": 60, "burn": 1},
            {"sli": "ok", "short_s": 5, "long_s": 60, "burn": 500},
            {"sli": "ok", "short_s": -1, "long_s": 60, "burn": 0,
             "severity": "sms", "min_events": -3},
        ],
    })
    text = "\n".join(errors)
    assert "duplicate SLI name 'a'" in text
    assert "unknown kind 'nope'" in text
    assert "objective must be in (0, 1)" in text
    assert "missing 'name'" in text
    assert "needs a numeric 'floor'" in text
    assert "needs 'good'" in text
    assert "unknown SLI name 'zzz'" in text
    assert "strictly inside the long window" in text
    assert "can never fire" in text
    assert "unknown severity 'sms'" in text
    assert "short_s must be a positive number" in text
    assert "burn must be a positive number" in text
    assert "min_events must be a non-negative int" in text
    with pytest.raises(SLOConfigError) as ei:
        parse_slo_config({"slis": [], "rules": [{"sli": "x"}]})
    assert "unknown SLI name" in str(ei.value)
    # the shipped default must be valid and parse
    assert validate_slo_config(DEFAULT_SLO_CONFIG) == []
    slis, rules = parse_slo_config(DEFAULT_SLO_CONFIG)
    assert {r.sli for r in rules} <= {s.name for s in slis}


def test_check_slo_rules_cli(tmp_path, capsys):
    mod = _load_script("check_slo_rules")
    assert mod.main([]) == 0             # built-in default validates
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({
        "slis": [{"name": "x", "kind": "latency", "metric": "m",
                  "threshold_ms": 1, "objective": 0.999}],
        "rules": [{"sli": "x", "short_s": 60, "long_s": 5,
                   "burn": 5000}]}))
    assert mod.main([str(bad)]) == 1
    err = capsys.readouterr().err
    assert "can never fire" in err and "strictly inside" in err
    assert mod.main([str(tmp_path / "missing.json")]) == 2


# ----------------------------------------------------- tenants + labels
def test_tenant_label_sanitization_shared_with_prometheus():
    assert metric_label("acme") == "acme"
    assert metric_label(3) == "3"
    assert metric_label("a/b c|d`e") == "a_b_c_d_e"
    assert metric_label("") == "_"
    assert len(metric_label("x" * 500)) == 64
    reg = MetricsRegistry()
    led = TenantLedger(reg)
    t = led.resolve('evil/tenant with "quotes" and\nnewlines')
    led.note_admitted(t, 7)
    led.note_ttft(t, 12.0)
    text = reg.to_prometheus()
    # every emitted line's metric name is a valid Prometheus name
    import re
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name = line.split("{")[0].split(" ")[0]
        assert re.fullmatch(r"[a-zA-Z_:][a-zA-Z0-9_:]*", name), line
    assert "evil_tenant" in text


def test_tenant_ledger_totals_roundtrip():
    led = TenantLedger(None)         # registry-less mode
    a = led.resolve("a")
    led.note_admitted(a, 10)
    led.note_prefill(a, 8, saved=2)
    led.note_tokens(a, 5)
    led.note_kv_occupancy(a, 4, 0.25, 100.0)
    led.note_preemption(a)
    led.note_shed(a)
    led.note_ttft(a, 5.0)
    led.note_tpot(a, 2.0)
    tot = led.totals()["a"]
    assert tot["prompt_tokens"] == 10 and tot["decode_tokens"] == 5
    assert tot["prefill_tokens_computed"] == 8
    assert tot["prefill_tokens_saved"] == 2
    assert tot["kv_block_seconds"] == pytest.approx(1.0)
    assert tot["kv_byte_seconds"] == pytest.approx(100.0)
    assert tot["preemptions"] == 1 and tot["sheds"] == 1
    assert tot["ttft_ms_p50"] is not None


# ------------------------------------------------------ flight recorder
def test_flight_recorder_rings_tee_and_dump(tmp_path):
    reg = MetricsRegistry()
    rec = FlightRecorder(dump_dir=str(tmp_path), max_spans=4,
                         max_events=3, registry=reg)
    sink = JsonlSink(str(tmp_path / "t.jsonl"))
    tee = rec.tee(sink)
    reg.attach_sink(tee)
    for i in range(10):
        tee.write({"kind": "span", "i": i})
    reg.event("serving/finished_requests", rid=1)
    rec.note_alert({"kind": "slo_eval", "t": 1.0,
                    "rules": {"r:page:2x": {"firing": True}},
                    "budget_consumed": {"ttft": 0.5}})
    # bounded ring kept the newest 4 spans; evictions counted
    assert [s["i"] for s in rec.spans] == [6, 7, 8, 9]
    assert rec.ring_evicted["spans"] == 6
    payload = rec.trigger("unit_incident", replica="r1")
    assert payload["path"] and os.path.exists(payload["path"])
    assert "flight_000_unit_incident" in payload["path"]
    with open(payload["path"]) as f:
        loaded = json.load(f)
    assert loaded["kind"] == "flight_dump"
    assert loaded["reason"] == "unit_incident"
    assert loaded["context"] == {"replica": "r1"}
    assert len(loaded["spans"]) == 4
    assert any(e.get("name") == "serving/finished_requests"
               for e in loaded["events"])
    assert loaded["alerts"][-1]["budget_consumed"] == {"ttft": 0.5}
    assert loaded["complete"] is True        # nothing dropped upstream
    assert loaded["metrics"]["counters"]["serving/finished_requests"] == 1
    # the tee forwarded everything to the real sink too
    sink.close()
    from deepspeed_tpu.telemetry import read_jsonl

    recs = read_jsonl(str(tmp_path / "t.jsonl"))
    assert sum(r.get("kind") == "span" for r in recs) == 10
    # trigger fired the telemetry event
    assert reg.snapshot()["counters"]["telemetry/flight_dump"] == 1


def test_flight_recorder_trigger_cooldown(tmp_path):
    rec = FlightRecorder(dump_dir=str(tmp_path), registry=MetricsRegistry(),
                         trigger_cooldown=5)
    rec.observe({"kind": "event"})
    assert rec.trigger("crash") is not None
    assert rec.trigger("crash") is None          # cooldown-suppressed
    for _ in range(5):
        rec.observe({"kind": "event"})
    assert rec.trigger("crash") is not None      # window elapsed
    assert rec.trigger("other_reason") is not None   # per-reason gates


def test_slo_page_alert_triggers_flight_dump(tmp_path):
    reg = MetricsRegistry()
    rec = FlightRecorder(dump_dir=str(tmp_path), registry=reg)
    slo = SLOEngine(_ttft_config(burn=1.0, min_events=1), registry=reg,
                    eval_interval_s=0.0, flight_recorder=rec)
    h = reg.histogram("serving/ttft_ms")
    for _ in range(10):
        h.observe(900.0)
    slo.evaluate(50.0)
    assert [d["reason"] for d in rec.dumps] == ["slo_page"]
    # every evaluation landed in the alert ring
    assert any(r.get("kind") == "slo_eval" for r in rec.alerts)


# ------------------------------------------------- dropped-data satellite
def test_span_tracer_drop_counter_and_warn_once():
    base = get_registry().counter("telemetry/spans_dropped").value
    tracer = SpanTracer(max_spans=2)
    for i in range(5):
        tracer.record(f"s{i}", 0.0, 1.0)
    assert tracer.dropped == 3
    assert get_registry().counter("telemetry/spans_dropped").value \
        == base + 3
    assert tracer._drop_warned is True


def test_jsonl_sink_counts_dropped_records(tmp_path):
    base = get_registry().counter("telemetry/events_dropped").value
    # armed BEFORE the drops: the completeness verdict is a DELTA over
    # the recorder's own observation window, so drops from earlier
    # unrelated runs can never taint a fresh recorder's dumps
    rec = FlightRecorder(registry=get_registry())

    class Unserializable:
        def __str__(self):
            raise RuntimeError("no str for you")

    sink = JsonlSink(str(tmp_path / "t.jsonl"), flush_every=1)
    sink.write({"kind": "event", "payload": Unserializable()})
    assert sink.records_dropped == 1
    # drain failure (file handle to a directory) drops the whole buffer
    sink2 = JsonlSink(str(tmp_path / "d.jsonl"), flush_every=100)
    os.mkdir(sink2.path)        # path now a directory: open("a") fails
    sink2.write({"kind": "event"})
    sink2.write({"kind": "event"})
    sink2.flush()
    assert sink2.records_dropped == 2
    assert get_registry().counter("telemetry/events_dropped").value \
        == base + 3
    # a dump over a window containing the drops says so
    payload = rec.trigger("completeness_probe")
    assert payload["complete"] is False
    assert payload["upstream_dropped"]["events"] >= 3
    # while a recorder armed AFTER them reports its own window complete
    late = FlightRecorder(registry=get_registry())
    assert late.trigger("late_probe")["complete"] is True


# ----------------------------------------------------- report sections
def _synthetic_snapshot():
    return {
        "kind": "snapshot", "step": 3, "metrics": {
            "counters": {
                "serving/finished_requests": 9,
                "serving/tenant/acme/prompt_tokens": 40,
                "serving/tenant/acme/decode_tokens": 18,
                "serving/tenant/acme/prefill_tokens_computed": 30,
                "serving/tenant/acme/prefill_tokens_saved": 10,
                "serving/tenant/acme/sheds": 1,
                "serving/tenant/beta/prompt_tokens": 12,
                "serving/tenant/beta/decode_tokens": 6,
            },
            "gauges": {},
            "histograms": {
                "serving/tenant/acme/ttft_ms": {
                    "count": 4, "p50": 8.0, "p95": 9.0, "p99": 9.5},
            },
        },
    }


def test_report_slo_tenants_postmortem_sections(tmp_path):
    mod = _load_script("telemetry_report")
    path = tmp_path / "run.jsonl"
    records = [
        _synthetic_snapshot(),
        {"kind": "slo_eval", "t": 1.0,
         "rules": {"ttft:page:2x": {"burn_short": 0.5, "burn_long": 0.2,
                                    "firing": False}},
         "budget_consumed": {"ttft": 0.1}},
        {"kind": "slo_eval", "t": 2.0,
         "rules": {"ttft:page:2x": {"burn_short": 9.0, "burn_long": 4.0,
                                    "firing": True}},
         "budget_consumed": {"ttft": 0.7}},
        {"kind": "event", "name": "slo/alert_fired", "rule": "ttft:page:2x",
         "severity": "page"},
    ]
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    recs, n_bad = mod.load_records(str(path))
    agg = mod.aggregate(recs, n_bad_lines=n_bad)
    slo = agg["slo"]
    assert slo["alerts_fired"] == 1
    assert slo["slo_evaluations"] == 2
    assert slo["budget_consumed/ttft"] == 0.7
    assert slo["rule/ttft:page:2x"]["max_burn_short"] == 9.0
    assert slo["rule/ttft:page:2x"]["evals_firing"] == 1
    tenants = agg["tenants"]
    assert tenants["acme"]["decode_tokens"] == 18
    assert tenants["acme"]["prefill_tokens_saved"] == 10
    assert tenants["acme"]["ttft_ms_p50"] == 8.0
    assert tenants["beta"]["decode_tokens"] == 6
    text = mod.render(agg)
    assert "tenants" in text and "acme" in text

    # postmortem: a flight dump rendered standalone AND as a section
    reg = MetricsRegistry()
    reg.counter("serving/tenant/acme/decode_tokens").inc(5)
    rec = FlightRecorder(dump_dir=str(tmp_path), registry=reg)
    rec.observe({"kind": "span", "name": "request", "trace": "t0",
                 "start": 0.0, "end": 1.0, "attrs": {"rid": 7}})
    rec.observe({"kind": "event", "name": "fabric/replica_crashes"})
    rec.note_alert({"kind": "slo_eval", "t": 1.0,
                    "rules": {"ttft:page:2x": {"firing": True}},
                    "budget_consumed": {"ttft": 0.9}})
    payload = rec.trigger("replica_crash", replica="r1")
    dump_path = payload["path"]
    dump = mod.load_flight_dump(dump_path)
    assert dump is not None
    agg2 = mod.aggregate(recs, postmortem=dump)
    pm = agg2["postmortem"]
    assert pm["trigger"] == "replica_crash"
    assert pm["context/replica"] == "r1"
    assert pm["request_ids"] == [7]
    assert pm["tenants"] == ["acme"]
    assert pm["rules_fired_in_window"] == ["ttft:page:2x"]
    assert pm["budget_consumed/ttft"] == 0.9
    assert pm["complete"] in (True, False)
    assert "postmortem" in mod.render(agg2)
    # CLI: dump passed as the positional path renders its own window
    assert mod.main([dump_path, "--json"]) == 0
    # a non-dump --postmortem argument is a typed failure
    assert mod.main([str(path), "--postmortem", str(path)]) == 2


def test_report_degrade_paths(tmp_path):
    """Every section (incl. slo/tenants/postmortem) renders without
    raising on: an empty JSONL, a partially-written stream (torn final
    record, mid-multibyte truncation), and streams missing that
    section's records entirely."""
    mod = _load_script("telemetry_report")
    sections = ("counters", "gauges", "histograms", "scalars", "events",
                "speculation", "prefix_cache", "slo", "tenants", "fabric",
                "resilience", "spans", "postmortem")

    def check(path):
        recs, n_bad = mod.load_records(str(path))
        agg = mod.aggregate(recs, n_bad_lines=n_bad)
        for s in sections:
            assert s in agg
        text = mod.render(agg)
        assert "telemetry report" in text
        return agg

    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    agg = check(empty)
    assert agg["n_records"] == 0

    torn = tmp_path / "torn.jsonl"
    good = json.dumps(_synthetic_snapshot())
    with open(torn, "wb") as f:
        f.write(good.encode() + b"\n")
        f.write(json.dumps({"kind": "slo_eval", "t": 1.0}).encode()
                + b"\n")
        # torn mid-record, cut inside a multi-byte UTF-8 sequence
        f.write('{"kind": "event", "name": "xé'.encode()[:-1])
    agg = check(torn)
    assert agg["n_bad_lines"] == 1
    assert agg["tenants"]          # the good snapshot still renders

    # streams missing each section's records entirely: single-kind files
    for name, rec in (
            ("only_scalar", {"kind": "scalar", "tag": "t", "value": 1.0,
                             "step": 1}),
            ("only_span", {"kind": "span", "name": "request",
                           "trace": "t0", "start": 0.0, "end": 1.0}),
            ("only_event", {"kind": "event", "name": "e"}),
            ("only_slo_eval", {"kind": "slo_eval", "t": 0.0}),
            ("only_snapshot_no_tenants",
             {"kind": "snapshot", "metrics": {"counters": {"x": 1}}})):
        p = tmp_path / f"{name}.jsonl"
        p.write_text(json.dumps(rec) + "\n")
        agg = check(p)
        assert agg["postmortem"] == {}       # no dump given
    # malformed dump payloads degrade to empty sections, never raise
    assert mod._postmortem_summary(None) == {}
    assert mod._postmortem_summary({"kind": "other"}) == {}
    bad_dump = tmp_path / "bad_dump.json"
    bad_dump.write_text("{not json")
    assert mod.load_flight_dump(str(bad_dump)) is None


# ------------------------------------------- training-engine integration
def test_training_anomaly_triggers_flight_dump(tmp_path):
    """The training sentinel's incident path freezes the recorder: a
    non-recoverable anomaly dumps the pre-incident window before the
    typed raise reaches the caller."""
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.runtime.sentinel import TrainingAnomalyError
    from deepspeed_tpu.telemetry import reset_registry
    from deepspeed_tpu.utils import groups

    from deepspeed_tpu.telemetry import get_registry as _get_reg

    groups.reset()
    reset_registry()
    cfg = GPT2Config(vocab_size=128, max_seq_len=32, num_layers=1,
                     hidden_size=32, num_heads=2)
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg, attn_impl="dense"), config={
            "train_batch_size": 8,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 0,
            "telemetry": {"enabled": True, "flight_recorder": True,
                          "flight_dir": str(tmp_path),
                          "jsonl_path": str(tmp_path / "train.jsonl")},
            "resilience": {"enabled": True, "check_interval": 1,
                           "on_anomaly": "raise"},
        })
    assert engine.flight_recorder is not None
    rng = np.random.RandomState(0)

    def batch():
        ids = rng.randint(0, cfg.vocab_size, size=(1, 8, 33)).astype(
            np.int32)
        return {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]}

    try:
        loss = engine.train_batch_from_stacked(batch())
        # events/snapshots reached the recorder through the sink tee
        assert engine.flight_recorder.observed >= 0
        from deepspeed_tpu.runtime.sentinel import TrainingAnomaly

        with pytest.raises(TrainingAnomalyError):
            engine._recover_or_raise(TrainingAnomaly(
                "nonfinite", engine.global_steps, float("nan"), 0.0,
                "synthetic"))
        assert [d["reason"] for d in engine.flight_recorder.dumps] \
            == ["training_anomaly"]
        dumps = list(tmp_path.glob("flight_*_training_anomaly.json"))
        assert len(dumps) == 1
        payload = json.loads(dumps[0].read_text())
        assert payload["context"]["cls"] == "nonfinite"
        # set_slo without a sentinel fails loudly
        engine.sentinel = None
        with pytest.raises(ValueError):
            engine.set_slo(object())
        del loss
    finally:
        # this engine attached its sink (under the recorder tee) to the
        # GLOBAL registry; later engine tests expect sink-less state
        _get_reg().attach_sink(None)
