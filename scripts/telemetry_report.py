#!/usr/bin/env python
"""Render a telemetry JSONL run into a human summary.

Usage:
    python scripts/telemetry_report.py RUN.jsonl [--json]

Input is the file produced by the telemetry subsystem (ISSUE 3): the
engine's periodic registry snapshots (``telemetry.jsonl_path`` config key),
the JSONL monitor backend's scalar stream (``jsonl_monitor`` section), and
discrete events (checkpoint saves, corruption fallbacks, elastic
restarts) — any mix of the three record kinds in one file.

Sections:
  counters    — final values from the newest snapshot
  gauges      — final values (device step time, MFU, memory, occupancy...)
  histograms  — count/mean/p50/p95/p99/max per latency histogram
  scalars     — per-tag last/min/max/mean over the monitor scalar stream
  events      — occurrence counts per event name
  spans       — span-graph critical paths (ISSUE 11): per-request
                p50/p95 time + fraction in queue/prefill/decode/
                swapped/failover, reconstructed from "span" records;
                ``setup_ms``: the phases of set-up (setup_weights,
                setup_cache, setup_warmup and its warmup_pass spans,
                setup_first_step) and the compile spans of a recompile
                in service, total milliseconds a name (ISSUE 42)
  slo         — SLO scheduling view (ISSUE 8) merged with the SLO
                control plane (ISSUE 13): error-budget consumption per
                SLI, burn-rate timeline stats per rule, and the
                fired/resolved alert sequence from "slo_eval" +
                slo/alert_* event records
  tenants     — per-tenant usage table (ISSUE 13): prompt/decode
                tokens, prefill computed vs saved, KV block-seconds,
                preemptions/sheds, TTFT/TPOT p50 from the
                serving/tenant/<t>/* metrics
  postmortem  — incident summary from a flight-recorder dump
                (``--postmortem DUMP.json``, or pass the dump file as
                the positional path): trigger, affected requests and
                tenants, alert state at the dump instant, record-
                completeness verdict

``--json`` emits the aggregate as one JSON object instead of tables
(machine-readable; the smoke test uses it). Stdlib only — runs anywhere.
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import OrderedDict


def load_records(path):
    """Tolerant JSONL reader, matching telemetry.sink.read_jsonl
    (ISSUE 9 satellite): lines torn by a crash mid-write — truncated
    JSON, bytes cut inside a UTF-8 sequence, non-object values — are
    skipped and COUNTED, never raised. The report renders the artifact
    that survives a crash, so it must not fail on crash damage.
    Returns ``(records, n_bad_lines)``."""
    out = []
    bad = 0
    with open(path, "rb") as f:
        for raw in f:
            line = raw.decode("utf-8", errors="replace").strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                bad += 1
                continue
            if isinstance(rec, dict):
                out.append(rec)
            else:
                bad += 1
    return out, bad


def aggregate(records, n_bad_lines=0, postmortem=None):
    last_snapshot = None
    scalars = OrderedDict()   # tag -> stats dict
    events = OrderedDict()    # name -> {count, last_fields}
    spans = []                # raw span records, arrival order
    slo_evals = []            # SLO-engine burn-rate timeline (ISSUE 13)
    elastic_events = []       # autoscaler + pool-membership events (ISSUE 16)
    for rec in records:
        kind = rec.get("kind")
        if kind == "snapshot":
            last_snapshot = rec
        elif kind == "span":
            spans.append(rec)
        elif kind == "slo_eval":
            slo_evals.append(rec)
        elif kind == "scalar":
            tag = rec.get("tag", "?")
            try:
                v = float(rec.get("value"))
            except (TypeError, ValueError):
                continue
            s = scalars.setdefault(tag, {
                "count": 0, "sum": 0.0, "min": v, "max": v,
                "last": v, "last_step": rec.get("step")})
            s["count"] += 1
            s["sum"] += v
            s["min"] = min(s["min"], v)
            s["max"] = max(s["max"], v)
            s["last"] = v
            s["last_step"] = rec.get("step")
        elif kind == "event":
            name = rec.get("name", "?")
            e = events.setdefault(name, {"count": 0, "last": {}})
            e["count"] += 1
            e["last"] = {k: v for k, v in rec.items()
                         if k not in ("kind", "name", "ts")}
            if name in ("fabric/autoscale", "fabric/replica_added",
                        "fabric/replica_draining",
                        "fabric/replica_removed"):
                elastic_events.append(rec)
    for s in scalars.values():
        s["mean"] = s["sum"] / s["count"] if s["count"] else 0.0
    metrics = (last_snapshot or {}).get("metrics", {})
    return {
        "snapshot_step": (last_snapshot or {}).get("step"),
        "counters": metrics.get("counters", {}),
        "gauges": metrics.get("gauges", {}),
        "histograms": metrics.get("histograms", {}),
        "scalars": scalars,
        "events": events,
        "speculation": _speculation_summary(metrics),
        "prefix_cache": _prefix_cache_summary(metrics),
        "slo": _slo_summary(metrics, slo_evals, events),
        "tenants": _tenants_summary(metrics),
        "fabric": _fabric_summary(metrics),
        "autoscaler": _autoscaler_summary(metrics, elastic_events),
        "resilience": _resilience_summary(metrics),
        "spans": _spans_summary(spans),
        "postmortem": _postmortem_summary(postmortem),
        "n_records": len(records),
        "n_bad_lines": n_bad_lines,
    }


def _spans_summary(spans):
    """Derived span-graph view (ISSUE 11): per-request critical-path
    breakdown — p50/p95 of absolute time and of the FRACTION of each
    request's life spent in queue/prefill/decode/swapped/failover —
    plus per-span-name counts. Stdlib reimplementation of
    telemetry.spans.trace_summaries/aggregate_phase_stats so the report
    stays runnable anywhere. Empty dict when the run recorded no
    spans."""
    if not spans:
        return {}
    phase_of = {"queue_wait": "queue", "router_queue": "queue",
                "prefill_chunk": "prefill", "decode_segment": "decode",
                "swap_out": "swapped", "swapped": "swapped",
                "swap_in": "swapped", "failover": "failover"}
    phases = ("queue", "prefill", "decode", "swapped", "failover")
    by_name = OrderedDict()
    by_trace = OrderedDict()
    setup_ms = OrderedDict()     # set-up phase or compile -> total ms
    for s in spans:
        name = s.get("name", "?")
        by_name[name] = by_name.get(name, 0) + 1
        by_trace.setdefault(s.get("trace"), []).append(s)
        if (name.startswith("setup_") or name in ("warmup_pass", "compile")) \
                and s.get("end") is not None:
            setup_ms[name] = round(setup_ms.get(name, 0.0) + max(
                s["end"] - s.get("start", 0.0), 0.0) * 1e3, 3)
    requests = []
    for group in by_trace.values():
        roots = [s for s in group if s.get("name") == "request"
                 and s.get("end") is not None]
        if not roots:
            continue
        root = roots[0]
        total = max(root["end"] - root.get("start", 0.0), 0.0)
        ph = {p: 0.0 for p in phases}
        for s in group:
            p = phase_of.get(s.get("name"))
            if p is None or s.get("end") is None:
                continue
            ph[p] += max(s["end"] - s.get("start", 0.0), 0.0)
        requests.append((total, ph))
    out = {"n_spans": len(spans), "span_counts": dict(by_name),
           "n_requests": len(requests)}
    if setup_ms:
        out["setup_ms"] = dict(setup_ms)
    if not requests:
        return out

    def pct(xs, p):
        xs = sorted(xs)
        return xs[min(int(len(xs) * p), len(xs) - 1)]

    totals = [t for t, _ in requests]
    out["total_ms"] = {"p50": round(pct(totals, 0.5) * 1e3, 3),
                       "p95": round(pct(totals, 0.95) * 1e3, 3)}
    for p in phases:
        ab = [ph[p] for _, ph in requests]
        if not any(ab):
            continue
        fr = [(ph[p] / t if t > 0 else 0.0) for t, ph in requests]
        out[p] = {"frac_p50": round(pct(fr, 0.5), 4),
                  "frac_p95": round(pct(fr, 0.95), 4),
                  "ms_p50": round(pct(ab, 0.5) * 1e3, 3),
                  "ms_p95": round(pct(ab, 0.95) * 1e3, 3)}
    return out


def _speculation_summary(metrics):
    """Derived speculative-decoding view (ISSUE 4) over the serving
    engine's raw counters/gauges/histograms: acceptance rate, committed
    tokens per verify step, and drafting's share of the decode wall.
    Empty dict when the run never speculated."""
    counters = metrics.get("counters", {})
    drafted = counters.get("serving/spec_drafted_tokens")
    if not drafted:
        return {}
    accepted = counters.get("serving/spec_accepted_tokens", 0)
    out = {
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "acceptance_rate": round(accepted / drafted, 4),
        "verify_steps": counters.get("serving/spec_verify_steps"),
    }
    gauges = metrics.get("gauges", {})
    for key, name in (("serving/spec_tokens_per_slot_step",
                       "tokens_per_slot_step"),
                      ("serving/spec_draft_overhead_frac",
                       "draft_overhead_frac"),
                      ("serving/spec_acceptance_rate",
                       "acceptance_rate_gauge")):
        if gauges.get(key) is not None:
            out[name] = gauges[key]
    h = metrics.get("histograms", {}).get(
        "serving/accepted_tokens_per_step")
    if h and h.get("count"):
        out["accepted_tokens_per_step_p50"] = h.get("p50")
        out["accepted_tokens_per_step_max"] = h.get("max")
    return out


def _prefix_cache_summary(metrics):
    """Derived prefix-cache view (ISSUE 6) over the serving engine's raw
    counters/gauges: tokens served from the radix index vs prefilled,
    the resulting hit rate, COW fork / LRU eviction counts, and pool
    occupancy. Empty dict when the run never enabled the cache."""
    counters = metrics.get("counters", {})
    gauges = metrics.get("gauges", {})
    hit = counters.get("serving/prefix_hit_tokens")
    miss = counters.get("serving/prefix_miss_tokens")
    if hit is None and miss is None \
            and gauges.get("serving/prefix_hit_rate") is None:
        return {}
    hit, miss = hit or 0, miss or 0
    out = {
        "hit_tokens": hit,
        "miss_tokens": miss,
        "hit_rate": round(hit / (hit + miss), 4) if hit + miss else 0.0,
        "blocks_cowed": counters.get("serving/blocks_cowed", 0),
        "blocks_evicted": counters.get("serving/blocks_evicted", 0),
    }
    for key, name in (("serving/prefix_hit_rate", "hit_rate_gauge"),
                      ("serving/prefix_pool_occupancy", "pool_occupancy"),
                      ("serving/prefix_cached_blocks", "cached_blocks")):
        if gauges.get(key) is not None:
            out[name] = gauges[key]
    return out


def _slo_summary(metrics, slo_evals=None, events=None):
    """Derived SLO view: the ISSUE-8 scheduling actions (chunked
    prefill, TPOT-guard deferrals, preemptions, host swap traffic,
    per-class latency tails) merged with the ISSUE-13 control plane —
    error-budget consumption per SLI, per-rule burn-rate timeline
    stats over the "slo_eval" records, and the alert transition
    sequence. Empty dict when the run used neither."""
    base = _slo_sched_summary(metrics)
    plane = _slo_plane_summary(slo_evals or [], events or {})
    base.update(plane)
    return base


def _slo_plane_summary(slo_evals, events):
    """SLO-engine fields (ISSUE 13). Empty dict when the run recorded
    no slo_eval records and no alert events."""
    out = {}
    fired = events.get("slo/alert_fired", {}).get("count", 0)
    resolved = events.get("slo/alert_resolved", {}).get("count", 0)
    if not slo_evals and not fired and not resolved:
        return out
    if fired or resolved:
        out["alerts_fired"] = fired
        out["alerts_resolved"] = resolved
    if not slo_evals:
        return out
    out["slo_evaluations"] = len(slo_evals)
    last = slo_evals[-1]
    for sli, consumed in sorted(
            (last.get("budget_consumed") or {}).items()):
        out[f"budget_consumed/{sli}"] = consumed
    # per-rule burn timeline: max observed burn + evaluations spent
    # firing — the compressed "when and how hard did it burn" view
    rules = {}
    for rec in slo_evals:
        for rule, st in (rec.get("rules") or {}).items():
            if not isinstance(st, dict):
                continue
            r = rules.setdefault(rule, {"max_burn_short": 0.0,
                                        "max_burn_long": 0.0,
                                        "evals_firing": 0})
            try:
                r["max_burn_short"] = max(r["max_burn_short"],
                                          float(st.get("burn_short", 0)))
                r["max_burn_long"] = max(r["max_burn_long"],
                                         float(st.get("burn_long", 0)))
            except (TypeError, ValueError):
                pass
            if st.get("firing"):
                r["evals_firing"] += 1
    for rule, r in sorted(rules.items()):
        out[f"rule/{rule}"] = {
            "max_burn_short": round(r["max_burn_short"], 2),
            "max_burn_long": round(r["max_burn_long"], 2),
            "evals_firing": r["evals_firing"]}
    return out


def _tenants_summary(metrics):
    """Per-tenant usage table (ISSUE 13) over the
    ``serving/tenant/<t>/<metric>`` namespace in the newest snapshot.
    Empty dict when the run carried no tenant accounting."""
    out = OrderedDict()
    prefix = "serving/tenant/"
    for name, v in sorted(metrics.get("counters", {}).items()):
        if not name.startswith(prefix):
            continue
        rest = name[len(prefix):]
        tenant, _, metric = rest.rpartition("/")
        if not tenant:
            continue
        row = out.setdefault(tenant, OrderedDict())
        row[metric] = round(v, 3) if isinstance(v, float) else v
    for name, h in sorted(metrics.get("histograms", {}).items()):
        if not name.startswith(prefix) or not h.get("count"):
            continue
        rest = name[len(prefix):]
        tenant, _, metric = rest.rpartition("/")
        if not tenant:
            continue
        row = out.setdefault(tenant, OrderedDict())
        row[f"{metric}_p50"] = h.get("p50")
        row[f"{metric}_p99"] = h.get("p99")
    return out


def _postmortem_summary(dump):
    """Incident summary from a flight-recorder dump payload (ISSUE 13):
    what tripped, which requests/tenants were in the blast radius, the
    alert state at the dump instant, and whether the record itself is
    complete. Empty dict when no dump was given."""
    if not isinstance(dump, dict) or dump.get("kind") != "flight_dump":
        return {}
    out = OrderedDict()
    out["trigger"] = dump.get("reason", "?")
    ctx = dump.get("context") or {}
    for k, v in sorted(ctx.items()):
        out[f"context/{k}"] = v
    spans = [s for s in dump.get("spans", []) if isinstance(s, dict)]
    events = [e for e in dump.get("events", []) if isinstance(e, dict)]
    out["window_spans"] = len(spans)
    out["window_events"] = len(events)
    rids = sorted({a.get("rid") for s in spans
                   for a in [s.get("attrs") or {}] if a.get("rid")
                   is not None})
    if rids:
        out["requests_in_window"] = len(rids)
        out["request_ids"] = rids[:20]
    counters = (dump.get("metrics") or {}).get("counters", {})
    tenants = sorted({name.split("/")[2]
                      for name in counters
                      if name.startswith("serving/tenant/")
                      and len(name.split("/")) > 3})
    if tenants:
        out["tenants"] = tenants
    alerts = [a for a in dump.get("alerts", []) if isinstance(a, dict)]
    firing = []
    budget = {}
    for rec in alerts:
        for rule, st in (rec.get("rules") or {}).items():
            if isinstance(st, dict) and st.get("firing") \
                    and rule not in firing:
                firing.append(rule)
        budget.update(rec.get("budget_consumed") or {})
    if firing:
        out["rules_fired_in_window"] = firing
    for sli, consumed in sorted(budget.items()):
        out[f"budget_consumed/{sli}"] = consumed
    ev_names = OrderedDict()
    for e in events:
        n = e.get("name", e.get("kind", "?"))
        ev_names[n] = ev_names.get(n, 0) + 1
    if ev_names:
        out["event_counts"] = dict(ev_names)
    dropped = dump.get("upstream_dropped") or {}
    out["complete"] = bool(dump.get("complete", False))
    if dropped.get("spans") or dropped.get("events"):
        out["upstream_dropped"] = dropped
    return out


def _slo_sched_summary(metrics):
    """The ISSUE-8 half of the slo section: scheduling actions + the
    per-priority-class latency tails. Empty dict when the run never
    used the SLO scheduling machinery."""
    counters = metrics.get("counters", {})
    hists = metrics.get("histograms", {})
    per_class = {name: h for name, h in hists.items()
                 if (name.startswith("serving/ttft_ms/p")
                     or name.startswith("serving/tpot_ms/p"))
                 and h.get("count")}
    keys = ("serving/prefill_chunks", "serving/preemptions",
            "serving/slo_deferred_steps", "serving/swapped_blocks_out",
            "serving/swapped_blocks_in")
    # the engine records per-class histograms unconditionally (every
    # request has a class — p0 by default), so class histograms only
    # signal SLO usage when a NON-default class appears; otherwise a
    # plain serving run would grow a noise section
    multi_class = any(not name.endswith("/p0") for name in per_class)
    if not any(counters.get(k) for k in keys) and not multi_class:
        return {}
    out = {}
    for k in keys:
        if counters.get(k) is not None:
            out[k.split("/", 1)[1]] = counters[k]
    gauges = metrics.get("gauges", {})
    for key, name in (("serving/swap_buffer_bytes", "swap_buffer_bytes"),
                      ("serving/swap_buffer_peak_bytes",
                       "swap_buffer_peak_bytes")):
        if gauges.get(key) is not None:
            out[name] = gauges[key]
    for name, h in sorted(per_class.items()):
        out[name.split("/", 1)[1]] = {
            "count": h.get("count"), "p50": h.get("p50"),
            "p95": h.get("p95"), "p99": h.get("p99")}
    return out


def _fabric_summary(metrics):
    """Derived multi-replica fabric view (ISSUE 9) over the router's
    raw counters/gauges/histograms: dispatch/failover/retry/shed/crash
    counters, the failover-latency tail, and the per-replica health
    gauges (load, queue depth, free slots, breaker state). Empty dict
    when the run never used the fabric."""
    counters = {k: v for k, v in metrics.get("counters", {}).items()
                if k.startswith("fabric/")}
    gauges = {k: v for k, v in metrics.get("gauges", {}).items()
              if k.startswith("fabric/")}
    hists = {k: h for k, h in metrics.get("histograms", {}).items()
             if k.startswith("fabric/") and h.get("count")}
    if not counters and not gauges and not hists:
        return {}
    out = {}
    for k, v in sorted(counters.items()):
        out[k.split("/", 1)[1]] = v
    for k, v in sorted(gauges.items()):
        out[k.split("/", 1)[1]] = v
    for k, h in sorted(hists.items()):
        out[k.split("/", 1)[1]] = {
            "count": h.get("count"), "p50": h.get("p50"),
            "p95": h.get("p95"), "p99": h.get("p99")}
    return out


def _autoscaler_summary(metrics, elastic_events):
    """Derived elastic-autoscaling view (ISSUE 16) pinned from the twin
    (or live) JSONL stream: the full scale-decision timeline WITH the
    evidence that justified each decision, the pool-size series, and
    the graceful-drain duration tail. Crash-tolerant like everything
    else here: torn or field-less event records degrade to '-' cells,
    never to a raised exception. Empty dict when the run never used
    the elastic pool."""
    counters = {k: v for k, v in metrics.get("counters", {}).items()
                if k.startswith("fabric/autoscale")
                or k in ("fabric/replicas_added", "fabric/replicas_removed",
                         "fabric/drain_redispatches")}
    if not counters and not elastic_events:
        return {}
    out = {}
    for k, v in sorted(counters.items()):
        out[k.split("/", 1)[1]] = v

    def _num(v):
        try:
            return float(v)
        except (TypeError, ValueError):
            return None

    decisions, pool_series, drains = [], [], []
    for rec in elastic_events:
        name, t = rec.get("name"), _num(rec.get("t"))
        if name == "fabric/autoscale":
            evidence = {k: rec[k] for k in
                        ("queue_depth", "shed_delta", "firing_pages",
                         "firing_warns", "budget_spent") if k in rec}
            decisions.append({
                "t": t, "action": rec.get("action", "?"),
                "reason": rec.get("reason", "?"),
                "replica": rec.get("replica"),
                "pool": f"{rec.get('pool_before', '?')}"
                        f"->{rec.get('pool_after', '?')}",
                "evidence": evidence})
            continue
        pool = _num(rec.get("pool_size"))
        if pool is not None and t is not None and \
                name in ("fabric/replica_added", "fabric/replica_removed"):
            pool_series.append((t, int(pool)))
        if name == "fabric/replica_removed":
            d = _num(rec.get("duration_ms"))
            if d is not None:
                drains.append(d)
    if decisions:
        out["decisions"] = decisions
    if pool_series:
        out["pool_size_series"] = sorted(pool_series)
    if drains:
        drains.sort()

        def pct(p):
            return round(drains[min(int(len(drains) * p),
                                    len(drains) - 1)], 3)

        out["drain_ms"] = {"count": len(drains), "p50": pct(0.5),
                           "p95": pct(0.95), "max": round(drains[-1], 3)}
    return out


def _resilience_summary(metrics):
    """Derived training-resilience view (ISSUE 10) over the engine's raw
    counters/histograms: anomalies by class (nonfinite/overflow/spike/
    divergence/sdc/replay), rewinds and skipped batches, SDC audit and
    step-replay outcomes, and the recovery-latency tail. Empty dict when
    the run never armed the sentinel."""
    counters = {k: v for k, v in metrics.get("counters", {}).items()
                if k.startswith("resilience/")}
    gauges = {k: v for k, v in metrics.get("gauges", {}).items()
              if k.startswith("resilience/")
              or k == "train/nonfinite_skipped_steps"}
    hists = {k: h for k, h in metrics.get("histograms", {}).items()
             if k.startswith("resilience/") and h.get("count")}
    if not counters and not gauges and not hists:
        return {}
    out = {}
    anomalies = {k.split("anomalies_", 1)[1]: v
                 for k, v in counters.items()
                 if k.startswith("resilience/anomalies_")}
    if anomalies:
        out["anomalies_total"] = sum(anomalies.values())
    for k, v in sorted(counters.items()):
        out[k.split("/", 1)[1]] = v
    for k, v in sorted(gauges.items()):
        out[k.split("/", 1)[1]] = v
    for k, h in sorted(hists.items()):
        out[k.split("/", 1)[1]] = {
            "count": h.get("count"), "p50": h.get("p50"),
            "p95": h.get("p95"), "p99": h.get("p99"),
            "max": h.get("max")}
    return out


def _fmt(v):
    if v is None:
        return "-"
    if isinstance(v, float):
        if v == 0:
            return "0"
        if abs(v) >= 1e6 or abs(v) < 1e-3:
            return f"{v:.3e}"
        return f"{v:,.3f}".rstrip("0").rstrip(".")
    return str(v)


def _table(title, header, rows, out):
    if not rows:
        return
    widths = [max(len(str(h)), *(len(str(r[i])) for r in rows))
              for i, h in enumerate(header)]
    out.append(f"\n== {title} ==")
    out.append("  ".join(str(h).ljust(w) for h, w in zip(header, widths)))
    out.append("  ".join("-" * w for w in widths))
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))


def render(agg):
    out = [f"telemetry report — {agg['n_records']} records"
           + (f", last snapshot at step {agg['snapshot_step']}"
              if agg["snapshot_step"] is not None else "")
           + (f", {agg['n_bad_lines']} corrupt line(s) skipped"
              if agg.get("n_bad_lines") else "")]
    _table("counters", ("counter", "value"),
           [(k, _fmt(v)) for k, v in sorted(agg["counters"].items())], out)
    _table("gauges", ("gauge", "value"),
           [(k, _fmt(v)) for k, v in sorted(agg["gauges"].items())], out)
    hrows = []
    for k, h in sorted(agg["histograms"].items()):
        if not h.get("count"):
            continue
        hrows.append((k, h["count"], _fmt(h.get("mean")), _fmt(h.get("p50")),
                      _fmt(h.get("p95")), _fmt(h.get("p99")),
                      _fmt(h.get("max"))))
    _table("histograms", ("histogram", "count", "mean", "p50", "p95", "p99",
                          "max"), hrows, out)
    srows = [(k, s["count"], _fmt(s["last"]), _fmt(s["min"]), _fmt(s["mean"]),
              _fmt(s["max"]))
             for k, s in agg["scalars"].items()]
    _table("scalars", ("tag", "n", "last", "min", "mean", "max"), srows, out)
    _table("speculation", ("metric", "value"),
           [(k, _fmt(v)) for k, v in agg.get("speculation", {}).items()],
           out)
    _table("prefix_cache", ("metric", "value"),
           [(k, _fmt(v)) for k, v in agg.get("prefix_cache", {}).items()],
           out)
    _table("slo", ("metric", "value"),
           [(k, _fmt(v) if not isinstance(v, dict) else
             " ".join(f"{kk}={_fmt(vv)}" for kk, vv in v.items()))
            for k, v in agg.get("slo", {}).items()], out)
    _table("tenants", ("tenant", "usage"),
           [(t, " ".join(f"{kk}={_fmt(vv)}" for kk, vv in row.items()))
            for t, row in agg.get("tenants", {}).items()], out)
    _table("postmortem", ("field", "value"),
           [(k, _fmt(v) if not isinstance(v, (dict, list)) else
             json.dumps(v, default=str)[:80])
            for k, v in agg.get("postmortem", {}).items()], out)
    _table("fabric", ("metric", "value"),
           [(k, _fmt(v) if not isinstance(v, dict) else
             " ".join(f"{kk}={_fmt(vv)}" for kk, vv in v.items()))
            for k, v in agg.get("fabric", {}).items()], out)
    asc = dict(agg.get("autoscaler", {}))
    asc_decisions = asc.pop("decisions", [])
    asc_pool = asc.pop("pool_size_series", [])
    if asc_pool:
        asc["pool_size_series"] = " ".join(
            f"{_fmt(t)}:{n}" for t, n in asc_pool)
    _table("autoscaler", ("metric", "value"),
           [(k, _fmt(v) if not isinstance(v, dict) else
             " ".join(f"{kk}={_fmt(vv)}" for kk, vv in v.items()))
            for k, v in asc.items()], out)
    _table("autoscaler decisions",
           ("t", "action", "reason", "replica", "pool", "evidence"),
           [(_fmt(d.get("t")), d.get("action", "?"), d.get("reason", "?"),
             d.get("replica") or "-", d.get("pool", "?"),
             json.dumps(d.get("evidence", {}), default=str)[:70])
            for d in asc_decisions], out)
    _table("resilience", ("metric", "value"),
           [(k, _fmt(v) if not isinstance(v, dict) else
             " ".join(f"{kk}={_fmt(vv)}" for kk, vv in v.items()))
            for k, v in agg.get("resilience", {}).items()], out)
    _table("spans", ("metric", "value"),
           [(k, _fmt(v) if not isinstance(v, dict) else
             " ".join(f"{kk}={_fmt(vv)}" for kk, vv in v.items()))
            for k, v in agg.get("spans", {}).items()], out)
    erows = [(k, e["count"],
              json.dumps(e["last"], default=str)[:60])
             for k, e in agg["events"].items()]
    _table("events", ("event", "count", "last"), erows, out)
    return "\n".join(out)


def load_flight_dump(path):
    """Parse a flight-recorder dump JSON; returns the payload dict or
    None when the file is not a dump (crash-tolerant: unreadable /
    corrupt files degrade to None, never raise — the postmortem tool
    must not fail on the artifact needed to debug the crash)."""
    try:
        with open(path, "rb") as f:
            payload = json.loads(
                f.read().decode("utf-8", errors="replace"))
    except (OSError, ValueError):
        return None
    if isinstance(payload, dict) and payload.get("kind") == "flight_dump":
        return payload
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("path", help="telemetry JSONL file, or a "
                                "flight-recorder dump JSON")
    p.add_argument("--json", action="store_true",
                   help="emit the aggregate as JSON instead of tables")
    p.add_argument("--postmortem", default=None, metavar="DUMP",
                   help="flight-recorder dump JSON rendered as the "
                        "postmortem section (ISSUE 13)")
    args = p.parse_args(argv)
    dump = load_flight_dump(args.postmortem) if args.postmortem else None
    if args.postmortem and dump is None:
        print(f"telemetry_report: --postmortem {args.postmortem} is not "
              f"a readable flight-recorder dump", file=sys.stderr)
        return 2
    # the positional path may itself be a dump: render the incident's
    # embedded window instead of demanding a separate JSONL
    primary_dump = load_flight_dump(args.path)
    if primary_dump is not None:
        records = (primary_dump.get("spans", [])
                   + primary_dump.get("events", [])
                   + primary_dump.get("snapshots", [])
                   + primary_dump.get("alerts", []))
        records = [r for r in records if isinstance(r, dict)]
        n_bad = 0
        dump = dump or primary_dump
    else:
        try:
            records, n_bad = load_records(args.path)
        except OSError as e:
            print(f"telemetry_report: cannot read {args.path}: {e}",
                  file=sys.stderr)
            return 2
    agg = aggregate(records, n_bad_lines=n_bad, postmortem=dump)
    if args.json:
        print(json.dumps(agg, indent=2, default=str))
    else:
        print(render(agg))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
