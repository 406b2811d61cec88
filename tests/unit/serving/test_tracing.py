"""End-to-end request tracing (ISSUE 11 acceptance) and the compile log
behind it (ISSUE 42). All in-process, on CPU, in virtual time.

Pinned here:

  * LIFECYCLE RECONSTRUCTION: an armed ServingEngine run yields one
    trace per request whose spans (queue_wait -> prefill_chunk* ->
    decode_segment, with swapped intervals under preemption)
    reconstruct the request end-to-end — phase times sum to the root
    span's duration;
  * BIT-IDENTITY: greedy output with tracing armed is bit-identical to
    the bare engine, with zero recompiles (arming adds no device work);
  * CHAOS SPAN GRAPH: a 3-replica fabric driven through a scripted
    mid-trace crash (PR 8's FaultInjector seams) produces a span graph
    where EVERY finished request reconstructs — including the
    failed-over request, whose survivor-replica spans link to the
    ORIGINAL trace id through the Request trace-context fields — the
    Chrome-trace export is valid JSON, and the report's spans section
    renders the critical paths;
  * COMPILE LOG: EVERY compiled serving program in the jit-cache
    registry, the draft model's too, is in the process's compile log
    under its ``program_cache_sizes()`` name, and the ``entry/*``
    counters reach the registry's JSONL snapshot.
"""

import importlib.util
import json
import os
import time
import types

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import (FabricRouter, InProcessReplica,
                                   ReplicaSupervisor, Request,
                                   ServingEngine, bimodal_trace,
                                   poisson_trace)
from deepspeed_tpu.telemetry import (JsonlSink, SpanTracer, compile_log,
                                     phase_breakdown, read_jsonl,
                                     trace_summaries)
from deepspeed_tpu.testing import FakeClock, FaultInjector
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.tracing, pytest.mark.serving,
    pytest.mark.observability, pytest.mark.quick]

_ENGINE = {}


def _inference_engine():
    if "eng" not in _ENGINE:
        groups.reset()
        cfg = GPT2Config.tiny()
        _ENGINE["cfg"] = cfg
        _ENGINE["eng"] = deepspeed_tpu.init_inference(
            GPT2Model(cfg), dtype="fp32", max_out_tokens=128)
    return _ENGINE["cfg"], _ENGINE["eng"]


def _serving(clock, **kw):
    _, eng = _inference_engine()
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (16, 64))
    kw.setdefault("telemetry", False)
    return ServingEngine(eng, time_fn=clock.time, **kw)


def _trace(n=8, seed=0, rate=150.0):
    cfg, _ = _inference_engine()
    return poisson_trace(np.random.RandomState(seed), n, rate=rate,
                         prompt_lens=(4, 6, 9), max_new_choices=(4, 6, 8),
                         vocab_size=cfg.vocab_size)


def _roots(tracer):
    return [s for s in tracer.spans
            if s.name == "request" and s.end is not None]


# ----------------------------------------------------- lifecycle spans
def test_request_lifecycle_reconstructs_end_to_end():
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = _serving(clock, tracer=tracer)
    reqs = _trace(8)
    results = {r.rid: r for r in srv.run(reqs)}
    assert len(results) == len(reqs)
    sums = {s["attrs"]["rid"]: s for s in trace_summaries(tracer.spans)}
    assert set(sums) == {r.rid for r in reqs}
    for rid, s in sums.items():
        res = results[rid]
        group = tracer.spans_for(s["trace"])
        names = {sp.name for sp in group}
        # full lifecycle present, every span closed, linked to the root
        assert {"request", "queue_wait", "prefill_chunk",
                "decode_segment"} <= names
        root_id = s["root_span"]
        for sp in group:
            assert sp.end is not None
            if sp.span_id != root_id:
                assert sp.parent_id == root_id
        # phases are sequential for a single request: they tile the
        # root span (small slack: span stamps read an auto-advancing
        # virtual clock between phase edges)
        ph = s["phases_s"]
        covered = ph["queue"] + ph["prefill"] + ph["decode"]
        assert covered == pytest.approx(s["total_s"], rel=0.35)
        assert s["fractions"]["failover"] == 0.0
        # root attrs carry the terminal state
        root = [sp for sp in group if sp.span_id == root_id][0]
        assert root.attrs["finish_reason"] == res.finish_reason
        assert root.attrs["tokens"] == len(res.tokens)


def test_greedy_bit_identical_and_zero_recompiles_when_armed():
    reqs = _trace(8, seed=1)
    bare = _serving(FakeClock(auto_dt=0.001))
    oracle = {r.rid: r.tokens for r in bare.run(reqs)}
    tracer = SpanTracer()
    armed = _serving(FakeClock(auto_dt=0.001), tracer=tracer)
    got = {r.rid: r.tokens for r in armed.run(reqs)}
    assert got == oracle
    assert armed.recompile_count() == 0
    assert all(v == 1 for v in armed.program_cache_sizes().values())
    assert len(tracer.spans) > 0


def test_rerun_of_same_requests_gets_fresh_traces():
    """Replaying the same Request objects (benches do) must not append
    run 2's spans into run 1's traces — the engine never mutates the
    caller's Request."""
    tracer = SpanTracer()
    reqs = _trace(4, seed=2)
    srv = _serving(FakeClock(auto_dt=0.001), tracer=tracer)
    srv.run(reqs)
    n1 = len(trace_summaries(tracer.spans))
    srv.run(reqs)
    assert len(trace_summaries(tracer.spans)) == 2 * n1
    for r in reqs:
        assert r.trace_id is None and r.parent_span is None


def test_trace_context_on_request_is_honored():
    """A request arriving WITH trace context (the fabric's shape) hangs
    its engine spans under the caller's root instead of allocating."""
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = _serving(clock, tracer=tracer)
    cfg, _ = _inference_engine()
    root = tracer.begin("request", t=0.0, rid=99)
    req = Request(rid=99, prompt=[1, 2, 3], max_new_tokens=4,
                  trace_id=root.trace_id, parent_span=root.span_id)
    [res] = srv.run([req])
    assert res.finish_reason in ("eos", "length")
    group = tracer.spans_for(root.trace_id)
    assert {"queue_wait", "prefill_chunk", "decode_segment"} <= \
        {s.name for s in group}
    for s in group:
        if s.span_id != root.span_id:
            assert s.parent_id == root.span_id
    # the engine did NOT close the caller-owned root
    assert root.end is None
    tracer.end(root, t=clock.now)


# --------------------------------------------- iteration phase spans
PHASES = ("iter_schedule", "iter_upload", "iter_launch", "iter_fetch",
          "iter_commit")


def test_phases_tile_the_iteration_span():
    """Every step() that found work records one ``iteration`` span on
    the engine-scope trace; its phases have it as parent, follow each
    other without a gap from its start to its end, and the decode phases
    close on iteration less schedule exactly. Results are fetched one
    launch behind (ISSUE 36): the first iteration of a run of steps
    launches and fetches nothing, the last fetches and launches nothing."""
    tracer = SpanTracer()
    srv = _serving(FakeClock(auto_dt=0.001), tracer=tracer)
    results = srv.run(_trace(8, seed=6))
    iters = [s for s in tracer.spans if s.name == "iteration"]
    assert len(iters) >= srv.decode_steps > 0
    req_traces = {s["trace"] for s in trace_summaries(tracer.spans)}
    assert {s.trace_id for s in iters}.isdisjoint(req_traces)
    decoded = 0
    for it in iters:
        kids = [s for s in tracer.spans if s.parent_id == it.span_id]
        phases = sorted((s for s in kids if s.name in PHASES),
                        key=lambda s: s.start)
        assert {s.name for s in kids} <= set(PHASES) | {"decode_step"}
        names = [s.name for s in phases]
        launch_only, fetch_only = list(PHASES[:3]), \
            [PHASES[0]] + list(PHASES[3:])
        assert names in (list(PHASES), ["iter_schedule"], launch_only,
                         fetch_only), names
        assert phases[0].start == it.start and phases[-1].end == it.end
        for a, b in zip(phases, phases[1:]):
            assert a.end == b.start
        assert all(s.trace_id == it.trace_id for s in kids)
        if len(phases) > 1:
            decoded += "iter_fetch" in names
            rest = sum(s.duration for s in phases[1:])
            assert rest == pytest.approx(it.duration - phases[0].duration)
    assert decoded == srv.decode_steps
    # decode_step keeps its place: from before the program's arguments
    # are bound to after the fetch (of the step launched an iteration
    # earlier), so it ends with iter_fetch
    fetch_ends = {s.end for s in tracer.spans if s.name == "iter_fetch"}
    steps = [s for s in tracer.spans if s.name == "decode_step"]
    assert len(steps) == srv.decode_steps
    assert all(s.end in fetch_ends for s in steps)
    assert len(results) == 8


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["monolithic", "chunked"])
def test_prefill_chunk_of_the_last_chunk_is_fenced(chunked):
    """The last chunk's span closes at the first-token commit, after the
    token fetch (``fenced=True``); an intermediate chunk has no fence of
    its own and says so."""
    cfg, _ = _inference_engine()
    tracer = SpanTracer()
    kw = dict(prefill_token_budget=16) if chunked \
        else {}
    srv = _serving(FakeClock(auto_dt=0.001), tracer=tracer, **kw)
    rng = np.random.RandomState(7)
    reqs = [Request(rid=i, prompt=rng.randint(
                0, cfg.vocab_size, size=n).tolist(), max_new_tokens=4)
            for i, n in enumerate((40, 9, 33))]
    results = {r.rid: r for r in srv.run(reqs)}
    sums = {s["attrs"]["rid"]: s for s in trace_summaries(tracer.spans)}
    for rid, res in results.items():
        chunks = sorted((s for s in tracer.spans_for(sums[rid]["trace"])
                         if s.name == "prefill_chunk"),
                        key=lambda s: s.start)
        assert len(chunks) == res.prefill_chunks
        assert [c.attrs["fenced"] for c in chunks] == \
            [False] * (len(chunks) - 1) + [True]
        assert chunks[-1].end >= res.first_token_time
        assert all(c.end <= res.first_token_time for c in chunks[:-1])
    assert any(r.prefill_chunks > 1 for r in results.values()) == chunked


class _RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs every enter
    and exit in order."""

    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


@pytest.mark.parametrize("mode", ["plain", "chunked", "speculative",
                                  "preemption"])
def test_no_two_serving_annotations_overlap(mode, monkeypatch):
    """A device-trace reader names an idle gap by the annotation that
    covers most of it, so at most one ``dstpu/serving_*`` annotation is
    open at any time: a prefill suspends the admit phase around it."""
    import jax

    cfg, _ = _inference_engine()
    kw = {"plain": {},
          "chunked": dict(prefill_token_budget=16),
          "speculative": dict(speculative="ngram", num_slots=2,
                              max_len=128, buckets=(64,)),
          "preemption": dict(preemption="swap", num_slots=1,
                             buckets=(16, 32))}[mode]
    srv = _serving(FakeClock(auto_dt=0.001), **kw)
    srv.warmup()
    log = _RecordingAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _RecordingAnnotation)
    rng = np.random.RandomState(8)
    if mode == "speculative":
        pattern = rng.randint(0, cfg.vocab_size, size=5).tolist()
        reqs = [Request(rid=i, prompt=pattern * 6, max_new_tokens=10)
                for i in range(2)]
    elif mode == "preemption":
        reqs = [Request(rid=0, prompt=rng.randint(
                    0, cfg.vocab_size, size=21).tolist(),
                    max_new_tokens=24, priority=1, arrival_time=0.0),
                Request(rid=1, prompt=rng.randint(
                    0, cfg.vocab_size, size=9).tolist(),
                    max_new_tokens=6, priority=0, arrival_time=0.02)]
    else:
        reqs = _trace(8, seed=9)
    assert len(srv.run(reqs, warmup=False)) == len(reqs)
    open_now = None
    seen = set()
    for what, name in log:
        assert name.startswith("dstpu/serving_")
        if what == "enter":
            assert open_now is None, (open_now, name)
            open_now = name
            seen.add(name)
        else:
            assert open_now == name
            open_now = None
    assert open_now is None
    assert "dstpu/serving_decode" not in seen
    want = {"dstpu/serving_admit", "dstpu/serving_prefill",
            "dstpu/serving_commit"}
    want |= {"dstpu/serving_draft", "dstpu/serving_verify"} \
        if mode == "speculative" else \
        {"dstpu/serving_upload", "dstpu/serving_launch",
         "dstpu/serving_fetch"}
    assert want <= seen, sorted(seen)


def test_bare_engine_reads_no_clock_the_parent_did_not(monkeypatch):
    """With ``tracer=None`` the iteration reads the engine's clock once
    for each first token, once for each decode step (the commit stamp)
    and once for each finished request, as before the phases existed,
    and ``perf_counter`` at most three times a decode step (once as a step
    is launched, for the decode-gap EMA and the decode wall's start, once
    as one is fetched; once more where an iteration only fetches); armed
    output is bit-identical."""
    from deepspeed_tpu.serving import engine as engine_mod

    reqs = _trace(8, seed=10)
    calls = {"clock": 0, "perf": 0}

    class CountingClock(FakeClock):
        def time(self):
            calls["clock"] += 1
            return super().time()

    def perf_counter():
        calls["perf"] += 1
        return time.perf_counter()

    bare = _serving(CountingClock(auto_dt=0.001))
    bare.warmup()
    monkeypatch.setattr(engine_mod, "time", types.SimpleNamespace(
        perf_counter=perf_counter))
    for r in reqs:
        bare.submit(r)
    bare._run_t0 = 0.0            # as run() does: stamps read the clock
    calls["clock"] = calls["perf"] = 0
    results, now = [], 0.0
    while bare.pending:
        now += 0.01               # past every arrival soon; no clock read
        results.extend(bare.step(now))
    assert len(results) == len(reqs)
    assert calls["clock"] == (bare.prefill_calls + bare.decode_steps
                              + len(results))
    assert 2 * bare.decode_steps <= calls["perf"] <= 3 * bare.decode_steps
    assert bare._iter_span is None and bare._open_phase is None
    monkeypatch.undo()
    tracer = SpanTracer()
    armed = _serving(FakeClock(auto_dt=0.001), tracer=tracer)
    got = {r.rid: r.tokens for r in armed.run(reqs)}
    assert got == {r.rid: r.tokens for r in results}
    assert all(v == 1 for v in armed.program_cache_sizes().values())


# -------------------------------------------------- preemption + swap
def test_preemption_swap_spans_and_phase():
    """A preempted request's trace grows swap_out/swapped/swap_in spans
    and a SECOND decode segment after resume; the swapped phase shows
    up in its critical-path fractions."""
    cfg, _ = _inference_engine()
    rng = np.random.RandomState(3)
    pA = rng.randint(0, cfg.vocab_size, size=21).tolist()
    pB = rng.randint(0, cfg.vocab_size, size=9).tolist()
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = _serving(clock, num_slots=1, max_len=64, buckets=(16, 32),
                   preemption="swap", tracer=tracer)
    res = {r.rid: r for r in srv.run([
        Request(rid=0, prompt=pA, max_new_tokens=24, priority=1,
                arrival_time=0.0),
        Request(rid=1, prompt=pB, max_new_tokens=6, priority=0,
                arrival_time=0.02)])}
    assert res[0].preemptions >= 1
    sums = {s["attrs"]["rid"]: s for s in trace_summaries(tracer.spans)}
    victim = sums[0]
    group = tracer.spans_for(victim["trace"])
    names = [s.name for s in group]
    assert names.count("decode_segment") >= 2     # split by the swap
    assert {"swap_out", "swapped", "swap_in"} <= set(names)
    assert victim["phases_s"]["swapped"] > 0
    assert victim["fractions"]["swapped"] > 0
    # the un-preempted request never swapped
    assert sums[1]["phases_s"]["swapped"] == 0.0
    # the swap spans name the programs as program_cache_sizes() does
    assert {s.attrs["program"] for s in group
            if s.name in ("swap_out", "swap_in")} \
        == {"swap_out", "swap_in"} <= set(srv.program_cache_sizes())
    ph = phase_breakdown(group)
    assert ph["swapped"] == pytest.approx(victim["phases_s"]["swapped"])


# ------------------------------------------------------- speculation
def test_speculative_iteration_spans():
    cfg, _ = _inference_engine()
    pattern = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=5).tolist()
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = _serving(clock, num_slots=2, max_len=128,
                   buckets=(64,), speculative="ngram", tracer=tracer)
    reqs = [Request(rid=i, prompt=pattern * 6, max_new_tokens=10)
            for i in range(2)]
    results = srv.run(reqs)
    assert len(results) == 2
    names = {s.name for s in tracer.spans}
    assert "spec_draft" in names and "spec_verify" in names
    verifies = [s for s in tracer.spans if s.name == "spec_verify"]
    # iteration spans live on the engine-scope trace, not a request's
    req_traces = {s["trace"] for s in trace_summaries(tracer.spans)}
    assert all(v.trace_id not in req_traces for v in verifies)
    assert all(v.attrs["program"].startswith("verify_")
               for v in verifies)
    assert {v.attrs["program"] for v in verifies} \
        <= set(srv.program_cache_sizes())


def test_draft_model_programs_are_in_the_compile_log():
    """Draft-backend speculation: the draft model's compiled programs
    appear in program_cache_sizes AND in the compile log under those
    names: 'every compiled program' includes them."""
    from deepspeed_tpu.serving.speculative import SpeculativeConfig

    cfg, eng = _inference_engine()
    groups.reset()
    draft_eng = deepspeed_tpu.init_inference(
        GPT2Model(cfg), dtype="fp32", max_out_tokens=128, seed=7)
    spec = SpeculativeConfig(mode="draft", draft_engine=draft_eng,
                             draft_window=32, k_buckets=(2,))
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = ServingEngine(eng, num_slots=2, max_len=128, buckets=(64,),
                        telemetry=False, time_fn=clock.time,
                        speculative=spec, tracer=tracer)
    pattern = np.random.RandomState(5).randint(
        0, cfg.vocab_size, size=5).tolist()
    srv.run([Request(rid=0, prompt=pattern * 6, max_new_tokens=8)])
    jit_programs = set(srv.program_cache_sizes())
    assert any(k.startswith("draft_") for k in jit_programs)
    log = compile_log()
    missing = {p for p in jit_programs
               if not log.programs.get(p, {}).get("trace")}
    assert not missing, (sorted(missing), sorted(log.programs))
    assert log.programs["draft_2"]["lower"][0] >= 1


# ------------------------------------------------------- compile log
def test_compile_log_covers_every_compiled_program(tmp_path):
    """The compile log names every program in the jit-cache registry
    (prefill buckets, decode, swap, in prefix mode block_copy) with the
    stages JAX ran for it, and the registry's ``entry/*`` counters,
    brought up to the process's totals, reach its JSONL snapshot."""
    from deepspeed_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    path = str(tmp_path / "run.jsonl")
    reg.attach_sink(JsonlSink(path))
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = _serving(clock, num_slots=2, max_len=64, buckets=(16, 32),
                   preemption="swap", prefix_cache=True, block_size=8,
                   telemetry=reg, tracer=tracer)
    srv.run(_trace(6, seed=4))
    log = compile_log()
    jit_programs = set(srv.program_cache_sizes())
    assert {"decode", "prefill_16", "prefill_32", "swap_out", "swap_in",
            "block_copy"} <= jit_programs
    for name in jit_programs:
        stages = log.programs.get(name, {})
        assert stages.get("trace", [0])[0] >= 1, (name, sorted(log.programs))
        assert stages["lower"][0] >= 1 and stages["lower"][1] > 0, name
        assert stages["backend_compile"][0] >= 1, name
    counters = reg.snapshot()["counters"]
    # the totals at subscription and what the engine's own work added
    assert len(jit_programs) <= counters["entry/traces"] \
        <= log.totals["entry/traces"]
    assert counters["entry/trace_ms"] > 0 and counters["entry/lower_ms"] > 0
    assert counters["entry/setup_warmup_ms"] > 0
    srv.close()
    reg.sink.close()
    snaps = [r for r in read_jsonl(path) if r["kind"] == "snapshot"]
    assert snaps[-1]["metrics"]["counters"]["entry/traces"] \
        == counters["entry/traces"]


# ------------------------------------------------------- chaos fabric
def test_chaos_fabric_span_graph_reconstructs_with_failover(tmp_path):
    """THE acceptance pin: 3-replica fabric, scripted mid-trace crash
    with supervised resurrection, tracer armed end to end. Every
    finished request's lifecycle reconstructs from the span graph; the
    failed-over request's survivor-replica spans link to the ORIGINAL
    trace id; the Chrome-trace export is valid JSON; the report's
    spans section renders the per-phase critical paths — and the run
    stays lossless vs a fault-free single-replica oracle."""
    cfg, _ = _inference_engine()
    trace = bimodal_trace(np.random.RandomState(0), 14, rate=200.0,
                          short_lens=(4, 6, 8), long_lens=(24,),
                          long_frac=0.25, short_new=(6, 8), long_new=(6,),
                          vocab_size=cfg.vocab_size)
    oracle_clock = FakeClock(auto_dt=0.001)
    oracle = {r.rid: r.tokens
              for r in _serving(oracle_clock).run(trace)}

    path = str(tmp_path / "spans.jsonl")
    clock = FakeClock(auto_dt=0.001)
    tracer = SpanTracer(time_fn=clock.time, sink=JsonlSink(path))
    inj = FaultInjector()
    inj.crash_replica_step("r1", 3)

    def factory(name):
        srv = _serving(clock, tracer=tracer)
        chaos = inj.replica_plan(name) if name == "r1" else None
        return InProcessReplica(name, srv, chaos=chaos, clock=clock)

    router = FabricRouter(
        [factory(n) for n in ("r0", "r1", "r2")],
        replica_factory=factory,
        supervisor=ReplicaSupervisor(max_restarts=3,
                                     restart_delay_s=0.05, jitter=0.0,
                                     tracer=tracer),
        time_fn=clock.time, telemetry=False,
        heartbeat_interval_s=0.05, tracer=tracer)
    results = router.run(trace)
    tracer.sink.close()

    assert len(results) == len(trace)
    assert router.replica_crashes == 1 and router.failovers >= 1
    for r in results:
        assert r.tokens == oracle[r.rid], r.rid
    assert router.recompile_count() == 0

    # every finished request reconstructs end-to-end, and the phases
    # TILE the root span — the engine-side queue_wait starts at the
    # dispatch-time submit, so it never double-counts the router_queue
    # interval (nor, post-failover, the whole first attempt)
    sums = {s["attrs"]["rid"]: s for s in trace_summaries(tracer.spans)}
    assert set(sums) == {r.rid for r in trace}
    for rid, s in sums.items():
        names = {sp.name for sp in tracer.spans_for(s["trace"])}
        assert {"router_queue", "queue_wait", "prefill_chunk",
                "decode_segment"} <= names, (rid, names)
        covered = sum(s["phases_s"].values())
        assert covered <= s["total_s"] * 1.10 + 1e-6, \
            (rid, covered, s["total_s"], s["phases_s"])

    # the failed-over request: spans from BOTH attempts under ONE trace
    failed_over = [r for r in results if r.failovers > 0]
    assert failed_over
    fo_rid = failed_over[0].rid
    group = tracer.spans_for(sums[fo_rid]["trace"])
    names = [sp.name for sp in group]
    assert "failover" in names
    attempts = [sp.attrs.get("replica") for sp in group
                if sp.name == "router_queue" and "replica" in sp.attrs]
    assert len(attempts) >= 2 and len(set(attempts)) >= 2, attempts
    fo_span = [sp for sp in group if sp.name == "failover"][0]
    assert fo_span.attrs["from_replica"] == attempts[0]
    assert fo_span.attrs["to_replica"] == attempts[1]
    assert sums[fo_rid]["fractions"]["failover"] > 0
    # the cancelled/crashed first attempt left no dangling open spans
    # in this trace (crash kills the replica's records; the router and
    # survivor closed theirs)
    open_spans = [sp for sp in group if sp.end is None]
    assert not open_spans

    # supervisor downtime span rode the same tracer
    assert any(sp.name == "replica_restart_backoff"
               for sp in tracer.spans)

    # Chrome-trace export: valid JSON with one track per trace
    chrome_path = tracer.export_chrome_trace(
        str(tmp_path / "chrome.json"))
    with open(chrome_path) as f:
        doc = json.load(f)
    events = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert len(events) >= len(tracer.spans) - len(
        [s for s in tracer.spans if s.end is None])
    assert {"name", "ts", "dur", "pid", "tid"} <= set(events[0])

    # spans flowed to JSONL -> report spans section
    spec = importlib.util.spec_from_file_location(
        "telemetry_report", os.path.join(
            os.path.dirname(__file__), "..", "..", "..", "scripts",
            "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    records, n_bad = mod.load_records(path)
    assert n_bad == 0
    agg = mod.aggregate(records)
    spans_sec = agg["spans"]
    assert spans_sec["n_requests"] == len(trace)
    assert spans_sec["queue"]["frac_p50"] >= 0
    assert "decode" in spans_sec
    assert "failover" in spans_sec      # the failed-over request's gap
    assert "spans" in mod.render(agg)
