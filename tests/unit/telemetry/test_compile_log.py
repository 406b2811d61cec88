"""The compile log and the phases of set-up (ISSUE 42).

The log's listeners are fed a made-up event stream here, so nothing is
registered with ``jax.monitoring`` (its listeners are process-wide, and the
suite runs several workers); the tests that drive real engines use the
process's own log, which the engines register, and take off whatever they
put on themselves.
"""

import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.telemetry import (CompileLog, MetricsRegistry, SetupPhase,
                                     SpanTracer, compile_log)
from deepspeed_tpu.telemetry.compile_log import (_COMPILE as COMPILE,
                                                 _HITS as HITS,
                                                 _LOWER as LOWER,
                                                 _MISSES as MISSES,
                                                 _RETRIEVAL as RETRIEVAL,
                                                 _TRACE as TRACE, COUNTERS)
from deepspeed_tpu.testing import FakeClock
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.tracing, pytest.mark.observability,
              pytest.mark.quick]


def _traces(log, name):
    """How often a function of this name was traced, by ``log``'s table."""
    return log.programs.get(name, {}).get("trace", [0])[0]


def _program(log, name, t=100.0, *, trace=0.25, lower=0.5, compile_=1.0,
             load=None):
    """One program through JAX's stages, as JAX would tell it: the start
    of a trace as a scalar, then the spans; lowering and compiling name
    the module, ``jit(<name>)``."""
    log.on_scalar(TRACE, t, fun_name=name)
    log.on_time_span(TRACE, t, t + trace, fun_name=name)
    t += trace
    log.on_scalar(LOWER, t, fun_name=f"jit({name})")
    log.on_time_span(LOWER, t, t + lower, fun_name=f"jit({name})")
    t += lower
    log.on_scalar(COMPILE, t, fun_name=f"jit({name})")
    if load is None:
        log.on_event(MISSES)
    else:
        log.on_event(HITS)
        log.on_duration(RETRIEVAL, load)
    log.on_time_span(COMPILE, t, t + compile_, fun_name=f"jit({name})")


# ------------------------------------------------------------ fake streams
EVENT_CASES = [
    ("trace", lambda log: (log.on_scalar(TRACE, 1.0, fun_name="decode"),
                           log.on_time_span(TRACE, 1.0, 1.5,
                                            fun_name="decode")),
     {"entry/trace_ms": 500.0, "entry/traces": 1}),
    ("lower", lambda log: log.on_time_span(LOWER, 1.0, 1.25,
                                           fun_name="jit(decode)"),
     {"entry/lower_ms": 250.0}),
    ("backend_compile", lambda log: log.on_time_span(
        COMPILE, 1.0, 3.0, fun_name="jit(decode)"),
     {"entry/backend_compile_ms": 2000.0}),
    ("cache_hit", lambda log: log.on_event(HITS), {"entry/cache_hits": 1}),
    ("cache_miss", lambda log: log.on_event(MISSES),
     {"entry/cache_misses": 1}),
    ("cache_retrieval", lambda log: log.on_duration(RETRIEVAL, 0.125),
     {"entry/cache_load_ms": 125.0}),
]


@pytest.mark.parametrize("feed,expected",
                         [c[1:] for c in EVENT_CASES],
                         ids=[c[0] for c in EVENT_CASES])
def test_each_event_lands_in_its_counter(feed, expected):
    log, reg = CompileLog(), MetricsRegistry()
    log.subscribe(reg)
    feed(log)
    counters = reg.snapshot()["counters"]
    assert set(counters) == set(COUNTERS)
    for name in COUNTERS:
        assert counters[name] == pytest.approx(expected.get(name, 0)), name
    assert counters == log.totals


UNKNOWN = [
    lambda log: log.on_time_span("/jax/core/compile/other_duration", 0.0,
                                 9.0, fun_name="decode"),
    lambda log: log.on_event("/jax/compilation_cache/compile_requests_"
                             "use_cache"),
    lambda log: log.on_duration("/jax/compilation_cache/"
                                "compile_time_saved_sec", 4.0),
    # JAX sends the three timed events as plain durations too: the spans
    # are what is counted, the durations would count them twice
    lambda log: log.on_duration(COMPILE, 4.0, fun_name="jit(decode)"),
    lambda log: log.on_scalar("/jax/some/scalar", 3.0),
]


@pytest.mark.parametrize("feed", UNKNOWN,
                         ids=["span", "event", "duration",
                              "compile_as_duration", "scalar"])
def test_unknown_event_touches_nothing(feed):
    log, reg = CompileLog(), MetricsRegistry()
    log.subscribe(reg)
    told = []
    log.subscribe(None, lambda *a: told.append(a))
    feed(log)
    assert not any(reg.snapshot()["counters"].values())
    assert not log.programs and not told
    assert not any(log.totals.values())


def test_retraces_count_a_name_traced_before():
    log, reg = CompileLog(), MetricsRegistry()
    log.subscribe(reg)
    _program(log, "prefill_128")
    assert reg.counter("entry/retraces").value == 0
    _program(log, "prefill_256")          # a first trace of another name
    assert reg.counter("entry/retraces").value == 0
    _program(log, "prefill_128")          # a second trace of the first
    assert reg.counter("entry/retraces").value == 1
    assert reg.counter("entry/traces").value == 3
    assert _traces(log, "prefill_128") == 2
    assert _traces(log, "prefill_256") == 1 and _traces(log, "decode") == 0
    assert log.programs["prefill_128"]["lower"] == [2, 1000.0]
    assert log.programs["prefill_128"]["backend_compile"][0] == 2


def test_a_trace_inside_a_trace_is_the_outer_programs():
    """``jnp.where`` called while ``decode`` is traced is traced inside
    it: its time lies in ``decode``'s span and it is no program."""
    log = CompileLog()
    log.on_scalar(TRACE, 1.0, fun_name="decode")
    for t in (1.1, 1.2):
        log.on_scalar(TRACE, t, fun_name="where")
        log.on_time_span(TRACE, t, t + 0.05, fun_name="where")
    log.on_time_span(TRACE, 1.0, 2.0, fun_name="decode")
    assert log.totals["entry/traces"] == 1
    assert log.totals["entry/retraces"] == 0
    assert log.totals["entry/trace_ms"] == pytest.approx(1000.0)
    assert set(log.programs) == {"decode"}
    # a lowering rule that traces a jnp helper (Mosaic's, for a Pallas
    # kernel's index arithmetic): inside the lowering, not a program
    log.on_scalar(LOWER, 2.0, fun_name="jit(decode)")
    log.on_scalar(TRACE, 2.1, fun_name="floor_divide")
    log.on_time_span(TRACE, 2.1, 2.2, fun_name="floor_divide")
    log.on_time_span(LOWER, 2.0, 2.5, fun_name="jit(decode)")
    assert log.totals["entry/traces"] == 1 and "floor_divide" not in log.programs
    assert log.totals["entry/lower_ms"] == pytest.approx(500.0)
    # eager, at top level, the same function is a program of its own
    log.on_scalar(TRACE, 3.0, fun_name="where")
    log.on_time_span(TRACE, 3.0, 3.25, fun_name="where")
    assert log.totals["entry/traces"] == 2 and _traces(log, "where") == 1


def test_subscriber_catches_up_follows_and_stops():
    log = CompileLog()
    _program(log, "_init_cast", load=0.125)       # before any registry
    late, stages = MetricsRegistry(), []
    sub = log.subscribe(late, lambda *a: stages.append(a))
    caught_up = late.snapshot()["counters"]
    assert caught_up == log.totals
    assert caught_up["entry/traces"] == 1 and caught_up["entry/cache_hits"] == 1
    assert caught_up["entry/cache_load_ms"] == pytest.approx(125.0)
    assert not stages                             # told later stages only
    _program(log, "decode", t=200.0, load=0.25)
    assert late.snapshot()["counters"] == log.totals
    assert late.counter("entry/traces").value == 2
    # the retrieval, which JAX sends nameless from inside the backend
    # compile, is told as that program's cache_load
    assert [(s[0], s[1]) for s in stages] == [
        ("trace", "decode"), ("lower", "decode"),
        ("backend_compile", "decode"), ("cache_load", "decode")]
    load = stages[-1]
    assert load[3] - load[2] == pytest.approx(0.25)
    assert log.programs["decode"]["cache_load"] == [1, pytest.approx(250.0)]
    before = dict(late.snapshot()["counters"])
    log.unsubscribe(sub)
    log.unsubscribe(sub)                          # twice is nothing
    _program(log, "prefill_128", t=300.0)
    assert late.snapshot()["counters"] == before and len(stages) == 4
    assert log.totals["entry/traces"] == 3
    # a second registry, on its own, is brought to the same totals
    other = MetricsRegistry()
    log.subscribe(other)
    assert other.snapshot()["counters"] == log.totals


def test_subscriber_follows_only_while_it_says_so():
    """A subscriber holds the totals of its subscription and what arrived
    while its ``follows`` was true: another's compiles in the same process
    (a reference check, a second engine) are not its own."""
    log = CompileLog()
    _program(log, "_init_cast", load=0.125)
    at_work = {"mine": False, "other": False}
    mine, other, stages = MetricsRegistry(), MetricsRegistry(), []
    log.subscribe(mine, lambda *a: stages.append(a[:2]),
                  follows=lambda: at_work["mine"])
    log.subscribe(other, follows=lambda: at_work["other"])
    caught_up = dict(mine.snapshot()["counters"])
    assert caught_up == log.totals
    _program(log, "<lambda>", t=200.0)            # nobody's: the caller's
    assert mine.snapshot()["counters"] == caught_up and not stages
    at_work["mine"] = True
    _program(log, "decode", t=300.0, load=0.25)
    at_work["mine"] = False
    at_work["other"] = True
    _program(log, "decode", t=400.0)              # the other's retrace
    counters = mine.snapshot()["counters"]
    assert counters["entry/traces"] == caught_up["entry/traces"] + 1
    assert counters["entry/retraces"] == 0
    assert counters["entry/cache_misses"] == caught_up["entry/cache_misses"]
    assert counters["entry/trace_ms"] == pytest.approx(
        caught_up["entry/trace_ms"] + 250.0)
    assert counters["entry/cache_load_ms"] == pytest.approx(375.0)
    assert stages == [("trace", "decode"), ("lower", "decode"),
                      ("backend_compile", "decode"), ("cache_load", "decode")]
    theirs = other.snapshot()["counters"]
    assert theirs["entry/traces"] == caught_up["entry/traces"] + 1
    assert theirs["entry/retraces"] == 1          # the process had traced it
    assert theirs["entry/cache_misses"] == 1
    assert log.totals["entry/traces"] == 4


def test_listeners_register_and_unregister_with_jax():
    """A log of the test's own among JAX's listeners: it hears a real
    compile by name, and hears nothing once it is taken off."""
    import jax
    import jax.numpy as jnp

    log = CompileLog()
    log.register()
    try:
        def twice_plus_one(x):
            return jnp.where(x > 0, x * 2 + 1, x)

        jax.jit(twice_plus_one)(jnp.ones((3,)))
        stages = log.programs["twice_plus_one"]
        assert stages["trace"][0] == stages["lower"][0] == 1
        assert stages["backend_compile"][0] == 1
        assert "where" not in log.programs        # traced inside it
    finally:
        log.unregister()
    heard = dict(log.totals)
    jax.jit(lambda x: x - 7)(jnp.ones((5,)))
    assert log.totals == heard


def test_setup_phase_is_published_once_a_registry(monkeypatch):
    import jax

    names = []
    real = jax.profiler.TraceAnnotation
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda n, *a, **k: names.append(n) or real(n, *a, **k))
    phase = SetupPhase("weights")
    time.sleep(0.002)
    fence = jax.numpy.ones((4,))
    assert phase.close(fence=fence) is phase and phase.ms >= 2.0
    ms = phase.ms
    assert phase.close().ms == ms                 # closed once
    reg, tracer = MetricsRegistry(), SpanTracer()
    span = phase.publish(reg, tracer, fenced=True)
    assert phase.publish(reg, tracer) is None     # a second engine on it
    assert reg.counter("entry/setup_weights_ms").value == ms
    assert [s.name for s in tracer.spans] == ["setup_weights"]
    assert span.attrs == {"fenced": True}
    assert (span.end - span.start) * 1e3 == pytest.approx(ms)
    other = MetricsRegistry()
    phase.publish(other, clock=lambda t0, t1: (0.0, 1.0))
    assert other.counter("entry/setup_weights_ms").value == ms
    assert names == ["dstpu/setup_weights"]


# ------------------------------------------------------------ real engines
_ENGINE = {}


def _inference_engine():
    if "eng" not in _ENGINE:
        groups.reset()
        cfg = GPT2Config.tiny()
        _ENGINE["cfg"] = cfg
        _ENGINE["eng"] = deepspeed_tpu.init_inference(
            GPT2Model(cfg), dtype="fp32", max_out_tokens=128)
    return _ENGINE["cfg"], _ENGINE["eng"]


def _requests(n, plen=5, max_new=6):
    cfg, _ = _inference_engine()
    rng = np.random.RandomState(11)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size,
                                              size=plen).tolist(),
                    max_new_tokens=max_new) for i in range(n)]


def _drop_bucket_16(srv, eng):
    """Bucket 16's program goes, as if ``warmup()`` had left the bucket
    out: the next short prompt builds it and jits it anew."""
    srv._prefill.clear()
    for key in [k for k in eng._compiled if k[0] == "slot_pf"]:
        del eng._compiled[key]


def test_warmup_logs_every_program_and_its_phases():
    """After ``warmup()`` every name of ``program_cache_sizes()`` is a
    ``fun_name`` of the log, the registry (made after ``init_inference``)
    holds that engine's compiles and weights, and set-up's phases are
    counters and spans."""
    _, eng = _inference_engine()
    reg, tracer = MetricsRegistry(), SpanTracer()
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        prefill_token_budget=16, preemption="swap",
                        telemetry=reg, tracer=tracer, tenants=False)
    srv.warmup()
    log = compile_log()
    names = set(srv.program_cache_sizes())
    assert names == {"decode", "prefill_16", "prefill_32",
                     "chunk_prefill_16", "swap_out", "swap_in"}
    for name in names:
        assert _traces(log, name) >= 1, (name, sorted(log.programs))
        assert log.programs[name]["backend_compile"][0] >= 1, name
    counters = reg.snapshot()["counters"]
    # brought up to the process's totals: the weights' init program, which
    # ran before this registry was made, is in them
    assert _traces(log, "_init_cast") >= 1
    assert counters["entry/traces"] == log.totals["entry/traces"] \
        > len(names)
    assert counters["entry/setup_weights_ms"] == eng.setup_weights.ms > 0
    assert counters["entry/setup_cache_ms"] > 0
    assert 0 < counters["entry/setup_warmup_repeat_ms"] \
        < counters["entry/setup_warmup_ms"]
    assert counters["entry/traces_after_warm"] == 0
    by_name = {}
    for s in tracer.spans:
        by_name.setdefault(s.name, []).append(s)
    assert set(by_name) == {"setup_weights", "setup_cache", "setup_warmup",
                            "warmup_pass"}
    [whole] = by_name["setup_warmup"]
    passes = by_name["warmup_pass"]
    assert [p.attrs["pass"] for p in passes] == [0, 1]
    assert all(p.parent_id == whole.span_id
               and whole.start <= p.start <= p.end <= whole.end
               for p in passes)
    assert passes[0].end <= passes[1].start
    assert passes[1].duration * 1e3 == pytest.approx(
        counters["entry/setup_warmup_repeat_ms"])
    # a second engine on the same weights and registry: they are not
    # counted twice, the second cache and warm-up are
    twin = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                         telemetry=reg, tenants=False)
    again = reg.snapshot()["counters"]
    assert again["entry/setup_weights_ms"] == counters["entry/setup_weights_ms"]
    assert again["entry/setup_cache_ms"] > counters["entry/setup_cache_ms"]
    for engine in (srv, twin):
        engine.close()


def test_recompile_after_warmup_is_counted_and_spanned():
    """A program jitted anew after warm-up (its prefill bucket's program
    is thrown away, as if ``warmup()`` had left the bucket out) raises
    ``entry/traces_after_warm`` by the traces it caused and leaves
    ``compile`` spans named as in ``program_cache_sizes()`` inside the
    ``iteration`` that waited for them."""
    _, eng = _inference_engine()
    reg, tracer = MetricsRegistry(), SpanTracer()
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        telemetry=reg, tracer=tracer, tenants=False)
    srv.warmup()
    log = compile_log()
    srv.run(_requests(2), warmup=False)
    assert reg.counter("entry/traces_after_warm").value == 0
    assert not [s for s in tracer.spans if s.name == "compile"]
    _drop_bucket_16(srv, eng)
    traces = log.totals["entry/traces"]
    before = _traces(log, "prefill_16")
    results = srv.run(_requests(2), warmup=False)
    assert len(results) == 2
    caused = log.totals["entry/traces"] - traces
    assert _traces(log, "prefill_16") == before + 1 and caused >= 1
    assert reg.counter("entry/traces_after_warm").value == caused
    compiles = [s for s in tracer.spans if s.name == "compile"]
    assert {s.attrs["program"] for s in compiles} >= {"prefill_16"}
    mine = [s for s in compiles if s.attrs["program"] == "prefill_16"]
    assert [s.attrs["stage"] for s in mine] == ["trace", "lower",
                                                "backend_compile"]
    assert "prefill_16" in srv.program_cache_sizes()
    iterations = {s.span_id: s for s in tracer.spans
                  if s.name == "iteration"}
    parents = {s.parent_id for s in mine}
    assert len(parents) == 1 and parents <= set(iterations)
    [parent] = [iterations[p] for p in parents]
    slack = 0.005     # JAX stamps on time.time(), the engine on monotonic
    for s in mine:
        assert s.trace_id == parent.trace_id and s.duration > 0
        assert parent.start - slack <= s.start <= s.end <= parent.end + slack
    # outside step(): the caller's own jnp work is not the engine's
    import jax.numpy as jnp

    counted = reg.counter("entry/traces_after_warm").value
    followed = reg.counter("entry/traces").value
    jnp.arange(7).reshape(7, 1) * 3.5
    assert log.totals["entry/traces"] > traces + caused
    assert reg.counter("entry/traces_after_warm").value == counted
    assert reg.counter("entry/traces").value == followed
    # closed: the same recompile, inside step(), is no longer told
    srv.close()
    _drop_bucket_16(srv, eng)
    told = log.totals["entry/traces"]
    assert len(srv.run(_requests(2), warmup=False)) == 2
    assert log.totals["entry/traces"] > told
    assert reg.counter("entry/traces").value == followed
    assert reg.counter("entry/traces_after_warm").value == counted


def test_another_engines_warmup_is_not_this_engines_recompile():
    """The log is the process's and program names are not an engine's
    own: a second engine that warms up while this one is warm, even with
    requests pending, raises neither this one's ``entry/traces_after_warm``
    nor its ``entry/*``, and leaves it no ``compile`` span."""
    _, eng = _inference_engine()
    reg, tracer = MetricsRegistry(), SpanTracer()
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        telemetry=reg, tracer=tracer, tenants=False)
    srv.warmup()
    for r in _requests(2):
        srv.submit(r)
    srv.step()
    assert srv.pending
    before = dict(reg.snapshot()["counters"])
    log = compile_log()
    traces = log.totals["entry/traces"]
    other_reg = MetricsRegistry()
    other = ServingEngine(eng, num_slots=2, max_len=64, buckets=(24,),
                          telemetry=other_reg, tenants=False)
    other.warmup()          # prefill_24 is new; decode is traced again
    assert log.totals["entry/traces"] > traces
    assert _traces(log, "prefill_24") >= 1
    assert reg.snapshot()["counters"] == before
    assert other_reg.counter("entry/traces").value \
        == log.totals["entry/traces"]
    assert other_reg.counter("entry/traces_after_warm").value == 0
    while srv.pending:
        srv.step()
    assert reg.counter("entry/traces_after_warm").value == 0
    assert not [s for s in tracer.spans if s.name == "compile"]
    for engine in (srv, other):
        engine.close()


def test_recompile_under_a_virtual_clock_keeps_the_timeline():
    """On a virtual clock a compile's real stamps mean nothing: the span
    has no length, at the iteration's own instant."""
    _, eng = _inference_engine()
    tracer = SpanTracer()
    clock = FakeClock(auto_dt=0.001)
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        telemetry=False, tracer=tracer, tenants=False,
                        time_fn=clock.time)
    srv.warmup()
    assert {s.name: s.duration for s in tracer.spans} == {
        "setup_weights": 0.0, "setup_cache": 0.0, "setup_warmup": 0.0,
        "warmup_pass": 0.0}
    _drop_bucket_16(srv, eng)
    srv.run(_requests(1), warmup=False)
    compiles = [s for s in tracer.spans if s.name == "compile"
                and s.attrs["program"] == "prefill_16"]
    iterations = {s.span_id: s for s in tracer.spans if s.name == "iteration"}
    assert len(compiles) == 3
    for s in compiles:
        assert s.duration == 0.0 and s.start == iterations[s.parent_id].start
    srv.close()


def test_warm_loop_never_calls_a_listener():
    """Fifty warm iterations: JAX has nothing to tell, so the log's
    listeners, and any other, are called zero times."""
    import jax.monitoring as monitoring

    _, eng = _inference_engine()
    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=4, max_len=64, buckets=(16, 32),
                        telemetry=reg, tenants=False)
    srv.warmup()
    srv.run(_requests(4, max_new=4), warmup=False)    # every path once
    log = compile_log()
    calls = []

    def heard(event, *a, **kw):
        calls.append(event)

    registered = ((monitoring.register_event_listener,
                   monitoring.unregister_event_listener),
                  (monitoring.register_event_duration_secs_listener,
                   monitoring.unregister_event_duration_listener),
                  (monitoring.register_event_time_span_listener,
                   monitoring.unregister_event_time_span_listener),
                  (monitoring.register_scalar_listener,
                   monitoring.unregister_scalar_listener))
    for register, _ in registered:
        register(heard)
    try:
        totals = dict(log.totals)
        for r in _requests(12, plen=9, max_new=40):
            srv.submit(r)
        srv._run_t0 = 0.0
        steps, now = 0, 0.0
        while srv.pending:
            now += 0.01
            srv.step(now)
            steps += 1
        assert steps >= 50
        assert calls == []
        assert log.totals == totals
    finally:
        for _, unregister in registered:
            unregister(heard)
    assert srv.recompile_count() == 0
    srv.close()


def test_initialize_stamps_weights_and_first_step_and_pins_retraces():
    """``initialize()`` on a tiny model: the weights phase and the first
    step are positive, and the fused step is traced as often as the engine
    traces it today. S7's ``perf_opt`` PR changes this pin in the open."""
    from deepspeed_tpu import telemetry

    groups.reset()
    telemetry.reset_registry()
    cfg = GPT2Config(vocab_size=256, max_seq_len=32, num_layers=1,
                     hidden_size=32, num_heads=2)
    log = compile_log()
    before = _traces(log, "train_step")
    lowered = log.programs.get("train_step", {}).get("lower", [0])[0]
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg), config={
            "train_batch_size": 8, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 0,
            "telemetry": {"enabled": True, "sync_interval": 2},
        })
    reg = telemetry.get_registry()
    assert reg.counter("entry/setup_weights_ms").value > 0
    assert "entry/setup_first_step_ms" not in reg.snapshot()["counters"]
    rng = np.random.RandomState(0)
    for _ in range(4):
        ids = rng.randint(0, cfg.vocab_size, size=(1, 8, 17)).astype(np.int32)
        engine.train_batch_from_stacked(
            {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]})
    counters = reg.snapshot()["counters"]
    first = counters["entry/setup_first_step_ms"]
    assert first > 0
    # THE PIN: the step is traced twice an engine, once when it is first
    # called and once more by the MFU probe's lower() at the first fence
    # (ROADMAP S7), and lowered as often
    assert _traces(log, "train_step") - before == 2
    assert log.programs["train_step"]["lower"][0] - lowered == 2
    assert counters["entry/retraces"] >= 1
    assert counters["entry/traces"] == log.totals["entry/traces"]
    # later steps leave the stamp and the log alone
    traces = log.totals["entry/traces"]
    for _ in range(3):
        ids = rng.randint(0, cfg.vocab_size, size=(1, 8, 17)).astype(np.int32)
        engine.train_batch_from_stacked(
            {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]})
    assert reg.counter("entry/setup_first_step_ms").value == first
    assert log.totals["entry/traces"] == traces
    engine.destroy()
    assert engine._compile_sub is None


def test_first_step_without_a_fence_says_so(tmp_path):
    """``sync_interval`` 0 leaves the engine no fence of its own: the
    reading ends at the dispatch and the span says ``fenced=False``."""
    from deepspeed_tpu import telemetry

    groups.reset()
    telemetry.reset_registry()
    cfg = GPT2Config(vocab_size=256, max_seq_len=32, num_layers=1,
                     hidden_size=32, num_heads=2)
    engine, *_ = deepspeed_tpu.initialize(
        model=GPT2Model(cfg), config={
            "train_batch_size": 8, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "steps_per_print": 0,
            "telemetry": {"enabled": True, "sync_interval": 0,
                          "spans": True},
        })
    ids = np.random.RandomState(1).randint(
        0, cfg.vocab_size, size=(1, 8, 17)).astype(np.int32)
    engine.train_batch_from_stacked(
        {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]})
    assert telemetry.get_registry().counter(
        "entry/setup_first_step_ms").value > 0
    [span] = [s for s in engine.tracer.spans if s.name == "setup_first_step"]
    assert span.attrs == {"fenced": False}
    assert engine._setup_first_step is None
    engine.destroy()
