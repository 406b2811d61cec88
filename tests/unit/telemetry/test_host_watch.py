"""The host watch (ISSUE 57): the collector's hook and the stall rule in both
engines' loops. All in-process, on CPU, at tiny sizes.

Timing goes through an injected clock where the rule itself is tested (a
watch driven by hand) and through one-sided bounds where a real engine is
paused: a pause is a ``time.sleep`` or a loop that burns a stated amount of
the thread's own CPU time, so a loaded machine can only lengthen what is
asserted from below, and CPU time is never asserted from above against wall.
"""

import gc
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from simple_model import SimpleModel, random_batch  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import Request, ServingEngine, poisson_trace
from deepspeed_tpu.serving import engine as serving_engine
from deepspeed_tpu.telemetry import SpanTracer, host_watch
from deepspeed_tpu.telemetry.compile_log import CompileLog
from deepspeed_tpu.telemetry.host_watch import (ServingWatch, TrainWatch,
                                                STALL_FIELDS)
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.testing import FakeClock
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.observability, pytest.mark.quick]

PAUSE_S = 0.15
_ENGINE = {}


def _inference_engine():
    if "eng" not in _ENGINE:
        groups.reset()
        cfg = GPT2Config.tiny()
        _ENGINE["cfg"] = cfg
        _ENGINE["eng"] = deepspeed_tpu.init_inference(
            GPT2Model(cfg), dtype="fp32", max_out_tokens=128)
    return _ENGINE["cfg"], _ENGINE["eng"]


def _serving(**kw):
    _, eng = _inference_engine()
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (16, 64))
    kw.setdefault("tenants", False)
    return ServingEngine(eng, **kw)


def _requests(n=8, seed=0, rate=400.0):
    cfg, _ = _inference_engine()
    return poisson_trace(np.random.RandomState(seed), n, rate=rate,
                         prompt_lens=(4, 6, 9), max_new_choices=(6, 8, 10),
                         vocab_size=cfg.vocab_size)


class _Clock(FakeClock):
    def advance(self, seconds):
        super().advance(seconds)
        return self.now


class _Sink:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(record)

    def flush(self):
        pass

    def stalls(self):
        return [r for r in self.records if r.get("name") == "host_stall"]


def _sleep():
    time.sleep(PAUSE_S)


def _burn():
    """Python's own work for PAUSE_S of this thread's CPU time."""
    end = time.thread_time() + PAUSE_S
    while time.thread_time() < end:
        sum(range(1000))


def _pause_in(monkeypatch, annotation, pause, nth=6):
    """Pause once, inside the ``nth`` phase that opens ``annotation``."""
    enter = serving_engine._Phase.__enter__
    seen = [0]

    def paused(self):
        out = enter(self)
        if self.annotation == annotation:
            seen[0] += 1
            if seen[0] == nth:
                pause()
        return out
    monkeypatch.setattr(serving_engine._Phase, "__enter__", paused)


@pytest.fixture
def low_floor(monkeypatch):
    """The rule's floor under the pauses the tests make, over what a loaded
    CPU does to a phase of a tiny model."""
    monkeypatch.setattr(host_watch, "FLOOR_MS", 40.0)


@pytest.fixture
def lone_hook():
    """``gc.callbacks`` and the hook's subscribers as a process without other
    engines has them: the tests that ran before in this worker may have left
    engines alive, whose watches keep the hook installed."""
    hook = host_watch._hook
    subs, installed = hook.subs, hook in gc.callbacks
    hook.subs = []
    if installed:
        gc.callbacks.remove(hook)
    yield hook
    hook.subs = subs + hook.subs
    if hook.subs and hook not in gc.callbacks:
        gc.callbacks.append(hook)


# ------------------------------------------------------------ the collector
def test_a_collection_lands_in_the_counter_and_in_a_span_under_an_iteration(
        monkeypatch):
    sink, tracer = _Sink(), SpanTracer()
    registry = MetricsRegistry(sink)
    srv = _serving(telemetry=registry, tracer=tracer)
    idle = MetricsRegistry()
    in_setup = _serving(telemetry=idle)     # built, never stepped
    _pause_in(monkeypatch, "dstpu/serving_commit", gc.collect, nth=3)
    srv.run(_requests())
    assert registry.counter("host/gc_pause_ms").value > 0
    spans = [s for s in tracer.spans if s.name == "host_gc"]
    assert spans and spans[0].attrs["generation"] == 2
    assert "collected" in spans[0].attrs
    iterations = {s.span_id: s for s in tracer.spans if s.name == "iteration"}
    parent = iterations[spans[0].parent_id]
    assert parent.start <= spans[0].start and spans[0].end <= parent.end
    # an engine in set-up is told and counts nothing: entry/* owns that
    assert idle.counter("host/gc_pause_ms").value == 0
    assert idle.counter("host/stall_ms").value == 0
    srv.close(), in_setup.close()


def test_a_pause_between_two_steps_is_added_when_the_next_begins():
    registry = MetricsRegistry()
    watch = ServingWatch(registry, at_work=lambda: False,
                         holds_work=lambda gap, now: 0.0)
    try:
        gc.collect()                          # before any step: set-up
        assert registry.counter("host/gc_pause_ms").value == 0
        watch.enter(0.0), watch.leave()
        gc.collect()                          # behind a step: nobody's yet
        assert registry.counter("host/gc_pause_ms").value == 0
        watch.enter(0.0)
        assert registry.counter("host/gc_pause_ms").value > 0
        assert watch.gc_oldest == 1
    finally:
        watch.close()


def test_one_hook_for_two_engines_and_none_behind_them(lone_hook):
    before = list(gc.callbacks)
    a = _serving(telemetry=MetricsRegistry())
    b = _serving(telemetry=MetricsRegistry())
    assert len(gc.callbacks) == len(before) + 1
    a.close()
    assert len(gc.callbacks) == len(before) + 1
    b.close(), b.close()                       # twice is once
    assert list(gc.callbacks) == before


def test_a_dropped_engine_leaves_the_hook_and_a_bare_one_never_took_it(
        lone_hook):
    before = list(gc.callbacks)
    bare = _serving(telemetry=None)
    assert bare._watch is None and list(gc.callbacks) == before
    bare.run(_requests(3))
    assert list(gc.callbacks) == before
    dropped = _serving(telemetry=MetricsRegistry())
    assert len(gc.callbacks) == len(before) + 1
    del dropped
    gc.collect()
    assert list(gc.callbacks) == before


def test_a_collection_takes_the_open_phases_place_on_the_profile(monkeypatch):
    """No two ``dstpu/*`` annotations of the thread overlap: the open phase
    is closed before ``dstpu/host_gc`` opens and opened anew behind it."""
    log = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append(("open", self.name))

        def __exit__(self, *exc):
            log.append(("close", self.name))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
    srv = _serving(telemetry=MetricsRegistry())
    _pause_in(monkeypatch, "dstpu/serving_commit", gc.collect, nth=2)
    srv.run(_requests(3))
    srv.close()
    at = log.index(("open", "dstpu/host_gc"))
    assert log[at - 1] == ("close", "dstpu/serving_commit")
    assert log[at + 1:at + 3] == [("close", "dstpu/host_gc"),
                                  ("open", "dstpu/serving_commit")]
    depth = 0
    for what, _ in log:
        depth += 1 if what == "open" else -1
        assert 0 <= depth <= 1
    assert depth == 0


# ------------------------------------------------- stalls of the serving loop
@pytest.mark.parametrize("annotation,phase", [
    ("dstpu/serving_admit", "schedule"),
    ("dstpu/serving_upload", "upload"),
    ("dstpu/serving_launch", "launch"),
    ("dstpu/serving_fetch", "fetch"),
    ("dstpu/serving_commit", "commit"),
])
def test_a_sleep_in_a_phase_is_flagged_with_that_phase(
        annotation, phase, monkeypatch, low_floor):
    sink, tracer = _Sink(), SpanTracer()
    registry = MetricsRegistry(sink)
    srv = _serving(telemetry=registry, tracer=tracer)
    _pause_in(monkeypatch, annotation, _sleep)
    srv.run(_requests())
    srv.close()
    stalls = [s for s in sink.stalls() if s["phase"] == phase]
    assert stalls, sink.stalls()
    stall = max(stalls, key=lambda s: s["wall_ms"])
    assert set(stall) >= set(STALL_FIELDS)
    assert stall["wall_ms"] >= PAUSE_S * 1e3
    assert stall["thread_cpu_ms"] < 0.5 * stall["wall_ms"]  # asleep, not busy
    assert stall["iteration"] >= 1 and stall["gc_oldest"] == 0
    if phase == "fetch":
        assert stall["flight_age_ms"] >= stall["wall_ms"]
        assert 0 < stall["expected_ms"] < stall["wall_ms"]
    assert registry.counter("host/stall_ms").value >= \
        stall["wall_ms"] - stall["expected_ms"]
    assert registry.counter("host_stall").value == len(sink.stalls())
    spans = [s for s in tracer.spans if s.name == "host_stall"
             and s.attrs["phase"] == phase]
    iterations = {s.span_id for s in tracer.spans if s.name == "iteration"}
    assert spans and all(s.parent_id in iterations for s in spans)
    assert max(s.duration for s in spans) >= PAUSE_S


def test_a_busy_loop_reads_as_the_threads_own_work(monkeypatch, low_floor):
    sink = _Sink()
    srv = _serving(telemetry=MetricsRegistry(sink))
    _pause_in(monkeypatch, "dstpu/serving_commit", _burn)
    srv.run(_requests())
    srv.close()
    stall = max((s for s in sink.stalls() if s["phase"] == "commit"),
                key=lambda s: s["wall_ms"])
    assert stall["wall_ms"] >= PAUSE_S * 1e3
    assert stall["thread_cpu_ms"] >= 0.8 * PAUSE_S * 1e3
    assert stall["process_cpu_ms"] >= 0.8 * PAUSE_S * 1e3


def test_the_callers_pause_is_a_stall_only_while_the_engine_holds_work(
        low_floor):
    sink = _Sink()
    srv = _serving(telemetry=MetricsRegistry(sink))
    srv.warmup()
    srv._run_t0 = t0 = time.monotonic()
    for r in _requests(2, rate=1e6):
        srv.submit(r)
    late = Request(rid=99, prompt=[1, 2, 3], max_new_tokens=2,
                   arrival_time=3600.0)
    srv.submit(late)
    for _ in range(3):
        srv.step(time.monotonic() - t0)
    assert any(s is not None for s in srv._slots)
    _sleep()                                   # slots occupied: a stall
    while any(s is not None for s in srv._slots):
        srv.step(time.monotonic() - t0)
    assert [s["phase"] for s in sink.stalls()] == ["caller"]
    assert sink.stalls()[0]["wall_ms"] >= PAUSE_S * 1e3
    _sleep()                                   # empty, the next not yet due
    srv.step(time.monotonic() - t0)
    assert len(sink.stalls()) == 1
    srv.close()


def _watch(clock, **kw):
    kw.setdefault("at_work", lambda: True)
    kw.setdefault("holds_work", lambda gap, now: gap)
    return ServingWatch(MetricsRegistry(_Sink()), clock=clock, **kw)


class _Flight:
    def __init__(self, t_launch=0.0, behind_chunk=False):
        self.t_launch, self.behind_chunk = t_launch, behind_chunk


def test_a_wait_is_judged_against_its_programs_own_waits():
    clock = _Clock()
    watch = _watch(clock.time)
    sink = watch.registry.sink
    watch.enter(0.0)
    try:
        # a prefill of a warm bucket is long and is no stall: its own kind
        for wall in (1.0, 1.1, 0.9, 1.2, 1.3):
            watch.last_t = clock.advance(wall)
            watch.prefill_done(16384, 8, clock.time() - wall)
        assert not sink.stalls()
        # nor is the first of another length, whatever it takes
        watch.last_t = clock.advance(9.0)
        watch.prefill_done(16384, 5, clock.time() - 9.0)
        assert not sink.stalls()
        # one queued behind another began when the first one's fetch came
        t_call = clock.time()
        watch.last_t = clock.advance(1.0)
        watch.prefill_done(16384, 8, t_call)
        watch.last_t = clock.advance(1.0)
        watch.prefill_done(16384, 8, t_call)   # 2 s since its call, 1 s run
        assert not sink.stalls()
        watch.last_t = clock.advance(3.5)      # 3 x 1.0 + 0.05 < 3.5
        watch.prefill_done(16384, 8, clock.time() - 3.5)
        assert [s["phase"] for s in sink.stalls()] == ["prefill"]
        assert sink.stalls()[0]["expected_ms"] == pytest.approx(1000.0)
        # decode fetches: 5 ms each; 60 ms is inside 3 x 5 + 50, 70 is not
        for _ in range(8):
            watch.phase("dstpu/serving_fetch", 0.005, clock.advance(0.005),
                        _Flight())
        watch.phase("dstpu/serving_fetch", 0.060, clock.advance(0.060),
                    _Flight())
        assert len(sink.stalls()) == 1
        # one behind an unfetched prefill chunk waits for the chunk: no word
        watch.phase("dstpu/serving_fetch", 2.0, clock.advance(2.0),
                    _Flight(behind_chunk=True))
        assert len(sink.stalls()) == 1
        t_launch = clock.time() - 0.002
        watch.phase("dstpu/serving_fetch", 0.070, clock.advance(0.070),
                    _Flight(t_launch))
        stall = sink.stalls()[-1]
        assert stall["phase"] == "fetch" and len(sink.stalls()) == 2
        assert stall["flight_age_ms"] == pytest.approx(72.0)
        assert watch.registry.counter("host/stall_ms").value == \
            pytest.approx(2500.0 + 65.0)
    finally:
        watch.close()


def test_eight_warnings_a_watch_and_the_recorder_over_a_second(monkeypatch):
    lines, dumps = [], []
    monkeypatch.setattr(host_watch.logger, "warning", lines.append)

    class Recorder:
        def trigger(self, reason, **context):
            dumps.append((reason, context))
    clock = _Clock()
    watch = _watch(clock.time, recorder=Recorder())
    watch.enter(0.0)
    try:
        for i in range(12):
            watch.host_phase("commit", 0.2 if i else 1.5, clock.advance(2.0))
        assert len(lines) == host_watch.MAX_WARNINGS
        assert all(line.startswith("host_stall phase=commit wall_ms=")
                   and "\n" not in line for line in lines)
        assert all(f" {field}=" in " " + line for field in STALL_FIELDS
                   for line in lines)
        assert watch.registry.counter("host_stall").value == 12
        assert [reason for reason, _ in dumps] == ["host_stall"]
        assert dumps[0][1]["wall_ms"] == pytest.approx(1500.0)
    finally:
        watch.close()


def test_greedy_tokens_are_the_same_with_and_without_a_registry():
    out = {}
    for name, registry in (("bare", None), ("watched", MetricsRegistry())):
        clock = FakeClock(auto_dt=0.001)
        srv = _serving(telemetry=registry, time_fn=clock.time)
        out[name] = {r.rid: list(r.tokens) for r in srv.run(_requests())}
        # a virtual clock is never read on the watch's behalf
        assert registry is None or srv._watch.clock is time.perf_counter
        srv.close()
    assert out["bare"] == out["watched"] and len(out["bare"]) == 8


def test_the_compile_log_names_the_stage_a_stall_may_have_stood_behind():
    log = CompileLog()
    assert log.stage_since(0.0) is None
    trace = "/jax/core/compile/jaxpr_trace_duration"
    log.on_scalar(trace, 10.0, fun_name="decode")
    assert log.stage_since(50.0) == "decode:trace"          # open now
    log.on_time_span(trace, 10.0, 12.0, fun_name="decode")
    assert log.stage_since(11.0) == "decode:trace"          # closed inside
    assert log.stage_since(12.5) is None                    # closed before


# --------------------------------------------------- stalls of the train loop
def _train_engine():
    groups.reset()
    engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(), config={
        "train_batch_size": 8, "steps_per_print": 0,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "telemetry": {"sync_interval": 0}})
    seeds = iter(range(1000))

    def batch():
        return jax.tree_util.tree_map(
            lambda x: x[None], random_batch(8, seed=next(seeds)))
    return engine, batch


def _stalls_of(watch):
    seen, stall = [], watch.stall

    def recording(*args, **kw):
        seen.append(stall(*args, **kw))
        return seen[-1]
    watch.stall = recording
    return seen


def test_the_train_loop_names_the_callers_pause_and_the_batch_put(
        monkeypatch, lone_hook):
    before = list(gc.callbacks)
    engine, batch = _train_engine()
    assert len(gc.callbacks) == len(before) + 1
    stalls = _stalls_of(engine._watch)
    pause = 0.4
    for _ in range(7):
        engine.train_batch_from_stacked(batch())
    time.sleep(pause)                           # between two calls
    for _ in range(3):
        engine.train_batch_from_stacked(batch())
    put, calls = jax.device_put, [0]

    def slow_put(*args, **kw):
        calls[0] += 1
        if calls[0] == 1:
            time.sleep(pause)
        return put(*args, **kw)
    monkeypatch.setattr(jax, "device_put", slow_put)
    for _ in range(2):
        engine.train_batch_from_stacked(batch())
    by_phase = {}
    for s in stalls:
        by_phase.setdefault(s["phase"], []).append(s)
    for phase in ("caller", "batch_put"):
        stall = max(by_phase[phase], key=lambda s: s["wall_ms"])
        assert stall["wall_ms"] >= pause * 1e3
        assert stall["thread_cpu_ms"] < 0.5 * stall["wall_ms"]
        assert stall["flight_age_ms"] is None
    assert engine.telemetry.counter("host/stall_ms").value > 0
    engine.destroy()
    assert list(gc.callbacks) == before


def test_the_train_rule_on_a_clock_of_its_own():
    clock = _Clock()
    watch = TrainWatch(MetricsRegistry(_Sink()), at_work=lambda: True,
                       clock=clock.time)
    sink = watch.registry.sink

    def step(put=0.01, run=0.02, after=0.01, caller=0.76, fence=False):
        watch.enter(clock.time())
        for phase, wall in zip(watch.phases, (put, run, after)):
            with phase:
                if fence and phase is watch.phases[-1]:
                    watch.fenced()
                clock.advance(wall)
        watch.leave()
        clock.advance(caller)
    try:
        for _ in range(6):
            step()
        step(after=1.6, fence=True)     # the engine's own fence: no word
        step(caller=0.0)                # the pipeline ran dry behind it
        step()
        assert not sink.stalls()
        step(run=0.9)                   # 1.7 s: over 1.5 x 0.8 + 0.05
        step()
        assert [s["phase"] for s in sink.stalls()] == ["dispatch"]
        stall = sink.stalls()[0]
        assert stall["wall_ms"] == pytest.approx(1680.0)
        assert stall["expected_ms"] == pytest.approx(800.0)
        step(caller=0.76 + 0.85)        # the caller drained its queue: two
        step()                          # medians and a little, nobody starved
        assert len(sink.stalls()) == 1
        step(caller=0.76 + 1.3)         # 2.1 s: over 2.5 x 0.8 + 0.05
        step()
        step(fence=True, caller=3.0)    # behind a fence the caller's side
        step()
        assert [s["phase"] for s in sink.stalls()] == ["dispatch", "caller",
                                                       "caller"]
        watch.forget()                  # a checkpoint: the gap judges nothing
        clock.advance(30.0)
        step()
        assert len(sink.stalls()) == 3
        assert watch.registry.counter("host/stall_ms").value == \
            pytest.approx(880.0 + 1300.0 + 3000.0 - 800.0)
    finally:
        watch.close()
