"""Host watch: what the host process was doing when a step took too long.

One run in ten to twenty of every benchmark cell stalls on the host for 0.1
to 2 s, and nothing outside the program can say why: a profile names the idle
gap by the phase annotation that was open, which says where the thread stood
and not why it stood there. This module is the program's own answer, on the
three things an engine already has (its ``MetricsRegistry``, its
``SpanTracer`` when armed, ``jax.profiler.TraceAnnotation``). It is on
wherever an engine has a registry and absent without one: no option, no
environment variable, no exporter.

**The garbage-collection hook** (:class:`_GcHook`; a :class:`HostWatch` subscribes as it
is made). One entry in
``gc.callbacks``, installed by the first subscriber and removed with the
last. It times every collection with two reads of ``time.perf_counter`` and
tells each subscribed watch, which adds the pause to ``host/gc_pause_ms`` of
its registry once its engine runs (from its first step behind set-up; a
pause that falls between two steps is added when the next one begins, so
what comes after the last step is nobody's). For a collection of the oldest
generation the hook also opens ``dstpu/host_gc`` on the profile's clock,
taking the open phase's place as a nested ``_Phase`` does (the outer
annotation is closed and reopened behind it), so that a reader which names
an idle gap by the annotation that covers most of it names a gap a
collection made ``dstpu/host_gc``; and a watch with a tracer records span
``host_gc`` (attrs ``generation``, ``collected``).

**The stall rule**, written once (:meth:`HostWatch.host_phase`,
:meth:`HostWatch.device_wait`, :class:`TrainWatch`), constants below:

* a phase that is host work alone, or the caller's gap between two steps
  while the engine holds work, that lasted over ``FLOOR_MS``;
* a wait on the device that lasted ``FLOOR_MS`` more than ``WAIT_FACTOR``
  times the running median of that program's own recent waits;
* a training step's call-to-call gap that lasted ``FLOOR_MS`` more than
  ``TRAIN_FACTOR`` times the running median of the last ``TRAIN_WINDOW``,
  attributed to the segment whose excess over its own median is largest;
  where that is the caller's side, ``TRAIN_CALLER_FACTOR`` times: a caller
  that waits for the steps it has queued (a loss read every so often, the
  end of a window) comes back a step or two late, the queue absorbs it and
  the device never starves, and on the chip a sound traced run read exactly
  two medians and 52 ms there.

**What a stall leaves behind** (:meth:`HostWatch.stall`): its excess over
the expectation in counter ``host/stall_ms`` (there from the start, at 0);
an event ``host_stall`` in the registry (and so in the JSONL sink and the
flight recorder's ring, where armed) and, with a tracer, a span
``host_stall``, both with the fields of :data:`STALL_FIELDS`; one
``logger.warning`` with the same fields on one line, ``MAX_WARNINGS`` a
watch at most; and, over ``RECORDER_MS``, a trigger of the engine's flight
recorder where it has one.

What it costs: without a registry nothing (the engines test ``is None``
where they already did). With one, a serving iteration reads the clock
twelve times and ``thread_time``, ``process_time`` and ``getrusage`` once
(:meth:`HostWatch.mark`), and ``/proc/stat``'s first line once a second; a
training step reads the clock three times more than it did. A collection
costs the hook two clock reads and a counter. Measured: PERF.md section 6,
PR 57 (no end-to-end metric of gpt2-large's two cells moved).
"""

from __future__ import annotations

import gc
import os
import resource
import statistics
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax

from deepspeed_tpu.telemetry.compile_log import _Subscription, compile_log
from deepspeed_tpu.utils.logging import logger

# ------------------------------------------------------------ the rule
FLOOR_MS = 50.0        # host work alone, or a wait's excess, under this is no stall
WAIT_FACTOR = 3.0      # a wait on the device against its program's own median
FETCH_WINDOW = 64      # decode fetches the running median is over
PREFILL_WINDOW = 16    # whole prefills of one (bucket, token blocks)
TRAIN_FACTOR = 1.5     # a training step's gap against the running median
TRAIN_CALLER_FACTOR = 2.5  # the same where the excess is the caller's (below)
TRAIN_WINDOW = 16
MIN_HISTORY = 3        # readings before a median judges anything
MAX_WARNINGS = 8       # warning lines a watch prints; the counters go on
RECORDER_MS = 1000.0   # a stall over this triggers the flight recorder

GC_ANNOTATION = "dstpu/host_gc"
STALL_FIELDS = ("phase", "wall_ms", "expected_ms", "thread_cpu_ms",
                "process_cpu_ms", "process_sys_ms", "gc_ms", "gc_oldest",
                "nivcsw", "nvcsw", "majflt", "minflt", "oublock", "steal_ms",
                "compile_open", "flight_age_ms", "iteration", "t")
STEAL_EVERY_S = 1.0    # how old the reading of stolen time may grow

_OLDEST = len(gc.get_threshold()) - 1


# ------------------------------------------------------------- the hook
class _GcHook:
    """The process's one ``gc.callbacks`` entry and its subscribers. A
    subscriber's ``on_stage`` is told ``("start", info, t0, None)`` when a
    collection of the oldest generation opens and ``("stop", info, t0, t1)``
    when any collection closes, ``info`` being the collector's own dict."""

    def __init__(self):
        self._lock = threading.Lock()
        self.subs: List[_Subscription] = []
        self._t0 = 0.0
        self._note = None

    def __call__(self, phase: str, info: dict) -> None:
        # collections do not nest and the GIL is held: one ``_t0`` does
        if phase == "start":
            if info["generation"] >= _OLDEST:
                for sub in self.subs:
                    sub.on_stage("start", info, 0.0, None)
                self._note = jax.profiler.TraceAnnotation(GC_ANNOTATION)
                self._note.__enter__()
            self._t0 = time.perf_counter()
            return
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        for sub in self.subs:
            sub.on_stage("stop", info, self._t0, t1)

    def subscribe(self, sub: _Subscription) -> None:
        with self._lock:
            self.subs = self.subs + [sub]     # the hook reads it unlocked
            if self not in gc.callbacks:
                gc.callbacks.append(self)

    def unsubscribe(self, sub: Optional[_Subscription]) -> None:
        with self._lock:
            self.subs = [s for s in self.subs if s is not sub]
            if not self.subs and self in gc.callbacks:
                gc.callbacks.remove(self)


_hook = _GcHook()


def unsubscribe(sub: Optional[_Subscription]) -> None:
    """Stop telling a watch; the last one takes the hook out of
    ``gc.callbacks``. Safe to call twice, and from ``weakref.finalize``."""
    _hook.unsubscribe(sub)


_TICK_MS = 1e3 / os.sysconf("SC_CLK_TCK")


def _stolen_ms() -> Optional[float]:
    """Milliseconds the hypervisor ran something else while a CPU of this
    guest wanted to run, all CPUs, since boot (``/proc/stat``'s ``steal``);
    None where there is no such file or column."""
    try:
        with open("/proc/stat", "rb") as f:
            return int(f.readline().split()[8]) * _TICK_MS
    except (OSError, IndexError, ValueError):
        return None


def _median(window) -> Optional[float]:
    return statistics.median(window) if len(window) >= MIN_HISTORY else None


class HostWatch:
    """One engine's watch. The engine gives it its registry and, as
    callables, what only it knows: ``at_work()`` (is one of its entry points
    running now, as ``compile_log`` asks), ``span(name, t0, t1, **attrs)``
    (an interval of ``clock`` on the engine's own: recorded on its trace,
    under what is open there, if it has a tracer; returns where ``t1``
    lies), ``gc_span`` (the same for stamps of ``time.perf_counter``),
    ``open_phase()`` (the object whose annotation
    is open on its thread: ``.live`` the annotation, ``._open()`` opens it
    anew) and ``recorder`` (its flight recorder, or None). ``clock`` is the
    monotonic clock the engine's phases are stamped with."""

    def __init__(self, registry, *, at_work: Callable[[], bool],
                 span: Optional[Callable] = None,
                 gc_span: Optional[Callable] = None,
                 open_phase: Optional[Callable] = None,
                 recorder=None, clock: Callable[[], float] = None):
        self.registry = registry
        self.clock = clock or time.perf_counter
        self._at_work = at_work
        self._span = span
        self._gc_span = gc_span
        self._open_phase = open_phase
        self.recorder = recorder
        self.running = False          # a step behind set-up has begun
        self.iteration = 0            # ordinal of the step that runs
        self.thread = 0               # the thread that steps the engine
        self.warned = 0
        self.last_t = 0.0             # the newest stamp of ``clock``
        # the collector's pauses while the engine runs, for a stall's fields
        self.gc_ms = 0.0
        self.gc_oldest = 0
        self._gc_between = 0.0        # pauses since the engine's last step
        self._suspended = None        # the phase a collection stands in for
        self._base: Optional[tuple] = None
        self._stolen = (0.0, None)    # when stolen time was last read, and it
        # there from the start: a sound run reads 0 and not nothing
        registry.counter("host/stall_ms")
        registry.counter("host/gc_pause_ms")
        self.sub = _Subscription(registry, self._on_gc, at_work)
        _hook.subscribe(self.sub)

    def close(self) -> None:
        unsubscribe(self.sub)

    # ------------------------------------------------------- the collector
    def _on_gc(self, stage: str, info: dict, t0: float,
               t1: Optional[float]) -> None:
        if stage == "start":
            # the collector runs on the thread that allocated: only there
            # is the engine's open annotation this thread's to close
            if (self._open_phase is not None
                    and threading.get_ident() == self.thread):
                phase = self._open_phase()
                if phase is not None:
                    phase.live.__exit__(None, None, None)
                    self._suspended = phase
            return
        suspended, self._suspended = self._suspended, None
        if suspended is not None:
            suspended._open()
        if not self.running:
            return                    # set-up: ``entry/*`` owns that
        ms = (t1 - t0) * 1e3
        self.gc_ms += ms
        if self._at_work():
            self.registry.counter("host/gc_pause_ms").inc(ms)
        else:
            self._gc_between += ms
        if info["generation"] >= _OLDEST:
            self.gc_oldest += 1
            if self._gc_span is not None:
                self._gc_span("host_gc", t0, t1,
                              generation=info["generation"],
                              collected=info["collected"])

    # ------------------------------------------------------------ a step
    def begin(self, t: float) -> None:
        """A step behind set-up begins at ``t``: the pauses since the last
        one were between two steps, and the stalls of this one are read
        against the process as it stands now."""
        self.running = True
        self.iteration += 1
        self.thread = threading.get_ident()
        self.last_t = t
        if self._gc_between:
            self.registry.counter("host/gc_pause_ms").inc(self._gc_between)
            self._gc_between = 0.0
        if t - self._stolen[0] > STEAL_EVERY_S:
            self._stolen = (t, _stolen_ms())    # a file read a second
        self.mark()

    def mark(self) -> None:
        self._base = (time.thread_time(), time.process_time(),
                      resource.getrusage(resource.RUSAGE_SELF),
                      self.gc_ms, self.gc_oldest)

    def _steal_since(self, t: float) -> Optional[float]:
        was, now = self._stolen[1], _stolen_ms()
        self._stolen = (t, now)
        return None if was is None or now is None else now - was

    # ----------------------------------------------------------- the rule
    def host_phase(self, phase: str, wall: float, t: float, **more) -> None:
        """``wall`` seconds of host work alone, ended at ``t``."""
        if wall * 1e3 > FLOOR_MS:
            self.stall(phase, wall, 0.0, t, **more)

    def device_wait(self, phase: str, window: Deque[float], wall: float,
                    t: float, **more) -> None:
        """``wall`` seconds of waiting for a program whose recent waits are
        ``window``; the wait joins them unless it was a stall."""
        if wall * 1e3 > FLOOR_MS:     # the median is sorted for only then
            expected = _median(window)
            if (expected is not None and
                    (wall - WAIT_FACTOR * expected) * 1e3 > FLOOR_MS):
                self.stall(phase, wall, expected, t, **more)
                return
        window.append(wall)

    def stall(self, phase: str, wall: float, expected: float, t: float,
              **more) -> dict:
        """Leave behind what the module's docstring lists; the fields."""
        thread0, process0, usage0, gc0, oldest0 = self._base
        usage = resource.getrusage(resource.RUSAGE_SELF)
        fields = {
            "phase": phase, "wall_ms": wall * 1e3,
            "expected_ms": expected * 1e3,
            # wall without CPU is a thread blocked or descheduled, wall
            # with CPU Python's own work; the process's CPU beyond the
            # thread's is other threads' (the compiler's, a cache write's)
            "thread_cpu_ms": (time.thread_time() - thread0) * 1e3,
            "process_cpu_ms": (time.process_time() - process0) * 1e3,
            # the part of it spent in the kernel (an unmap, a driver call)
            "process_sys_ms": (usage.ru_stime - usage0.ru_stime) * 1e3,
            "gc_ms": self.gc_ms - gc0, "gc_oldest": self.gc_oldest - oldest0,
            "nivcsw": usage.ru_nivcsw - usage0.ru_nivcsw,
            "nvcsw": usage.ru_nvcsw - usage0.ru_nvcsw,
            "majflt": usage.ru_majflt - usage0.ru_majflt,
            "minflt": usage.ru_minflt - usage0.ru_minflt,
            "oublock": usage.ru_oublock - usage0.ru_oublock,
            # what the guest was told of CPUs given to someone else, since
            # the last reading, a second old at most (0.0 on the chip's host
            # under every pause met so far: PERF.md section 6, PR 57)
            "steal_ms": self._steal_since(t),
            "compile_open": compile_log().stage_since(time.time() - wall),
            "flight_age_ms": None, "iteration": self.iteration, **more}
        self.registry.counter("host/stall_ms").inc((wall - expected) * 1e3)
        if self._span is not None:
            t = self._span("host_stall", t - wall, t, **fields)
        fields["t"] = t               # on the engine's clock
        self.registry.event("host_stall", **fields)
        if self.warned < MAX_WARNINGS:
            self.warned += 1
            logger.warning("host_stall " + " ".join(
                f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in fields.items()))
        if self.recorder is not None and fields["wall_ms"] > RECORDER_MS:
            self.recorder.trigger("host_stall", **fields)
        self.mark()                   # the next stall's fields are its own
        return fields


# ----------------------------------------------------------- serving loop
# the phases of an iteration that are host work alone, by annotation
_HOST_PHASES = {"dstpu/serving_admit": "schedule",
                "dstpu/serving_upload": "upload",
                "dstpu/serving_launch": "launch",
                "dstpu/serving_commit": "commit"}
_FETCH = "dstpu/serving_fetch"


class ServingWatch(HostWatch):
    """``ServingEngine``'s watch. ``_Phase`` stamps every phase edge and
    hands the closed phase over (:meth:`phase`); ``step()`` says where it
    begins and returns (:meth:`enter`, :meth:`leave`) and ``_land_firsts``
    where a prompt's first token arrived (:meth:`prefill_done`)."""

    def __init__(self, registry, *,
                 holds_work: Callable[[float, float], float], **kw):
        super().__init__(registry, **kw)
        # ``holds_work(gap, now)``: the seconds of the gap that ends at the
        # engine's ``now`` during which the engine held work
        self._holds_work = holds_work
        self._t_leave: Optional[float] = None
        self._fetches: Deque[float] = deque(maxlen=FETCH_WINDOW)
        self._prefills: Dict[Tuple[int, int], Deque[float]] = {}
        self._fence_t = 0.0           # when the device's queue last ran dry

    def enter(self, now: float) -> None:
        t = self.clock()
        left, self._t_leave = self._t_leave, None
        if left is not None and (t - left) * 1e3 > FLOOR_MS:
            self.host_phase("caller", self._holds_work(t - left, now), t)
        self.begin(t)

    def leave(self) -> None:
        self._t_leave = self.last_t = self.clock()

    def phase(self, annotation: str, wall: float, t: float, flight) -> None:
        """A closed ``_Phase``: ``wall`` seconds less the phases nested in
        it, ended at ``t``; ``flight`` is what a fetch waited for."""
        self.last_t = t
        name = _HOST_PHASES.get(annotation)
        if name is not None:
            self.host_phase(name, wall, t)
        elif annotation == _FETCH:
            self._fence_t = t
            if flight.behind_chunk:
                return                # the wait is the chunk's, not the step's
            self.device_wait(
                "fetch", self._fetches, wall, t,
                flight_age_ms=(t - flight.t_launch) * 1e3)

    def prefill_done(self, bucket: int, blocks: int, t_call: float) -> None:
        """A prompt's first token is on the host (the newest stamp): its
        whole prefill ran from its program call, or from where the device
        finished what was queued before it, to here."""
        t = self.last_t
        wall = t - max(t_call, self._fence_t)
        self._fence_t = t
        window = self._prefills.get((bucket, blocks))
        if window is None:
            window = self._prefills[bucket, blocks] = deque(
                maxlen=PREFILL_WINDOW)
        self.device_wait("prefill", window, wall, t,
                         flight_age_ms=(t - t_call) * 1e3)


# ------------------------------------------------------------- train loop
TRAIN_SEGMENTS = ("batch_put", "dispatch", "after_step", "caller")


class _TrainPhase:
    """One of a training step's three annotated phases, kept for the life of
    the engine: the annotation (which a collection can take the place of)
    and, at its end, an edge on the watch's clock."""

    __slots__ = ("watch", "annotation", "index", "live")

    def __init__(self, watch: "TrainWatch", annotation: str, index: int):
        self.watch = watch
        self.annotation = annotation
        self.index = index

    def _open(self) -> None:
        self.live = jax.profiler.TraceAnnotation(self.annotation)
        self.live.__enter__()

    def __enter__(self) -> "_TrainPhase":
        self._open()
        self.watch.open = self
        return self

    def __exit__(self, *exc) -> bool:
        self.live.__exit__(*exc)
        self.watch.open = None
        self.watch.edges[self.index] = self.watch.clock()
        return False


class TrainWatch(HostWatch):
    """``DeepSpeedEngine``'s watch: ``_run_fused_step`` stamps its entry
    (:meth:`enter`), runs its three annotations through :attr:`phases` and
    says where it returns (:meth:`leave`). The gap from one entry to the
    next is judged at the next; a step at whose end the engine itself
    waited for the device (:meth:`fenced`: the telemetry fence, a print or
    monitor read, the sentinel's check) is the device's length, and of the
    gap it belongs to the caller's side alone is judged."""

    def __init__(self, registry, **kw):
        self.open: Optional[_TrainPhase] = None
        super().__init__(registry, open_phase=lambda: self.open, **kw)
        self.phases = tuple(
            _TrainPhase(self, f"dstpu/train_{name}", i) for i, name in
            enumerate(("batch_put", "step", "after_step")))
        self.edges = [0.0, 0.0, 0.0]
        self._entered: Optional[float] = None
        self._left = 0.0
        self._fenced = False
        self._gaps: Deque[float] = deque(maxlen=TRAIN_WINDOW)
        self._segments = tuple(deque(maxlen=TRAIN_WINDOW)
                               for _ in TRAIN_SEGMENTS)

    def enter(self, t: float) -> None:
        entered, self._entered = self._entered, t
        fenced, self._fenced = self._fenced, False
        if entered is not None:
            if not fenced:
                self._judge(entered, t)
            else:       # the caller's side alone, against a whole gap
                expected = _median(self._gaps)
                wall = t - self._left
                if (expected is not None and (
                        wall - TRAIN_CALLER_FACTOR * expected) * 1e3
                        > FLOOR_MS):
                    self.stall("caller", wall, expected, t)
        self.begin(t)

    def leave(self) -> None:
        self._left = self.last_t = self.clock()

    def fenced(self) -> None:
        self._fenced = True

    def forget(self) -> None:
        """What comes next is no step (a checkpoint, a rewind): the gap
        across it judges nothing."""
        self._entered = None

    def _judge(self, entered: float, t: float) -> None:
        gap = t - entered
        e1, e2, _ = self.edges
        walls = (e1 - entered, e2 - e1, self._left - e2, t - self._left)
        expected = _median(self._gaps)
        if (expected is None or
                (gap - TRAIN_FACTOR * expected) * 1e3 <= FLOOR_MS):
            self._gaps.append(gap)
            for window, wall in zip(self._segments, walls):
                window.append(wall)
            return
        # the segment that is furthest over its own median
        excess = [wall - (_median(window) or 0.0)
                  for window, wall in zip(self._segments, walls)]
        worst = TRAIN_SEGMENTS[max(range(len(walls)),
                                   key=excess.__getitem__)]
        if (worst == "caller" and
                (gap - TRAIN_CALLER_FACTOR * expected) * 1e3 <= FLOOR_MS):
            return      # the caller waited for what it had queued
        self.stall(worst, gap, expected, t)
