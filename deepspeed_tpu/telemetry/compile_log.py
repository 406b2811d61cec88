"""Compile log and set-up phases: what start-up cost, measured from inside.

JAX publishes every trace, lowering and backend compile as a time span with
the function's name, and every persistent-cache hit, miss and retrieval,
through ``jax.monitoring``. :class:`CompileLog` listens, once a process
(:func:`compile_log`), to six of those events and to no others (any other
event costs one dict miss and returns):

==================================================  =========================
event                                               counter
==================================================  =========================
``/jax/core/compile/jaxpr_trace_duration``          ``entry/trace_ms``,
                                                    ``entry/traces``,
                                                    ``entry/retraces``
``/jax/core/compile/jaxpr_to_mlir_module_duration``  ``entry/lower_ms``
``/jax/core/compile/backend_compile_duration``      ``entry/backend_compile_ms``
``/jax/compilation_cache/cache_hits``               ``entry/cache_hits``
``/jax/compilation_cache/cache_misses``             ``entry/cache_misses``
``/jax/compilation_cache/cache_retrieval_time_sec``  ``entry/cache_load_ms``
==================================================  =========================

The counters are accumulated milliseconds or counts. The log keeps the
process's totals; a registry holds those totals as they stood when it
subscribed and, from then on, the events its subscriber *follows* (below).
``entry/traces`` counts top-level traces (a ``jnp`` function traced while a
program is traced, or by a rule while a kernel is lowered, is inside that
stage's span) and ``entry/retraces`` those of a function name this process
had traced before: a program traced twice, and equally an eager ``jnp`` call
met with a new shape (each is a trace that start-up paid for). JAX times
``backend_compile_duration`` around ``compile_or_get_cached``, so on a cache
hit it *contains* the retrieval: ``entry/backend_compile_ms`` and
``entry/cache_load_ms`` are published as JAX gives them and are never to be
added. JAX counts a miss where it writes the compiled program to the cache,
and sends the retrieval time as a plain duration (the other timed events
come as spans too), hence the third listener; the fourth hears a stage
open (JAX sends its start as a scalar), which is what tells a nested trace.

A registry *subscribes*: it is first brought up to the process's totals,
so a registry made after ``init_inference`` still holds what
``init_inference`` compiled, and is then added every later event that its
subscriber follows. The listeners are process-wide and an engine is not
alone in its process (the caller's own ``jnp`` calls, a benchmark's
reference check, a second engine), so an engine follows an event only if
it arrives while the engine is at work: inside a method marked
:func:`at_work` (its constructor, ``warmup()``, ``step()``; the training
engine's step entry points). A subscriber may also ask to be told each
stage it follows (``trace``, ``lower``, ``backend_compile``,
``cache_load``) with the program's name and JAX's own ``time.time()``
stamps; ``ServingEngine`` turns those into ``compile`` spans. Names are the
jitted function's ``__name__``: the engines give every program the name it
has in ``program_cache_sizes()``, and lowering's ``jit_`` prefix is cut.

:class:`SetupPhase` stamps one phase of set-up (weights, cache, warm-up,
first step) on the host's clock and, as a ``dstpu/setup_<phase>``
annotation, on the profile's.
"""

from __future__ import annotations

import functools
import threading
import time
import weakref
from typing import Callable, Dict, List, Optional

import jax
import jax.monitoring as monitoring

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

# timed event -> stage
_STAGES = {_TRACE: "trace", _LOWER: "lower", _COMPILE: "backend_compile"}
_IS_HIT = {_HITS: True, _MISSES: False}
COUNTERS = ("entry/trace_ms", "entry/lower_ms", "entry/backend_compile_ms",
            "entry/cache_load_ms", "entry/traces", "entry/retraces",
            "entry/cache_hits", "entry/cache_misses")

# on_stage(stage, program, start, end): times as JAX stamped them
StageListener = Callable[[str, str, float, float], None]


class _Subscription:
    __slots__ = ("registry", "on_stage", "follows")

    def __init__(self, registry, on_stage: Optional[StageListener],
                 follows: Optional[Callable[[], bool]]):
        self.registry = registry
        self.on_stage = on_stage
        self.follows = follows


def at_work(method):
    """Mark an engine's method as the engine at work: compile events that
    arrive while it runs are the engine's own (``follows`` of
    :meth:`CompileLog.subscribe` reads ``engine._at_work``). Two attribute
    writes a call; no clock is read and nothing is told."""
    @functools.wraps(method)
    def working(self, *args, **kwargs):
        was, self._at_work = self._at_work, True
        try:
            return method(self, *args, **kwargs)
        finally:
            self._at_work = was
    return working


class CompileLog:
    """Process totals of the six events, per-program counts, subscribers.

    The four ``on_*`` methods are the ``jax.monitoring`` listeners; a test
    feeds them a made-up event stream without registering anything."""

    def __init__(self):
        self._lock = threading.Lock()
        self.totals: Dict[str, float] = dict.fromkeys(COUNTERS, 0)
        # program -> stage -> [times seen, accumulated ms]
        self.programs: Dict[str, Dict[str, list]] = {}
        self._subs: List[_Subscription] = []
        # a retrieval reported since the last backend compile closed: JAX
        # sends it nameless from inside that compile's span
        self._loaded: Optional[tuple] = None
        # stages open on this thread: a ``jnp`` function called while a
        # program is traced, or by a lowering rule while a Pallas kernel is
        # lowered, is traced inside that stage, and is the program's time
        self._open = threading.local()
        # the newest stage, any thread's: (stage, program, start, end), the
        # end None while it is open (``stage_since``)
        self.last_stage: Optional[tuple] = None
        self._registered = False

    # ------------------------------------------------------- jax.monitoring
    def register(self) -> None:
        if not self._registered:
            monitoring.register_scalar_listener(self.on_scalar)
            monitoring.register_event_time_span_listener(self.on_time_span)
            monitoring.register_event_listener(self.on_event)
            monitoring.register_event_duration_secs_listener(
                self.on_duration)
            self._registered = True

    def unregister(self) -> None:
        if self._registered:
            monitoring.unregister_scalar_listener(self.on_scalar)
            monitoring.unregister_event_time_span_listener(self.on_time_span)
            monitoring.unregister_event_listener(self.on_event)
            monitoring.unregister_event_duration_listener(self.on_duration)
            self._registered = False

    def on_scalar(self, event: str, value=None, fun_name: str = "",
                  **_) -> None:
        """JAX sends a timed event's start as a scalar when it opens."""
        if event in _STAGES:
            self._open.depth = getattr(self._open, "depth", 0) + 1
            self.last_stage = (_STAGES[event], str(fun_name), value, None)

    def on_time_span(self, event: str, start: float, end: float,
                     fun_name: str = "", **_) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        program = str(fun_name)
        outer = max(getattr(self._open, "depth", 1) - 1, 0)
        self._open.depth = outer
        if stage != "trace" and program.endswith(")") and "(" in program:
            # lowering and compiling name the module, ``jit(<function>)``
            program = program[program.index("(") + 1:-1]
        self.last_stage = (stage, program, start, end)
        if stage == "trace" and outer:
            return               # inside another stage, which holds its time
        with self._lock:
            subs = self._following()
            ms = (end - start) * 1e3
            seen = self._note(program, stage, ms)
            # literal names: scripts/check_metric_names.py reads call sites
            if stage == "trace":
                self._count("entry/trace_ms", ms, subs)
                self._count("entry/traces", 1, subs)
                if seen:
                    self._count("entry/retraces", 1, subs)
            elif stage == "lower":
                self._count("entry/lower_ms", ms, subs)
            else:
                self._count("entry/backend_compile_ms", ms, subs)
            told = [(stage, program, start, end)]
            if stage == "backend_compile" and self._loaded is not None:
                t0, t1 = self._loaded
                self._loaded = None
                self._note(program, "cache_load", (t1 - t0) * 1e3)
                told.append(("cache_load", program, t0, t1))
        for sub in subs:
            if sub.on_stage is not None:
                for stamp in told:
                    sub.on_stage(*stamp)

    def on_event(self, event: str, **_) -> None:
        hit = _IS_HIT.get(event)
        if hit is None:
            return
        with self._lock:
            subs = self._following()
            if hit:
                self._count("entry/cache_hits", 1, subs)
            else:
                self._count("entry/cache_misses", 1, subs)

    def on_duration(self, event: str, duration_secs: float, **_) -> None:
        if event != _RETRIEVAL:
            return
        now = time.time()
        with self._lock:
            self._loaded = (now - duration_secs, now)
            self._count("entry/cache_load_ms", duration_secs * 1e3,
                        self._following())

    # ------------------------------------------------------------- the log
    def _note(self, program: str, stage: str, ms: float) -> int:
        """Add one ``stage`` of ``program``; how often it was seen before."""
        rec = self.programs.setdefault(program, {}).setdefault(stage, [0, 0.0])
        seen = rec[0]
        rec[0] += 1
        rec[1] += ms
        return seen

    def stage_since(self, t: float) -> Optional[str]:
        """``<program>:<stage>`` of the stage that is open now, on whatever
        thread, or that closed last and after ``t`` (``time.time()``'s
        clock); None where neither: what a stalled phase may have stood
        behind (``telemetry/host_watch.py``)."""
        last = self.last_stage
        if last is None or (last[3] is not None and last[3] < t):
            return None
        return f"{last[1]}:{last[0]}"

    def _following(self) -> List[_Subscription]:
        """The subscribers whose event this one is."""
        return [s for s in self._subs if s.follows is None or s.follows()]

    def _count(self, counter: str, n, subs: List[_Subscription]) -> None:
        self.totals[counter] += n
        for sub in subs:
            if sub.registry is not None:
                sub.registry.counter(counter).inc(n)

    # ---------------------------------------------------------- subscribers
    def subscribe(self, registry=None,
                  on_stage: Optional[StageListener] = None,
                  follows: Optional[Callable[[], bool]] = None
                  ) -> _Subscription:
        """Bring ``registry``'s ``entry/*`` counters up to the process's
        totals; from then on add to them, and tell ``on_stage``, every
        event at whose arrival ``follows()`` is true (every event, where
        none is given). A registry never follows on its own: what it
        holds stands still once its subscribers are gone or idle."""
        sub = _Subscription(registry, on_stage, follows)
        with self._lock:
            if registry is not None:
                for counter, total in self.totals.items():
                    registry.counter(counter).value = total
            self._subs.append(sub)
        return sub

    def unsubscribe(self, sub: Optional[_Subscription]) -> None:
        with self._lock:
            if sub in self._subs:
                self._subs.remove(sub)


_process_log: Optional[CompileLog] = None
_process_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's log, registered with ``jax.monitoring`` on first use
    (the listeners are process-wide, so there is one)."""
    global _process_log
    with _process_lock:
        if _process_log is None:
            _process_log = CompileLog()
            _process_log.register()
    return _process_log


class SetupPhase:
    """One phase of set-up, opened where it is made: a
    ``dstpu/setup_<name>`` annotation on the profile's clock and
    ``time.perf_counter`` stamps ``t0`` / ``t1`` on the host's. ``close``
    takes what the phase made and waits for it, so the reading is the
    work and not its dispatch; set-up only, never a step path."""

    def __init__(self, name: str):
        self.name = name
        self._note = jax.profiler.TraceAnnotation(f"dstpu/setup_{name}")
        self._note.__enter__()
        self._told = weakref.WeakSet()    # registries and tracers that have it
        self.t1: Optional[float] = None
        self.t0 = time.perf_counter()

    def close(self, fence=None) -> "SetupPhase":
        if self.t1 is None:
            if fence is not None:
                jax.block_until_ready(fence)
            self.t1 = time.perf_counter()
            self._note.__exit__(None, None, None)
        return self

    @property
    def ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def publish(self, registry=None, tracer=None, *, clock=None, **span):
        """``entry/setup_<name>_ms`` into ``registry`` and a span
        ``setup_<name>`` into ``tracer``, once each: an ``InferenceEngine``
        behind two ``ServingEngine``s made its weights once.
        ``clock(t0, t1)`` maps the stamps onto the tracer's clock where that
        is not ``perf_counter``. Returns the span, if it recorded one."""
        if registry is not None and registry not in self._told:
            self._told.add(registry)
            registry.counter(f"entry/setup_{self.name}_ms").inc(self.ms)
        if tracer is not None and tracer not in self._told:
            self._told.add(tracer)
            t0, t1 = (self.t0, self.t1) if clock is None \
                else clock(self.t0, self.t1)
            return tracer.record(f"setup_{self.name}", t0, t1, **span)
        return None
