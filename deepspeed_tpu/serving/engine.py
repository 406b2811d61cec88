"""Continuous-batching serving engine: token-granularity scheduling on
top of the compiled prefill/decode programs.

The ROADMAP north star is serving heavy traffic "as fast as the hardware
allows"; ``InferenceEngine.generate`` runs one static batch to completion,
so every mixed-length batch idles finished slots on its stragglers and
every new (batch, prompt_len) shape pays an XLA recompile. This engine
closes both gaps (ISSUE 2):

  * **Iteration-level scheduling** (Orca): between decode steps the
    scheduler admits waiting requests into free slots of the persistent
    slot-paged KV cache (serving/kv_slots.py) — a finished request's
    slot decodes a NEW request on the very next iteration.
  * **Recompile-free shape bucketing**: prefill runs bucket-padded
    ([1, bucket] with the true length traced), decode runs at a fixed
    slot count with a per-slot valid-length vector — the entire serving
    loop executes exactly ``len(buckets) + 1`` compiled XLA programs
    (ONE prefill per configured bucket + ONE decode step), no matter the
    arrival pattern, admission order, or per-request lengths. With a
    single bucket that is the classic TWO-program serving loop.

Token identity: the decode step masks each slot to its own valid prefix
and bucket padding is causally invisible to the true last prompt
position, so a request's tokens are bit-identical whether it runs solo
or packed next to strangers (pinned by tests/unit/serving/). That
argument is about key-value rows. A model with recurrent layers keeps a
fixed-size state per slot beside them (serving/kv_slots.py), and padding
is not invisible to a recurrence: the prefill programs hand the model
the true length and it stops its state there, and the decode program
hands it the active mask, so the state of a slot that is idle or mid-way
through a chunked prefill does not move. What addresses the cache by
token rows — ``prefix_cache``, ``kv_dtype``, ``speculative``,
``preemption="swap"`` — is refused at construction for such a model.
"""

from __future__ import annotations

import functools
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.models.base import recurrent_state_keys, row_state_keys, slot_state_keys
from deepspeed_tpu.ops.decode_step import decode_rows_fetched
from deepspeed_tpu.serving.errors import (EmptyPromptError,
                                          EngineConfigError,
                                          EngineInvariantError,
                                          InvalidMaxNewTokensError,
                                          PromptTooLongError,
                                          SlotCapacityError,
                                          SwapCapacityError)
from deepspeed_tpu.serving.kv_blocks import BlockKVPool
from deepspeed_tpu.serving.kv_quant import normalize_kv_dtype
from deepspeed_tpu.serving.kv_slots import SlotKVCache
from deepspeed_tpu.serving.radix import PrefixCache
from deepspeed_tpu.serving.scheduler import (Request, RequestResult,
                                             SlotScheduler, pick_bucket)
from deepspeed_tpu.serving.speculative import (AdaptiveK, DraftModelDrafter,
                                               NgramDrafter,
                                               normalize_speculative,
                                               pick_k_bucket)
from deepspeed_tpu.serving.swap import HostSwapBuffer
from deepspeed_tpu.telemetry.compile_log import (SetupPhase, at_work,
                                                 compile_log)
from deepspeed_tpu.telemetry.host_watch import ServingWatch
from deepspeed_tpu.telemetry.registry import metric_label
from deepspeed_tpu.utils.logging import log_dist

# accepted-tokens-per-step / tokens-per-decode-call histograms count small
# integers (1 .. k+1), not latencies — unit-wide buckets keep the
# interpolated percentiles exact for the range any sane k reaches
_TOKENS_PER_STEP_BUCKETS = tuple(float(x) for x in range(1, 34))


def _host_blocks(tree, n_used: int):
    """device_get a swap-out gather and trim to the first ``n_used``
    blocks (axis 1 is block-major on every leaf — payloads AND the
    quantized pools' scale arrays), as host numpy."""
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a)[:, :n_used], jax.device_get(tree))  # dstpu-lint: fence=swap-out gather lands host-side by definition


def _expand_blocks(tree, mb: int):
    """Zero-pad host block leaves back to the fixed [*, MB, ...] upload
    shape (swap-in programs never vary their operand shapes with how
    much actually uploads)."""
    def f(a):
        full = np.zeros((a.shape[0], mb) + a.shape[2:], a.dtype)
        full[:, :a.shape[1]] = a
        return full

    return jax.tree_util.tree_map(f, tree)


def _to_device(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


class _SlotState:
    """Host-side state of one occupied slot. The speculative drafters'
    token-history view is DERIVED (request.prompt + result.tokens), not
    stored — a second copy could silently desynchronize from the
    emitted stream.

    A slot is in the PREFILL phase while ``prefill_pos <
    prefill_total`` (chunked prefill, ISSUE 8): it consumes prefill
    budget between decode iterations, emits no tokens, and is excluded
    from the decode batch. The first generated token (and TTFT) exists
    only once the last chunk lands. ``order`` is the engine's admission
    sequence — chunk continuations run priority-then-admission order,
    so earlier same-class prompts finish prefilling first. ``tenant``
    is the request's SANITIZED accounting tenant (ISSUE 13), resolved
    once at admission. ``in_flight`` counts the slot's tokens that a
    launched program (the last prefill chunk, a decode step) has picked
    and the host has not fetched yet: the host's bookkeeping of lengths
    and of ends by length runs that far ahead of ``result.tokens``."""

    __slots__ = ("request", "result", "last_token", "prefill_pos",
                 "prefill_total", "order", "tenant", "in_flight")

    def __init__(self, request: Request, result: RequestResult,
                 last_token: int, prefill_pos: int, prefill_total: int,
                 order: int, tenant: str = "default"):
        self.request = request
        self.result = result
        self.last_token = last_token
        self.prefill_pos = prefill_pos
        self.prefill_total = prefill_total
        self.order = order
        self.tenant = tenant
        self.in_flight = 0

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < self.prefill_total


class _Flight:
    """A launched decode step whose tokens have not been fetched:
    ``answer`` is what the program returned behind the cache's carry (the
    next tokens, then the model's step counters), still on the device;
    ``states`` pairs every slot active in the
    step with the ``_SlotState`` it held at launch, so that commit gives a
    token only to a slot that still holds the same object; ``overlapped``
    says the step before it was still unfetched when this one launched.
    For the host watch: ``t_launch``, its clock as the launch began, and
    ``behind_chunk``, whether a prefill chunk that nobody fetched was
    queued ahead (the fetch then waits for the chunk too)."""

    __slots__ = ("answer", "states", "overlapped", "t_launch",
                 "behind_chunk")

    def __init__(self, answer, states, overlapped: bool,
                 t_launch: float = 0.0, behind_chunk: bool = False):
        self.answer = answer
        self.states = states
        self.overlapped = overlapped
        self.t_launch = t_launch
        self.behind_chunk = behind_chunk


class _FirstToken:
    """A prompt's last prefill chunk whose pick has not been fetched: the
    token on the device, and what the commit needs to stamp the chunk's
    span from its program call."""

    __slots__ = ("slot", "state", "token", "counted", "t_span0", "program",
                 "bucket", "chunk", "t_call")

    def __init__(self, slot, state, token, counted, t_span0, program,
                 bucket, chunk, t_call=0.0):
        self.slot = slot
        self.state = state
        self.token = token
        # the prompt-block counts of the programs launched up to this one
        # and not yet fetched: done when the token is
        self.counted = counted
        self.t_span0 = t_span0
        self.program = program
        self.bucket = bucket
        self.chunk = chunk
        self.t_call = t_call      # the host watch's clock at the program call


class _Preempted:
    """Host-side state of one preempted (swapped-out) request: the slot
    state to reattach on resume, the KV length it had computed, and the
    engine-clock instant it left the slot set (the preempted interval
    is queue wait, not decode latency)."""

    __slots__ = ("state", "length", "since")

    def __init__(self, state: _SlotState, length: int, since: float):
        self.state = state
        self.length = length
        self.since = since


class _ReqTrace:
    """Engine-side span bookkeeping for one request (ISSUE 11): the
    trace id, the root span to hang lifecycle spans under (engine-owned
    when the request arrived without trace context; the fabric router's
    otherwise), and the currently-open decode-segment / swapped-out
    interval spans."""

    __slots__ = ("trace_id", "root", "root_span", "decode_span",
                 "swap_span", "submitted_t")

    def __init__(self, trace_id: str, root: Optional[str],
                 root_span=None, submitted_t: Optional[float] = None):
        self.trace_id = trace_id
        self.root = root               # parent span id for child spans
        self.root_span = root_span     # open root Span iff engine-owned
        self.decode_span = None
        self.swap_span = None
        # queue_wait start for CONTEXT-CARRYING requests (fabric
        # dispatch): the router's router_queue span already covers
        # [arrival, dispatch], so the engine-side wait must start at
        # the dispatch-time submit — starting at the original arrival
        # would double-count the router interval into the queue phase
        # (and, after a failover, swallow the whole first attempt).
        # Stamped by the engine's FIRST step() after submit (the same
        # clock instant the router dispatched at); None on an
        # engine-owned root, where arrival_time is correct.
        self.submitted_t = submitted_t


class _Phase:
    """``with _Phase(engine, annotation, span, now):`` — one phase of a
    serving iteration on both clocks. The ``jax.profiler.TraceAnnotation``
    (a no-op without a profiler session) suspends the phase open around
    it and reopens that one after, so no two ``dstpu/serving_*``
    annotations of the thread overlap: a device-trace reader names an
    idle gap by the annotation that covers most of it, and a nested phase
    would never win. ``span`` is recorded on exit only while an
    ``iteration`` span is open, which takes a tracer: the bare engine
    pays the annotation and two ``is None`` tests. With a registry the
    engine has a host watch, and both edges are stamped on its clock:
    the phase's length, less the phases nested in it, is the watch's to
    judge (``telemetry/host_watch.py``), and the stamp of the exit is the
    one the armed path closes the span at."""

    __slots__ = ("engine", "annotation", "span", "now", "outer", "live",
                 "t0", "nested", "flight")

    def __init__(self, engine: "ServingEngine", annotation: str,
                 span: Optional[str], now: float):
        self.engine = engine
        self.annotation = annotation
        self.span = span
        self.now = now

    def _open(self) -> None:
        self.live = jax.profiler.TraceAnnotation(self.annotation)
        self.live.__enter__()

    def __enter__(self) -> "_Phase":
        self.outer = self.engine._open_phase
        if self.outer is not None:
            self.outer.live.__exit__(None, None, None)
        self._open()
        self.engine._open_phase = self
        watch = self.engine._watch
        if watch is not None:
            self.nested = 0.0
            self.flight = None
            self.t0 = watch.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.live.__exit__(*exc)
        engine = self.engine
        engine._open_phase = self.outer
        if self.outer is not None:
            self.outer._open()
        t = None
        watch = engine._watch
        if watch is not None:
            t = watch.clock()
            wall = t - self.t0
            if self.outer is not None:
                self.outer.nested += wall
            if exc[0] is None:
                watch.phase(self.annotation, wall - self.nested, t,
                            self.flight)
        if (engine._iter_span is not None and self.span is not None
                and exc[0] is None):
            engine._phase_end(self.span, self.now, t)
        return False


class ServingEngine:
    """Drives an :class:`InferenceEngine`'s slot programs with an
    iteration-level scheduler.

    Parameters
    ----------
    engine: InferenceEngine — owns params + the jitted slot programs.
    num_slots: fixed decode batch width (the slot-paged cache's batch dim).
    max_len: per-slot KV capacity in tokens; prompt + max_new_tokens of
        every admitted request must fit (rejected at submit otherwise).
    buckets: ascending prefill pad lengths (e.g. (128, 512, 2048)); a
        prompt prefills in the smallest bucket that holds it. One
        compiled prefill program per bucket.
    eos_token_id: finish a request early when it emits this token (the
        token is kept in the output, matching generate()'s EOS path).
    time_fn: clock used for arrival admission + latency metrics; defaults
        to time.monotonic. Tests inject a virtual clock so mixed arrival
        traces replay deterministically.
    telemetry: True (default) instruments the serving loop into the
        global metrics registry (queue-wait/TTFT/TPOT latency histograms,
        slot-occupancy and batch-fill gauges, recompile counter,
        finished-requests/sec — ISSUE 3); pass a MetricsRegistry to use a
        private one, or False/None to run bare.
    speculative: speculative decoding (ISSUE 4): None/"off" (default),
        a mode string ("ngram" | "draft"), a dict of
        :class:`~deepspeed_tpu.serving.speculative.SpeculativeConfig`
        fields, or a config instance. When on, every decode iteration
        drafts up to k tokens per slot (prompt-lookup or draft model),
        verifies them ALL in one target forward, and emits each slot's
        accepted prefix + one bonus token — losslessly (greedy output is
        bit-identical to the plain decode path; sampling is
        distribution-exact). Verify programs are bucketed by k exactly
        like prefill is by length, so the zero-recompile guarantee
        holds; slot capacity reserves ``k_max`` lookahead rows for the
        pre-acceptance draft writes.
    prefix_cache: block-paged KV with radix prefix sharing (ISSUE 6).
        False (default) keeps the slot-paged cache. True switches the
        KV store to a :class:`~deepspeed_tpu.serving.kv_blocks.BlockKVPool`
        fronted by a :class:`~deepspeed_tpu.serving.radix.PrefixCache`:
        on admit the request's prompt is matched against the radix index
        and only the UNMATCHED suffix is prefilled (bucketed by suffix
        length); on finish the prompt's blocks are donated to the index
        instead of freed. Admission accounts in free pool BLOCKS (no
        fragmentation); ``block_size``/``num_blocks`` size the pool
        (defaults: 16-token blocks, worst-case slot parity). Outputs are
        bit-identical to the slot-paged engine (greedy, with and without
        speculation — pinned by tests), and the zero-recompile invariant
        holds: block tables are traced data, never shapes.
    kv_dtype: quantized KV-cache blocks (ISSUE 12; requires
        ``prefix_cache=True``). None/"bf16" (default) stores KV in the
        engine's compute dtype. "int8" / "fp8" switch the pool to
        int8 / float8_e4m3fn payloads with per-token-per-head bf16
        scales (serving/kv_quant.py): writes quantize on store, reads
        dequantize in-register (fused kernel) or in the gather (einsum
        path), and every downstream consumer — radix COW forks,
        preemption swap (byte-identical round trip at ~half the host
        bandwidth), speculation rollback — carries payload+scales as
        one opaque pytree, so zero recompiles hold by construction.
        int8 stores ~1.94x the blocks per HBM byte of bf16 (fp8 ~3.88x
        vs an fp32-serving pool); greedy output matches the bf16-KV
        engine at >= 0.99 exact-token rate on the test traces.
    prefill_token_budget: chunked prefill (ISSUE 8, Sarathi-style
        stall-free scheduling). None (default) keeps monolithic
        prefills. An int caps the BUCKET-PADDED prefill tokens (the
        compute actually dispatched) per serving iteration: long
        prompts prefill in fixed-bucket-sized chunks
        (at most the largest bucket <= budget per chunk) interleaved
        with decode steps, so a 2k-token prompt can no longer
        monopolize an iteration and spike every decoding tenant's
        TPOT. Chunk count is traced data — the zero-recompile
        invariant holds across chunk transitions — and prompts LONGER
        than the largest bucket become servable (submit's bucket
        rejection lifts; the slot capacity check remains). TTFT is
        stamped when the LAST chunk emits the first token.
    preemption: "swap" enables priority preemption with host KV swap
        (ISSUE 8): when an arrived request of a strictly higher class
        cannot be admitted (no free slot, or — block-paged — the pool
        doesn't fit it), the worst lower-class running slot is swapped
        OUT to a host-side numpy buffer (serving/swap.py), its
        slot/blocks freed, and the request re-queued at its original
        arrival position; it swaps back IN when resources free and
        finishes bit-identically to an uninterrupted run (pinned by
        tests). None (default) disables preemption.
    swap_max_bytes: byte cap on the host swap buffer (ISSUE 9): a
        preemption whose KV would push the buffer past the cap is
        declined (typed SwapCapacityError internally, surfaced as the
        ``serving/swap_capacity_rejections`` counter) so sustained
        preemption pressure cannot grow host memory without bound.
        None (default) leaves the buffer unbounded.
    priority_aging_sec: scheduler aging rate — a waiting request gains
        one full priority class per ``priority_aging_sec`` seconds
        waited, so the lowest class never starves under sustained
        high-priority load. None disables aging (raw classes only).
    tpot_slo_ms: decode-TPOT SLO guard for the admission side: when the
        EMA of inter-decode-invocation wall time exceeds this budget
        while decode-phase slots exist, the iteration's prefill budget
        drops to 0 (decode runs first, prefill defers) — for at most
        ``slo_max_defer`` consecutive iterations, so prefill always
        makes progress. Requires ``prefill_token_budget``.
    tracer: span-graph tracer (ISSUE 11), or None (default) to run
        untraced. When armed, every request's lifecycle is stamped
        host-side at fences that already exist — queue wait, each
        prefill chunk, decode segments, speculative draft/verify,
        preemption swap-out/swapped/swap-in, shed/cancel — under a root
        span the engine owns (or the fabric router's, when the request
        arrives with trace context). On the engine-scope trace every
        step() that found work records one ``iteration`` span tiled by
        ``iter_schedule``, ``iter_upload``,
        ``iter_launch``, ``iter_fetch`` and ``iter_commit``, the same
        phases the ``dstpu/serving_*`` profiler annotations name; the
        phases of set-up (``setup_weights``, ``setup_cache``,
        ``setup_warmup`` over two ``warmup_pass``) and, after warm-up, a
        ``compile`` span for every stage of a program compiled inside
        ``step()`` lie on the same trace. Arming
        adds no device work: greedy output stays bit-identical (pinned by
        tests/unit/serving/test_tracing.py). On the chip, armed against
        the registry alone: ``itl_p95_ms`` 0.4 to 2.0% up, nothing else
        outside its spread (PERF.md section 6, PR 57).
    slo: an :class:`~deepspeed_tpu.telemetry.slo.SLOEngine` (ISSUE 13),
        or None (default). When armed, the engine calls
        ``slo.maybe_evaluate(now)`` once per serving iteration ON THE
        ENGINE'S OWN CLOCK — a FakeClock trace replays its alert
        timeline deterministically. Pure host work at the top of
        step(); greedy output stays bit-identical.
    tenants: per-tenant usage accounting (ISSUE 13). None (default)
        follows ``telemetry`` (accounting into the same registry);
        True forces a (possibly registry-less) ledger; False disables.
        Tracks per :attr:`Request.tenant_id`: prompt/decode tokens,
        prefill tokens computed vs saved by the prefix cache, KV
        block-seconds (pool occupancy integrated over engine-clock
        time; quantized pools billed at payload bytes), preemptions,
        deadline sheds, and TTFT/TPOT histograms — all at call sites
        the engine already owns (zero extra device syncs; the
        per-tenant token totals sum exactly to the engine counters,
        pinned by tests).
    """

    # set by ``at_work`` for the length of the constructor, ``warmup()`` and
    # ``step()``: the compile events that arrive then are this engine's
    _at_work = False

    @at_work
    def __init__(self, engine, *, num_slots: int = 8, max_len: int = 1024,
                 buckets: Sequence[int] = (128, 512, 2048),
                 eos_token_id: Optional[int] = None, pad_token_id: int = 0,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 time_fn: Optional[Callable[[], float]] = None,
                 telemetry=True, speculative=None,
                 prefix_cache: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_dtype: Optional[str] = None,
                 prefill_token_budget: Optional[int] = None,
                 preemption: Optional[str] = None,
                 swap_max_bytes: Optional[int] = None,
                 priority_aging_sec: Optional[float] = None,
                 tpot_slo_ms: Optional[float] = None,
                 slo_max_defer: int = 4, tracer=None,
                 slo=None, tenants: Optional[bool] = None):
        self.engine = engine
        model = engine.module
        mcfg = getattr(model, "config", None)
        model_max = getattr(mcfg, "max_seq_len", None)
        if not getattr(mcfg, "has_position_table", True):
            model_max = None
        if model_max is not None and max_len > model_max:
            raise EngineConfigError(
                f"serving max_len {max_len} exceeds the model's max_seq_len "
                f"{model_max} (position table size)")
        rows = row_state_keys(model)
        recurrent = recurrent_state_keys(slot_state_keys(model), rows)
        latent = tuple(k for k in rows if k not in ("k", "v"))
        if recurrent or latent:
            # state with no token rows: nothing below can share a prefix of
            # it, quantize its pool blocks, roll a rejected draft back out
            # of it or (yet) park it on the host. Token rows that are no
            # head's keys or values: the block pool, the verify step and the
            # swap programs address ``k`` / ``v`` pairs and do not know them
            name = type(model).__name__
            what = (f"token rows, and {name} keeps state {list(recurrent)} "
                    f"that has none (recurrent state, or a sliding window's "
                    f"ring)") if recurrent else (
                f"rows of k / v pairs, and {name} keeps its token rows in "
                f"{list(latent)} (one latent row a token that all heads "
                f"share): the block pool, the verify step and the swap "
                f"programs do not hold such a leaf yet")
            for option, value in (("prefix_cache", prefix_cache),
                                  ("kv_dtype", kv_dtype),
                                  ("speculative", speculative),
                                  ("preemption", preemption)):
                if value:
                    raise EngineConfigError(
                        f"{option}={value!r} addresses the cache by {what}")
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        if self.kv_dtype is not None and not prefix_cache:
            raise EngineConfigError(
                f"kv_dtype={kv_dtype!r} needs prefix_cache=True: quantized "
                "KV lives in the block-paged pool (serving/kv_quant.py); "
                "the slot-paged cache stays in the compute dtype")
        cache_phase = SetupPhase("cache")
        if prefix_cache:
            self.cache = BlockKVPool(model, num_slots, max_len,
                                     block_size=block_size,
                                     num_blocks=num_blocks,
                                     dtype=engine.dtype,
                                     kv_dtype=self.kv_dtype)
        else:
            self.cache = SlotKVCache(model, num_slots, max_len,
                                     dtype=engine.dtype)
        # block-program jit-cache key component: one InferenceEngine may
        # back pools of DIFFERENT kv_dtypes (e.g. the kv-quant bench's
        # bf16-vs-int8 engines) — without the key the two pool pytree
        # structures would land in ONE jitted program's cache and break
        # the cache-size==1 zero-recompile pinning
        self._kv_key = self.kv_dtype or "compute"
        # canonical placement: freshly-allocated carry arrays are
        # uncommitted SingleDeviceSharding while jitted-program outputs
        # carry the mesh's NamedSharding — the jit cache keys on that, so
        # un-canonicalized resets would each cost one phantom recompile
        # (caught by the zero-recompile serving test)
        self._canon = lambda x: jax.device_put(
            x, NamedSharding(engine.mesh, P()))
        self.cache.update(*map(self._canon, self.cache.carry()))
        cache_phase.close(fence=self.cache.carry())
        # a serving program's leading outputs are the cache's carry back
        self._n_carry = len(self.cache.carry())
        # clamp oversized buckets to the slot capacity (silently DROPPING
        # them would reject prompts that fit the slot: the default
        # buckets (128, 512, 2048) with max_len 1024 must yield a
        # 1024-token bucket, not a 512 ceiling)
        self.buckets = tuple(sorted({min(b, max_len) for b in buckets}))
        if not self.buckets:
            raise EngineConfigError(f"no prefill buckets given: {buckets}")
        for b in self.buckets:
            if b % max(self.cache.pair, 1):
                raise EngineConfigError(
                    f"prefill bucket {b} must be a multiple of the cache "
                    f"token-pair pack factor {self.cache.pair} "
                    "(ops/attention.kv_pack_factor)")
            window = getattr(self.cache, "restart_window", 0)
            if window and b % window:
                raise EngineConfigError(
                    f"prefill bucket {b} must be a multiple of the window "
                    f"of {window} positions that "
                    f"{type(model).__name__}'s cache starts over at: a "
                    "prompt, and a prefill chunk, passes in whole windows")
        self.num_slots = num_slots
        self.max_len = max_len
        self.eos_token_id = eos_token_id
        self.pad_token_id = pad_token_id
        self.do_sample = do_sample
        self._temp = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        self._sample_kw = dict(do_sample=do_sample, top_k=top_k,
                               top_p=float(top_p))
        # tokens of a prompt that pass the model's stack at once, if it says
        self._prompt_block = getattr(mcfg, "prompt_block", None)
        self._time = time_fn or time.monotonic
        # a wall clock only ADVANCES WITH real time, so idle gaps must
        # time.sleep (a tight poll would spin one core for the whole
        # gap); injected virtual clocks advance per CALL, so their idle
        # loops terminate by polling and must NOT sleep
        self._real_clock = self._time in (time.monotonic, time.time,
                                          time.perf_counter)
        self._rng = jax.random.PRNGKey(engine.config.seed + 1)
        self._zero_key = jax.random.PRNGKey(0)

        # ---- SLO-aware scheduling (ISSUE 8)
        if prefill_token_budget is not None:
            if prefill_token_budget < self.buckets[0]:
                raise EngineConfigError(
                    f"prefill_token_budget {prefill_token_budget} below the "
                    f"smallest prefill bucket {self.buckets[0]}: no chunk "
                    f"program could ever run under it")
            # chunks are fixed-bucket-sized: the largest bucket the
            # budget holds (chunk count is data, bucket set is fixed —
            # the recompile-free invariant)
            self._chunk_max: Optional[int] = max(
                b for b in self.buckets if b <= prefill_token_budget)
        else:
            self._chunk_max = None
        self.prefill_token_budget = prefill_token_budget
        if preemption not in (None, "swap"):
            raise EngineConfigError(f"preemption policy must be None or 'swap', "
                             f"got {preemption!r}")
        self.preemption = preemption
        # swap_max_bytes (ISSUE 9 satellite) caps the host swap buffer:
        # a preemption whose KV would not fit is DECLINED (typed
        # SwapCapacityError inside, counted outside) so sustained
        # preemption pressure degrades into "candidate waits" instead
        # of unbounded host-memory growth
        self.swap = HostSwapBuffer(max_bytes=swap_max_bytes) \
            if preemption else None
        self._preempted: Dict[int, _Preempted] = {}
        if tpot_slo_ms is not None and prefill_token_budget is None:
            raise EngineConfigError(
                "tpot_slo_ms needs prefill_token_budget: the SLO guard "
                "defers budgeted prefill work, and monolithic admission "
                "has no budget to defer")
        self.tpot_slo_ms = tpot_slo_ms
        self._slo_max_defer = slo_max_defer
        self._defer_streak = 0
        self._decode_gap_ema: Optional[float] = None
        self._last_decode_t: Optional[float] = None
        self._admit_seq = 0

        self.scheduler = SlotScheduler(num_slots,
                                       aging_sec=priority_aging_sec)
        self._slots: List[Optional[_SlotState]] = [None] * num_slots
        self._warm = False
        self._run_t0: Optional[float] = None
        # programs (built lazily, counted by tests): bucket -> prefill fn
        self._prefill: Dict[int, Callable] = {}
        # slot-paged chunk-prefill programs (chunked mode only; the
        # block-paged mode chunks through the same suffix-prefill
        # programs via their `start` operand)
        self._chunk_prefill: Dict[int, Callable] = {}
        self._swap_out_fn: Optional[Callable] = None
        self._swap_in_fn: Optional[Callable] = None
        self._copy_fn: Optional[Callable] = None
        if prefix_cache:
            self._decode = engine.block_decode_program(
                num_slots, self.cache.max_blocks_per_slot,
                pad_token_id=pad_token_id, kv_dtype=self._kv_key,
                **self._sample_kw)
            self._copy_fn = engine.block_copy_program(
                self.cache.num_blocks, block_size, kv_dtype=self._kv_key)
        else:
            self._decode = engine.slot_decode_program(
                num_slots, max_len, pad_token_id=pad_token_id,
                **self._sample_kw)
        # ---- speculative decoding (ISSUE 4)
        self.spec = normalize_speculative(speculative)
        self._verify: Dict[int, Callable] = {}     # k-bucket -> verify fn
        self._drafter = None
        self._adaptive = None
        self._lookahead = 0
        if self.spec is not None:
            # the verify step writes all k draft candidates' K/V BEFORE
            # acceptance — reserve the lookahead rows at admission
            self._lookahead = self.spec.k_max
            if max_len <= self._lookahead:
                raise EngineConfigError(
                    f"speculative k_max {self._lookahead} leaves no slot "
                    f"capacity at max_len {max_len}")
            if self.spec.mode == "draft":
                self._drafter = DraftModelDrafter(
                    self.spec, num_slots, pad_token_id=pad_token_id)
            else:
                self._drafter = NgramDrafter(self.spec)
            if self.spec.adaptive:
                self._adaptive = AdaptiveK(self.spec, num_slots)
        # ---- results fetched one launch behind (ISSUE 36): decode step
        # N+1 is launched before step N's tokens are fetched, its inputs
        # taken on the device from step N's output. The look-ahead is 1
        # where the loop can see that the next step needs no host token and
        # 0 where it cannot: drafts are made from host tokens, and the
        # block-paged mode's radix and tables move with every finish
        self._ahead = self.spec is None and not prefix_cache
        self._flight: Optional[_Flight] = None
        # last chunks launched this schedule phase, first tokens unfetched
        self._firsts: List[_FirstToken] = []
        # prompt-block counts of the prefill programs launched since, on the
        # device: they ride to the host with the next first token
        self._counted: list = []
        # every slot's newest pick, on the device: the last decode step's
        # next tokens, with the first token of each prefill launched since
        self._previous = self._canon(
            jnp.full((num_slots,), pad_token_id, jnp.int32))
        # metrics
        self.decode_steps = 0
        self.decode_steps_overlapped = 0
        self.slot_steps_wasted = 0
        self.prefill_calls = 0
        # prompt tokens actually run through a prefill program (suffix
        # tokens in prefix-cache mode — the bench's "prefill tokens
        # computed" axis; radix-matched tokens never hit the device)
        self.prefill_tokens_computed = 0
        self.tokens_generated = 0
        # SLO-aware scheduling accounting (ISSUE 8; bench + telemetry)
        self.prefill_chunks = 0
        self.preemptions = 0
        # swap traffic in pool blocks (block-paged) / slot pages
        # (slot-paged: the whole slot row is the swap unit, 1 per trip)
        self.swapped_blocks_out = 0
        self.swapped_blocks_in = 0
        self.swap_capacity_rejections = 0
        self.slo_deferred_steps = 0
        self._active_slot_iterations = 0
        # speculative accounting (spec mode only; bench + telemetry)
        self.spec_drafted_tokens = 0
        self.spec_accepted_tokens = 0
        self._draft_wall = 0.0
        self._verify_wall = 0.0
        # decode-phase wall clock (plain decode + draft + verify calls,
        # host-observed): the denominator of the bench's decode
        # tokens/sec — run() wall would dilute the decode hot path with
        # prefill and idle time
        self.decode_wall = 0.0
        if telemetry is True:
            from deepspeed_tpu.telemetry import get_registry

            self.telemetry = get_registry()
        else:
            self.telemetry = telemetry or None
        if self.telemetry is not None:
            # read as a share of serving/decode_steps: there from the start
            self.telemetry.counter("serving/decode_steps_overlapped")
            self.telemetry.counter("serving/slot_steps_wasted")
        # ---- SLO control plane + per-tenant accounting (ISSUE 13)
        self.slo = slo
        if tenants is None:
            tenants = self.telemetry is not None
        if tenants:
            from deepspeed_tpu.telemetry.tenants import TenantLedger

            self.tenants = TenantLedger(self.telemetry)
        else:
            self.tenants = None
        # KV occupancy billing unit: PAYLOAD bytes per pool block (a
        # quantized pool's blocks bill at what they actually cost in
        # HBM — the int8 capacity lever shows up on the tenant's bill);
        # slot-paged mode bills the whole slot row as one "block", every
        # leaf of its state tree
        if prefix_cache:
            from deepspeed_tpu.serving.kv_quant import pool_payload

            n_rows = self.cache.num_blocks + 1
            self._kv_bytes_per_block = (
                pool_payload(self.cache.k).nbytes
                + pool_payload(self.cache.v).nbytes) / n_rows
        else:
            self._kv_bytes_per_block = self.cache.hbm_bytes() / num_slots
            if self.telemetry is not None:
                self.telemetry.gauge("serving/state_bytes_per_slot").set(
                    self._kv_bytes_per_block)
        self._acct_last_t: Optional[float] = None
        # ---- span-graph tracing (ISSUE 11)
        self.tracer = tracer
        self._rtraces: Dict[int, _ReqTrace] = {}
        self._engine_trace: Optional[str] = None  # iteration-span trace
        # the open `iteration` span (armed, and only while a step() that
        # found work runs), the instant its last recorded phase ended —
        # the next phase starts there, so the phases tile it — and the
        # phase whose TraceAnnotation is open (see _Phase)
        self._iter_span = None
        self._phase_t = 0.0
        self._open_phase: Optional[_Phase] = None
        self._last_step_now = 0.0     # cancel() has no `now` argument
        # context-carrying records awaiting their submit-time stamp
        # (resolved by the next step(); see _ReqTrace.submitted_t)
        self._pending_submit_stamps: List[_ReqTrace] = []
        # ---- set-up and compiles, measured from inside (ISSUE 42); the
        # host process from inside (ISSUE 57): None without a registry
        self._compile_sub = None
        self._watch: Optional[ServingWatch] = None
        # a prefill chunk was launched that no fetch has waited for yet
        self._chunk_unfetched = False
        # stages told, and the host watch's spans, while no iteration span
        # was open (armed only)
        self._loose_compiles: List[tuple] = []
        self._loose_spans: List[tuple] = []
        self._subscribe_compile_log(cache_phase)
        # radix prefix index over the block pool (ISSUE 6) — created
        # after telemetry so its hit/miss/COW/eviction counters land in
        # the same registry as the serving histograms
        self.prefix = (PrefixCache(self.cache, registry=self.telemetry)
                       if prefix_cache else None)
        log_dist(f"ServingEngine: slots={num_slots} max_len={max_len} "
                 f"buckets={self.buckets} cache={self.cache!r}", ranks=[0])

    # -------------------------------------------------------------- programs
    def _adopt(self, out) -> tuple:
        """Hand a serving program's leading outputs (the cache's carry)
        back to the cache; what is left is the program's answer."""
        self.cache.update(*out[:self._n_carry])
        return out[self._n_carry:]

    def _adopt_first(self, out):
        """:meth:`_adopt` for a prefill program: a slot-paged one also
        hands back the decode step's previous tokens with its pick written
        at the slot, and a model that counts what a prompt block did
        (``prompt_counters``) its counts last, kept on the device until a
        first token is fetched. Returns the pick, on the device."""
        token, *previous = self._adopt(out)
        if self.prefix is None and getattr(self.engine.module,
                                           "prompt_counters", ()):
            self._counted.append(previous.pop())
        if previous:
            self._previous, = previous
        return token

    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefill:
            if self.prefix is not None:
                self._prefill[bucket] = self.engine.block_prefill_program(
                    bucket, self.num_slots, self.cache.max_blocks_per_slot,
                    kv_dtype=self._kv_key, **self._sample_kw)
            else:
                self._prefill[bucket] = self.engine.slot_prefill_program(
                    bucket, self.num_slots, self.max_len, **self._sample_kw)
        return self._prefill[bucket]

    def _chunk_fn(self, bucket: int):
        """Slot-paged mid-prompt chunk prefill (ISSUE 8) — the chunk
        attends over the slot's own already-written prefix, so unlike
        the monolithic bucket prefill it can start at a traced offset.
        Block-paged chunking needs no separate program (the suffix
        prefill's ``start`` operand is the chunk offset)."""
        if bucket not in self._chunk_prefill:
            self._chunk_prefill[bucket] = \
                self.engine.slot_chunk_prefill_program(
                    bucket, self.num_slots, self.max_len, **self._sample_kw)
        return self._chunk_prefill[bucket]

    def _build_swap_programs(self) -> None:
        """Preemption swap-out/in programs for the active cache mode
        (ISSUE 8) — compiled at warmup when the policy is on, so a
        preemption mid-trace never compiles."""
        if self._swap_out_fn is not None:
            return
        eng = self.engine
        if self.prefix is not None:
            mb = self.cache.max_blocks_per_slot
            self._swap_out_fn = eng.block_swap_out_program(
                self.cache.num_blocks, mb, kv_dtype=self._kv_key)
            self._swap_in_fn = eng.block_swap_in_program(
                self.cache.num_blocks, mb, kv_dtype=self._kv_key)
        else:
            self._swap_out_fn = eng.slot_swap_out_program(
                self.num_slots, self.max_len)
            self._swap_in_fn = eng.slot_swap_in_program(
                self.num_slots, self.max_len)

    def _verify_fn(self, kb: int):
        """Speculative verify program for draft-width bucket ``kb`` —
        one compiled program per bucket in the FIXED k_buckets set, so
        adaptive-k transitions never compile (the spec analog of the
        prefill length buckets)."""
        if kb not in self._verify:
            if self.prefix is not None:
                self._verify[kb] = self.engine.block_verify_program(
                    self.num_slots, self.cache.max_blocks_per_slot, kb,
                    pad_token_id=self.pad_token_id, kv_dtype=self._kv_key,
                    **self._sample_kw)
            else:
                self._verify[kb] = self.engine.slot_verify_program(
                    self.num_slots, self.max_len, kb,
                    pad_token_id=self.pad_token_id, **self._sample_kw)
        return self._verify[kb]

    @property
    def program_count(self) -> int:
        """Compiled serving programs built so far (== len(buckets) + 1
        after warmup without speculation — the no-recompile tests pin
        this; speculation adds one verify program per k-bucket plus the
        draft-model programs; the prefix cache adds exactly one COW
        block-copy program)."""
        n = len(self._prefill) + 1 + len(self._verify)
        n += len(self._chunk_prefill)
        if self._swap_out_fn is not None:
            n += 2
        if self._copy_fn is not None:
            n += 1
        if self._drafter is not None:
            n += len(self._drafter.program_cache_sizes())
        return n

    def program_cache_sizes(self) -> Dict[str, int]:
        """jit-cache entry count per serving program — every value must
        be 1 after any trace ("zero XLA recompiles after warmup"):
        a second entry would mean some argument's shape/dtype varied.
        Covers the speculative verify programs (one per k-bucket) and
        the draft-model programs when speculation is on."""
        out = {"decode": self._decode._cache_size()}
        for b, fn in self._prefill.items():
            out[f"prefill_{b}"] = fn._cache_size()
        for b, fn in self._chunk_prefill.items():
            out[f"chunk_prefill_{b}"] = fn._cache_size()
        for kb, fn in self._verify.items():
            out[f"verify_{kb}"] = fn._cache_size()
        if self._swap_out_fn is not None:
            out["swap_out"] = self._swap_out_fn._cache_size()
            out["swap_in"] = self._swap_in_fn._cache_size()
        if self._copy_fn is not None:
            out["block_copy"] = self._copy_fn._cache_size()
        if self._drafter is not None:
            out.update(self._drafter.program_cache_sizes())
        return out

    # ------------------------------------- set-up and compiles (ISSUE 42)
    def _subscribe_compile_log(self, cache_phase: SetupPhase) -> None:
        """Bring the registry up to what this process has traced, lowered,
        compiled and loaded so far (``init_inference`` ran before the
        registry existed) and keep it there; publish the set-up phases that
        are over: the engine's weights, once a registry, and the cache.

        JAX stamps compile events with ``time.time()`` and the phases are
        stamped with ``time.perf_counter()``. The offsets from both to the
        engine's clock are taken once, here, where that clock is a real one;
        under a virtual clock such an interval is recorded with no length at
        the engine's last instant, so a replayed timeline stays its own."""
        self._from_perf = self._from_wall = None
        if self.telemetry is None and self.tracer is None:
            return
        if self._real_clock:
            now = self._time()
            self._from_perf = now - time.perf_counter()
            self._from_wall = now - time.time()
        if self.telemetry is not None:
            # read beside entry/traces: there from the start, at 0
            self.telemetry.counter("entry/traces_after_warm")
        for phase in (getattr(self.engine, "setup_weights", None),
                      cache_phase):
            if phase is not None:
                self._publish_phase(phase)
        engine = weakref.ref(self)

        def on_stage(stage, program, start, end):
            live = engine()
            if live is not None:
                live._on_compile_stage(stage, program, start, end)

        def follows():
            # this engine's own: the event arrives inside its constructor,
            # its warmup() or its step(), whoever else compiles meanwhile
            live = engine()
            return live is not None and live._at_work

        log = compile_log()
        self._compile_sub = log.subscribe(self.telemetry, on_stage, follows)
        # an engine dropped without close() must not be told for ever
        weakref.finalize(self, log.unsubscribe, self._compile_sub)
        if self.telemetry is None:
            return
        # the host watch stamps on the engine's clock where that is a
        # monotonic one (a stamp then serves the armed path too), else on
        # perf_counter: a virtual clock is never read on its behalf

        def span(name, t0, t1, offset, **attrs):
            live = engine()
            return t1 if live is None else live._watch_span(
                name, t0, t1, offset, **attrs)

        def holds_work(gap, now):
            live = engine()
            return 0.0 if live is None else live._held_for(gap, now)

        def open_phase():
            live = engine()
            return None if live is None else live._open_phase

        own = self._time in (time.monotonic, time.perf_counter)
        self._watch = ServingWatch(
            self.telemetry, at_work=follows, holds_work=holds_work,
            span=functools.partial(
                span, offset=0.0 if own else self._from_perf),
            gc_span=functools.partial(span, offset=self._from_perf),
            open_phase=open_phase,
            clock=self._time if own else time.perf_counter)
        weakref.finalize(self, self._watch.close)

    def _engine_clock(self, t0: float, t1: float, offset) -> tuple:
        """An interval of one of the host's clocks on the engine's."""
        if offset is None:
            t = self._phase_t if self._iter_span is not None \
                else self._last_step_now
            return t, t
        offset -= self._run_t0 or 0.0
        return t0 + offset, t1 + offset

    def _publish_phase(self, phase: SetupPhase):
        """A set-up phase into the registry and, armed, onto the engine's
        trace; the span, if one was recorded."""
        span = {} if self.tracer is None else {"trace_id": self._iter_trace()}
        return phase.publish(
            self.telemetry, self.tracer,
            clock=lambda t0, t1: self._engine_clock(t0, t1, self._from_perf),
            **span)

    def _on_compile_stage(self, stage: str, program: str, start: float,
                          end: float) -> None:
        """One stage of one program's compile that arrived while this
        engine was at work, as the compile log tells it. Before
        ``warmup()`` has returned that is set-up, and the ``entry/*``
        counters hold it. After it, it arrived inside ``step()`` and is a
        recompile in service: the trace counts in
        ``entry/traces_after_warm`` and, armed, every stage is a ``compile``
        span under the open ``iteration``. What compiles outside ``step()``
        (the caller's own ``jnp`` calls, another engine warming up in this
        process) is not told here."""
        if not self._warm:
            return
        if stage == "trace" and self.telemetry is not None:
            self.telemetry.counter("entry/traces_after_warm").inc()
        if self.tracer is None:
            return
        if self._iter_span is None:
            # a prefill compiles in the schedule phase, before step() has
            # opened the iteration it belongs to: step() records it then
            self._loose_compiles.append((stage, program, start, end))
        else:
            self._record_compile(stage, program, start, end, self._iter_span)

    def _record_compile(self, stage: str, program: str, start: float,
                        end: float, parent) -> None:
        self._record_under(
            parent, "compile",
            *self._engine_clock(start, end, self._from_wall),
            {"program": program, "stage": stage})

    def _watch_span(self, name: str, t0: float, t1: float, offset,
                    **attrs) -> float:
        """An interval the host watch stamped (a stall, a collection), on
        the engine's clock: where it ends there and, armed, a span under
        the open ``iteration``."""
        t0, t1 = self._engine_clock(t0, t1, offset)
        if self.tracer is None:
            pass
        elif self._iter_span is None and self._at_work:
            # the schedule phase closes before step() has opened the
            # iteration it belongs to: step() records it then
            self._loose_spans.append((name, t0, t1, attrs))
        else:
            self._record_under(self._iter_span, name, t0, t1, attrs)
        return t1

    def _record_under(self, parent, name: str, t0: float, t1: float,
                      attrs: dict) -> None:
        """A span of the engine's own trace under the ``iteration`` it
        ended in, if it ended in one."""
        if parent is not None and t1 < parent.start:
            parent = None    # between two steps: no iteration's
        self.tracer.record(
            name, t0, t1, trace_id=self._iter_trace(),
            parent_id=None if parent is None else parent.span_id, **attrs)

    def _held_for(self, gap: float, now: float) -> float:
        """Of the ``gap`` seconds since ``step()`` last returned, those in
        which the engine held work: all of them with a slot occupied or
        anything launched and unfetched; with requests queued alone, since
        the first of them was due (an empty system waiting for its next
        arrival is not stalled)."""
        if (self._flight is not None or self._firsts
                or any(s is not None for s in self._slots)):
            return gap
        due = self.scheduler.next_arrival()
        if due is None or not self._real_clock:
            return 0.0
        return min(gap, max(now - due, 0.0))

    def close(self) -> None:
        """Stop following the compile log and the collector. The engine
        holds no other process-wide registration; dropping it unsubscribes
        too."""
        compile_log().unsubscribe(self._compile_sub)
        self._compile_sub = None
        if self._watch is not None:
            self._watch.close()

    @at_work
    def warmup(self) -> None:
        """Compile every serving program (each bucket's prefill + the
        decode step + with speculation each k-bucket's verify and draft
        programs) on dummy data, then reset the slot lengths. Two
        passes, so both signatures — canonical (post-reset) and
        program-output — of the carry and of the decode step's previous
        tokens are cached for every program; after this, a
        trace of ANY shape mix (including adaptive-k transitions) runs
        zero compiles. Each pass ends at a fence of its own, so
        ``entry/setup_warmup_ms`` and, for the second pass alone,
        ``entry/setup_warmup_repeat_ms`` read work done (spans
        ``setup_warmup`` and, a pass, ``warmup_pass``)."""
        if self._warm:
            return
        whole = SetupPhase("warmup")
        passes = []
        for _ in range(2):
            t_pass = time.perf_counter()
            self._warmup_pass()
            # dstpu-lint: fence=warmup: a pass is read at its end, set-up only
            jax.block_until_ready((self.cache.carry(), self._previous))
            passes.append((t_pass, time.perf_counter()))
        whole.close()
        if self.telemetry is not None:
            self.telemetry.counter("entry/setup_warmup_repeat_ms").inc(
                (passes[1][1] - passes[1][0]) * 1e3)
        outer = self._publish_phase(whole)
        if outer is not None:
            for i, (t0, t1) in enumerate(passes):
                self.tracer.record(
                    "warmup_pass",
                    *self._engine_clock(t0, t1, self._from_perf),
                    trace_id=outer.trace_id, parent_id=outer.span_id,
                    **{"pass": i})
        self._warm = True

    def _warmup_pass(self) -> None:
        """Every serving program once, on dummy data."""
        eng = self.engine
        paged = self.prefix is not None
        for b in self.buckets:
            ids = jnp.zeros((1, b), jnp.int32)
            if paged:
                # sentinel table row: the dummy prefill's writes land
                # in the pool's garbage block, never a real one
                out = self._prefill_fn(b)(
                    eng.params, *self.cache.carry(), ids,
                    self.cache.table_row(0), np.int32(0), np.int32(0),
                    np.int32(1), self._temp, self._zero_key)
            else:
                out = self._prefill_fn(b)(
                    eng.params, *self.cache.carry(), ids, np.int32(0),
                    np.int32(1), self._temp, self._zero_key,
                    self._previous)
            self._adopt_first(out)
            if (self._chunk_max is not None and not paged
                    and b <= self._chunk_max):
                # slot-paged chunk programs: chunks never exceed
                # _chunk_max, so only buckets up to it can run one
                out = self._chunk_fn(b)(
                    eng.params, *self.cache.carry(), ids, np.int32(0),
                    np.int32(0), np.int32(1), self._temp,
                    self._zero_key, self._previous)
                self._adopt_first(out)
        if self.preemption is not None:
            # swap round trip through slot/garbage rows, with the
            # host upload in the loop so BOTH runtime operand
            # signatures (canonical carry + numpy-uploaded rows) are
            # cached — a first preemption mid-trace must not compile
            self._build_swap_programs()
            if paged:
                sent = jnp.asarray(np.full(
                    (self.cache.max_blocks_per_slot,),
                    self.cache.sentinel, np.int32))
                ko, vo = self._swap_out_fn(self.cache.k, self.cache.v, sent)
                args_in = (_to_device(jax.device_get(ko)),  # dstpu-lint: fence=warmup: pre-cache numpy-upload swap signature
                           _to_device(jax.device_get(vo)),
                           sent)
            else:
                ko, vo = self._swap_out_fn(self.cache.k, self.cache.v,
                                           np.int32(0))
                args_in = (jnp.asarray(np.asarray(jax.device_get(ko))),  # dstpu-lint: fence=warmup: pre-cache numpy-upload swap signature
                           jnp.asarray(np.asarray(jax.device_get(vo))))
            out = self._swap_in_fn(self.cache.k, self.cache.v,
                                   *args_in, self.cache.lengths,
                                   np.int32(0), np.int32(0))
            self.cache.update(*out)
        toks = np.zeros((self.num_slots,), np.int32)
        active = np.zeros((self.num_slots,), bool)
        # the previous step's tokens too come with both signatures:
        # uploaded on the first pass, a program's output on the second
        out = self._decode(
            eng.params, *self.cache.carry(), *self._table_args(),
            jnp.asarray(toks), jnp.asarray(active),
            self._temp, self._zero_key, self._previous,
            jnp.asarray(active))
        self._previous = self._adopt(out)[0]
        if paged:
            # COW copy program: garbage row onto itself is a no-op
            k, v = self._copy_fn(self.cache.k, self.cache.v,
                                 np.int32(self.cache.sentinel),
                                 np.int32(self.cache.sentinel))
            self.cache.update_kv(k, v)
        if self.spec is not None:
            zeros = jnp.zeros((self.num_slots,), jnp.int32)
            for kb in self.spec.k_buckets:
                blk = jnp.zeros((self.num_slots, kb + 1), jnp.int32)
                out = self._verify_fn(kb)(
                    eng.params, *self.cache.carry(),
                    *self._table_args(), blk, zeros,
                    jnp.asarray(active), self._temp, self._zero_key)
                self._adopt(out)
                if isinstance(self._drafter, DraftModelDrafter):
                    window = jnp.zeros(
                        (self.num_slots, self._drafter.window),
                        jnp.int32)
                    self._drafter._program(kb)(
                        self._drafter.engine.params, window,
                        jnp.ones((self.num_slots,), jnp.int32))
        self.cache.lengths = self._canon(
            jnp.zeros((self.num_slots,), jnp.int32))
        self._counted.clear()       # the dummy prompts' blocks

    def _table_args(self) -> tuple:
        """Extra traced operand for the block-paged programs: the full
        [B, MB] block table from the host tables (empty in slot-paged
        mode). ``table_array()`` caches the device mirror and only
        re-uploads after ``PrefixCache.admit``/``finish`` call
        ``invalidate_tables()`` — any new code path that mutates
        ``pool.tables`` must invalidate too. Same shape/dtype every
        call — traced DATA, so remapping blocks between steps reuses
        the compiled programs."""
        if self.prefix is None:
            return ()
        return (self.cache.table_array(),)

    # ----------------------------------------------------------------- queue
    def submit(self, request: Request) -> None:
        """Queue a request, validating it up front (ISSUE 9 satellite):
        a malformed prompt/budget raises a TYPED error here — at submit
        time, where the caller can act on it — instead of surfacing as
        an XLA shape or trace failure several decode iterations later.
        All the types subclass ``ValueError`` (serving/errors.py), so
        pre-typed call sites keep working."""
        plen = len(request.prompt)
        if plen < 1:
            raise EmptyPromptError(f"request {request.rid}: empty prompt")
        if request.max_new_tokens < 1:
            raise InvalidMaxNewTokensError(
                f"request {request.rid}: max_new_tokens must be >= 1, "
                f"got {request.max_new_tokens}")
        if self._chunk_max is None and \
                pick_bucket(plen, self.buckets) is None:
            raise PromptTooLongError(
                f"request {request.rid}: prompt length {plen} exceeds the "
                f"largest prefill bucket {self.buckets[-1]} (set "
                f"prefill_token_budget to serve longer prompts via "
                f"chunked prefill)")
        if not self.cache.capacity_for(plen, request.max_new_tokens,
                                       self._lookahead):
            extra = (f" (speculation reserves {self._lookahead} lookahead "
                     f"rows for pre-acceptance draft writes)"
                     if self._lookahead else "")
            raise SlotCapacityError(
                f"request {request.rid}: prompt {plen} + max_new "
                f"{request.max_new_tokens} exceeds slot capacity "
                f"{self.max_len}{extra}")
        if self.tracer is not None:
            # trace context (ISSUE 11): a request arriving WITH context
            # (the fabric's re-dispatch, or any upstream caller) keeps
            # its trace — the engine's spans link under the caller's
            # root. Otherwise the engine owns the root span. The
            # incoming Request is never mutated: context lives in the
            # engine-side record, so replaying the same trace objects
            # (benches, tests) yields fresh traces per run.
            if request.trace_id is not None:
                rt = _ReqTrace(request.trace_id, request.parent_span)
                self._rtraces[request.rid] = rt
                self._pending_submit_stamps.append(rt)
            else:
                root = self.tracer.begin(
                    "request", t=request.arrival_time, rid=request.rid,
                    priority=request.priority, prompt_len=plen)
                self._rtraces[request.rid] = _ReqTrace(
                    root.trace_id, root.span_id, root_span=root)
        self.scheduler.submit(request)

    def cancel(self, rid: int) -> bool:
        """Withdraw a request wherever it currently lives (ISSUE 9 —
        the fabric router's failover/timeout path: a request being
        re-dispatched to another replica must not also finish here).
        Queued: removed from the scheduler (a preempted request's host
        KV is dropped too). Running: its slot is freed — in
        prefix-cache mode the blocks it COMPUTED are donated to the
        radix index (they are valid prefixes; unwritten tails are not)
        and the rest freed. Returns False when the rid is unknown or
        already finished; no result is ever emitted for a cancelled
        request."""
        if self.scheduler.remove(rid):
            if rid in self._preempted:
                self._preempted.pop(rid)
                # discard, not pop: nothing returns to the device, so
                # this must not count as a swap-in
                self.swap.discard(rid)
            self._trace_cancel(rid, "queued")
            return True
        for i, st in enumerate(self._slots):
            if st is not None and st.request.rid == rid:
                self._slots[i] = None
                self.scheduler.release(i)
                if self.prefix is not None:
                    length = int(jax.device_get(self.cache.lengths[i]))  # dstpu-lint: fence=cancel path (cold): computed length gates the radix donate
                    self.prefix.finish(i, donate_upto=length)
                self._trace_cancel(rid, "slot")
                return True
        return False

    def _trace_cancel(self, rid: int, where: str) -> None:
        """Close a cancelled request's open spans (ISSUE 11) — the
        fabric's failover/timeout path cancels here and re-dispatches
        the SAME trace to a survivor, so the cancelled attempt's spans
        must not dangle open. cancel() carries no clock argument; the
        last step() instant is the best engine-base stamp available."""
        if self.tracer is None:
            return
        rt = self._rtraces.pop(rid, None)
        if rt is None:
            return
        t = self._last_step_now
        self.tracer.end(rt.decode_span, t=t, reason="cancelled")
        self.tracer.end(rt.swap_span, t=t, reason="cancelled")
        self.tracer.record("cancel", t, t, trace_id=rt.trace_id,
                           parent_id=rt.root, where=where)
        self.tracer.end(rt.root_span, t=t, finish_reason="cancelled")

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + in flight)."""
        return self.scheduler.waiting + sum(
            s is not None for s in self._slots)

    # ------------------------------------------------------------ iteration
    def _next_rng(self):
        if not self.do_sample:
            return self._zero_key
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _now(self, fallback: float, stamp: Optional[float] = None) -> float:
        """Fresh clock read in run()'s offset base — result timestamps
        include the device work that happened since step() entry (the
        admission-gating ``now`` would understate latency by one
        prefill/decode's compute). ``stamp`` is a read the host watch has
        just made: of this clock where the watch stamps on it."""
        if self._run_t0 is None:
            return fallback
        if stamp is None or self._watch.clock is not self._time:
            stamp = self._time()
        return stamp - self._run_t0

    def _finish(self, slot: int, now: float, reason: str) -> RequestResult:
        st = self._slots[slot]
        st.result.finish_time = self._now(now)
        st.result.finish_reason = reason
        self._slots[slot] = None
        self.scheduler.release(slot)
        if self.prefix is not None:
            # insert-on-finish: donate the prompt's full blocks to the
            # radix index (one cached prefill serves every future match),
            # free the rest, park the table row at the sentinel
            self.prefix.finish(slot)
        if self.tracer is not None:
            rt = self._rtraces.pop(st.request.rid, None)
            if rt is not None:
                t_fin = st.result.finish_time
                self.tracer.end(rt.decode_span, t=t_fin,
                                tokens=len(st.result.tokens),
                                decode_calls=st.result.decode_calls)
                self.tracer.end(rt.swap_span, t=t_fin)
                self.tracer.end(rt.root_span, t=t_fin,
                                finish_reason=reason,
                                tokens=len(st.result.tokens),
                                preemptions=st.result.preemptions)
        if self.telemetry is not None:
            res = st.result
            reg = self.telemetry
            reg.counter("serving/finished_requests").inc()
            reg.histogram("serving/latency_ms").observe(res.latency * 1e3)
            # Orca-style iteration accounting over the decode phase only
            # (TTFT covers the prefill). Divide by ACTUAL decode
            # invocations, not len(tokens) - 1: a speculative verify step
            # emits up to k+1 tokens per invocation, so the token count
            # would overstate the step count and understate TPOT. Time
            # spent PREEMPTED DURING DECODE is queue wait, not decode
            # latency — it is subtracted from the span (and decode_calls
            # never counted swapped-out iterations in the first place);
            # a mid-PREFILL park fell before first_token_time and is
            # already outside the span.
            n_dec = res.decode_calls
            if n_dec > 0:
                tpot = max(res.finish_time - res.first_token_time
                           - res.decode_preempted_wall, 0.0) / n_dec * 1e3
                reg.histogram("serving/tpot_ms").observe(tpot)
                reg.histogram(
                    f"serving/tpot_ms/p{metric_label(res.priority)}"
                ).observe(tpot)
                reg.histogram(
                    "serving/tokens_per_decode_call",
                    buckets=_TOKENS_PER_STEP_BUCKETS).observe(
                    (len(res.tokens) - 1) / n_dec)
                if self.tenants is not None:
                    self.tenants.note_tpot(st.tenant, tpot)
        return st.result

    def _maybe_finish(self, slot: int, now: float) -> Optional[RequestResult]:
        st = self._slots[slot]
        if (self.eos_token_id is not None
                and st.result.tokens
                and st.result.tokens[-1] == self.eos_token_id):
            return self._finish(slot, now, "eos")
        if len(st.result.tokens) >= st.request.max_new_tokens:
            return self._finish(slot, now, "length")
        return None

    def _admit_fits(self, req: Request) -> bool:
        """Admission predicate (scheduler ``fits`` hook). Slot-paged:
        the free-slot list is the only resource, always True.
        Block-paged: the request's UNMATCHED block demand — prompt +
        max_new + speculative lookahead, minus radix-matched full
        blocks — must be servable from free + evictable pool blocks
        (identical accounting for fresh admissions and preempted
        resumes: ``readmit`` re-pins exactly the blocks ``fits``
        credits)."""
        if self.prefix is None:
            return True
        return self.prefix.fits(
            req.prompt,
            len(req.prompt) + req.max_new_tokens + self._lookahead)

    def _stream(self, st: _SlotState, tokens) -> None:
        """Token-streaming callback (ISSUE 8 satellite): invoked once
        per COMMITTED token in emission order — under speculation only
        the accepted (post-EOS-truncation) block ever reaches it, so
        the streamed sequence is exactly ``RequestResult.tokens``."""
        cb = st.request.on_token
        if cb is not None:
            for t in tokens:
                cb(int(t))

    def _iteration_prefill_budget(self, now: float) -> Optional[int]:
        """Prefill tokens this iteration may spend. None = unlimited
        (monolithic mode). With ``tpot_slo_ms`` set, an iteration whose
        decode-gap EMA exceeds the budget while decode-phase slots
        exist defers ALL prefill work (returns 0) — decode runs
        untaxed — but never more than ``slo_max_defer`` times in a row,
        so prefilling requests always progress (deferral shapes WHEN
        prefill happens, never WHETHER). The streak counts only
        iterations that actually had prefill work to defer (an
        in-flight chunked prompt, or an arrived fresh head): idle
        at-risk iterations neither defer anything nor burn the streak —
        otherwise a long prompt arriving right after an idle at-risk
        stretch would prefill undeferred in the exact iteration the EMA
        flags a breach."""
        budget = self.prefill_token_budget
        if budget is None:
            return None
        at_risk = (self.tpot_slo_ms is not None
                   and self._decode_gap_ema is not None
                   and self._decode_gap_ema * 1e3 > self.tpot_slo_ms
                   and any(s is not None and not s.prefilling
                           for s in self._slots))
        if not at_risk:
            self._defer_streak = 0
            return budget
        head = self.scheduler.peek(now)
        work = (any(s is not None and s.prefilling for s in self._slots)
                or (head is not None and head.rid not in self._preempted))
        if not work:
            return budget       # nothing to defer; streak untouched
        if self._defer_streak >= self._slo_max_defer:
            self._defer_streak = 0
            return budget
        self._defer_streak += 1
        self.slo_deferred_steps += 1
        if self.telemetry is not None:
            self.telemetry.counter("serving/slo_deferred_steps").inc()
        return 0

    def _schedule(self, now: float, finished: List[RequestResult]) -> None:
        """One iteration of the admit/prefill side of the serving loop
        (ISSUE 8): continue in-flight chunked prefills (priority, then
        admission order), then admit — preempting lower-priority slots
        when the policy allows — all under this iteration's prefill
        token budget. Swap-ins ride free (a resume is an HBM copy, not
        prefill compute), so a preempted request never waits on budget.

        Prefix-cache mode admits ONE request per scheduler call (each
        admission consumes pool blocks the next ``fits`` check must
        see), matches the prompt against the radix index, pins + names
        the matched block chain in the slot's table, runs the COW fork
        copies, and prefills only the unmatched suffix — bucketed by
        SUFFIX length (and chunked under a prefill budget), so a long
        shared system prompt with a short unique tail prefills in the
        smallest bucket.

        A prompt's first token is not fetched after its own launch (ISSUE
        36) but after the iteration's last, the decode step's
        (:meth:`_land_firsts`): a burst's prefills and the step behind them
        run on the device back to back, and each first token is still
        stamped when its own fetch returns. A request that ends at its first
        token (``max_new_tokens`` 1, EOS) frees its slot at that commit, for
        the next iteration's admission."""
        budget = self._iteration_prefill_budget(now)
        # (1) in-flight chunked prefills first: an admitted prompt
        # finishes prefilling before new admissions eat the budget
        # (Sarathi's stall-free ordering — decode-phase slots are
        # protected by the budget itself). EXCEPT when the queue head
        # strictly outranks a prefilling slot (same double guard as
        # preemption): its budget share is yielded so the admission
        # loop below can preempt and admit the head — otherwise a
        # lower-class long prompt's chunking would block an interactive
        # arrival for its whole prefill (priority inversion).
        spent = self._continue_prefills(now, budget, 0, finished,
                                        yield_to_head=True)
        # (2) admission (+ preemption to make room)
        while True:
            if budget is not None and spent >= budget:
                head = self.scheduler.peek(now)
                if head is None or head.rid not in self._preempted:
                    break
            pairs = self.scheduler.admit(now, fits=self._admit_fits,
                                         limit=1)
            if not pairs:
                if not self._try_preempt(now, finished):
                    break
                continue
            (req, slot), = pairs
            if req.rid in self._preempted:
                self._resume(slot, req, now, finished)
                continue
            spent += self._admit_one(
                slot, req, now, None if budget is None else budget - spent,
                finished)
        # (3) leftover budget back to whoever is still prefilling (the
        # head either got placed above or cannot be placed at all —
        # idling the budget would help nobody)
        self._continue_prefills(now, budget, spent, finished,
                                yield_to_head=False)

    def _continue_prefills(self, now: float, budget: Optional[int],
                           spent: int, finished: List[RequestResult],
                           yield_to_head: bool) -> int:
        """Advance in-flight chunked prefills in (priority, admission)
        order under the remaining budget. With ``yield_to_head``, a
        slot that the best arrived queue head strictly outranks (raw
        class AND aged effective priority — preemption's guard) is
        skipped: its budget share belongs to the head the admission
        loop is about to place — into a free slot, or (policy
        permitting) into this very slot after preempting it. If the
        head turns out unplaceable, the post-admission leftover pass
        returns the yielded budget to the skipped slot, so yielding
        never idles an iteration."""
        head = self.scheduler.peek(now) if yield_to_head else None
        eff = self.scheduler.effective_priority
        pre = sorted((i for i, s in enumerate(self._slots)
                      if s is not None and s.prefilling),
                     key=lambda i: (self._slots[i].request.priority,
                                    self._slots[i].order))
        for slot in pre:
            if budget is not None and spent >= budget:
                break
            st = self._slots[slot]
            if (head is not None
                    and head.priority < st.request.priority
                    and eff(head, now) < eff(st.request, now)):
                continue
            spent += self._run_prefill_chunks(
                slot, now, None if budget is None else budget - spent,
                finished)
        return spent

    def _admit_one(self, slot: int, req: Request, now: float,
                   budget_left: Optional[int],
                   finished: List[RequestResult]) -> int:
        """Admit one fresh request into ``slot``: radix match + COW
        forks (prefix-cache mode), then prefill as much of the prompt
        as the budget allows (the rest continues on later iterations).
        Returns prefill tokens spent.

        A request whose ``deadline`` already passed is SHED here —
        after it won its slot but BEFORE any prefill compute (ISSUE 9:
        an answer nobody is waiting for must not waste the iteration
        budget): it finishes immediately with ``finish_reason
        "shed_deadline"`` and the slot is released. Preempted resumes
        never pass through here, so sunk prefill work is never thrown
        away by the shed."""
        plen = len(req.prompt)
        if req.deadline is not None and now > req.deadline:
            self.scheduler.release(slot)
            res = RequestResult(rid=req.rid, prompt_len=plen,
                                arrival_time=req.arrival_time,
                                admitted_time=now, priority=req.priority)
            res.finish_time = self._now(now)
            res.finish_reason = "shed_deadline"
            finished.append(res)
            if self.telemetry is not None:
                self.telemetry.counter("serving/shed_deadline").inc()
            if self.tenants is not None:
                self.tenants.note_shed(self.tenants.resolve(req.tenant_id))
            if self.tracer is not None:
                rt = self._rtraces.pop(req.rid, None)
                if rt is not None:
                    start = req.arrival_time if rt.submitted_t is None \
                        else rt.submitted_t
                    self.tracer.record(
                        "queue_wait", min(start, now), now,
                        trace_id=rt.trace_id, parent_id=rt.root, slot=slot)
                    self.tracer.end(rt.root_span, t=res.finish_time,
                                    finish_reason="shed_deadline")
            return 0
        start = 0
        if self.prefix is not None:
            total = plen + req.max_new_tokens + self._lookahead
            start, copies = self.prefix.admit(slot, req.prompt, total)
            for src, dst in copies:
                k, v = self._copy_fn(self.cache.k, self.cache.v,
                                     np.int32(src), np.int32(dst))
                self.cache.update_kv(k, v)
        res = RequestResult(rid=req.rid, prompt_len=plen,
                            arrival_time=req.arrival_time,
                            admitted_time=now, priority=req.priority)
        tenant = self.tenants.resolve(req.tenant_id) \
            if self.tenants is not None else "default"
        self._slots[slot] = _SlotState(req, res, last_token=0,
                                       prefill_pos=start,
                                       prefill_total=plen,
                                       order=self._admit_seq,
                                       tenant=tenant)
        self._admit_seq += 1
        if self.tenants is not None:
            # per-tenant usage (ISSUE 13): the prompt lands on the bill
            # at admission; radix-matched tokens are the prefix cache's
            # per-tenant dividend (prefill the tenant did NOT pay for)
            self.tenants.note_admitted(tenant, plen)
            if start:
                self.tenants.note_prefill(tenant, 0, saved=start)
        if self.telemetry is not None:
            reg = self.telemetry
            reg.counter("serving/prefills").inc()
            reg.histogram("serving/queue_wait_ms").observe(
                max(now - req.arrival_time, 0.0) * 1e3)
        if self.tracer is not None:
            rt = self._rtraces.get(req.rid)
            if rt is not None:
                t_q0 = req.arrival_time if rt.submitted_t is None \
                    else rt.submitted_t
                self.tracer.record(
                    "queue_wait", min(t_q0, now), now,
                    trace_id=rt.trace_id, parent_id=rt.root, slot=slot,
                    priority=req.priority, radix_matched_tokens=start)
        if self._adaptive is not None:
            self._adaptive.reset_slot(slot)
        return self._run_prefill_chunks(slot, now, budget_left, finished)

    def _run_prefill_chunks(self, slot: int, now: float,
                            budget_left: Optional[int],
                            finished: List[RequestResult]) -> int:
        """Advance slot ``slot``'s prefill by whole chunks until its
        prompt is done or the budget is spent. Monolithic mode
        (``budget_left`` None, no chunk cap) is the single-chunk
        degenerate case and runs the exact pre-ISSUE-8 program path.
        The first generated token is picked only by the LAST chunk —
        intermediate chunk picks are never device_get (discarded, still
        async) — and TTFT is stamped at that commit (ISSUE 8
        latency-accounting fix), which :meth:`_land_firsts` makes: behind
        the iteration's decode launch, or here where the loop never
        launches ahead. A slot-paged program also writes the pick into
        ``_previous`` at the slot, where that decode step finds it."""
        st = self._slots[slot]
        req = st.request
        eng = self.engine
        spent = 0
        while st.prefilling and (budget_left is None or spent < budget_left):
            remaining = st.prefill_total - st.prefill_pos
            chunk = remaining if self._chunk_max is None \
                else min(remaining, self._chunk_max)
            last = st.prefill_pos + chunk == st.prefill_total
            bucket = pick_bucket(chunk, self.buckets)
            ids = np.full((1, bucket), self.pad_token_id, np.int32)
            ids[0, :chunk] = np.asarray(
                req.prompt[st.prefill_pos:st.prefill_pos + chunk], np.int32)
            armed = self.tracer is not None
            if armed:
                t_span0 = self._now(now)
            # an idle device from the program call on is the prefill's, not
            # the admission's; the last chunk's fetch opens the same
            # annotation again
            with _Phase(self, "dstpu/serving_prefill", None, now) as phase:
                if self.prefix is not None:
                    pname = f"prefill_{bucket}"
                    out = self._prefill_fn(bucket)(
                        eng.params, *self.cache.carry(), jnp.asarray(ids),
                        self.cache.table_row(slot), np.int32(slot),
                        np.int32(st.prefill_pos), np.int32(chunk),
                        self._temp, self._next_rng())
                elif st.prefill_pos == 0 and last:
                    # whole prompt in one chunk: the monolithic bucket
                    # program (fresh bucket-sized cache + slot insert)
                    pname = f"prefill_{bucket}"
                    out = self._prefill_fn(bucket)(
                        eng.params, *self.cache.carry(), jnp.asarray(ids),
                        np.int32(slot), np.int32(chunk), self._temp,
                        self._next_rng(), self._previous)
                else:
                    pname = f"chunk_prefill_{bucket}"
                    out = self._chunk_fn(bucket)(
                        eng.params, *self.cache.carry(), jnp.asarray(ids),
                        np.int32(slot), np.int32(st.prefill_pos),
                        np.int32(chunk), self._temp, self._next_rng(),
                        self._previous)
                token = self._adopt_first(out)
                if armed:
                    # host-stamped at the instants the loop already holds:
                    # no fence added. An intermediate chunk has no fence,
                    # so under async dispatch its span brackets the
                    # dispatch only (fenced=False); the LAST chunk's span
                    # closes at its commit, after the token fetch the
                    # untraced engine always paid
                    rt = self._rtraces.get(req.rid)
                    if rt is not None and not last:
                        self.tracer.record(
                            "prefill_chunk", t_span0, self._now(now),
                            trace_id=rt.trace_id, parent_id=rt.root,
                            program=pname, bucket=bucket, tokens=chunk,
                            slot=slot, fenced=False)
                st.prefill_pos += chunk
                # the budget is charged in BUCKET-PADDED tokens — the
                # compute actually dispatched — so one iteration's prefill
                # work genuinely stays near the cap (true-token charging
                # would let padding push real work past it); chunks are
                # never clamped below their natural size, since a padded
                # bucket costs the same forward whether half full or full
                spent += bucket
                self.prefill_tokens_computed += chunk
                self.prefill_chunks += 1
                st.result.prefill_chunks += 1
                if self.telemetry is not None:
                    self.telemetry.counter("serving/prefill_chunks").inc()
                    # rows every prefill program call ran, and those of
                    # them beyond the true length: attention hides its
                    # padding, a recurrence can only mask it
                    self.telemetry.counter(
                        "serving/prefill_rows_run").inc(bucket)
                    self.telemetry.counter(
                        "serving/prefill_rows_padding").inc(bucket - chunk)
                if self.tenants is not None:
                    # billed at the same increment as the engine counter,
                    # so per-tenant computed tokens sum EXACTLY to it
                    self.tenants.note_prefill(st.tenant, chunk)
                if last:
                    st.in_flight += 1
                    counted, self._counted = self._counted, []
                    self._firsts.append(_FirstToken(
                        slot, st, token, counted, t_span0 if armed else 0.0,
                        pname, bucket, chunk,
                        0.0 if self._watch is None else phase.t0))
                else:
                    self._chunk_unfetched = True
            if last and not self._ahead:
                self._land_firsts(now, finished)
        return spent

    def _land_firsts(self, now: float,
                     finished: List[RequestResult]) -> None:
        """Fetch and commit, in launch order, the first tokens of the
        prefills launched since the last call: each token is stamped (TTFT,
        the ``fenced`` chunk span's end) at a fresh read of the clock when
        its own fetch returns."""
        firsts, self._firsts = self._firsts, []
        for first in firsts:
            slot, st = first.slot, first.state
            req = st.request
            with _Phase(self, "dstpu/serving_prefill", None, now):
                tok, counted = jax.device_get((first.token, first.counted))  # dstpu-lint: fence=token emission: the chunk's final pick must reach the host stream
                tok = int(tok)
            self._chunk_unfetched = False     # this fetch waited for it
            if self._watch is not None:
                pb = self._prompt_block
                self._watch.prefill_done(
                    first.bucket,
                    -(-first.chunk // pb) if pb and first.bucket > pb else 1,
                    first.t_call)
            if counted and self.telemetry is not None:
                self.engine.module.record_prompt_counters(self.telemetry,
                                                          counted)
            st.in_flight -= 1
            self.prefill_calls += 1
            self.tokens_generated += 1
            st.last_token = tok
            st.result.tokens.append(tok)
            t_emit = self._now(now)
            st.result.first_token_time = t_emit
            st.result.token_times.append(t_emit)
            self._stream(st, [tok])
            ttft = max(t_emit - req.arrival_time, 0.0) * 1e3
            if self.telemetry is not None:
                self.telemetry.histogram("serving/ttft_ms").observe(ttft)
                self.telemetry.histogram(
                    f"serving/ttft_ms/p{metric_label(req.priority)}"
                ).observe(ttft)
            if self.tenants is not None:
                self.tenants.note_tokens(st.tenant, 1)
                self.tenants.note_ttft(st.tenant, ttft)
            if self.tracer is not None:
                rt = self._rtraces.get(req.rid)
                if rt is not None:
                    # the fenced chunk ends at the first-token commit,
                    # where decode-phase residency starts (closed at
                    # finish/preemption/cancel)
                    self.tracer.record(
                        "prefill_chunk", first.t_span0, t_emit,
                        trace_id=rt.trace_id, parent_id=rt.root,
                        program=first.program, bucket=first.bucket,
                        tokens=first.chunk, slot=slot, fenced=True)
                    rt.decode_span = self.tracer.begin(
                        "decode_segment", trace_id=rt.trace_id,
                        parent_id=rt.root, t=t_emit, slot=slot)
            done = self._maybe_finish(slot, now)
            if done is not None:
                finished.append(done)

    def _land_all(self, now: float, finished: List[RequestResult]) -> bool:
        """Commit whatever is launched and unfetched, first tokens and the
        decode step in flight, before host work that needs every slot's
        state whole (a swap-out parks ``last_token``). Says whether there
        was anything: a commit can finish requests and free their slots."""
        if not self._firsts and self._flight is None:
            return False
        self._land_firsts(now, finished)
        flight = self._flight
        if flight is not None:
            self._commit(flight, self._fetch(flight, now), now, finished)
        return True

    # -------------------------------------------------------- preemption
    def _try_preempt(self, now: float,
                     finished: List[RequestResult]) -> bool:
        """Make room for the best waiting request by swapping out one
        strictly-lower-priority running slot (ISSUE 8). Called only
        after admission came up empty, i.e. the candidate is blocked on
        a slot or (block-paged) on pool blocks. Two guards bound
        thrash: the victim's RAW class must be strictly worse (a
        resumed request can never be preempted by the class that
        displaced it), and its AGED effective priority must be worse
        too — a victim that waiting has promoted past the candidate
        would rank AHEAD of it in the queue after resubmit, so evicting
        it would only swap it straight back in (the resume→preempt
        ping-pong this guard exists to prevent). Victim choice: the
        worst class, and within it the most recently admitted (least
        sunk work). Returns True if a slot was freed (the caller
        retries admission). Nothing is in flight while a victim is chosen
        and swapped out: what was is committed first, and since that alone
        can free a slot the caller retries after it too."""
        if self.preemption is None:
            return False
        cand = self.scheduler.peek(now)
        if cand is None:
            return False
        eff = self.scheduler.effective_priority
        cand_eff = eff(cand, now)
        victims = [i for i, s in enumerate(self._slots)
                   if s is not None and s.request.priority > cand.priority
                   and eff(s.request, now) > cand_eff]
        if not victims:
            return False
        if self._land_all(now, finished):
            return True
        victim = max(victims, key=lambda i: (self._slots[i].request.priority,
                                             self._slots[i].order))
        try:
            self._preempt(victim, now)
        except SwapCapacityError:
            # swap buffer at its max_bytes cap (ISSUE 9 satellite): the
            # preemption is declined BEFORE any engine state mutated
            # (put happens first in _preempt) — the candidate waits for
            # a natural slot release instead of the host growing
            # unboundedly; surfaced via counter + gauge so operators
            # see sustained pressure
            self.swap_capacity_rejections += 1
            if self.telemetry is not None:
                self.telemetry.counter(
                    "serving/swap_capacity_rejections").inc()
            return False
        return True

    def _preempt(self, slot: int, now: float) -> None:
        """Swap slot ``slot``'s KV out to the host buffer and return its
        request to the arrival queue (original position — resubmit is
        arrival-ordered). The preempted interval counts as queue wait;
        the slot state (emitted tokens, chunk progress, drafter
        history) is parked host-side and reattached verbatim on resume,
        so the finished stream is bit-identical to an uninterrupted run
        (pinned by tests)."""
        st = self._slots[slot]
        self._build_swap_programs()
        armed = self.tracer is not None
        rt = self._rtraces.get(st.request.rid) if armed else None
        if armed:
            t_sw0 = self._now(now)
        length = int(jax.device_get(self.cache.lengths[slot]))  # dstpu-lint: fence=preemption swap-out: computed length bounds the parked blocks
        if self.prefix is not None:
            n_used = self.cache.blocks_for(length)
            table = jnp.asarray(self.cache.tables[slot])
            ko, vo = self._swap_out_fn(self.cache.k, self.cache.v, table)
            # park only the blocks the request actually computed into
            # (garbage gathers past n_used are dropped here); quantized
            # pools park payload+scale trees — the exact stored bytes,
            # at half (int8/fp8) the bf16 swap bandwidth
            host_k = _host_blocks(ko, n_used)
            host_v = _host_blocks(vo, n_used)
            self.swap.put(st.request.rid, host_k, host_v)
            # donate fully-computed prompt blocks to the radix index
            # (they are valid cached prefixes — the resume's re-match
            # usually finds them again and skips their upload), free the
            # rest; donate_upto caps at the COMPUTED length so a
            # mid-prefill preemption never donates unwritten tails
            self.prefix.finish(slot, donate_upto=length)
            self.swapped_blocks_out += n_used
        else:
            ko, vo = self._swap_out_fn(self.cache.k, self.cache.v,
                                       np.int32(slot))
            self.swap.put(st.request.rid,
                          np.asarray(jax.device_get(ko)),  # dstpu-lint: fence=preemption swap-out parks KV host-side
                          np.asarray(jax.device_get(vo)))
            n_used = 1
            self.swapped_blocks_out += 1      # the slot page
        self._slots[slot] = None
        self.scheduler.release(slot)
        self.scheduler.resubmit(st.request)
        st.result.preemptions += 1
        since = self._now(now)
        self._preempted[st.request.rid] = _Preempted(st, length, since)
        if rt is not None:
            # the decode segment ends where the swap began; the
            # swapped interval opens at the park instant and closes
            # on resume — preempted time lands in its own phase
            self.tracer.end(rt.decode_span, t=t_sw0,
                            reason="preempted")
            rt.decode_span = None
            self.tracer.record("swap_out", t_sw0, since,
                               trace_id=rt.trace_id,
                               parent_id=rt.root, program="swap_out",
                               blocks=n_used, slot=slot)
            rt.swap_span = self.tracer.begin(
                "swapped", trace_id=rt.trace_id, parent_id=rt.root,
                t=since, blocks=n_used)
        self.preemptions += 1
        if self.tenants is not None:
            self.tenants.note_preemption(st.tenant)
        if self.telemetry is not None:
            reg = self.telemetry
            reg.counter("serving/preemptions").inc()
            reg.counter("serving/swapped_blocks_out").inc(
                n_used if self.prefix is not None else 1)

    def _resume(self, slot: int, req: Request, now: float,
                finished: List[RequestResult]) -> None:
        """Swap a preempted request back into ``slot``: upload its host
        KV, restore its length, and reattach its slot state. Block-paged
        mode first re-matches the prompt against the radix index —
        still-cached full prefix blocks are re-pinned and skipped by the
        upload (and a trie that learned a LONGER prefix while the
        request was parked fast-forwards a mid-prefill resume past it).
        Decode continues exactly where it left off, from the host's token,
        with nothing in flight."""
        self._land_all(now, finished)
        rec = self._preempted.pop(req.rid)
        st = rec.state
        armed = self.tracer is not None
        rt = self._rtraces.get(req.rid) if armed else None
        if armed:
            t_in0 = self._now(now)
        host_k, host_v = self.swap.pop(req.rid)
        length = rec.length
        if self.prefix is not None:
            total = len(req.prompt) + req.max_new_tokens + self._lookahead
            shared = self.prefix.readmit(slot, req.prompt, total)
            # the trie may now hold MORE of the prompt than this request
            # had computed (another tenant donated it meanwhile): skip
            # the prefill ahead over the re-pinned shared prefix
            length = max(length, min(shared * self.cache.block_size,
                                     st.prefill_total))
            st.prefill_pos = max(st.prefill_pos, length) \
                if st.prefilling else st.prefill_pos
            n_used = jax.tree_util.tree_leaves(host_k)[0].shape[1]
            mb = self.cache.max_blocks_per_slot
            dst = np.full((mb,), self.cache.sentinel, np.int32)
            row = self.cache.tables[slot]
            dst[shared:n_used] = row[shared:n_used]
            up_k = _expand_blocks(host_k, mb)
            up_v = _expand_blocks(host_v, mb)
            out = self._swap_in_fn(self.cache.k, self.cache.v,
                                   _to_device(up_k), _to_device(up_v),
                                   jnp.asarray(dst), self.cache.lengths,
                                   np.int32(slot), np.int32(length))
            swapped_in = max(n_used - shared, 0)
        else:
            out = self._swap_in_fn(self.cache.k, self.cache.v,
                                   jnp.asarray(host_k), jnp.asarray(host_v),
                                   self.cache.lengths, np.int32(slot),
                                   np.int32(length))
            swapped_in = 1
        self.cache.update(*out)
        t_res = self._now(now)
        if rt is not None:
            self.tracer.end(rt.swap_span, t=t_in0)
            rt.swap_span = None
            self.tracer.record("swap_in", t_in0, t_res,
                               trace_id=rt.trace_id,
                               parent_id=rt.root, program="swap_in",
                               blocks=swapped_in, slot=slot)
            if st.result.tokens:
                rt.decode_span = self.tracer.begin(
                    "decode_segment", trace_id=rt.trace_id,
                    parent_id=rt.root, t=t_res, slot=slot,
                    resumed=True)
        gap = max(t_res - rec.since, 0.0)
        st.result.preempted_wall += gap
        if st.result.tokens:
            # decode-phase preemption (first token already out): this
            # gap must be discounted from the TPOT span at finish. A
            # mid-prefill park fell before TTFT — discounting it would
            # deflate TPOT toward zero.
            st.result.decode_preempted_wall += gap
        st.order = self._admit_seq
        self._admit_seq += 1
        self._slots[slot] = st
        if self._adaptive is not None:
            self._adaptive.reset_slot(slot)
        self.swapped_blocks_in += swapped_in
        if self.telemetry is not None:
            reg = self.telemetry
            reg.counter("serving/swapped_blocks_in").inc(swapped_in)
            # the preempted interval is queue wait (ISSUE 8 accounting
            # fix): it lands in the same histogram the initial admission
            # wait did
            reg.histogram("serving/queue_wait_ms").observe(gap * 1e3)

    @at_work
    def step(self, now: Optional[float] = None) -> List[RequestResult]:
        """One serving iteration: run the budgeted admit/prefill side
        (chunk continuations, admissions, preemptions — ISSUE 8), then
        decode one step for every DECODE-PHASE slot (slots still
        prefilling their prompt sit the decode out). Returns requests
        finished this iteration.

        Results are fetched one launch behind (ISSUE 36): the iteration
        launches its decode step and then fetches and commits the step the
        iteration before launched, so tokens, ``on_token`` calls and
        finished requests arrive one call later than the step that made
        them, and an iteration with a step in flight and nothing to launch
        still fetches and commits. With ``speculative`` or ``prefix_cache``
        set the step is fetched in the iteration that launched it."""
        if not self._warm:
            self.warmup()
        if now is None:
            now = self._time()
        watch = self._watch
        if watch is not None:
            watch.enter(now)
        self._last_step_now = now
        self._account_kv_occupancy(now)
        if self.slo is not None:
            # SLO judgment rides the serving clock (ISSUE 13): virtual
            # traces replay their alert timelines deterministically.
            # Pure host work — no device interaction, no output change.
            self.slo.maybe_evaluate(now)
        if self._pending_submit_stamps:
            # first step after a context-carrying submit: this instant
            # is where the dispatcher's router_queue span ends, so the
            # engine-side queue_wait begins exactly here (the phases
            # tile; stamping a since-cancelled record is harmless)
            for rt in self._pending_submit_stamps:
                rt.submitted_t = now
            self._pending_submit_stamps.clear()
        finished: List[RequestResult] = []
        armed = self.tracer is not None
        if armed:
            self._iter_span = None     # a step that raised left its own
            t_iter0 = self._now(now)
        with _Phase(self, "dstpu/serving_admit", None, now):
            self._schedule(now, finished)
        if armed and (finished or self._flight is not None
                      or any(s is not None for s in self._slots)):
            # this step admitted, prefilled or will decode: one
            # `iteration` span in the engine-scope trace, tiled by its
            # phases (an idle poll of the queue records nothing)
            self._iter_span = self.tracer.begin(
                "iteration", trace_id=self._iter_trace(), t=t_iter0)
            self._phase_t = t_iter0
            if self._loose_compiles:
                for told in self._loose_compiles:
                    self._record_compile(*told, self._iter_span)
                self._loose_compiles.clear()
            self._phase_end("iter_schedule", now)
        if self._loose_spans:
            for told in self._loose_spans:
                self._record_under(self._iter_span, *told)
            self._loose_spans.clear()
        # a slot whose last token the step in flight is picking sits out:
        # an end by length is known at launch, one by EOS only at commit
        active_slots = [i for i, s in enumerate(self._slots)
                        if s is not None and not s.prefilling
                        and len(s.result.tokens) + s.in_flight
                        < s.request.max_new_tokens]
        if self.telemetry is not None:
            # iteration-level gauges: slot occupancy after admission
            # (prefilling slots included) and the decode batch's fill
            # ratio (decode-phase slots only — they diverge under
            # chunked prefill)
            occupied = sum(s is not None for s in self._slots)
            self.telemetry.gauge("serving/slot_occupancy").set(
                occupied / self.num_slots)
            if active_slots:
                self.telemetry.gauge("serving/batch_fill_ratio").set(
                    len(active_slots) / self.num_slots)
        if not active_slots and self._flight is None:
            # no decode ran: a later gap against _last_decode_t would
            # fold queue-idle time into the TPOT-SLO EMA
            self._last_decode_t = None
            self._land_firsts(now, finished)    # prompts of one token
        elif self.spec is not None:
            self._note_decode_gap()
            self._spec_step(now, active_slots, finished)
        else:
            # an iteration that only fetches is no decode invocation
            t0 = self._note_decode_gap() if active_slots \
                else time.perf_counter()
            self._plain_step(now, active_slots, finished, t0)
        if self._iter_span is not None:
            # ends where its last phase ended
            self.tracer.end(self._iter_span, t=self._phase_t)
            self._iter_span = None
        if watch is not None:
            watch.leave()
        return finished

    def _account_kv_occupancy(self, now: float) -> None:
        """Integrate per-tenant KV occupancy over the interval since
        the last step (ISSUE 13): each occupied slot bills its tenant
        for the pool blocks its table names (block-paged — HOST numpy,
        no device read; shared radix blocks bill every tenant that
        depends on them) or its whole slot row (slot-paged). dt is
        engine-clock time, so virtual traces produce deterministic
        block-second bills."""
        if self.tenants is None:
            return
        last = self._acct_last_t
        self._acct_last_t = now
        if last is None:
            return
        dt = now - last
        if dt <= 0:
            return
        paged = self.prefix is not None
        for i, st in enumerate(self._slots):
            if st is None:
                continue
            if paged:
                blocks = int((self.cache.tables[i]
                              != self.cache.sentinel).sum())
            else:
                blocks = 1
            self.tenants.note_kv_occupancy(st.tenant, blocks, dt,
                                           self._kv_bytes_per_block)

    def _iter_trace(self) -> str:
        """Lazy engine-scope trace for iteration-level spans (the
        iteration and its phases, decode steps, speculative
        draft/verify) — structural context that is not any single
        request's lifecycle."""
        if self._engine_trace is None:
            self._engine_trace = self.tracer.new_trace()
        return self._engine_trace

    def _phase_end(self, name: str, now: float,
                   stamp: Optional[float] = None) -> None:
        """Armed, inside an open iteration: record phase ``name`` from
        where the previous phase ended to a fresh read of the engine
        clock (``stamp``, where the host watch has just made one), and
        start the next phase there."""
        t = self._now(now, stamp)
        self.tracer.record(name, self._phase_t, t,
                           trace_id=self._iter_span.trace_id,
                           parent_id=self._iter_span.span_id)
        self._phase_t = t

    def _note_decode_gap(self) -> float:
        """EMA of wall time between consecutive decode invocations —
        the signal the ``tpot_slo_ms`` admission guard watches. Host
        wall, not the injected clock: the guard protects real decode
        latency from real prefill compute."""
        t = time.perf_counter()
        if self._last_decode_t is not None:
            gap = t - self._last_decode_t
            self._decode_gap_ema = gap if self._decode_gap_ema is None \
                else 0.7 * self._decode_gap_ema + 0.3 * gap
        self._last_decode_t = t
        return t

    def _plain_step(self, now: float, active_slots: List[int],
                    finished: List[RequestResult],
                    t0: float) -> List[RequestResult]:
        """One plain decode iteration: launch one token for every active
        slot, then fetch and commit the step launched an iteration earlier
        (this one's own where the loop does not launch ahead). A slot whose
        last pick is still unfetched (the step in flight covers it, or its
        prefill ran in this iteration) takes its input on the device, from
        ``_previous``; the host uploads the token of the others (a
        resume, a step committed early). Also the speculative path's fallback
        when drafting proposes nothing anywhere (a 1-wide step beats an
        empty k-wide verify). ``t0`` is the host's wall as the step
        begins."""
        landing = self._flight
        armed = self.tracer is not None
        # decode_step opens with the iteration's first decode phase, which
        # starts where the phase before it ended
        t_dec0 = self._phase_t
        if active_slots:
            with _Phase(self, "dstpu/serving_upload", "iter_upload", now):
                toks = np.full((self.num_slots,), self.pad_token_id, np.int32)
                from_host = np.zeros((self.num_slots,), bool)
                states = [(i, self._slots[i]) for i in active_slots]
                for i, st in states:
                    if not st.in_flight:
                        toks[i] = st.last_token
                        from_host[i] = True
                active = np.zeros((self.num_slots,), bool)
                active[active_slots] = True
                args = (self.engine.params, *self.cache.carry(),
                        *self._table_args(),
                        jnp.asarray(toks), jnp.asarray(active),
                        self._temp, self._next_rng(), self._previous,
                        jnp.asarray(from_host))
            with _Phase(self, "dstpu/serving_launch", "iter_launch", now):
                answer = self._adopt(self._decode(*args))
                # no copy_to_host_async() here: the way back runs under the
                # step queued behind in any case (the fetch comes after the
                # next launch), and a copy asked of a result not yet
                # computed brought stalls of 1.6 to 7.1 s in device_get in 6
                # of 16 runs of the hybrid serve cell, none in 10 without
                # (PERF.md, PR 36)
                self._previous = answer[0]
                for _, st in states:
                    st.in_flight += 1
                self._flight = _Flight(
                    answer, states, landing is not None,
                    0.0 if self._watch is None else self._watch.last_t,
                    self._chunk_unfetched)
        # the prompts prefilled in this iteration's schedule phase: their
        # first tokens come back behind the last launch, in launch order
        self._land_firsts(now, finished)
        if landing is None and not self._ahead:
            landing = self._flight
        if landing is not None:
            fetched = self._fetch(landing, now)
            dt = time.perf_counter() - t0
            self.decode_wall += dt
            if armed:
                # upload opened to fetch closed: the fetch IS a fence, and
                # in a run of decode iterations each waits for the step
                # launched one earlier, so the span is what a step costs;
                # iter_fetch closed at a read of the clock after the fence
                self.tracer.record("decode_step", t_dec0, self._phase_t,
                                   trace_id=self._iter_trace(),
                                   program="decode",
                                   n_slots=len(landing.states))
            self._commit(landing, fetched, now, finished)
        return finished

    def _fetch(self, flight: _Flight, now: float) -> list:
        """Wait for a launched decode step's answer on the host."""
        if flight is self._flight:
            self._flight = None
        if flight.behind_chunk:
            self._chunk_unfetched = False     # this fetch waits for it
        with _Phase(self, "dstpu/serving_fetch", "iter_fetch", now) as phase:
            phase.flight = flight
            return jax.device_get(flight.answer)  # dstpu-lint: fence=token emission: decode's picks feed host continuations + streams

    def _commit(self, flight: _Flight, fetched: list, now: float,
                finished: List[RequestResult]) -> None:
        """Commit a fetched decode step: a token goes to a slot only if it
        still holds the state it held at launch. A request that ended at an
        EOS the host had not seen when the next step was launched (or was
        cancelled under it) ran one slot-step for nothing; its token is
        dropped here."""
        # a model's device-side step counters ride behind the tokens
        nxt, *counted = fetched
        nxt = np.asarray(nxt)
        with _Phase(self, "dstpu/serving_commit", "iter_commit", now):
            states = flight.states
            live = [(i, st) for i, st in states if self._slots[i] is st]
            wasted = len(states) - len(live)
            self.decode_steps += 1
            self.decode_steps_overlapped += flight.overlapped
            self.slot_steps_wasted += wasted
            self._active_slot_iterations += len(states)
            if self.telemetry is not None:
                self.telemetry.counter("serving/decode_steps").inc()
                if flight.overlapped:
                    self.telemetry.counter(
                        "serving/decode_steps_overlapped").inc()
                if wasted:
                    self.telemetry.counter("serving/slot_steps_wasted").inc(
                        wasted)
                self.telemetry.counter("serving/slot_iterations_active").inc(
                    len(states))
                if self.prefix is None and self.cache.fused_walk:
                    # what the fused decode step's walk moved in this step,
                    # a layer, by the host's own bookkeeping: a slot's cache
                    # held its prompt and every token but the one fed then
                    lens = [len(st.request.prompt)
                            + len(st.result.tokens) - 1 for _, st in states]
                    self.telemetry.counter("serving/decode_rows_live").inc(
                        sum(lens))
                    self.telemetry.counter("serving/decode_rows_fetched").inc(
                        decode_rows_fetched(lens))
                    if self.cache.fused_window_walk:
                        # the same of a sliding layer's ring: the last
                        # ``window`` positions at most, every sliding layer
                        ring = [min(n, self.cache.window) for n in lens]
                        layers = self.cache.window_layers
                        self.telemetry.counter(
                            "serving/decode_rows_live_window").inc(
                                layers * sum(ring))
                        self.telemetry.counter(
                            "serving/decode_rows_fetched_window").inc(
                                layers * decode_rows_fetched(ring))
                if counted:     # the model names what its step counted
                    self.engine.module.record_step_counters(self.telemetry,
                                                            counted[0])
            t_emit = self._now(now)
            for i, st in states:
                st.in_flight -= 1
            for i, st in live:
                tok = int(nxt[i])
                st.result.tokens.append(tok)
                st.result.token_times.append(t_emit)
                st.result.decode_calls += 1
                st.last_token = tok
                self.tokens_generated += 1
                if self.tenants is not None:
                    self.tenants.note_tokens(st.tenant, 1)
                self._stream(st, [tok])
                done = self._maybe_finish(i, now)
                if done is not None:
                    finished.append(done)

    def _spec_step(self, now: float, active_slots: List[int],
                   finished: List[RequestResult]) -> List[RequestResult]:
        """One speculative decode iteration: draft up to k tokens per
        slot, verify them ALL in one target forward, emit each slot's
        accepted prefix + one bonus/correction token.

        Per-step variable emission: a slot commits between 1 and
        ``draft_len + 1`` tokens per invocation (never 0 — the
        correction token guarantees baseline-speed progress even at zero
        acceptance). The verify width is bucketed over the FIXED
        k_buckets set — the smallest bucket holding the longest draft
        actually PROPOSED this step — so adaptive-k transitions reuse
        compiled programs, and a step where drafting found nothing at
        all falls back to the (also warmed) 1-wide plain decode program
        instead of paying an empty k-wide verify. Per-slot draft length
        is additionally capped at ``remaining_budget - 1``: emission can
        then never overshoot max_new_tokens, so output truncation
        happens only at EOS (where the slot retires and its dead cache
        tail is reclaimed by the next prefill anyway)."""
        spec = self.spec
        nslots = self.num_slots
        want = np.zeros((nslots,), np.int32)
        for i in active_slots:
            st = self._slots[i]
            remaining = st.request.max_new_tokens - len(st.result.tokens)
            k_des = (self._adaptive.desired_k(i)
                     if self._adaptive is not None else spec.k_max)
            want[i] = max(0, min(k_des, remaining - 1))
        kb = pick_k_bucket(max(int(want.max()), 1), spec.k_buckets)
        # drafters read each slot's full token stream (prompt + emitted,
        # derived — result.tokens IS the emitted history; slots still
        # PREFILLING have no stream yet and sit speculation out)
        histories = [list(s.request.prompt) + s.result.tokens
                     if s is not None and not s.prefilling else None
                     for s in self._slots]
        armed = self.tracer is not None
        if armed:
            t_sp0 = self._now(now)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dstpu/serving_draft"):
            drafts, lens = self._drafter.propose(histories, want, kb)
        lens = np.minimum(np.asarray(lens, np.int32), want)
        dt = time.perf_counter() - t0
        self._draft_wall += dt
        self.decode_wall += dt
        if armed:
            # the speculative phases do not tile the iteration (host work
            # between them is in no span); each moves the phase clock to
            # its end, so that the next tiled phase starts there
            self._phase_t = self._now(now)
            self.tracer.record("spec_draft", t_sp0, self._phase_t,
                               trace_id=self._iter_trace(),
                               parent_id=self._iter_span.span_id,
                               k_bucket=kb, n_slots=len(active_slots))
        longest = int(lens.max())
        if longest == 0:
            # nothing proposed anywhere (e.g. prompt-lookup on novel
            # text): the plain decode step emits the identical token at
            # 1-token width
            return self._plain_step(now, active_slots, finished,
                                    time.perf_counter())
        # shrink the verify width to the drafts we actually have (a
        # partial match needs a narrower program than the full want)
        kb = pick_k_bucket(longest, spec.k_buckets)
        tokens = np.full((nslots, kb + 1), self.pad_token_id, np.int32)
        active = np.zeros((nslots,), bool)
        for i in active_slots:
            tokens[i, 0] = self._slots[i].last_token
            n = int(lens[i])
            tokens[i, 1:1 + n] = drafts[i, :n]
            active[i] = True
        if armed:
            t_vf0 = self._now(now)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dstpu/serving_verify"):
            out = self._verify_fn(kb)(
                self.engine.params, *self.cache.carry(),
                *self._table_args(),
                jnp.asarray(tokens), jnp.asarray(lens),
                jnp.asarray(active), self._temp, self._next_rng())
            out = self._adopt(out)
            out_tokens = np.asarray(jax.device_get(out[0]))  # dstpu-lint: fence=token emission: accepted drafts reach host streams
            n_emit = np.asarray(jax.device_get(out[1]))  # dstpu-lint: fence=token emission: accepted drafts reach host streams
        dt = time.perf_counter() - t0
        self._verify_wall += dt
        self.decode_wall += dt
        if armed:
            self._phase_t = self._now(now)
            self.tracer.record("spec_verify", t_vf0, self._phase_t,
                               trace_id=self._iter_trace(),
                               parent_id=self._iter_span.span_id,
                               program=f"verify_{kb}",
                               n_slots=len(active_slots))
        with _Phase(self, "dstpu/serving_commit", "iter_commit", now):
            self.decode_steps += 1
            self._active_slot_iterations += len(active_slots)
            reg = self.telemetry
            if reg is not None:
                reg.counter("serving/decode_steps").inc()
                reg.counter("serving/spec_verify_steps").inc()
                reg.counter("serving/slot_iterations_active").inc(
                    len(active_slots))
            t_emit = self._now(now)
            for i in active_slots:
                st = self._slots[i]
                n = int(n_emit[i])
                emitted = [int(t) for t in out_tokens[i, :n]]
                n_drafted, n_accepted = int(lens[i]), n - 1
                if (self.eos_token_id is not None
                        and self.eos_token_id in emitted):
                    # EOS inside the accepted block: baseline decode stops
                    # at its first EOS, so every token behind it is dropped
                    # (the slot retires; its dead cache tail is overwritten
                    # by the next prefill into the slot)
                    emitted = emitted[:emitted.index(self.eos_token_id) + 1]
                st.result.tokens.extend(emitted)
                st.result.token_times.extend([t_emit] * len(emitted))
                st.result.decode_calls += 1
                st.last_token = emitted[-1]
                self.tokens_generated += len(emitted)
                if self.tenants is not None:
                    self.tenants.note_tokens(st.tenant, len(emitted))
                # stream only the ACCEPTED (post-truncation) block — a
                # rejected draft token is never observable
                self._stream(st, emitted)
                self.spec_drafted_tokens += n_drafted
                self.spec_accepted_tokens += n_accepted
                if self._adaptive is not None:
                    self._adaptive.update(i, n_accepted, n_drafted)
                if reg is not None:
                    reg.counter("serving/spec_drafted_tokens").inc(n_drafted)
                    reg.counter("serving/spec_accepted_tokens").inc(n_accepted)
                    reg.histogram("serving/accepted_tokens_per_step",
                                  buckets=_TOKENS_PER_STEP_BUCKETS).observe(n)
                done = self._maybe_finish(i, now)
                if done is not None:
                    finished.append(done)
        return finished

    # ----------------------------------------------------------------- run
    def run(self, requests: Sequence[Request], *,
            warmup: bool = True) -> List[RequestResult]:
        """Serve a trace to completion. ``arrival_time``s are offsets from
        the moment run() starts; the engine idles (real clock: sleeps)
        until the next arrival when no slot is active."""
        for r in requests:
            self.submit(r)
        if warmup:
            self.warmup()
        t0 = self._time()
        self._run_t0 = t0
        tokens_before = self.tokens_generated
        results: List[RequestResult] = []
        stall = 0
        while self.pending:
            now = self._time() - t0
            if (not any(s is not None for s in self._slots)
                    and self.scheduler.waiting):
                nxt = self.scheduler.next_arrival()
                if nxt is not None and nxt > now:
                    if self._real_clock:
                        time.sleep(min(nxt - now, 0.05))
                    stall += 1
                    if stall > 10_000_000:
                        raise EngineInvariantError(
                            "serving clock is not advancing toward the "
                            "next arrival (non-monotonic time_fn?)")
                    continue
            stall = 0
            results.extend(self.step(now))
        if self.telemetry is not None:
            self._record_run_telemetry(
                len(results), self._time() - t0,
                self.tokens_generated - tokens_before)
        return results

    # ------------------------------------------------------------- telemetry
    def recompile_count(self) -> int:
        """Excess jit-cache entries across the serving programs — any
        value > 0 means some program recompiled after warmup (an
        argument's shape/dtype/sharding varied)."""
        return sum(max(0, v - 1) for v in self.program_cache_sizes().values())

    def _record_run_telemetry(self, n_finished: int, elapsed: float,
                              run_tokens: int) -> None:
        reg = self.telemetry
        reg.gauge("serving/run_elapsed_s").set(elapsed)
        if elapsed > 0:
            reg.gauge("serving/finished_requests_per_sec").set(
                n_finished / elapsed)
            # THIS run's tokens only — self.tokens_generated is cumulative
            # across runs while elapsed resets, so using it would inflate
            # the rate on every run() after the first
            reg.gauge("serving/tokens_per_sec").set(run_tokens / elapsed)
        reg.gauge("serving/peak_queue_depth").set(
            self.scheduler.peak_queue_depth)
        reg.gauge("serving/compiled_programs").set(self.program_count)
        reg.gauge("serving/jit_cache_entries").set(
            sum(self.program_cache_sizes().values()))
        reg.gauge("serving/recompiles").set(self.recompile_count())
        if self.decode_steps:
            reg.gauge("serving/mean_batch_fill_ratio").set(
                self._active_slot_iterations /
                (self.decode_steps * self.num_slots))
        if self.swap is not None:
            reg.gauge("serving/swap_buffer_bytes").set(
                self.swap.bytes_stored)
            reg.gauge("serving/swap_buffer_peak_bytes").set(
                self.swap.peak_bytes)
            if self.swap.max_bytes is not None:
                reg.gauge("serving/swap_buffer_max_bytes").set(
                    self.swap.max_bytes)
        if self.prefix is not None:
            # KV capacity gauges (ISSUE 12): pool bytes incl. quantized
            # scales, and the blocks-per-byte capacity lever kv_dtype
            # buys (int8 ~1.94x bf16, fp8 ~3.88x fp32)
            reg.gauge("serving/kv_pool_bytes").set(self.cache.hbm_bytes())
            reg.gauge("serving/kv_blocks_per_mib").set(
                self.cache.blocks_per_mib())
            # cumulative cache effectiveness (counters already streamed
            # per admit/evict/fork by PrefixCache); occupancy covers
            # running slots' blocks + radix-cached blocks
            reg.gauge("serving/prefix_hit_rate").set(self.prefix.hit_rate())
            reg.gauge("serving/prefix_pool_occupancy").set(
                self.cache.occupancy())
            reg.gauge("serving/prefix_cached_blocks").set(
                self.prefix.cached_blocks())
        if self.spec is not None:
            if self.spec_drafted_tokens:
                reg.gauge("serving/spec_acceptance_rate").set(
                    self.spec_accepted_tokens / self.spec_drafted_tokens)
            if self._active_slot_iterations:
                # decode-phase tokens per slot-step: 1.0 = baseline, the
                # spec speedup headroom is this number (verify cost aside)
                reg.gauge("serving/spec_tokens_per_slot_step").set(
                    (self.tokens_generated - self.prefill_calls)
                    / self._active_slot_iterations)
            wall = self._draft_wall + self._verify_wall
            if wall > 0:
                # drafting's share of the decode hot path (host wall):
                # n-gram drafting should be noise, a draft MODEL should
                # stay well under the verify forward
                reg.gauge("serving/spec_draft_overhead_frac").set(
                    self._draft_wall / wall)
        reg.flush()
