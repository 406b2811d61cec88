"""Results fetched one launch behind (ISSUE 36): ``ServingEngine`` launches
decode step N+1 before it fetches step N, the step's input tokens taken on
the device from the output of the step before.

What is pinned, on the CPU at tiny size, over the three kinds of per-slot
state where the case depends on it (key-value rows; recurrent state written
in place; rings and step counters fetched behind the tokens): streams equal
``generate()``'s and the closed loop's, greedy and sampled; an end at EOS
costs one wasted slot-step and leaves the slot clean for the next request;
``cancel`` under a step in flight; the drain; ``max_new_tokens == 1``; no
look-ahead with ``speculative`` or ``prefix_cache``; the spans still tile an
iteration; callbacks, clocks and chunked prefill behave as before, one launch
later."""

import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                 GraniteHybridModel)
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.telemetry.registry import MetricsRegistry
from deepspeed_tpu.telemetry.spans import SpanTracer
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.serving, pytest.mark.quick]

MODELS = {
    "gpt2": lambda: GPT2Model(GPT2Config.tiny()),
    "granite_hybrid": lambda: GraniteHybridModel(GraniteHybridConfig.tiny()),
    "exaone_moe": lambda: ExaoneMoeModel(ExaoneMoeConfig.tiny()),
}
MAX_LEN = 64


class VirtualClock:
    def __init__(self, dt=0.001):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


_ENGINES = {}


def _engine(family):
    """One ``InferenceEngine`` a family for the module: its compiled
    programs are shared by every ``ServingEngine`` built on it."""
    if family not in _ENGINES:
        groups.reset()
        _ENGINES[family] = deepspeed_tpu.init_inference(
            MODELS[family](), dtype="fp32", max_out_tokens=MAX_LEN)
    return _ENGINES[family]


# module-scoped for the ORDER it gives: pytest runs a family's cases one after
# another, so a stretch of them handed to one xdist worker (`--dist load`
# hands out runs of consecutive tests) builds that family's engine, and not
# all three in every worker (the engine itself is `_ENGINES`' either way)
@pytest.fixture(scope="module", params=list(MODELS))
def engine(request):
    return _engine(request.param)


@pytest.fixture
def gpt2():
    return _engine("gpt2")


def _serving(engine, num_slots=3, ahead=None, **kw):
    kw.setdefault("telemetry", MetricsRegistry())
    kw.setdefault("buckets", (16, 32))
    srv = ServingEngine(engine, num_slots=num_slots, max_len=MAX_LEN,
                        time_fn=VirtualClock(), **kw)
    if ahead is not None:
        # the closed loop of before: every step fetched where it is launched
        srv._ahead = ahead
    return srv


def _requests(engine, lens, new, seed=0, **kw):
    rng = np.random.RandomState(seed)
    vocab = engine.module.config.vocab_size
    return [Request(rid=i, prompt=rng.randint(0, vocab, size=n).tolist(),
                    max_new_tokens=m, **kw)
            for i, (n, m) in enumerate(zip(lens, new))]


GEN_NEW = 16        # the longest stream a case of this file asks for


def _generate(engine, req):
    """What ``generate()`` gives the request alone. A greedy stream is the
    head of every longer one of its prompt, so ``generate()`` compiles one
    program a prompt length (its key is the length and ``max_new_tokens``)
    and not one a request; the cases draw their prompts from few lengths."""
    assert req.max_new_tokens <= GEN_NEW
    out = engine.generate(np.asarray(req.prompt, np.int32)[None],
                          max_new_tokens=GEN_NEW)
    at = len(req.prompt)
    return np.asarray(out)[0, at:at + req.max_new_tokens].tolist()


def _counters(srv):
    return srv.telemetry.snapshot()["counters"]


LENS, NEW = [27, 3, 11, 8, 16, 21], [12, 3, 7, 9, 2, 6]


def test_greedy_streams_are_generates(engine):
    """Six requests over three slots, so slots are reused under steps in
    flight: token for token what ``generate()`` gives each alone."""
    reqs = _requests(engine, LENS, NEW)
    srv = _serving(engine)
    served = {r.rid: r.tokens for r in srv.run(reqs)}
    for req in reqs:
        assert served[req.rid] == _generate(engine, req), req.rid
    c = _counters(srv)
    assert 0 < c["serving/decode_steps_overlapped"] \
        <= c["serving/decode_steps"] - 1
    assert c["serving/slot_steps_wasted"] == 0
    assert srv.decode_steps_overlapped == c["serving/decode_steps_overlapped"]
    # still one compiled decode program, one entry: both signatures of the
    # previous step's tokens were warmed
    assert set(srv.program_cache_sizes().values()) == {1}
    assert srv.program_count == 3 and srv.pending == 0


def test_sampled_streams_are_the_closed_loops_for_one_seed(engine):
    """The step's key is split on the host per launch, in the order the
    closed loop launched: with slots to spare, equal seeds give equal
    streams, fetched behind or not."""
    kw = dict(do_sample=True, temperature=0.8, top_k=20, num_slots=6)
    reqs = _requests(engine, LENS, NEW, seed=1)
    behind = {r.rid: r.tokens for r in _serving(engine, **kw).run(reqs)}
    closed = {r.rid: r.tokens
              for r in _serving(engine, ahead=False, **kw).run(reqs)}
    assert behind == closed
    greedy = {r.rid: r.tokens
              for r in _serving(engine, num_slots=6).run(reqs)}
    assert behind != greedy            # it did sample


def _eos_case(engine):
    """Two requests and an EOS id from the model's own greedy streams: the
    first emits it mid-stream (not as its first token, not as its last), the
    second never does."""
    reqs = _requests(engine, [8, 16, 3, 11, 21, 27], [14] * 6, seed=2)
    streams = [_generate(engine, r) for r in reqs]
    for a, sa in enumerate(streams):
        for b, sb in enumerate(streams):
            for at in range(1, len(sa) - 2):
                eos = sa[at]
                if a != b and eos not in sa[:at] and eos not in sb:
                    return reqs[a], reqs[b], eos, sa[:at + 1], sb
    raise AssertionError("no greedy stream of this model fits the case")


def test_an_end_at_eos_costs_one_slot_step_and_leaves_the_slot_clean(engine):
    """One slot. The first request ends at an EOS the host sees one step
    late: nothing is emitted after it, one slot-step is wasted, and the
    request admitted into the freed slot next, behind that step's in-place
    writes to recurrent state and rings, serves the stream it serves alone."""
    first, second, eos, want_first, want_second = _eos_case(engine)
    srv = _serving(engine, num_slots=1, eos_token_id=eos)
    out = {r.rid: r for r in srv.run([first, second])}
    assert out[first.rid].tokens == want_first
    assert out[first.rid].finish_reason == "eos"
    assert out[second.rid].tokens == want_second
    assert out[second.rid].finish_reason == "length"
    c = _counters(srv)
    assert c["serving/slot_steps_wasted"] == 1 == srv.slot_steps_wasted
    # the wasted step is a step: counted, and its slot counted active
    assert c["serving/slot_iterations_active"] == \
        len(want_first) - 1 + len(want_second) - 1 + 1
    [alone] = _serving(engine, num_slots=1, eos_token_id=eos).run([second])
    assert alone.tokens == want_second


def test_cancel_under_a_step_in_flight(engine):
    """A running request is cancelled while a step that decodes it is in
    flight: no token of that step reaches it, no result is ever returned for
    it, the other slots' streams are whole, and the freed slot serves the
    next request as a fresh engine would."""
    reqs = _requests(engine, [11, 3, 16, 8], [12, 12, 12, 10], seed=3)
    seen = []
    reqs[1].on_token = seen.append
    srv = _serving(engine, num_slots=3)
    for r in reqs[:3]:
        srv.submit(r)
    srv.warmup()
    results, t0 = [], srv._time()
    for _ in range(4):
        results += srv.step(srv._time() - t0)
    assert srv._flight is not None and not results
    assert 1 in [st.request.rid for _, st in srv._flight.states]
    before = list(seen)
    assert srv.cancel(1) and not srv.cancel(1)
    srv.submit(reqs[3])                     # into the slot request 1 left
    while srv.pending:
        results += srv.step(srv._time() - t0)
    assert seen == before
    served = {r.rid: r.tokens for r in results}
    assert sorted(served) == [0, 2, 3]
    for rid in served:
        assert served[rid] == _generate(engine, reqs[rid]), rid
    assert _counters(srv)["serving/slot_steps_wasted"] == 1


def test_the_drain_commits_the_step_in_flight(gpt2):
    """Every remaining slot ends in the step in flight: the next iteration
    has nothing to launch, still fetches and commits, and ``pending`` stays
    above 0 until it has."""
    [req] = _requests(gpt2, [7], [3])
    srv = _serving(gpt2, tracer=SpanTracer())
    srv.submit(req)
    srv.warmup()
    t0 = srv._time()
    assert srv.step(srv._time() - t0) == []         # prefill, launch step 1
    assert srv._flight is not None and srv.pending == 1
    assert len(srv._slots[0].result.tokens) == 1
    assert srv.step(srv._time() - t0) == []         # launch 2, commit 1
    assert len(srv._slots[0].result.tokens) == 2 and srv.pending == 1
    [res] = srv.step(srv._time() - t0)              # nothing to launch
    assert res.tokens == _generate(gpt2, req) and res.finish_reason == "length"
    assert srv._flight is None and srv.pending == 0
    assert srv.step(srv._time() - t0) == []
    assert srv.decode_steps == 2 and srv.decode_steps_overlapped == 1
    # the last iteration's phases: no upload, no launch
    last = max((s for s in srv.tracer.spans if s.name == "iteration"),
               key=lambda s: s.start)
    names = [s.name for s in srv.tracer.spans if s.parent_id == last.span_id]
    assert names == ["iter_schedule", "iter_fetch", "iter_commit"]


def test_a_request_of_one_token_needs_no_decode_step(engine):
    reqs = _requests(engine, [11, 3], [1, 1], seed=4)
    srv = _serving(engine, num_slots=1)
    out = {r.rid: r for r in srv.run(reqs)}
    for req in reqs:
        assert out[req.rid].tokens == _generate(engine, req)
        assert out[req.rid].finish_reason == "length"
    assert srv.decode_steps == 0 and srv._flight is None
    # beside a longer request: it ends at its prefill, the other decodes on
    mixed = _requests(engine, [11, 8], [1, 6], seed=4)
    srv = _serving(engine, num_slots=2)
    out = {r.rid: r.tokens for r in srv.run(mixed)}
    for req in mixed:
        assert out[req.rid] == _generate(engine, req)
    assert srv.decode_steps == 5


@pytest.mark.parametrize("option", [
    dict(speculative={"mode": "ngram", "k_buckets": (2,)}),
    dict(prefix_cache=True, block_size=16),
    dict(prefix_cache=True, block_size=16,
         speculative={"mode": "ngram", "k_buckets": (2,)})],
    ids=["speculative", "prefix_cache", "both"])
def test_no_step_is_launched_ahead_where_the_host_owns_the_next_input(
        gpt2, option):
    reqs = _requests(gpt2, LENS, NEW)
    srv = _serving(gpt2, **option)
    calls = []
    fetch = srv._fetch
    srv._fetch = lambda flight, now: calls.append(
        srv._flight is flight) or fetch(flight, now)
    served = {r.rid: r.tokens for r in srv.run(reqs)}
    for req in reqs:
        assert served[req.rid] == _generate(gpt2, req), req.rid
    c = _counters(srv)
    assert c["serving/decode_steps"] > 0
    assert c["serving/decode_steps_overlapped"] == 0
    assert c["serving/slot_steps_wasted"] == 0
    # every plain step was fetched in the iteration that launched it
    assert all(calls) and srv._flight is None
    assert set(srv.program_cache_sizes().values()) == {1}


def test_the_phases_still_tile_an_iteration(engine):
    """Armed: every ``iteration`` span is covered by its phase spans end to
    end, in the new order, and ``decode_step`` runs from the iteration's
    upload to its fetch."""
    srv = _serving(engine, tracer=SpanTracer())
    srv.run(_requests(engine, LENS, NEW))
    spans = srv.tracer.spans
    iterations = [s for s in spans if s.name == "iteration"]
    assert len(iterations) > 5
    order = ["iter_schedule", "iter_upload", "iter_launch", "iter_fetch",
             "iter_commit"]
    for it in iterations:
        kids = [s for s in spans if s.parent_id == it.span_id]
        assert kids[0].start == it.start and kids[-1].end == it.end
        for a, b in zip(kids, kids[1:]):
            assert a.end == b.start
        names = [k.name for k in kids]
        assert names == [n for n in order if n in names]
        assert names[0] == "iter_schedule"
    steps = [s for s in spans if s.name == "decode_step"]
    fetches = [s for s in spans if s.name == "iter_fetch"]
    assert len(steps) == len(fetches) == srv.decode_steps
    for step, fetch in zip(steps, fetches):
        assert step.end == fetch.end and step.start <= fetch.start
    c = _counters(srv)
    assert c["serving/decode_steps_overlapped"] <= c["serving/decode_steps"]
    chunks = [s for s in spans if s.name == "prefill_chunk"]
    assert len(chunks) == len(LENS) and all(s.attrs["fenced"] for s in chunks)


def test_callbacks_come_in_the_closed_loops_order_one_launch_later(gpt2):
    def run(ahead):
        calls, reqs = [], _requests(gpt2, LENS, NEW, seed=5)
        for r in reqs:
            r.on_token = lambda tok, rid=r.rid: calls.append((rid, tok))
        srv = _serving(gpt2, num_slots=6, ahead=ahead)
        for r in reqs:
            srv.submit(r)
        srv.warmup()
        t0, per_step = srv._time(), []
        while srv.pending:
            n = len(calls)
            srv.step(srv._time() - t0)
            per_step.append(len(calls) - n)
        return calls, per_step

    behind, steps_behind = run(None)
    closed, steps_closed = run(False)
    assert behind == closed
    # the first call prefills and launches; its step's tokens come a call later
    assert steps_behind[0] == len(LENS) and steps_closed[0] == 2 * len(LENS)
    assert len(steps_behind) == len(steps_closed) + 1


def test_a_replay_on_a_virtual_clock_is_deterministic(engine):
    def run():
        srv = _serving(engine)
        return [(r.rid, r.tokens, r.token_times, r.finish_time)
                for r in srv.run(_requests(engine, LENS, NEW, seed=6))]

    first = run()
    assert first == run()
    for _, tokens, times, finish in first:
        assert len(times) == len(tokens) and times == sorted(times)
        assert finish >= times[-1]


def test_chunked_prefill_under_a_budget_needs_nothing_new(engine):
    """A slot mid-way through its chunks is inactive in the steps launched
    around them (what an intermediate chunk writes into the previous tokens
    at its slot is never read); the step behind its last chunk takes the
    first token on the device."""
    reqs = _requests(engine, [3, 45, 8, 40], [14, 5, 12, 6], seed=7)
    srv = _serving(engine, num_slots=4, buckets=(16, 64),
                   prefill_token_budget=16)
    out = {r.rid: r for r in srv.run(reqs)}
    assert out[1].prefill_chunks == 3 and out[3].prefill_chunks == 3
    for req in reqs:
        assert out[req.rid].tokens == _generate(engine, req), req.rid
    assert srv.decode_steps_overlapped > 0
    assert set(srv.program_cache_sizes().values()) == {1}


def _host_masks(srv):
    """Record the ``from_host`` operand of every decode launch."""
    srv.warmup()
    masks, decode = [], srv._decode

    def recording(*args):
        masks.append(np.asarray(args[-1]).copy())
        return decode(*args)

    recording._cache_size = decode._cache_size
    srv._decode = recording
    return masks


def test_a_prefilled_slots_first_token_stays_on_the_device(engine):
    """The step launched behind a prefill is launched before that prompt's
    first token is fetched: no decode step of a run without resumes takes
    a token from the host, and the closed loop takes every one from it."""
    reqs = _requests(engine, LENS, NEW)
    srv = _serving(engine)
    masks = _host_masks(srv)
    served = {r.rid: r.tokens for r in srv.run(reqs)}
    assert len(masks) == srv.decode_steps and not np.any(masks)
    closed = _serving(engine, ahead=False)
    masks = _host_masks(closed)
    assert {r.rid: r.tokens for r in closed.run(reqs)} == served
    assert all(m.any() for m in masks)


def test_a_swap_out_first_commits_what_is_in_flight(gpt2):
    """Slot-paged preemption keeps the look-ahead: the victim's
    ``last_token`` is parked whole because the step in flight is committed
    before the swap-out, and the resumed stream is the uninterrupted one."""
    reqs = _requests(gpt2, [10, 12], [16, 16], seed=8, priority=2)
    high = _requests(gpt2, [8], [6], seed=9, priority=0)[0]
    high.rid, high.arrival_time = 7, 0.012
    srv = _serving(gpt2, num_slots=2, preemption="swap")
    masks = _host_masks(srv)
    out = {r.rid: r for r in srv.run(reqs + [high])}
    assert srv.preemptions == 1 and srv.decode_steps_overlapped > 0
    # the host's tokens are uploaded where it alone has them: the step
    # behind the early commit, and the resumed slot's first step
    assert 0 < sum(m.any() for m in masks) <= 3
    assert sum(r.preemptions for r in out.values()) == 1
    for req in reqs + [high]:
        assert out[req.rid].tokens == _generate(gpt2, req), req.rid


def test_the_decode_program_takes_the_previous_steps_tokens(gpt2):
    """At the program: a slot's input is the host's token where the mask
    says so and the previous step's output elsewhere, and without the two
    trailing operands the program is the one it was."""
    import jax

    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    program = gpt2.slot_decode_program(2, MAX_LEN)
    key = jax.random.PRNGKey(0)

    def step(tokens, *behind):
        cache = SlotKVCache(gpt2.module, 2, MAX_LEN, dtype=jnp.float32)
        out = program(gpt2.params, *cache.carry(), jnp.asarray(tokens),
                      jnp.asarray([True, True]), 1.0, key, *behind)
        return np.asarray(out[-1]).tolist()

    plain = step([5, 9])
    assert step([5, 0], jnp.asarray([0, 9]),
                jnp.asarray([True, False])) == plain
    assert step([0, 0], jnp.asarray([5, 9]),
                jnp.asarray([False, False])) == plain
    assert step([5, 9], jnp.asarray([1, 1]),
                jnp.asarray([True, True])) == plain
    assert step([0, 0], jnp.asarray([5, 9]),
                jnp.asarray([True, True])) != plain
