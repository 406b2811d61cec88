"""What the fused decode step's per-slot walk fetches (ISSUE 33): the host's
count of it against the walk itself, the two counters over a serving run, and
a slot freed by a longer request served again. All on the CPU, tiny models;
the last case runs the Pallas kernel itself, interpreted, inside the engine's
decode program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops import attention, decode_step
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.serving, pytest.mark.quick]


def _walk_rows(lengths, active, bg, cs, dma=None):
    """Rows the kernel's chunk DMAs move, by its own control flow: groups of
    ``bg`` sorted positions, each walked to its first row's chunk count, a
    row's DMA started while ``c < ceil(len / cs)``; where a chunk is fetched
    in parts of ``dma`` rows, part ``u`` while ``c * parts + u < ceil(len /
    dma)``."""
    walk = decode_step.slot_walk(jnp.asarray(lengths), jnp.asarray(active))
    order, n = np.asarray(walk.order), int(walk.n_active[0])
    b = len(lengths)
    dma = cs if dma is None else dma
    parts = cs // dma

    def nch(p, rows=cs):
        return -(-int(lengths[order[min(p, b - 1)]]) // rows) if p < n else 0

    rows = 0
    for g in range(-(-n // bg)):
        for c in range(nch(g * bg)):
            rows += dma * sum(c * parts + u < nch(g * bg + j, dma)
                              for j in range(bg) for u in range(parts))
    return rows


@pytest.mark.parametrize("seed,b,bg,cs", [(0, 32, 4, 128), (1, 64, 4, 128),
                                          (2, 8, 2, 256), (3, 16, 16, 128)])
def test_rows_fetched_is_the_walks_count(seed, b, bg, cs):
    rng = np.random.RandomState(seed)
    for fill in (0.0, 0.1, 0.5, 1.0):
        lengths = rng.randint(0, 1024, size=b)
        active = rng.rand(b) < fill
        assert decode_step.decode_rows_fetched(lengths[active], cs) \
            == _walk_rows(lengths, active, bg, cs)


# the plan of every cell that decodes through the per-slot walk, by its
# global layers' geometry (b, hkv, s_max, dh, itemsize, dv, hq): a chunk of
# 128 where a slot holds 1,024 or 2,048 rows, the long step where it holds
# thousands. The rings (``s_max`` the window of 128) are one chunk whatever
SLOT_PLANS = {
    "gpt2-large.serve-chat": ((32, 20, 1024, 64, 2, 64, 20), (4, 128)),
    "granite-4.0-h-micro.serve-chat-bursty":
        ((64, 8, 2048, 64, 2, 64, 32), (4, 128)),
    "k-exaone-236b-a23b.serve-mixed-lengths":
        ((32, 8, 4096, 128, 2, 128, 64), (1, 512)),
    "solar-open2-250b.serve-agent-contexts":
        ((16, 8, 16384, 128, 2, 128, 64), (1, 512)),
    "mimo-v2.5.serve-long-context-decode":
        ((16, 4, 16384, 256, 2, 128, 64), (1, 512)),
    "k-exaone-236b-a23b.rings": ((32, 8, 128, 128, 2, 128, 64), (4, 128)),
    "mimo-v2.5.rings": ((16, 8, 128, 256, 2, 128, 64), (4, 128)),
}


@pytest.mark.parametrize("cell", list(SLOT_PLANS))
def test_rows_fetched_counts_in_the_plans_chunk(cell):
    """The plan's table, and the host's count against the walk's own under
    the plan each cache gets: a row's tail rounds up to the DMA's 128 rows
    whatever the loop step covers."""
    (b, hkv, s_max, dh, itemsize, dv, hq), want = SLOT_PLANS[cell]
    bg, cs = decode_step._slot_plan(b, hkv, s_max, dh, itemsize, dv=dv,
                                    hq=hq)
    assert (bg, cs) == want
    assert s_max % cs == 0 and cs % decode_step._SLOT_CHUNK == 0
    dma = decode_step._SLOT_CHUNK
    assert decode_step.decode_rows_fetched([0, 1, dma, dma + 1]) == 4 * dma
    rng = np.random.RandomState(len(cell))
    for fill in (0.0, 0.1, 0.4, 1.0):
        lengths = rng.randint(0, s_max, size=b)
        active = rng.rand(b) < fill
        assert decode_step.decode_rows_fetched(lengths[active]) \
            == _walk_rows(lengths, active, bg, cs, dma)


class VirtualClock:
    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _serving(num_slots, reg=None, max_len=128, buckets=(16, 64)):
    groups.reset()
    cfg = GPT2Config.tiny()
    eng = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="fp32",
                                       max_out_tokens=max_len)
    return cfg, ServingEngine(eng, num_slots=num_slots, max_len=max_len,
                              buckets=buckets, time_fn=VirtualClock(),
                              telemetry=reg if reg is not None else False)


def _requests(cfg, lens, new, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i, prompt=rng.randint(0, cfg.vocab_size, size=n)
                    .tolist(), max_new_tokens=m)
            for i, (n, m) in enumerate(zip(lens, new))]


def test_decode_row_counters_over_a_serving_run():
    reg = MetricsRegistry()
    cfg, srv = _serving(4, reg)
    for r in _requests(cfg, [40, 3, 17, 60, 9, 25], [9, 14, 5, 7, 11, 6]):
        srv.submit(r)
    srv.warmup()
    t0, seen = srv._time(), 0
    while srv.pending:
        srv.step(srv._time() - t0)
        # the host's arithmetic is the device's: a slot's cache holds its
        # prompt and every emitted token but the last, which the next step
        # feeds; that is the length the counters take before a step. The
        # device runs one launched step ahead of the committed tokens
        dev = np.asarray(srv.cache.lengths)
        for i, st in enumerate(srv._slots):
            if st is not None and not st.prefilling:
                assert dev[i] == len(st.request.prompt) \
                    + len(st.result.tokens) + st.in_flight - 1
                seen += 1
    assert seen > 3
    live = reg.counter("serving/decode_rows_live").value
    fetched = reg.counter("serving/decode_rows_fetched").value
    slot_steps = reg.counter("serving/slot_iterations_active").value
    assert 0 < live <= fetched <= live + slot_steps * decode_step._SLOT_CHUNK


def test_block_paged_engine_keeps_no_decode_row_counters():
    reg = MetricsRegistry()
    groups.reset()
    cfg = GPT2Config.tiny()
    eng = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="fp32",
                                       max_out_tokens=128)
    srv = ServingEngine(eng, num_slots=2, max_len=128, buckets=(16, 64),
                        time_fn=VirtualClock(), telemetry=reg,
                        prefix_cache=True, block_size=16)
    srv.run(_requests(cfg, [20, 9], [4, 4]))
    assert reg.counter("serving/decode_steps").value > 0
    assert reg.counter("serving/decode_rows_fetched").value == 0


class _AsTpu:
    """``jax`` as ops/attention.py sees it, with the backend named "tpu": the
    route to the fused step, taken here on the CPU."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend():
        return "tpu"


@pytest.fixture
def fused_on_cpu(monkeypatch):
    calls = []
    real = decode_step.fused_decode_step

    def interpreted(*args, **kw):
        calls.append(kw.get("active"))
        return real(*args, **dict(kw, interpret=True))

    monkeypatch.setattr(attention, "jax", _AsTpu())
    monkeypatch.setattr(decode_step, "fused_decode_step", interpreted)
    return calls


def test_slot_freed_by_a_longer_request_serves_the_same_tokens(fused_on_cpu):
    """Two slots, five requests: a long request leaves its rows and its
    length behind, a short one is served into its slot, then one as long
    again, while the neighbour decodes or sits freed. Each emits
    what it emits in a fresh engine on the einsum path, so neither the
    stale rows past the new length nor the skipped inactive write show."""
    cfg, srv = _serving(2)
    reqs = _requests(cfg, [60, 5, 33, 7, 58], [12, 9, 4, 10, 6], seed=5)
    served = {r.rid: r.tokens for r in srv.run(reqs)}
    # the kernel ran, walking by the order the decode program made once
    assert fused_on_cpu and all(
        isinstance(a, decode_step.SlotWalk) for a in fused_on_cpu)
    assert sum(srv.scheduler.admissions_per_slot) == 5
    fused_on_cpu.clear()
    for req in reqs:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(attention, "jax", jax)        # the einsum path
            _, fresh = _serving(2)
            [res] = fresh.run([Request(rid=req.rid, prompt=req.prompt,
                                       max_new_tokens=req.max_new_tokens)])
        assert res.tokens == served[req.rid], f"rid {req.rid}"
    assert not fused_on_cpu
