"""Serving-engine telemetry (ISSUE 3): queue-wait/TTFT/TPOT histograms,
slot-occupancy gauges, recompile accounting, finished-request counters —
and the acceptance property that histogram percentiles agree with direct
measurement of the same trace. Virtual clock => deterministic replay."""

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu import telemetry
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.serving import Request, ServingEngine
from deepspeed_tpu.telemetry import MetricsRegistry
from deepspeed_tpu.utils import groups

pytestmark = [pytest.mark.observability, pytest.mark.serving,
              pytest.mark.quick]


class VirtualClock:
    def __init__(self, dt=0.001):
        self.t = 0.0
        self.dt = dt

    def __call__(self):
        self.t += self.dt
        return self.t


def _serving(telemetry_arg, num_slots=3, max_len=128, buckets=(16,)):
    groups.reset()
    cfg = GPT2Config.tiny()
    eng = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="fp32",
                                       max_out_tokens=max_len)
    srv = ServingEngine(eng, num_slots=num_slots, max_len=max_len,
                        buckets=buckets, time_fn=VirtualClock(),
                        telemetry=telemetry_arg)
    return cfg, srv


def _reqs(cfg, lens, news, seed=0):
    rng = np.random.RandomState(seed)
    return [Request(rid=i,
                    prompt=rng.randint(0, cfg.vocab_size, size=l).tolist(),
                    max_new_tokens=n)
            for i, (l, n) in enumerate(zip(lens, news))]


def test_request_lifecycle_metrics():
    reg = MetricsRegistry()
    cfg, srv = _serving(reg)
    reqs = _reqs(cfg, [9, 3, 12, 6, 14], [4, 1, 6, 3, 2])
    results = srv.run(reqs)
    assert len(results) == 5
    snap = reg.snapshot()
    assert snap["counters"]["serving/finished_requests"] == 5
    assert snap["counters"]["serving/prefills"] == 5
    assert snap["histograms"]["serving/queue_wait_ms"]["count"] == 5
    assert snap["histograms"]["serving/ttft_ms"]["count"] == 5
    assert snap["histograms"]["serving/latency_ms"]["count"] == 5
    # TPOT only defined for requests that decoded past the prefill token
    n_multi = sum(1 for r in reqs if r.max_new_tokens > 1)
    assert snap["histograms"]["serving/tpot_ms"]["count"] == n_multi
    # iteration gauges live in (0, 1]
    occ = snap["gauges"]["serving/slot_occupancy"]
    assert 0.0 <= occ <= 1.0
    assert 0.0 < snap["gauges"]["serving/mean_batch_fill_ratio"] <= 1.0
    assert snap["counters"]["serving/decode_steps"] == srv.decode_steps
    assert snap["counters"]["serving/slot_iterations_active"] == \
        srv._active_slot_iterations
    assert snap["gauges"]["serving/finished_requests_per_sec"] > 0
    # TTFT >= queue wait for every request => same ordering of means
    assert snap["histograms"]["serving/ttft_ms"]["mean"] >= \
        snap["histograms"]["serving/queue_wait_ms"]["mean"]


def test_recompile_accounting_zero_after_warmup():
    reg = MetricsRegistry()
    cfg, srv = _serving(reg)
    srv.run(_reqs(cfg, [9, 3, 12, 6], [3, 2, 4, 1]))
    assert srv.recompile_count() == 0
    snap = reg.snapshot()
    assert snap["gauges"]["serving/recompiles"] == 0
    assert snap["gauges"]["serving/compiled_programs"] == \
        len(srv.buckets) + 1
    assert snap["gauges"]["serving/jit_cache_entries"] == \
        len(srv.buckets) + 1


def test_histogram_percentiles_agree_with_direct(capsys):
    """The acceptance property bench.py re-measures on real latencies:
    telemetry-histogram p50/p95 vs a direct sort of the same requests'
    latencies, equal up to fixed-bucket quantization (1.25x ratio)."""
    reg = MetricsRegistry()
    cfg, srv = _serving(reg, num_slots=4)
    lens = [9, 3, 12, 6, 14, 5, 8, 11]
    news = [4, 2, 6, 3, 2, 5, 1, 4]
    results = srv.run(_reqs(cfg, lens, news))
    direct = sorted(r.latency * 1e3 for r in results)
    lat_h = reg.histogram("serving/latency_ms")
    assert lat_h.count == len(results)
    for p in (0.50, 0.95):
        d = direct[min(int(len(direct) * p), len(direct) - 1)]
        est = lat_h.percentile(p)
        assert est == pytest.approx(d, rel=0.25), f"p{int(p * 100)}"
    # exact stats are exact
    assert lat_h.max == pytest.approx(max(direct))
    assert lat_h.min == pytest.approx(min(direct))


def test_bare_mode_writes_nothing():
    # an engine on the global registry that nobody closed: it follows the
    # compile log for its own work alone (ISSUE 42), so the bare engine's
    # programs, compiled beside it, leave nothing in that registry
    _, forgotten = _serving(True, num_slots=3)
    assert forgotten._compile_sub in telemetry.compile_log()._subs
    telemetry.reset_registry()
    traced = telemetry.compile_log().totals["entry/traces"]
    cfg, srv = _serving(False, buckets=(24,))
    assert srv.telemetry is None and srv._compile_sub is None
    srv.run(_reqs(cfg, [5, 7], [2, 2]))
    assert telemetry.compile_log().totals["entry/traces"] > traced
    snap = telemetry.get_registry().snapshot()
    assert snap["counters"] == {} and snap["gauges"] == {}
    forgotten.close()


def test_default_telemetry_uses_global_registry():
    telemetry.reset_registry()
    cfg, srv = _serving(True)
    assert srv.telemetry is telemetry.get_registry()
    srv.run(_reqs(cfg, [5], [2]))
    assert telemetry.get_registry().counter(
        "serving/finished_requests").value == 1
