"""The benchmark's own tests: arithmetic, traffic, the trace reducer, the
by-name resolution of every cell, the plain references against the program,
and tiny in-process rehearsals of both kinds for both families.

What is read of ``BENCHMARK.json`` is read twice: as accepted, and with one
cell appended as the next PR that brings a configuration will leave it
(appended.py; the ``bench`` fixture and ``CELL_CASES``). No test here may pin
the end of a list, a list's length or the absence of a neighbour.

One module on purpose (tests/conftest.py runs every module in a child
process). It starts no subprocess, describes no TPU topology
(tests/unit/ops/test_tpu_compile.py stays the only file that does) and
imports nothing at module level that loads libtpu.
"""
import importlib
import json
import math
import os
import re
import time

import numpy as np
import pytest

from benchmarks import flops, harness, stats, trace_reduce, traffic_gen
from tests.unit.benchmarks import appended

BENCH = appended.ACCEPTED
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
LARGE = {"layers": 36, "hidden": 1280, "heads": 20, "kv_heads": 20,
         "head_dim": 64, "mlp": 5120, "vocab": 50257, "positions": 1024,
         "params": 774_030_080, "active_params": 774_030_080}
XL = {"layers": 48, "hidden": 1600, "heads": 25, "kv_heads": 25,
      "head_dim": 64, "mlp": 6400, "vocab": 50257, "positions": 1024,
      "params": 1_557_611_200, "active_params": 1_557_611_200}
# A configuration of the second family under that kind of config.json's
# published key names (these sizes are Mistral-7B-v0.1's); it holds no GPT-2
# key. It is no cell's: the rehearsals run it at the family's tiny sizes.
LLAMA_CFG = {"family": "llama", "hidden_size": 4096, "num_hidden_layers": 32,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "intermediate_size": 14336, "rms_norm_eps": 1e-5,
             "rope_theta": 10000.0, "max_position_embeddings": 32768,
             "vocab_size": 32000, "tie_word_embeddings": False}
GPT2_KEYS = ("n_head", "n_embd", "n_layer", "n_positions", "n_inner",
             "layer_norm_epsilon")


# ------------------------------------------------------------- arithmetic
@pytest.mark.parametrize("values,q,want", [
    ([1.0, 2.0, 3.0, 4.0, 5.0], 50, 3.0),
    ([1.0, 2.0, 3.0, 4.0], 50, 2.5),
    ([10.0, 20.0], 95, 19.5),
    ([7.0], 95, 7.0),
    (list(range(101)), 95, 95.0),
    ([5.0, 1.0, 3.0], 0, 1.0),
    ([5.0, 1.0, 3.0], 100, 5.0),
])
def test_percentile_by_hand(values, q, want):
    assert stats.percentile(values, q) == pytest.approx(want)
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)))


@pytest.mark.parametrize("bad", [([], 50), ([1.0], 101), ([1.0], -1)])
def test_percentile_refuses(bad):
    with pytest.raises(ValueError):
        stats.percentile(*bad)


def test_window_arithmetic_on_hand_made_timestamps():
    # three requests: token times in seconds on the engine's clock
    times = [[0.10, 0.20, 0.35], [0.90, 1.00, 1.05, 1.30], [0.50]]
    assert stats.inter_token_gaps(times) == pytest.approx(
        [0.10, 0.15, 0.10, 0.05, 0.25])
    # tokens committed inside [0, 1.0): 3 + 1 + 1; the stamp at 1.00 is out
    assert stats.count_in_window(times, 0.0, 1.0) == 5
    assert stats.count_in_window(times, 0.0, 2.0) == 8


def test_an_unfinished_request_stays_in_the_tail():
    # 20 requests due a second apart; the system returned a first token 0.1 s
    # after arrival for all but the last, and the loop gave up at 30 s
    due = {rid: float(rid) for rid in range(20)}
    first = {rid: rid + 0.1 for rid in range(19)}
    ttft = stats.times_to_first_token(due, first, gave_up=30.0)
    assert len(ttft) == 20 and max(ttft) == pytest.approx(11.0)
    assert stats.percentile(ttft, 95.0) > 0.1 + 0.5  # the tail sees it
    assert stats.times_to_first_token(due, {**first, 19: 19.1},
                                      gave_up=30.0) == pytest.approx([0.1] * 20)


# ---------------------------------------------------------------- traffic
CHAT = {"rate": 20.0, "shape_seed": 7, "max_total": 1024,
        "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.7,
                   "min": 16, "max": 768},
        "output": {"dist": "lognormal", "median": 96, "sigma": 0.6,
                   "min": 8, "max": 256}}
SESSIONS = {"rate": 10.0, "shape_seed": 8, "max_total": 1024,
            "shared_prefix": {"count": 4, "len": 384},
            "prompt": {"dist": "uniform", "min": 32, "max": 256},
            "output": {"dist": "uniform", "min": 16, "max": 128}}


def _gen(params, seed, seconds=10.0):
    return traffic_gen.open_loop_requests(params, seed=seed, seconds=seconds,
                                          vocab_size=50257)


@pytest.mark.parametrize("params", [CHAT, SESSIONS], ids=["chat", "sessions"])
def test_traffic_same_seed_same_requests_other_seed_same_schedule(params):
    big = 2**31 + 12345            # the driver's seeds pass 32 signed bits
    a, b, c = _gen(params, big), _gen(params, big), _gen(params, 5)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # every seed gets the same schedule (arrivals and lengths come from the
    # mix's shape_seed) and other tokens; another shape_seed moves it
    assert len(a) == len(c) == round(params["rate"] * 10.0)
    schedule = lambda rs: [(r.arrival_time, len(r.prompt), r.max_new_tokens)
                           for r in rs]
    assert schedule(a) == schedule(c)
    assert schedule(a) != schedule(_gen(dict(params, shape_seed=9), big))


@pytest.mark.parametrize("params", [CHAT, SESSIONS], ids=["chat", "sessions"])
def test_traffic_honours_clips_and_window(params):
    reqs = _gen(params, 3, seconds=30.0)
    shared = params.get("shared_prefix", {"len": 0})["len"]
    for r in reqs:
        body = len(r.prompt) - shared
        assert params["prompt"]["min"] <= body <= params["prompt"]["max"]
        assert 1 <= r.max_new_tokens <= params["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= params["max_total"]
        assert 0.0 < r.arrival_time < 30.0
        assert all(0 <= t < 50257 for t in r.prompt[:8])
    times = [r.arrival_time for r in reqs]
    assert times == sorted(times)
    if shared:
        heads = {tuple(r.prompt[:shared]) for r in reqs}
        assert len(heads) == params["shared_prefix"]["count"]


def test_traffic_bursts_land_together_and_lengths_follow_the_spec():
    reqs = _gen(dict(CHAT, burst_size=16), 1, seconds=8.0)
    assert len({r.arrival_time for r in reqs}) == math.ceil(len(reqs) / 16)
    rng = np.random.RandomState(0)
    x = traffic_gen.draw_lengths(rng, CHAT["prompt"], 4000)
    assert 170 < np.median(x) < 215 and x.min() >= 16 and x.max() <= 768
    assert set(traffic_gen.draw_lengths(
        rng, {"dist": "choice", "values": [3, 9]}, 50)) == {3, 9}
    assert set(traffic_gen.draw_lengths(
        rng, {"dist": "fixed", "value": 384}, 5)) == {384}
    with pytest.raises(ValueError):
        traffic_gen.draw_lengths(rng, {"dist": "zipf"}, 1)
    rows = traffic_gen.arith_rows(rng, 512, (2, 3, 16))
    assert rows["input_ids"].shape == rows["labels"].shape == (2, 3, 16)
    assert (rows["input_ids"][..., 1:] == rows["labels"][..., :-1]).all()
    assert 0 <= traffic_gen.fold_seed(2**31 + 9) < 2**31


# ------------------------------------------------------------------ flops
@pytest.mark.parametrize("name,shapes,params,per_token", [
    # 36 x (4 x 1280^2 + 2 x 1280 x 5120) = 707,788,800; head 64,328,960;
    # biases and norms 36 x 16,640 = 599,040; wpe 1,310,720; ln_f 2,560
    ("gpt2-large", LARGE, 774_030_080,
     6 * 774_030_080 + 12 * 36 * 1280 * 1024),
    # 48 x (4 x 1600^2 + 2 x 1600 x 6400) = 1,474,560,000; head 80,411,200;
    # 48 x 20,800 = 998,400; wpe 1,638,400; ln_f 3,200
    ("gpt2-xl", XL, 1_557_611_200,
     6 * 1_557_611_200 + 12 * 48 * 1600 * 1024),
], ids=["gpt2-large", "gpt2-xl"])
def test_flops_against_hand_worked_numbers(name, shapes, params, per_token):
    # the family's own count of the published model, to the unit
    family = harness.module("families", "gpt2")
    assert family.shapes(harness.load_json("configs", name + ".json")) == shapes
    assert shapes["params"] == shapes["active_params"] == params
    assert flops.train_flops_per_token(shapes, 1024) == per_token
    # flash attention, 2 rows of 1024 through every layer, causal: one
    # matmul is 2 x rows x heads x T x T x Dh / 2; six of them fwd + bwd
    one = 2 * 2 * shapes["heads"] * 1024 * 1024 * 64 / 2
    f, b = flops.flash_train_work(shapes, rows=2, seq_len=1024)
    assert f == shapes["layers"] * 6 * one
    assert b == shapes["layers"] * 12 * (2 * 1024 * shapes["heads"] * 64 * 2)
    # decode: two slots at 100 and 300 cached tokens read 400 rows of K and V
    f, b = flops.decode_attn_work(shapes, context_lens=[100, 300])
    assert b == shapes["layers"] * 400 * shapes["heads"] * 64 * 2 * 2
    assert f == shapes["layers"] * 400 * shapes["heads"] * 4 * 64


def test_flops_count_key_value_heads_and_active_parameters():
    """The second family by hand: 32 query heads over 8 key-value heads of
    128, SwiGLU 14336, untied head."""
    s = harness.module("families", "llama").shapes(LLAMA_CFG)
    # a layer: wq and wo 2 x 4096^2, wk and wv 2 x 4096 x 1024, three MLP
    # matrices of 4096 x 14336, two norms; embedding, head, final norm
    per_layer = 2 * 4096 ** 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert per_layer == 218_112_000
    assert s["params"] == 2 * 32000 * 4096 + 32 * per_layer + 4096
    assert s["params"] == s["active_params"] == 7_241_732_096
    assert (s["heads"], s["kv_heads"], s["head_dim"]) == (32, 8, 128)
    assert (s["positions"], s["vocab"], s["mlp"]) == (32768, 32000, 14336)
    assert flops.train_flops_per_token(s, 4096) == (
        6 * 7_241_732_096 + 12 * 32 * 4096 * 4096)
    # decode reads the 8 key-value heads once; FLOPs are per query head
    f, b = flops.decode_attn_work(s, context_lens=[100, 300])
    assert b == 32 * 400 * 8 * 128 * 2 * 2
    assert f == 32 * 400 * 32 * 4 * 128
    # flash: q, o, do, dq of 32 heads and k, v, dk, dv of 8, six passes
    f, b = flops.flash_train_work(s, rows=2, seq_len=1024)
    assert f == 32 * 6 * (2 * 2 * 32 * 1024 * 1024 * 128 / 2)
    assert b == 32 * 6 * (32 + 8) * (2 * 1024 * 128 * 2)
    # a sparse model's tokens pass through fewer parameters than it has
    sparse = dict(s, active_params=s["params"] // 4)
    assert flops.train_flops_per_token(sparse, 4096) == (
        6 * (7_241_732_096 // 4) + 12 * 32 * 4096 * 4096)


def test_attention_is_counted_from_heads_and_head_size_not_from_hidden():
    """The contract of ``shapes()``: ``hidden`` is the residual stream's
    width and ``flops.py`` computes nothing from it; a family whose value
    heads or cache rows are of another size states ``v_head_dim`` and
    ``cache_row_dim``. By hand, for shapes no configuration here has: 16
    heads of 192 for queries and keys, values of 128, one cache row of 576
    a token shared by all heads, on a stream of 1024."""
    s = dict(LARGE, hidden=1024, heads=16, kv_heads=1, head_dim=192)
    for hidden in (1024, 16 * 192, 7):
        assert flops.train_flops_per_token(dict(s, hidden=hidden), 512) == \
            6 * s["active_params"] + 12 * 36 * 16 * 192 * 512
    f, b = flops.decode_attn_work(dict(s, hidden=7), context_lens=[100, 300])
    assert (f, b) == (36 * 400 * 16 * 4 * 192, 36 * 400 * 2 * 192 * 2)
    wide = dict(s, v_head_dim=128, cache_row_dim=576)
    assert flops.train_flops_per_token(wide, 512) == \
        6 * s["active_params"] + 6 * 36 * 16 * (192 + 128) * 512
    f, b = flops.decode_attn_work(wide, context_lens=[100, 300])
    assert (f, b) == (36 * 400 * 16 * 2 * (192 + 128), 36 * 400 * 576 * 2)


# A fixed window, and what every function of operations and bytes returned
# for it on the commit before ``hidden`` stopped meaning attention's width
# (8bcba6a, ``python3`` on the files as they were): the four configurations'
# numbers do not move with the contract.
FIXED_OBS = {
    "trace_span": [10.0, 11.0],
    "counters": {"serving/decode_steps": 300,
                 "serving/moe_experts_touched": 2250,
                 "serving/moe_assignments_held": 3600,
                 "serving/moe_assignments": 28800},
    "spans": [{"name": "decode_step", "start": 9.99, "end": 10.01},
              {"name": "decode_step", "start": 10.2, "end": 10.21},
              {"name": "decode_step", "start": 10.9, "end": 11.1}],
    "requests": [{"prompt_len": 1000, "admitted": 10.5,
                  "token_times": [10.1, 10.2, 10.6, 11.2]},
                 {"prompt_len": 400, "admitted": 9.0,
                  "token_times": [9.5, 9.9, 10.9]}],
    "train": {"rows_per_device_step": 16, "seq_len": 1024,
              "traced_steps": 2}}
ON_THE_PARENT = {
    "gpt2-large": {"train_flops_per_token": 5210411520.0,
                   "decode_attn": (443289600.0, 443289600.0),
                   "flash_train": (9277129359360.0, 36238786560)},
    "gpt2-xl": {"train_flops_per_token": 10289385600.0,
                "decode_attn": (738816000.0, 738816000.0),
                "flash_train": (15461882265600.0, 60397977600)},
    "granite-4.0-h-micro": {"train_flops_per_token": 20155009536.0,
                            "decode_attn": (788070400.0, 197017600.0),
                            "flash_train": (16492674416640.0, 40265318400),
                            "ssm_update": (283115520.0, 452984832.0)},
    "k-exaone-236b-a23b": {"train_flops_per_token": 9185942016.0,
                           "decode_attn": (394035200.0, 49254400.0),
                           "flash_train": (8246337208320.0, 18119393280),
                           "moe_experts": (303801827328.0, 5964300288.0)}}


@pytest.mark.parametrize("config,what", [
    (c, w) for c, row in ON_THE_PARENT.items() for w in row])
def test_work_and_flops_return_what_they_returned_on_the_parent(config, what):
    cfg = harness.load_json("configs", config + ".json")
    shapes = harness.module("families", cfg["family"]).shapes(cfg)
    if what == "train_flops_per_token":
        got = flops.train_flops_per_token(shapes, 1024)
    else:
        got = harness.module("work", what).work(dict(FIXED_OBS, shapes=shapes))
    assert got == ON_THE_PARENT[config][what]


def test_roofline_and_peaks_table():
    peaks = harness.load_json("peaks.json")
    v5e = peaks["devices"]["TPU v5 lite"]
    assert (v5e["bf16_tflops"], v5e["hbm_gbps"]) == (197.0, 819.0)
    assert peaks["source"]
    assert flops.roofline_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    t, bound = flops.roofline_seconds(1.0, 819e9 * 2, v5e)
    assert bound == "memory" and t == pytest.approx(2.0)
    # at 1024 tokens causal flash sits near the ridge: compute bound forward
    f, b = flops.flash_train_work(LARGE, rows=2, seq_len=1024)
    assert flops.roofline_seconds(f, b, v5e)[1] == "compute"


# ---------------------------------------------------------- trace reducer
ALL_GATHER = "%all-gather.2 = bf16[8]{0} all-gather(bf16[2]{0} %p.1)"
ALL_REDUCE = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p.2)"
# a collective's result among a fusion's operands does not make it one
CONSUMER = "%fusion.9 = bf16[8]{0} fusion(bf16[8]{0} %all-gather.3, %p.4)"
# the TPU's wait for a collective it started earlier: a fusion by opcode
ASYNC_DONE = ("%async-collective-done.4 = bf16[8]{0} "
              "fusion(%async-collective-start.4)")


def _synthetic():
    """Two devices over a 10 s window (seconds on the profile's clock).
    Device 0: a ``while`` parent 1..6 around fusion 1..3, all-gather 3..4
    (alone: exposed) and fusion 4..6; then an all-reduce 7..9 with a fusion
    8..9 nested in it, so only 7..8 of it is its own and exposed. Device 1:
    fusion 0..5, a fusion that reads an all-gather's result 5..6 (compute)
    and an ``async-collective-done`` 6..7 (alone: an exposed wait).
    Collectives are named as the chip names them, by their HLO text."""
    d0 = [("while.1", 1.0, 6.0), ("fusion.1", 1.0, 3.0),
          (ALL_GATHER, 3.0, 4.0), ("fusion.2", 4.0, 6.0),
          (ALL_REDUCE, 7.0, 9.0), ("fusion.3", 8.0, 9.0)]
    d1 = [("fusion.1", 0.0, 5.0), (CONSUMER, 5.0, 6.0),
          (ASYNC_DONE, 6.0, 7.0)]
    host = [("bench/window", 0.0, 10.0), ("dstpu/serving_admit", 6.1, 6.9),
            ("dstpu/serving_decode", 9.0, 9.4)]
    return trace_reduce.Trace({0: d0, 1: d1}, host, (0.0, 10.0))


def test_interval_arithmetic():
    assert trace_reduce.union([(3, 4), (1, 2), (1.5, 3.5), (6, 6)]) == [(1, 4)]
    assert trace_reduce.clip([(0, 2), (5, 9), (11, 12)], (1, 8)) == [
        (1, 2), (5, 8)]
    assert trace_reduce.subtract([(0, 10)], [(1, 2), (4, 6)]) == [
        (0, 1), (2, 4), (6, 10)]
    assert trace_reduce.subtract([(0, 3), (5, 8)], [(2, 6)]) == [
        (0, 2), (6, 8)]
    assert trace_reduce.subtract([(0, 1)], []) == [(0, 1)]


def test_reducer_on_a_synthetic_event_list():
    tr = _synthetic()
    # busy: device 0 is 1..6 and 7..9 = 7 s, device 1 is 0..7; mean 7 s
    assert trace_reduce.busy_seconds(tr) == pytest.approx(7.0)
    assert trace_reduce.idle_share(tr) == pytest.approx(0.3)
    # self times: the while keeps nothing of its own
    by = trace_reduce.time_by_name(tr)
    assert by["while.1"] == pytest.approx(0.0)
    assert by["fusion.1"] == pytest.approx((2.0 + 5.0) / 2)
    assert by[ALL_GATHER] == pytest.approx(0.5)
    assert trace_reduce.matched_seconds(tr, "^%all-") == pytest.approx(
        (1.0 + 1.0) / 2)
    # the pattern is anchored at the result name: the reader of
    # ``%all-gather.3`` is compute, the async wait is a collective
    kinds = {n: bool(trace_reduce.COLLECTIVE.search(n))
             for n in (ALL_GATHER, ALL_REDUCE, CONSUMER, ASYNC_DONE,
                       "%all-gather-start.1 = (bf16[2]) all-gather-start(%p)",
                       "%collective-permute-done.7 = bf16[2] "
                       "collective-permute-done(%collective-permute-start.7)",
                       "%async-collective-start.4 = bf16[2] fusion(%p.3)",
                       "fusion.1")}
    assert [k for k, v in kinds.items() if not v] == [
        CONSUMER, "%async-collective-start.4 = bf16[2] fusion(%p.3)",
        "fusion.1"]
    # exposed: all-gather 3..4 and all-reduce 7..8 on device 0, the wait
    # 6..7 on device 1 and not its reader 5..6: (2 + 1) / 2
    assert trace_reduce.exposed_collective_seconds(tr) == pytest.approx(1.5)
    # gaps of device 0, longest first, named by the host annotation open then
    gaps = trace_reduce.idle_gaps(tr)
    assert sorted((n, round(s, 6)) for n, s in gaps) == [
        ("dstpu/serving_admit", 1.0), ("dstpu/serving_decode", 1.0),
        ("none", 1.0)]
    bd = trace_reduce.breakdown(tr)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 5
    assert bd["device_ops"][0][0] == "fusion.1"
    # a window that cuts events counts only what is inside
    cut = trace_reduce.Trace(tr.device_ops, tr.host, (2.0, 5.0))
    assert trace_reduce.busy_seconds(cut) == pytest.approx(3.0)


def test_readers_on_the_synthetic_trace():
    tr = _synthetic()
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": LARGE, "counters": {"compiles_in_window": 0,
                                         "slot_iterations_active": 48,
                                         "decode_steps": 3, "num_slots": 32},
           "train": {"rows_per_device_step": 16, "seq_len": 1024,
                     "traced_steps": 1},
           "requests": [{"arrival": 0.0, "admitted": 0.010,
                         "first_token": 0.030, "prompt_len": 10,
                         "token_times": [0.03, 0.04]},
                        {"arrival": 1.0, "admitted": 1.030,
                         "first_token": 1.040, "prompt_len": 20,
                         "token_times": [1.04]}],
           "spans": [{"name": "decode_step", "start": 0.0, "end": 0.004},
                     {"name": "decode_step", "start": 1.0, "end": 1.006},
                     {"name": "queue_wait", "start": 0.0, "end": 0.5}]}

    def read(metric):
        spec = harness.load_json("layer_metrics", metric + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    assert read("entry.compiles_in_window.serve") == 0
    assert read("sched.batch_fill") == pytest.approx(50.0)
    assert read("sched.queue_wait_p95_ms") == pytest.approx(29.0)
    assert read("step.prefill_ms") == pytest.approx(15.0)
    assert read("step.decode_ms") == pytest.approx(5.0)
    assert read("device.idle_share.train") == pytest.approx(30.0)
    assert read("partition.exposed_collective_share") == pytest.approx(15.0)
    # the metric files that name a collective were anchored before
    assert read("partition.param_gather_share") == pytest.approx(
        100.0 * 0.5 / 7.0)
    # nothing in this trace is a flash kernel: the reader returns nothing
    assert read("kernel.flash_roofline") is None
    # and with nothing to read, every reader returns nothing
    for metric in os.listdir(os.path.join(harness.BENCH_DIR, "layer_metrics")):
        spec = harness.load_json("layer_metrics", metric)
        assert harness.module("readers", spec["reader"]).read(
            spec["params"], {}) is None, metric


def test_roofline_reader_counts_the_work_of_the_traced_window():
    f, b = flops.flash_train_work(LARGE, rows=16, seq_len=1024)
    least = f / 197e12
    tr = trace_reduce.Trace(
        {0: [("_flash_fwd_kernel", 0.0, 2 * least)]},
        [("bench/window", 0.0, 1.0)], (0.0, max(1.0, 2 * least)))
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": LARGE, "train": {"rows_per_device_step": 16,
                                      "seq_len": 1024, "traced_steps": 1}}
    reader = harness.module("readers", "trace_kernel_roofline")
    share = reader.read({"pattern": "flash", "work": "flash_train"}, obs)
    assert share == pytest.approx(50.0)


def test_reducer_on_a_real_profile(tmp_path):
    """A real ``.xplane.pb`` from this backend: host annotations are found
    and the window is the annotation the harness opens. (The trimmed TPU
    trace of PERF.md's first runs is over 200 KB and is not committed.)"""
    import jax
    import jax.numpy as jnp

    with harness.profile_if(True) as prof:
        prof.start()
        prof.open_window()
        with jax.profiler.TraceAnnotation("dstpu/train_step"):
            jnp.ones((64, 64)).sum().block_until_ready()
        time.sleep(0.01)
        prof.close_window()
        tr = prof.reduce()
    assert not os.path.exists(prof.dir)      # raw files are deleted
    assert tr.window_s >= 0.01
    assert any(n == "dstpu/train_step" for n, _, _ in tr.host)
    assert tr.device_ops == {} and trace_reduce.busy_seconds(tr) == 0.0


# ------------------------------------------------ names, files, resolution
def test_every_name_and_unit_holds_only_the_allowed_characters(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]]
    assert len(names) == len(set(names))
    for g in ("configs", "workloads"):
        assert len({e["name"] for e in bench[g]}) == len(bench[g])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["workloads"]) <= 24 >= len(bench["configs"]) >= 1
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert len(json.dumps(bench)) < 64 * 1024


def test_the_appended_benchmark_appends_and_edits_nothing():
    """The fixture's second benchmark is the accepted one with entries
    appended: every list of the first is a prefix of the second's, and the
    twin reports what its donor reports."""
    one, two = BENCH, appended.BENCHES["one-appended"]
    twin = appended.APPENDED_CELL
    assert twin not in CELLS
    for g, name in (("configs", appended.APPENDED_CONFIG),
                    ("workloads", twin)):
        assert two[g][:len(one[g])] == one[g]
        assert [e["name"] for e in two[g][len(one[g]):]] == [name]
    for g in ("end_to_end", "per_layer"):
        assert [m["name"] for m in two[g]] == [m["name"] for m in one[g]]
        for a, b in zip(one[g], two[g]):
            was = a.get("workloads", [])
            assert b == dict(a, workloads=was + [twin] * (
                appended.DONOR in was)) or (b == a and "workloads" not in a)
        assert [m["name"] for m in harness.metrics_of(twin, g, two)] == \
            [m["name"] for m in harness.metrics_of(appended.DONOR, g, two)]
    for key in ("command", "paths", "run_seconds"):
        assert two[key] == one[key]
    assert harness.load_cell(twin, two)["config_file"] == \
        harness.load_cell(appended.DONOR, one)["config_file"]


def _longest(spec):
    """The longest length a spec of ``traffic_gen.draw_lengths`` can draw, by
    its ``dist``: a ``choice`` or ``fixed`` spec needs no ``max`` beside its
    values, and is clipped by one it has."""
    if spec["dist"] == "choice":
        top = max(spec["values"])
    elif spec["dist"] == "fixed":
        top = spec["value"]
    else:                       # lognormal is clipped at max, uniform ends there
        top = spec["max"]
    return min(top, spec.get("max", top))


@pytest.mark.parametrize("spec,want", [
    ({"dist": "lognormal", "median": 192, "sigma": 0.7, "min": 16,
      "max": 768}, 768),
    ({"dist": "uniform", "min": 32, "max": 256}, 256),
    ({"dist": "choice", "values": [96, 3584, 640]}, 3584),
    ({"dist": "choice", "values": [96, 3584, 640], "max": 1024}, 1024),
    ({"dist": "fixed", "value": 384}, 384),
], ids=["lognormal", "uniform", "choice", "choice-clipped", "fixed"])
def test_the_longest_length_of_a_spec_follows_its_dist(spec, want):
    assert _longest(spec) == want
    drawn = traffic_gen.draw_lengths(np.random.RandomState(0), spec, 2000)
    assert drawn.max() <= want
    if spec["dist"] != "lognormal":     # its clip is reached by few draws
        assert drawn.max() == want


@pytest.mark.parametrize("bench,cell_name", appended.CELL_CASES)
def test_every_cell_resolves_its_files_by_name(bench, cell_name):
    cell = harness.load_cell(cell_name, bench)
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert cfg["source"] == entry["source"]
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    assert hasattr(harness.module("kinds", traffic["kind"]), "run")
    family = harness.module("families", cfg["family"])
    reference = harness.module("reference", cfg["family"])
    assert callable(reference.forward_logits) and callable(reference.loss)
    shapes = family.shapes(cfg)
    # what a family owes the harness. ``hidden`` is the stream's width;
    # attention's is heads x head_dim and need not equal it
    assert set(shapes) >= set(LARGE)
    assert shapes["heads"] % shapes["kv_heads"] == 0
    assert 0 < shapes["active_params"] <= shapes["params"]
    check = traffic["check"]
    assert 0.0 <= check["logit_tol"] and len(check["why"]) > 80
    e2e = {m["name"] for m in harness.metrics_of(cell_name, "end_to_end",
                                                 bench)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of(cell_name, "per_layer", bench)
    assert layer
    for m in layer:
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        assert callable(harness.module("readers", spec["reader"]).read)
        for key in ("layer", "unit", "moves", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        # the metric it should move is reported wherever it is
        assert m["moves"] in e2e, (m["name"], cell_name)
    if traffic["kind"] == "train_job":
        job = traffic["job"]
        assert job["mesh"]["dp"] * job["mesh"]["tp"] == cell["chips"]
        assert (job["micro_batch"] * job["gas"] * job["mesh"]["dp"]
                == job["rows_per_step"])
    else:
        assert 0.0 < check["mean_gap_tol"] < check["logit_tol"]
        a, s = traffic["arrivals"], traffic["server"]
        assert a["max_total"] <= s["max_len"] <= shapes["positions"]
        longest = (_longest(a["prompt"])
                   + a.get("shared_prefix", {}).get("len", 0))
        assert longest <= max(s["buckets"])


@pytest.mark.parametrize("name,want", [
    ("gpt2-large", dict(n_layer=36, n_embd=1280, n_head=20)),
    ("gpt2-xl", dict(n_layer=48, n_embd=1600, n_head=25)),
])
def test_configuration_files_hold_the_published_widths(name, want):
    cfg = harness.load_json("configs", name + ".json")
    for k, v in dict(want, vocab_size=50257, n_positions=1024,
                     n_inner=None, layer_norm_epsilon=1e-5).items():
        assert cfg[k] == v, (name, k)
    assert "huggingface.co/openai-community/" + name in cfg["source"]
    assert not [k for k in cfg["reduced"] if WIDTH_KEY.search(k)]


# What ``reduced`` may never name: GPT-2's width keys and the catalog's
# (/opt/skills/guides/model-configs: hidden, intermediate, latent, state and
# projection sizes, head counts and sizes, experts and experts per token).
WIDTH_KEY = re.compile(
    r"(_dim|_rank)$|hidden_size|intermediate|latent|state_size|experts"
    r"|^(n_embd|n_inner|n_head|num_attention_heads|num_key_value_heads"
    r"|sliding_window|expand|d_state|d_conv|d_model|d_ff|d_kv)$")


@pytest.mark.parametrize("key", [
    "n_embd", "n_inner", "n_head", "hidden_size", "intermediate_size",
    "moe_intermediate_size", "num_attention_heads", "num_key_value_heads",
    "head_dim", "num_experts", "num_local_experts", "n_routed_experts",
    "num_experts_per_tok", "sliding_window", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "ssm_state_size", "d_model"])
def test_a_width_under_any_published_name_is_a_width(key):
    assert WIDTH_KEY.search(key)


@pytest.mark.parametrize("key", [
    "n_layer", "num_hidden_layers", "n_positions", "max_position_embeddings",
    "attn_pdrop", "embd_pdrop", "resid_pdrop", "rope_theta",
    "first_k_dense_replace"])
def test_a_depth_or_a_rate_is_not_a_width(key):
    assert not WIDTH_KEY.search(key)


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path, monkeypatch):
    """A later PR adds a configuration of a new family, a traffic mix, a
    kind, a per-layer metric with its reader, a kernel's work and a cell, and
    edits no file that is there."""
    bench_dir = tmp_path / "benchmarks"
    for sub in ("configs", "traffic", "layer_metrics", "work"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "toy.json").write_text(json.dumps(
        {"family": "toy_family", "width": 3, "source": "paper",
         "reduced": []}))
    (bench_dir / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "toy_kind"}))
    (bench_dir / "layer_metrics" / "toy.metric.json").write_text(json.dumps(
        {"reader": "toy_reader", "params": {"k": 3}}))
    (bench_dir / "layer_metrics" / "toy.kernel_roofline.json").write_text(
        json.dumps({"reader": "trace_kernel_roofline",
                    "params": {"pattern": "^%toy_kernel", "work": "toy_work"}}))
    # a work module as a file, found through the package's path: nothing
    # registers it
    (bench_dir / "work" / "toy_work.py").write_text(
        "def work(obs):\n"
        "    return 197e12 * obs['shapes']['width'], 1.0\n")
    bench = {"configs": [{"name": "toy", "file": "benchmarks/configs/toy.json"}],
             "workloads": [{"name": "toy.toy-mix", "config": "toy",
                            "traffic": "toy-mix", "chips": 1, "why": "x"}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "toy.metric", "unit": "count",
                            "workloads": ["toy.toy-mix"]},
                           {"name": "toy.kernel_roofline", "unit": "%",
                            "workloads": ["toy.toy-mix"]},
                           {"name": "other", "workloads": ["elsewhere"]}]}
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH_DIR", str(bench_dir))
    import sys
    import types

    import benchmarks.work
    monkeypatch.setattr(benchmarks.work, "__path__",
                        list(benchmarks.work.__path__)
                        + [str(bench_dir / "work")])
    monkeypatch.delitem(sys.modules, "benchmarks.work.toy_work", raising=False)
    family = types.ModuleType("benchmarks.families.toy_family")
    family.shapes = lambda cfg: {"width": cfg["width"]}
    monkeypatch.setitem(sys.modules, family.__name__, family)
    kind = types.ModuleType("benchmarks.kinds.toy_kind")

    def run(cell, **kw):    # as the real kinds: the family, then its shapes
        cfg = cell["config_file"]
        shapes = harness.module("families", cfg["family"]).shapes(cfg)
        return {"seen": cell["traffic_file"]["kind"], "shapes": shapes}
    kind.run = run
    reader = types.ModuleType("benchmarks.readers.toy_reader")
    reader.read = lambda params, obs: params["k"] * obs["n"]
    monkeypatch.setitem(sys.modules, kind.__name__, kind)
    monkeypatch.setitem(sys.modules, reader.__name__, reader)
    cell = harness.load_cell("toy.toy-mix", bench)
    runner = harness.module("kinds", cell["traffic_file"]["kind"])
    out = runner.run(cell)
    assert out["seen"] == "toy_kind" and out["shapes"] == {"width": 3}
    from benchmarks import run as bench_run
    # the toy kernel ran 4 s of a 10 s window; its work is 3 s at the peak
    tr = trace_reduce.Trace(
        {0: [("%toy_kernel.1 = f32[2] custom-call(%p)", 1.0, 5.0)]},
        [("bench/window", 0.0, 10.0)], (0.0, 10.0))
    got = bench_run.per_layer_metrics(
        "toy.toy-mix", bench,
        {"n": 2, "trace": tr, "shapes": out["shapes"],
         "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0}})
    assert got == {"toy.metric": {"value": 6, "unit": "count"},
                   "toy.kernel_roofline": {"value": pytest.approx(75.0),
                                           "unit": "%"}}
    sys.modules.pop("benchmarks.work.toy_work", None)
    with pytest.raises(KeyError):
        harness.load_cell("no.such-cell", bench)


# -------------------------------------------------- reference and guard
def test_reference_agrees_with_the_program_at_tiny_size():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    family = importlib.import_module("benchmarks.families.gpt2")
    reference = importlib.import_module("benchmarks.reference.gpt2")
    cfg = GPT2Config.tiny()
    model = GPT2Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    # the initialiser zeroes every bias: give them values, or a reference
    # that dropped one would pass
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48))
    with jax.default_matmul_precision("highest"):
        want = model.logits(params, model.forward_hidden(params, ids))
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        want_loss, _ = model.apply(params, batch)
    file_cfg = family.tiny(harness.load_json("configs", "gpt2-large.json"))
    got = reference.forward_logits(params, jnp.asarray(ids), file_cfg)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    got_loss = reference.loss(params, jnp.asarray(batch["input_ids"]),
                              jnp.asarray(batch["labels"]), file_cfg)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-4)
    # the family builds the same model from the file's keys
    assert family.build_model(file_cfg, {}).config == cfg


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["multi-head",
                                                  "grouped-query"])
def test_llama_reference_agrees_with_the_program_at_tiny_size(kv_heads):
    """Full forward and loss, then a prompt's prefill and eight decode steps
    through the cache against the reference's one full forward: float32 at
    "highest" on both sides, logits to 1e-4."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.llama import LlamaModel

    family = importlib.import_module("benchmarks.families.llama")
    reference = importlib.import_module("benchmarks.reference.llama")
    file_cfg = dict(family.tiny(LLAMA_CFG), num_key_value_heads=kv_heads)
    assert not set(file_cfg) & set(GPT2_KEYS)
    built = family.build_model(file_cfg, {})
    cfg = built.config
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (4, kv_heads, 16)
    model = LlamaModel(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    # the initialiser sets every norm's scale to one: give them values, or
    # a reference that dropped one would pass
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 48))
    got = reference.forward_logits(params, jnp.asarray(ids), file_cfg)
    assert got.dtype == jnp.float32
    with jax.default_matmul_precision("highest"):
        want = model.logits(params, model.forward_hidden(params, ids))
        batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
        want_loss, _ = model.apply(params, batch)
        # serving's route: the prompt in one call, then a token a call
        prompt = 40
        logits, cache = model.forward_with_cache(
            params, jnp.asarray(ids[:, :prompt]),
            model.init_cache(2, 64, dtype=jnp.float32))
        served = [logits]
        for i in range(prompt, 48):
            logits, cache = model.forward_with_cache(
                params, jnp.asarray(ids[:, i:i + 1]), cache)
            served.append(logits)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got),
                               np.concatenate(served, axis=1), atol=1e-4)
    got_loss = reference.loss(params, jnp.asarray(batch["input_ids"]),
                              jnp.asarray(batch["labels"]), file_cfg)
    assert float(got_loss) == pytest.approx(float(want_loss), abs=1e-4)


def test_a_run_without_a_tpu_raises_and_prints_no_result(capsys, monkeypatch,
                                                          bench):
    from benchmarks import run as bench_run

    # main() would point this process's compile cache at benchmarks/.cache,
    # and later test modules run in the same worker
    monkeypatch.setattr(harness, "setup_compile_cache", lambda: "unused")
    monkeypatch.setattr(harness, "benchmark_json", lambda: bench)
    with pytest.raises(harness.NoAcceleratorError, match="no TPU"):
        harness.device_guard(1)
    for cell in [w["name"] for w in bench["workloads"]]:
        with pytest.raises(harness.NoAcceleratorError):
            bench_run.main(["--workload", cell, "--seed", "1",
                            "--seconds", "1", "--trace", "0"])
    assert "correct" not in capsys.readouterr().out
    with pytest.raises(KeyError):
        bench_run.main(["--workload", "no.such-cell", "--seed", "1",
                        "--seconds", "1", "--trace", "0"])


# ------------------------------------------------------------ rehearsals
def _one_of_kind(kind):
    for name in CELLS:
        cell = harness.load_cell(name, BENCH)
        if cell["traffic_file"]["kind"] == kind:
            return cell
    pytest.skip(f"no cell of kind {kind} in BENCHMARK.json")


@pytest.mark.parametrize("family", ["gpt2", "llama"])
@pytest.mark.parametrize("kind", ["train_job", "serve_open_loop"])
def test_rehearsal_in_process_at_tiny_size(kind, family, monkeypatch):
    """The kind's runner end to end at the family's tiny sizes for under a
    second of window, traced, through ``rehearse=True`` (tests only: it skips
    the device guard, and what it returns is no result). The second family
    runs a configuration that holds none of GPT-2's keys through the same
    kinds and mixes."""
    from deepspeed_tpu import telemetry
    from deepspeed_tpu.telemetry import registry as registry_module

    from benchmarks import run as bench_run

    cell = _one_of_kind(kind)
    if family == "llama":
        cell = dict(cell, config_file=LLAMA_CFG)
        assert not set(LLAMA_CFG) & set(GPT2_KEYS)
    # serving makes a registry of its own; the training engine counts in the
    # program's global one
    real = registry_module.MetricsRegistry

    def counting_registry(*args, **kwargs):
        registry = real(*args, **kwargs)
        registry.counter("test/added_in_a_test").inc(7)
        return registry
    monkeypatch.setattr(registry_module, "MetricsRegistry", counting_registry)
    telemetry.get_registry().counter("test/added_in_a_test").inc(7)
    out = harness.module("kinds", kind).run(
        cell, seed=2**31 + 7, seconds=0.6, trace=True,
        clock0=time.perf_counter(), rehearse=True)
    assert out["device"]["platform"] == "cpu"      # never a chip result
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    e2e = {m["name"] for m in harness.metrics_of(cell["name"], "end_to_end",
                                                 BENCH)}
    assert e2e <= set(out["end_to_end"])
    assert all(v > 0 for v in out["end_to_end"].values())
    counters = out["observations"]["counters"]
    assert counters["compiles_in_window"] == 0
    # every counter of the program's registry reaches the readers under its
    # registry name: one the kind's file never names, and one added here
    never_named = {"train_job": "train/steps",
                   "serve_open_loop": "serving/prefills"}[kind]
    read = harness.module("readers", "counter").read
    assert read({"counter": never_named}, out["observations"]) > 0
    assert read({"counter": "test/added_in_a_test"},
                out["observations"]) >= 7
    assert read({"counter": "no/such_counter"}, out["observations"]) is None
    if kind == "serve_open_loop":       # the first metric files' short names
        assert counters["decode_steps"] == counters["serving/decode_steps"] > 0
    line = bench_run.result_line(cell, BENCH, out, trace=True)
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["window_s"] > 0
    # no device plane on this backend: the trace readers leave their
    # metrics out of the line and the program's own counters remain; a
    # device metric is told by its source, whatever its name
    sources = {m["name"]: m["source"] for m in BENCH["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    assert [m for m in line["metrics"] if sources[m] == "program_counter"]
    line0 = bench_run.result_line(cell, BENCH, out, trace=False)
    assert set(line0["metrics"]) == e2e
    json.dumps(line), json.dumps(line0)


def test_a_serve_run_that_leaves_requests_unfinished_is_not_correct(
        monkeypatch):
    """The loop gives up before the window ends, so some requests never
    finish: they are failed, they stay in the tail, and the run is not
    ``correct`` whatever the logits of the finished ones say."""
    kind = harness.module("kinds", "serve_open_loop")
    monkeypatch.setattr(kind, "DRAIN_LIMIT_S", -0.3)
    out = kind.run(_one_of_kind("serve_open_loop"), seed=3, seconds=0.6,
                   trace=False, clock0=time.perf_counter(), rehearse=True)
    assert 0 < out["failed"] < out["attempted"]
    assert out["notes"]["worst_logit_gap"] <= out["notes"]["logit_tol"]
    assert not out["correct"]


def test_a_serve_run_whose_tokens_sit_too_far_below_on_average_is_not_correct():
    """Every request finishes and no token is far off, but the mix allows no
    gap at all on average: the second limit alone decides."""
    cell = _one_of_kind("serve_open_loop")
    check = dict(cell["traffic_file"]["check"], mean_gap_tol=-1e-9)
    cell = dict(cell, traffic_file=dict(cell["traffic_file"], check=check))
    out = harness.module("kinds", "serve_open_loop").run(
        cell, seed=3, seconds=0.6, trace=False, clock0=time.perf_counter(),
        rehearse=True)
    assert out["failed"] == 0 and out["attempted"] > 0
    assert 0.0 <= out["notes"]["mean_logit_gap"] <= out["notes"]["worst_logit_gap"]
    assert out["notes"]["worst_logit_gap"] <= out["notes"]["logit_tol"]
    assert not out["correct"]


# --------------------------------------------------------------- the control
def test_control_comparison_by_hand():
    import jax.numpy as jnp

    from benchmarks import control

    ref = jnp.asarray([[0.0, 1.0, 3.0], [2.0, 0.5, 1.5], [1.0, 4.0, 3.5],
                       [0.0, 0.0, 9.0]])
    got = jnp.asarray([[0.0, 1.0, 3.5], [1.0, 0.5, 1.75], [1.0, 3.0, 3.5],
                       [7.0, 0.0, 0.0]])
    # positions 0..2: picks 2 (the best), 2 (0.5 below 2.0), 2 (0.5 below 4.0)
    c = control.compare(ref, got, first=0, count=3)
    assert (c["worst_gap"], c["gap_sum"], c["flipped"], c["checked"]) == (
        0.5, 1.0, 2, 3)
    assert c["logit_err"] == 9.0           # anywhere in the row
    assert control.compare(ref, ref, 0, 4)["gap_sum"] == 0.0


@pytest.mark.parametrize("family_name,cfg", [
    ("gpt2", None), ("llama", LLAMA_CFG)], ids=["gpt2", "llama"])
def test_the_control_moves_logits_more_than_the_stated_precision(family_name,
                                                                 cfg):
    """int8 weights in the reference's place, at tiny size: every matrix is
    on 255 levels a slice, vectors are untouched, and the logits move several
    times as far as under bf16 weights, the precision the cells state. (The
    limits themselves are read on the chip at the cells' sizes: PERF.md.)"""
    import jax
    import jax.numpy as jnp

    from benchmarks import control

    family = harness.module("families", family_name)
    reference = harness.module("reference", family_name)
    cfg = family.tiny(cfg or harness.load_json("configs", "gpt2-large.json"))
    params = family.build_model(cfg, {}).init(jax.random.PRNGKey(3))
    low = control.int8_weights(params)
    for w, q in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(low)):
        if w.ndim < 2:
            assert (w == q).all()
            continue
        scale = np.asarray(jnp.max(jnp.abs(w), axis=-2, keepdims=True)) / 127
        assert np.abs(np.asarray(w - q)).max() <= scale.max() * 0.5001
        column = np.asarray(q).reshape(-1, *q.shape[-2:])[0, :, 0]
        assert len(np.unique(column)) <= 255
    # rounded where it lies, a leaf at a time (what ``main`` does, so that one
    # chip holds a configuration that fills half of it): the tree that the
    # whole tree rounded in one compiled call gives, as ``main`` did before
    for q, r in zip(jax.tree_util.tree_leaves(jax.jit(control.int8_weights)(
            params)), jax.tree_util.tree_leaves(control.int8_weights_in_place(
                jax.tree_util.tree_map(jnp.copy, params)))):
        assert (q == r).all()
    ids = jnp.asarray(np.random.RandomState(1).randint(
        0, cfg["vocab_size"], (1, 96)))
    bf16 = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    full = reference.forward_logits(params, ids, cfg)[0]
    stated = control.compare(
        full, reference.forward_logits(bf16, ids, cfg)[0], 0, 96)
    lowered = control.compare(
        full, reference.forward_logits(low, ids, cfg)[0], 0, 96)
    assert 0.0 < stated["logit_err"] * 3.0 <= lowered["logit_err"]


@pytest.mark.parametrize("kind", ["train_job", "serve_open_loop"])
def test_the_kinds_read_no_configuration_key_but_family(kind):
    """A configuration is touched through its family and its reference
    alone, so a family whose ``config.json`` has other key names needs no
    edit to a kind: the source names none of GPT-2's keys, subscripts the
    configuration by ``family`` only, and keeps no tolerance of its own."""
    with open(os.path.join(harness.BENCH_DIR, "kinds", kind + ".py")) as f:
        source = f.read()
    assert not [key for key in GPT2_KEYS if key in source]
    assert set(re.findall(r"cfg\[\"(\w+)\"\]", source)) == {"family"}
    assert "cfg.get(" not in source
    assert not re.search(r"^[A-Z_]*TOL[A-Z_]* *=", source, re.M)
    assert 'traffic["check"]["logit_tol"]' in source


def test_no_other_file_of_the_harness_names_a_gpt2_key():
    """The acceptance grep of the PR that opened the harness to a second
    family: readers, ``flops.py``, ``run.py`` and ``harness.py``."""
    files = [os.path.join("readers", f) for f in os.listdir(
        os.path.join(harness.BENCH_DIR, "readers")) if f.endswith(".py")]
    for name in files + ["flops.py", "run.py", "harness.py",
                         "trace_reduce.py", "traffic_gen.py", "stats.py"]:
        with open(os.path.join(harness.BENCH_DIR, name)) as f:
            source = f.read()
        # as a whole word: ``attn_layers`` of ``shapes()`` is no GPT-2 key
        assert not [k for k in GPT2_KEYS
                    if re.search(rf"\b{k}\b", source)], name
