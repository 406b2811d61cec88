"""The fourth family, ``exaone_moe``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, the work of its expert matmuls against a hand-worked window, its metric
files through their readers, and a tiny in-process rehearsal of its cell
(``rehearse=True``: no device guard, never a result). What it reads of
``BENCHMARK.json`` it reads through the ``bench`` fixture, as accepted and with
a cell appended behind this family's (appended.py), and it speaks of its own
cell only: that the cell is listed, never that it is last or alone.

One module (tests/conftest.py runs every module in a child process); it starts
no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "k-exaone-236b-a23b.serve-mixed-lengths"
CFG = harness.load_json("configs", "k-exaone-236b-a23b.json")
FAMILY = harness.module("families", "exaone_moe")
# the published config.json (catalog row K-EXAONE-236B-A23B), key for key,
# but for the lists, which are checked by their pattern below
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "max_position_embeddings": 262144, "model_type": "exaone_moe",
    "moe_intermediate_size": 2048, "n_group": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0]}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 19200,
       "num_nextn_predict_layers": 0, "mtp_layer_types": [],
       "mtp_sliding_windows": [], "max_position_embeddings": 4096,
       "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                    "sliding_attention"],
       "sliding_windows": [128, 128, 128, 0, 128],
       "mlp_layer_types": ["dense"] + ["sparse"] * 4}


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        if key not in CUT:
            assert CFG[key] == value, key
    for key, value in CUT.items():
        assert CFG[key] == value, key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_experts_per_tok", "sliding_window",
              "num_attention_heads", "num_key_value_heads"}
    assert not widths & set(CFG["reduced"])
    # the published pattern: the lists are its first five of 48
    assert CFG["layer_types"] == ((["sliding_attention"] * 3
                                   + ["full_attention"]) * 12)[:5]
    assert (CFG["num_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"]) == (128, 153600, 0)
    for needle in ("48 -> 5", "128 -> 16", "153600 -> 19200", "1 -> 0",
                   "262144 -> 4096", "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"]
                 if c["name"] == "k-exaone-236b-a23b")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/k-exaone-236b-a23b.json"
    assert set(CFG["assumed"]) >= {"norm_placement", "qk_norm", "rotation",
                                   "selection_bias", "initial_values",
                                   "weights_dtype", "weights_seed",
                                   "weights_seed_why"}
    assert "eight chips share each layer by experts" in CFG["deployment"]
    assert "pipeline stages" in CFG["deployment"]


@pytest.mark.parametrize("stated", [True, False])
def test_the_cell_serves_one_checkpoint_whatever_the_seed(stated):
    """``assumed.weights_seed``: the weights are those that ``--seed
    <weights_seed>`` draws, whatever key ``init`` is given (a step costs what
    its routing touches, so weights drawn anew a run moved ``itl_p95_ms`` by
    the seed); without the key the weights follow the seed."""
    import jax

    from benchmarks import traffic_gen

    cfg = FAMILY.tiny(CFG)
    assert cfg["assumed"]["weights_seed"] == CFG["assumed"]["weights_seed"]
    if not stated:
        del cfg["assumed"]["weights_seed"]
    model = FAMILY.build_model(cfg, {})
    one, other = (model.init(jax.random.PRNGKey(k)) for k in (1, 2))
    same = all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(other)))
    assert same == stated
    if stated:
        del cfg["assumed"]["weights_seed"]
        drawn = FAMILY.build_model(cfg, {}).init(jax.random.PRNGKey(
            traffic_gen.fold_seed(CFG["assumed"]["weights_seed"])))
        assert bool((drawn["lm_head"] == one["lm_head"]).all())


def test_shapes_against_the_hand_count():
    """ISSUE 35's arithmetic: attention 113.25M, a routed or the shared
    expert 37.75M, the router 0.79M, the dense layer 453.0M, embedding and
    head slices 236M: 7.42 GB in bf16."""
    s = FAMILY.shapes(CFG)
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144
    expert = 3 * 6144 * 2048
    assert (attn, expert) == (113_246_208, 37_748_736)
    norms = 2 * 128 + 2 * 6144
    dense = attn + norms + 3 * 6144 * 18432
    sparse = attn + norms + 6144 * 128 + 128 + expert * (1 + 16)
    want = 2 * 19200 * 6144 + 6144 + dense + 4 * sparse
    assert s["params"] == want == 3_712_028_416
    assert 7.42e9 < 2 * s["params"] < 7.43e9
    # a token passes through one routed expert here on average: 8 x 16 / 128
    assert s["active_params"] == want - 4 * expert * 15
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["expert_mlp"], s["window"], s["sliding_layers"],
            s["global_layers"], s["sparse_layers"]) == \
        (128, 16, 8, 2048, 128, 4, 1, 4)
    # "hidden" is the residual stream's width; attention is 64 x 128 = 8192
    # wide on it, which the family states in heads and head_dim alone
    assert (s["layers"], s["width"], s["hidden"], s["heads"], s["kv_heads"],
            s["head_dim"], s["mlp"], s["vocab"], s["positions"]) == \
        (5, 6144, 6144, 64, 8, 128, 18432, 19200, 4096)
    assert s["heads"] * s["head_dim"] == 8192 != s["hidden"]
    assert "v_head_dim" not in s and "cache_row_dim" not in s
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    assert model.config.held == (0, 16) and model.config.num_experts == 128
    # a slot: the global layer's rows at max_len, four rings of the window
    row = 2 * 8 * 128 * 2
    assert row * (4096 + 4 * 128) * 32 == 603_979_776


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 32,
                             "max_len": 4096,
                             "buckets": [256, 512, 1024, 2048, 4096],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    assert arr["prompt"]["values"] == [96, 160, 224, 320, 448, 640, 896,
                                       1280, 1920, 3584]
    assert arr["max_total"] == 4096 and arr.get("burst_size", 1) == 1
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert {"kernel.moe_experts_roofline", "kernel.moe_experts_share",
            "moe.expert_live_share", "cache.window_live_share",
            "kernel.decode_attn_share", "kernel.decode_attn_live_share",
            "device.idle_share.serve", "entry.compiles_in_window.serve",
            "step.decode_ms", "sched.batch_fill"} <= layer
    # its pattern finds every in-place kernel and its work one kind of layer
    assert "kernel.decode_attn_roofline" not in layer
    for name in ("kernel.moe_experts_roofline", "kernel.moe_experts_share",
                 "moe.expert_live_share", "cache.window_live_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        # listed; which other family's cell shares the metric is theirs
        assert CELL in m["workloads"] and m["moves"] == spec["moves"]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"])


def test_the_schedule_replays_long_prompts(bench):
    """At least two of the first 16 requests, the ones the check replays,
    carry a prompt past 1,280 tokens (ten windows), whatever the seed: a
    sliding layer that attends past its window, or a global layer that
    rotates, then fails ``correct``."""
    from benchmarks import traffic_gen

    arr = harness.load_cell(CELL, bench)["traffic_file"]["arrivals"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=19200)
        first = [len(p.prompt) for p in planned[:16]]
        assert sum(n > 1280 for n in first) >= 2, first
        assert max(max(p.prompt) for p in planned[:16]) < 19200
        assert all(len(p.prompt) + p.max_new_tokens <= 4096 for p in planned)
    share = sum(len(p.prompt) > 128 for p in planned) / len(planned)
    assert share > 0.8          # nine in ten past the window


def test_moe_experts_work_against_a_hand_worked_window():
    """Window [10, 11): two decode steps start in it and one before; the run
    counted 300 steps, 7.5 touched experts and 12 held pairs a step, an eighth
    of all pairs; one request of 1,000 prompt tokens was admitted in it."""
    s = FAMILY.shapes(CFG)
    obs = {"trace_span": [10.0, 11.0], "shapes": s,
           "counters": {"serving/decode_steps": 300,
                        "serving/moe_experts_touched": 2250,
                        "serving/moe_assignments_held": 3600,
                        "serving/moe_assignments": 28800},
           "spans": [{"name": "decode_step", "start": 9.99, "end": 10.01},
                     {"name": "decode_step", "start": 10.2, "end": 10.21},
                     {"name": "decode_step", "start": 10.9, "end": 11.1},
                     {"name": "iteration", "start": 10.2, "end": 10.3}],
           "requests": [{"prompt_len": 1000, "admitted": 10.5},
                        {"prompt_len": 400, "admitted": 9.0}]}
    n_flops, n_bytes = harness.module("work", "moe_experts").work(obs)
    expert = 3 * 6144 * 2048
    pairs = 2 * 12 + 1000 * 8 * 0.125 * 4
    experts_read = 2 * 7.5 + 4 * 16
    assert n_flops == pytest.approx(2 * expert * pairs)
    assert n_bytes == pytest.approx(2 * expert * experts_read)
    # a program without the counters: no decode work, the prefill's held
    # share by the shapes
    f0, b0 = harness.module("work", "moe_experts").work(dict(obs, counters={}))
    assert f0 == pytest.approx(2 * expert * 4000) and \
        b0 == pytest.approx(2 * expert * 64)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    def read(name, obs):
        spec = harness.load_json("layer_metrics", name + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    counters = {"serving/moe_experts_touched": 30,
                "serving/moe_experts_streamed": 64,
                "serving/decode_rows_live_window": 300,
                "serving/decode_rows_fetched_window": 384}
    assert read("moe.expert_live_share", {"counters": counters}) == \
        pytest.approx(46.875)
    assert read("cache.window_live_share", {"counters": counters}) == \
        pytest.approx(78.125)
    # a program without the counters (the parent commit): nothing to read
    for name in ("moe.expert_live_share", "cache.window_live_share"):
        assert read(name, {"counters": {}}) is None
    s = FAMILY.shapes(CFG)
    experts = ("%ragged-dot-none.3 = bf16[256,2048]{1,0} custom-call(%a, %b)")
    meta = "%ragged-dot-metadata.1 = (s32[65]) custom-call(%gs)"
    other = "%fusion.7 = bf16[32,6144] fusion(%ragged-dot-none.3)"
    tr = trace_reduce.Trace(
        {0: [(experts, 0.0, 0.002), (meta, 0.002, 0.003),
             (other, 0.003, 0.008)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0),
        # the roofline reads the decode program's events alone
        {0: [("jit_decode(4070338962791433473)", 0.0, 0.008)]})
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0], "requests": [],
           "spans": [{"name": "decode_step", "start": 0.5, "end": 0.6}],
           "counters": {"serving/decode_steps": 10,
                        "serving/moe_experts_touched": 80,
                        "serving/moe_assignments_held": 120,
                        "serving/moe_assignments": 960}}
    assert read("kernel.moe_experts_share", obs) == pytest.approx(25.0)
    # one step, 8 experts touched: 604 MB at 819 GB/s of the kernel's 2 ms
    assert read("kernel.moe_experts_roofline", obs) == pytest.approx(
        100 * 8 * 2 * 3 * 6144 * 2048 / 819e9 / 0.002)
    bare = dict(obs, trace=trace_reduce.Trace(
        {0: [(other, 0.0, 0.004), (meta, 0.004, 0.005)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    assert read("kernel.moe_experts_roofline", bare) is None
    assert read("kernel.moe_experts_share", bare) is None


@pytest.fixture(scope="module")
def rehearsed():
    """The serving kind's runner end to end at the family's tiny sizes,
    traced, under the cell's own mix: the cell and what the run returned."""
    cell = harness.load_cell(CELL, BENCH)
    out = harness.module("kinds", "serve_open_loop").run(
        cell, seed=2**31 + 11, seconds=0.6, trace=True,
        clock0=time.perf_counter(), rehearse=True)
    return cell, out


def test_rehearsal_in_process_at_tiny_size(rehearsed):
    _, out = rehearsed
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    counters = out["observations"]["counters"]
    assert counters["compiles_in_window"] == 0
    shapes = out["observations"]["shapes"]
    assert (shapes["experts"], shapes["experts_held"],
            shapes["sliding_layers"], shapes["global_layers"]) == (16, 2, 3, 1)
    assert 0 < counters["serving/moe_experts_streamed"] <= \
        3 * 2 * counters["serving/decode_steps"]
    assert 0 < counters["serving/moe_assignments_held"] < \
        counters["serving/moe_assignments"]


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    # no device plane on this backend, and a window of 8 is no ring the
    # fused step walks: the trace readers and the window's ratio leave
    # theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    assert "cache.window_live_share" not in line["metrics"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
