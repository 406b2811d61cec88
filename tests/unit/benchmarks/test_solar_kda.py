"""The sixth family, ``solar_kda``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, the work of its one-token delta-rule update against a hand-worked
window, its metric files through their readers, and a tiny in-process
rehearsal of its cell (``rehearse=True``: no device guard, never a result).
What it reads of ``BENCHMARK.json`` it reads through the ``bench`` fixture, as
accepted and with a cell appended (appended.py), and it speaks of its own cell
only: that the cell is listed, never that it is last or alone.

One module (tests/conftest.py runs every module in a child process); it starts
no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "solar-open2-250b.serve-agent-contexts"
CFG = harness.load_json("configs", "solar-open2-250b.json")
FAMILY = harness.module("families", "solar_kda")
# the published config.json (catalog row Solar-Open2-250B), key for key
PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "num_experts_per_tok": 8}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 4, "gqa_layers": [0], "n_routed_experts": 40,
       "vocab_size": 24576, "max_position_embeddings": 16384}


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "linear_attn_config", "num_experts_per_tok",
              "num_attention_heads", "num_key_value_heads"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"]) == (320, 196608, 0)
    for needle in ("48 -> 4", "320 -> 40", "196608 -> 24576",
                   "1048576 -> 16384", "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"]
                 if c["name"] == "solar-open2-250b")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/solar-open2-250b.json"
    assert CFG["source"] == ("https://huggingface.co/upstage/"
                             "Solar-Open2-250B/blob/main/config.json")
    assert set(CFG["assumed"]) >= {
        "kda_equations", "kda_use_full_proj", "linear_attn_num_kv_heads",
        "gqa_gate", "scoring_func", "n_group", "topk_group", "router_why",
        "param_count", "state_dtype", "initial_values", "weights_dtype",
        "weights_seed", "weights_seed_why", "published_code"}
    assert "eight chips share each layer by experts" in CFG["deployment"]
    assert "pipeline stages" in CFG["deployment"]


@pytest.mark.parametrize("stated", [True, False])
def test_the_cell_serves_one_checkpoint_whatever_the_seed(stated):
    import jax

    cfg = FAMILY.tiny(CFG)
    if not stated:
        del cfg["assumed"]["weights_seed"]
    model = FAMILY.build_model(cfg, {})
    one, other = (model.init(jax.random.PRNGKey(k)) for k in (1, 2))
    same = all(bool((a == b).all()) for a, b in zip(
        jax.tree_util.tree_leaves(one), jax.tree_util.tree_leaves(other)))
    assert same == stated


def test_shapes_against_the_hand_count():
    """ISSUE 48's arithmetic: a delta-rule layer 154,780,160 outside its
    experts, the softmax layer 126,099,776, 40 experts 629,145,600 a layer,
    embedding and head 201,326,592: 6.62 GB in bf16; the whole model by the
    same formulas 250.3 B with 14.7 B active."""
    s = FAMILY.shapes(CFG)
    expert = 3 * 4096 * 1280
    common = 2 * 4096 + 4096 * 320 + 320 + expert
    kda = (3 * 4096 * 8192 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192)
           + 4096 * 64 + 3 * 8192 * 4 + 64 + 8192 + 128 + common)
    gqa = 4096 * 8192 * 3 + 2 * 4096 * 1024 + common
    assert (kda, gqa, expert * 40) == (154_780_160, 126_099_776, 629_145_600)
    want = 2 * 24576 * 4096 + 4096 + gqa + 3 * kda + 4 * 40 * expert
    assert s["params"] == want == 3_308_353_344
    assert 6.61e9 < 2 * s["params"] < 6.62e9
    # a token passes through one routed expert here on average: 8 x 40 / 320
    assert s["active_params"] == want - 4 * expert * 39
    whole = FAMILY.shapes(dict(CFG, num_hidden_layers=48, vocab_size=196608,
                               gqa_layers=PUBLISHED["gqa_layers"],
                               n_routed_experts=320))
    assert (whole["params"], whole["active_params"]) == \
        (250_287_810_304, 14_735_697_664)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["expert_mlp"], s["sparse_layers"]) == (320, 40, 8, 1280, 4)
    # rows on ONE layer of four: 2 x 8 x 128 elements a token there
    assert (s["layers"], s["width"], s["hidden"], s["heads"], s["kv_heads"],
            s["head_dim"], s["cache_row_dim"], s["vocab"], s["positions"]) == \
        (4, 4096, 4096, 64, 8, 128, 512, 24576, 16384)
    assert s["layers"] * s["cache_row_dim"] * 2 == 4096    # bytes a token
    assert (s["attn_layers"], s["kda_layers"], s["kda_heads"],
            s["kda_key_dim"], s["kda_value_dim"], s["kda_state_bytes"]) == \
        (1, 3, 64, 128, 128, 4)
    # a slot: 12,582,912 bytes of state, 442,368 of tails, 67,108,864 of rows
    assert s["state_bytes_per_slot"] == 12_582_912 + 442_368 + 67_108_864 \
        == 80_134_144
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    c = model.config
    assert c.held == (0, 40) and c.num_experts == 320
    assert c.runs() == (("gqa", 0, 1), ("kda", 0, 3))
    assert (c.prompt_block, c.key_block, c.kda_chunk) == (2048, 512, 64)
    from deepspeed_tpu.ops import kda as ops_kda

    assert ops_kda.supports(c.kda_heads, c.kda_head_dim, c.kda_head_dim,
                            c.kda_conv)


def test_the_family_refuses_what_the_program_does_not_compute():
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("tie_word_embeddings", True),
                       ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match=key):
            FAMILY.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError, match="num_kv_heads"):
        FAMILY.build_model(dict(CFG, linear_attn_config=dict(
            CFG["linear_attn_config"], num_kv_heads=8)), {})
    with pytest.raises(ValueError, match="scoring_func"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], scoring_func="softmax")), {})
    with pytest.raises(ValueError, match="state_dtype"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], state_dtype="bfloat16")), {})
    with pytest.raises(ValueError, match="rematerialisation"):
        FAMILY.build_model(CFG, {"remat": True})


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == ("solar-open2-250b",
                                                 "serve-agent-contexts")
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 16,
                             "max_len": 16384,
                             "buckets": [2048, 4096, 8192, 16384],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    assert arr["prompt"]["values"] == [1792, 2560, 3584, 4608, 6144, 7680,
                                       9216, 11264, 13312, 15872]
    assert sum(arr["prompt"]["values"]) / 10 == 7603.2
    assert arr["output"] == {"dist": "lognormal", "median": 128,
                             "sigma": 0.6, "min": 16, "max": 384}
    assert arr["max_total"] == 16384 and arr.get("burst_size", 1) == 1
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert {"kernel.kda_update_roofline", "kernel.kda_update_share",
            "kernel.moe_experts_roofline", "kernel.moe_experts_share",
            "moe.expert_live_share", "kernel.decode_attn_share",
            "kernel.decode_attn_live_share", "device.idle_share.serve",
            "entry.compiles_in_window.serve", "entry.traces_after_warm",
            "step.decode_ms", "step.prefill_pad_share",
            "sched.batch_fill"} <= layer
    # a roofline whose pattern finds every in-place call, other families'
    # kernels, and a ring this model has not
    assert not {"kernel.decode_attn_roofline", "kernel.ssm_update_roofline",
                "kernel.ssm_update_share", "kernel.mla_decode_roofline",
                "kernel.mla_prefill_share", "cache.window_live_share"} & layer
    for name in ("kernel.kda_update_roofline", "kernel.kda_update_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == spec["moves"] == \
            "itl_p95_ms"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"])
        assert spec["params"]["pattern"] == r"^%[\w.\-]*dstpu_kda_update"


def test_the_schedule_replays_long_prompts(bench):
    """Of the first 16 requests, the ones the check replays, several carry a
    prompt past one token block and past 8k, whatever the seed; everything
    fits a slot."""
    from benchmarks import traffic_gen

    arr = harness.load_cell(CELL, bench)["traffic_file"]["arrivals"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=24576)
        first = [len(p.prompt) for p in planned[:16]]
        assert sum(n > 2048 for n in first) >= 8, first
        assert sum(n > 8192 for n in first) >= 3, first
        assert max(max(p.prompt) for p in planned[:16]) < 24576
        assert all(len(p.prompt) + p.max_new_tokens <= 16384
                   for p in planned)
        assert all(16 <= p.max_new_tokens <= 384 for p in planned)
    assert len(planned) == max(1, round(arr["rate"] * 51))


def test_kda_update_work_against_a_hand_worked_window():
    """Window [10, 11): a request commits its decode tokens 1 and 2 in it (the
    first token is the prefill's), another one token; a token outside the
    window adds nothing. A slot-step is 3 layers x 64 heads x 128 x 128
    elements, read and written once in float32, 7 FLOPs each."""
    s = FAMILY.shapes(CFG)
    obs = {"trace_span": [10.0, 11.0], "shapes": s,
           "requests": [
               {"prompt_len": 1000, "token_times": [9.9, 10.1, 10.2, 11.5]},
               {"prompt_len": 5000, "token_times": [9.0, 9.1, 9.2, 10.5]}]}
    n_flops, n_bytes = harness.module("work", "kda_update").work(obs)
    elements = 3 * 3 * 64 * 128 * 128
    assert n_bytes == elements * 8 == 3 * 25_165_824
    assert n_flops == elements * 7
    # 0.875 FLOPs a byte: the bytes set the least time
    assert n_flops / n_bytes == pytest.approx(0.875)
    assert harness.module("work", "kda_update").work(
        dict(obs, requests=[])) == (0.0, 0.0)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    def read(name, obs):
        spec = harness.load_json("layer_metrics", name + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    s = FAMILY.shapes(CFG)
    step = ("%dstpu_kda_update.3 = (bf16[16,64,128]{2,1,0}, "
            "f32[3,16,64,128,128]{4,3,2,1,0}) custom-call(%a, %b)")
    reader = "%fusion.7 = bf16[16,1,8192] fusion(%dstpu_kda_update.3)"
    other = "%dstpu_decode_step.1 = bf16[16,64,128] custom-call(%q)"
    tr = trace_reduce.Trace(
        {0: [(step, 0.0, 0.001), (reader, 0.001, 0.002),
             (other, 0.002, 0.004)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0))
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0], "counters": {}, "spans": [],
           "requests": [{"prompt_len": 8000,
                         "token_times": [0.1, 0.2, 0.3]}]}
    assert read("kernel.kda_update_share", obs) == pytest.approx(25.0)
    # two slot-steps: 50.3 MB at 819 GB/s of 1 ms
    assert read("kernel.kda_update_roofline", obs) == pytest.approx(
        100 * 2 * 25_165_824 / 819e9 / 0.001)
    # a program without the kernel (the parent commit): nothing to read
    bare = dict(obs, trace=trace_reduce.Trace(
        {0: [(reader, 0.0, 0.004), (other, 0.004, 0.005)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    assert read("kernel.kda_update_roofline", bare) is None
    assert read("kernel.kda_update_share", bare) is None


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
    assert (shapes["experts"], shapes["experts_held"], shapes["kv_heads"],
            shapes["kda_layers"], shapes["kda_key_dim"]) == (16, 2, 2, 3, 16)
    assert 0 < counters["serving/moe_experts_streamed"] <= \
        4 * 2 * counters["serving/decode_steps"]
    assert 0 < counters["serving/moe_assignments_held"] < \
        counters["serving/moe_assignments"]
    assert counters["serving/prefill_rows_run"] > \
        counters["serving/prefill_rows_padding"] > 0


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    # no device plane on this backend, and 64 rows are no whole chunk the
    # fused step walks: the trace readers and the walk's ratio leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    assert "kernel.decode_attn_live_share" not in line["metrics"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
