"""The fifth family, ``sarvam_mla``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, the work of its absorbed decode step against a hand-worked window, its
metric files through their readers, and a tiny in-process rehearsal of its
cell (``rehearse=True``: no device guard, never a result). What it reads of
``BENCHMARK.json`` it reads through the ``bench`` fixture, as accepted and with
a cell appended (appended.py), and it speaks of its own cell only: that the
cell is listed, never that it is last or alone.

One module (tests/conftest.py runs every module in a child process); it starts
no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "sarvam-105b.serve-long-documents"
CFG = harness.load_json("configs", "sarvam-105b.json")
FAMILY = harness.module("families", "sarvam_mla")
# the published config.json (catalog row sarvam-105b), key for key
PUBLISHED = {
    "attn_implementation": None, "default_theta": 10000,
    "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "sarvam_mla",
    "moe_intermediate_size": 2048, "moe_router_enable_expert_bias": True,
    "num_attention_heads": 64, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "deepseek_yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "tie_word_embeddings": False, "use_qk_norm": True, "v_head_dim": 128,
    "vocab_size": 262144}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 32768,
       "max_position_embeddings": 16384}


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "q_head_dim", "qk_nope_head_dim", "qk_rope_head_dim",
              "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
              "num_attention_heads"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["num_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"]) == (128, 262144, 0)
    for needle in ("32 -> 5", "128 -> 16", "262144 -> 32768",
                   "131072 -> 16384", "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"] if c["name"] == "sarvam-105b")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/sarvam-105b.json"
    assert CFG["source"] == \
        "https://huggingface.co/sarvamai/sarvam-105b/blob/main/config.json"
    assert set(CFG["assumed"]) >= {
        "scoring_func", "norm_topk_prob", "n_group", "topk_group",
        "selection_bias", "qk_norm", "rotation", "attention_bias",
        "initial_values", "weights_dtype", "weights_seed",
        "weights_seed_why", "published_code"}
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
    """ISSUE 46's arithmetic: attention 94,634,496 a layer, the dense layer
    295,969,280, a sparse layer 522,986,112 of which 402,653,184 are its 16
    experts, embedding and head 268,435,456: 5.31 GB in bf16."""
    s = FAMILY.shapes(CFG)
    attn = (4096 * 12288 + 4096 * 576 + 512 + 512 * 16384 + 8192 * 4096)
    expert = 3 * 4096 * 2048
    assert (attn, expert) == (94_634_496, 25_165_824)
    dense = attn + 2 * 4096 + 3 * 4096 * 16384
    sparse = attn + 2 * 4096 + 4096 * 128 + 128 + expert * (1 + 16)
    assert (dense, sparse) == (295_969_280, 522_986_112)
    want = 2 * 32768 * 4096 + 4096 + dense + 4 * sparse
    assert s["params"] == want == 2_656_353_280
    assert 5.31e9 < 2 * s["params"] < 5.32e9
    # a token passes through one routed expert here on average: 8 x 16 / 128
    assert s["active_params"] == want - 4 * expert * 15
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["expert_mlp"], s["sparse_layers"], s["dense_layers"]) == \
        (128, 16, 8, 2048, 4, 1)
    # the cached row serves all heads: one key-value head, 576 a row, and
    # the decompressed sizes a prompt's attention runs at
    assert (s["layers"], s["width"], s["hidden"], s["heads"], s["kv_heads"],
            s["head_dim"], s["v_head_dim"], s["cache_row_dim"], s["latent"],
            s["rope_dim"], s["mlp"], s["vocab"], s["positions"]) == \
        (5, 4096, 4096, 64, 1, 192, 128, 576, 512, 64, 16384, 32768, 16384)
    assert s["heads"] % s["kv_heads"] == 0
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    assert model.config.held == (0, 16) and model.config.num_experts == 128
    assert (model.config.row_width, model.config.prompt_block) == (640, 2048)
    # a slot: 16,384 rows of 576 live lanes (640 kept) over five layers
    assert 5 * 16384 * 576 * 2 * 16 == 1_509_949_440
    assert 5 * 16384 * 640 * 2 * 16 == 1_677_721_600


def test_the_family_refuses_what_the_program_does_not_compute():
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("use_qk_norm", False)):
        with pytest.raises(ValueError, match=key):
            FAMILY.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError, match="deepseek_yarn"):
        FAMILY.build_model(dict(CFG, rope_scaling=dict(
            CFG["rope_scaling"], type="linear")), {})
    with pytest.raises(ValueError, match="scoring_func"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], scoring_func="softmax")), {})
    with pytest.raises(ValueError, match="head_dim"):
        FAMILY.shapes(dict(CFG, head_dim=192))
    with pytest.raises(ValueError, match="rematerialisation"):
        FAMILY.build_model(CFG, {"remat": True})


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == ("sarvam-105b",
                                                 "serve-long-documents")
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 16,
                             "max_len": 16384,
                             "buckets": [2048, 4096, 8192, 16384],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    assert arr["prompt"]["values"] == [1536, 2048, 3072, 3584, 5120, 6144,
                                       7680, 10240, 12288, 15872]
    assert sum(arr["prompt"]["values"]) / 10 == 6758.4
    assert arr["output"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.5, "min": 64, "max": 512}
    assert arr["max_total"] == 16384 and arr.get("burst_size", 1) == 1
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert {"kernel.mla_decode_roofline", "kernel.mla_decode_share",
            "kernel.moe_experts_roofline", "kernel.moe_experts_share",
            "moe.expert_live_share", "kernel.decode_attn_live_share",
            "device.idle_share.serve", "entry.compiles_in_window.serve",
            "entry.traces_after_warm", "step.decode_ms",
            "step.prefill_pad_share", "sched.batch_fill"} <= layer
    # another kernel's roofline and share, and a ring this model has not
    assert not {"kernel.decode_attn_roofline", "kernel.decode_attn_share",
                "cache.window_live_share"} & layer
    for name in ("kernel.mla_decode_roofline", "kernel.mla_decode_share"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == spec["moves"] == \
            "itl_p95_ms"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"])
        assert spec["params"]["pattern"] == \
            r"^%[\w.\-]*dstpu_mla_decode_step"


def test_the_schedule_replays_long_prompts(bench):
    """Of the first 16 requests, the ones the check replays, several carry a
    prompt past the rotation's 4,096 trained positions and past one token
    block, whatever the seed; everything fits a slot."""
    from benchmarks import traffic_gen

    arr = harness.load_cell(CELL, bench)["traffic_file"]["arrivals"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=32768)
        first = [len(p.prompt) for p in planned[:16]]
        assert sum(n > 4096 for n in first) >= 4, first
        assert max(max(p.prompt) for p in planned[:16]) < 32768
        assert all(len(p.prompt) + p.max_new_tokens <= 16384
                   for p in planned)
        assert all(64 <= p.max_new_tokens <= 512 for p in planned)
    assert len(planned) == max(1, round(arr["rate"] * 51))


def test_mla_decode_work_against_a_hand_worked_window():
    """Window [10, 11): a request of 1,000 prompt tokens commits its decode
    tokens 1 and 2 in it (contexts 1,001 and 1,002; the first token is the
    prefill's), another one token at context 5,003; a token outside the
    window adds nothing."""
    s = FAMILY.shapes(CFG)
    obs = {"trace_span": [10.0, 11.0], "shapes": s,
           "requests": [
               {"prompt_len": 1000, "token_times": [9.9, 10.1, 10.2, 11.5]},
               {"prompt_len": 5000, "token_times": [9.0, 9.1, 9.2, 10.5]}]}
    n_flops, n_bytes = harness.module("work", "mla_decode").work(obs)
    rows = (1001 + 1002 + 5003) * 5
    assert n_bytes == rows * 576 * 2
    assert n_flops == rows * 64 * 2 * (576 + 512)
    # 121 FLOPs a byte: under the v5e's 240, so the bytes set the least time
    assert n_flops / n_bytes == pytest.approx(64 * (576 + 512) / 576)
    assert harness.module("work", "mla_decode").work(
        dict(obs, requests=[])) == (0.0, 0.0)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    def read(name, obs):
        spec = harness.load_json("layer_metrics", name + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    s = FAMILY.shapes(CFG)
    step = ("%dstpu_mla_decode_step.3 = (bf16[16,64,512]{2,1,0}, "
            "bf16[5,16,16384,640]{3,2,1,0}) custom-call(%a, %b)")
    reader = "%fusion.7 = bf16[16,64,128] fusion(%dstpu_mla_decode_step.3)"
    other = "%dstpu_decode_step.1 = bf16[32,64,128] custom-call(%q)"
    tr = trace_reduce.Trace(
        {0: [(step, 0.0, 0.001), (reader, 0.001, 0.002),
             (other, 0.002, 0.004)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0))
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0], "counters": {}, "spans": [],
           "requests": [{"prompt_len": 8000,
                         "token_times": [0.1, 0.2, 0.3]}]}
    assert read("kernel.mla_decode_share", obs) == pytest.approx(25.0)
    # two tokens at contexts 8,001 and 8,002: 92 MB at 819 GB/s of 1 ms
    rows = (8001 + 8002) * 5
    assert read("kernel.mla_decode_roofline", obs) == pytest.approx(
        100 * rows * 576 * 2 / 819e9 / 0.001)
    # a program without the kernel (the parent commit): nothing to read
    bare = dict(obs, trace=trace_reduce.Trace(
        {0: [(reader, 0.0, 0.004), (other, 0.004, 0.005)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    assert read("kernel.mla_decode_roofline", bare) is None
    assert read("kernel.mla_decode_share", bare) is None


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
            shapes["cache_row_dim"], shapes["latent"]) == (16, 2, 1, 40, 32)
    assert 0 < counters["serving/moe_experts_streamed"] <= \
        2 * 2 * counters["serving/decode_steps"]
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
