"""The third family, ``granite_hybrid``, in the benchmark: its configuration
file against the published keys, its sizes against the hand count, the work of
its kernel against a hand-worked window, its metric files through their
readers, and tiny in-process rehearsals of both kinds (``rehearse=True``: no
device guard, never a result). What it reads of ``BENCHMARK.json`` it reads
through the ``bench`` fixture, as accepted and with a cell appended
(appended.py), and it speaks of its own cell only.

One module (tests/conftest.py runs every module in a child process); it starts
no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "granite-4.0-h-micro.serve-chat-bursty"
CFG = harness.load_json("configs", "granite-4.0-h-micro.json")
FAMILY = harness.module("families", "granite_hybrid")
# the published config.json, key for key
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert CFG[key] == value, key
    kinds = CFG["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    # the one key changed, with its reason in the file
    assert CFG["reduced"] == ["max_position_embeddings"]
    assert CFG["max_position_embeddings"] == 2048
    assert "131072" in CFG["reduced_why"] and "nope" in CFG["reduced_why"]
    entry = next(c for c in bench["configs"]
                 if c["name"] == "granite-4.0-h-micro")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert set(CFG["assumed"]) >= {"state_dtype", "time_step_limit",
                                   "initial_values", "weights_dtype"}
    assert "one v5e chip" in CFG["deployment"]


def test_shapes_against_the_hand_count():
    s = FAMILY.shapes(CFG)
    mamba = (2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * 2048
             + 3 * 2048 * 8192 + 2 * 2048)
    attn = (2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192 + 2 * 2048)
    assert mamba == 76_182_976 and attn == 60_821_504
    assert s["params"] == 36 * mamba + 4 * attn + 100352 * 2048 + 2048 \
        == 3_191_396_096 == s["active_params"]
    assert (s["layers"], s["mamba_layers"], s["attn_layers"]) == (40, 36, 4)
    assert (s["hidden"], s["heads"], s["kv_heads"], s["head_dim"], s["mlp"],
            s["vocab"], s["positions"]) == (2048, 32, 8, 64, 8192, 100352,
                                            2048)
    assert (s["ssm_heads"], s["ssm_head_dim"], s["ssm_state"],
            s["conv_width"], s["ssm_state_bytes"]) == (64, 64, 128, 4352, 4)
    # a slot: 36 x (64 x 64 x 128 float32 + a 3 x 4352 bf16 tail)
    assert s["state_bytes_per_slot"] == 36 * (2_097_152 + 26_112) \
        == 76_437_504


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-chat-bursty"
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert {"kernel.ssm_update_roofline", "kernel.ssm_update_share",
            "step.prefill_pad_share", "device.idle_share.serve",
            "sched.batch_fill", "step.decode_ms"} <= layer
    # its pattern would find every Pallas call that updates an operand in
    # place, and its work counts attention rows only
    assert "kernel.decode_attn_roofline" not in layer
    mix = harness.load_json("traffic", "serve-chat-bursty.json")
    a, s = mix["arrivals"], mix["server"]
    assert a["burst_size"] == 8 and a["max_total"] == 2048
    assert a["prompt"] == {"dist": "lognormal", "median": 192, "sigma": 0.7,
                           "min": 16, "max": 1024}
    assert a["output"] == {"dist": "lognormal", "median": 128, "sigma": 0.6,
                           "min": 8, "max": 512}
    assert s == {"dtype": "bf16", "num_slots": 64, "max_len": 2048,
                 "buckets": [128, 256, 512, 1024], "trace_seconds": 3.0}


def test_ssm_update_work_against_a_hand_worked_window():
    """Two requests, window [10, 11): the first has decode tokens at 10.2 and
    10.6 inside it (its first token, from the prefill, is not a decode step),
    the second one at 10.9 and one outside. Three slot-steps."""
    s = FAMILY.shapes(CFG)
    obs = {"trace_span": [10.0, 11.0], "shapes": s, "requests": [
        {"prompt_len": 5, "token_times": [10.1, 10.2, 10.6, 11.2]},
        {"prompt_len": 9, "token_times": [9.5, 9.9, 10.9]}]}
    n_flops, n_bytes = harness.module("work", "ssm_update").work(obs)
    elements = 3 * 36 * 64 * 64 * 128
    assert n_bytes == 2 * 4 * elements == 3 * 2 * 75_497_472
    assert n_flops == 5 * elements
    # memory bound: 453 MB at 819 GB/s is 0.553 ms, the FLOPs 1.4 us
    from benchmarks import flops
    least, bound = flops.roofline_seconds(
        n_flops, n_bytes, {"bf16_tflops": 197.0, "hbm_gbps": 819.0})
    assert bound == "memory" and least == pytest.approx(5.531e-4, rel=1e-3)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    spec = harness.load_json("layer_metrics", "step.prefill_pad_share.json")
    read = harness.module("readers", spec["reader"]).read
    counters = {"serving/prefill_rows_run": 640,
                "serving/prefill_rows_padding": 160}
    assert read(spec["params"], {"counters": counters}) == 25.0
    # a program without the counters (the parent commit): nothing to read
    assert read(spec["params"], {"counters": {}}) is None
    s = FAMILY.shapes(CFG)
    update = ("%dstpu_ssm_update.3 = (f32[64,64,64], f32[36,64,64,64,128]) "
              "custom-call(...)")
    other = "%fusion.7 = bf16[64,2048] fusion(%dstpu_ssm_update.3)"
    tr = trace_reduce.Trace({0: [(update, 0.0, 0.002), (other, 0.002, 0.008)]},
                            [("bench/window", 0.0, 1.0)], (0.0, 1.0))
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0],
           "requests": [{"prompt_len": 4, "token_times": [0.1, 0.2, 0.3]}]}
    share = harness.load_json("layer_metrics", "kernel.ssm_update_share.json")
    assert harness.module("readers", share["reader"]).read(
        share["params"], obs) == pytest.approx(25.0)
    roof = harness.load_json("layer_metrics",
                             "kernel.ssm_update_roofline.json")
    value = harness.module("readers", roof["reader"]).read(roof["params"],
                                                           obs)
    # two slot-steps need 302 MB: 0.3687 ms of the kernel's 2 ms
    assert value == pytest.approx(100 * 2 * 2 * 75_497_472 / 819e9 / 0.002)
    # a trace without the kernel (the parent commit): nothing to read
    bare = dict(obs, trace=trace_reduce.Trace(
        {0: [(other, 0.0, 0.004)]}, [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    assert harness.module("readers", roof["reader"]).read(
        roof["params"], bare) is None


@pytest.fixture(scope="module")
def rehearsed():
    """kind -> (cell, what the kind's runner returned) at the family's tiny
    sizes, traced, run once a kind: the serving kind under the cell's own
    mix, the training kind under the mix of the first training cell."""
    done = {}

    def run(kind):
        if kind not in done:
            if kind == "serve_open_loop":
                cell = harness.load_cell(CELL, BENCH)
            else:
                name = next(w["name"] for w in BENCH["workloads"]
                            if harness.load_cell(w["name"], BENCH)
                            ["traffic_file"]["kind"] == kind)
                cell = dict(harness.load_cell(name, BENCH), config_file=CFG)
            done[kind] = cell, harness.module("kinds", kind).run(
                cell, seed=2**31 + 11, seconds=0.6, trace=True,
                clock0=time.perf_counter(), rehearse=True)
        return done[kind]
    return run


@pytest.mark.parametrize("kind", ["serve_open_loop", "train_job"])
def test_rehearsal_in_process_at_tiny_size(kind, rehearsed):
    _, out = rehearsed(kind)
    assert out["device"]["platform"] == "cpu"
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 0
    assert out["observations"]["counters"]["compiles_in_window"] == 0
    assert out["observations"]["shapes"]["mamba_layers"] == 3
    if kind == "serve_open_loop":
        counters = out["observations"]["counters"]
        assert counters["serving/prefill_rows_run"] > \
            counters["serving/prefill_rows_padding"] > 0
    else:
        assert out["notes"]["logit_max_abs_err"] < 1e-2


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of the serving run, whatever else ``BENCHMARK.json``
    lists beside this cell."""
    cell, out = rehearsed("serve_open_loop")
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert "step.prefill_pad_share" in line["metrics"]
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
