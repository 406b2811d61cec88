"""The seventh family, ``longcat_flash``, in the benchmark: its configuration
file against the published keys and its stated cut, its sizes against the hand
count, the program against the plain reference at tiny size in float32 (full
forward, prefill then decode through the slot cache), its metric file through
its reader, and a tiny in-process rehearsal of its cell (``rehearse=True``: no
device guard, never a result). What it reads of ``BENCHMARK.json`` it reads
through the ``bench`` fixture, as accepted and with a cell appended
(appended.py), and it speaks of its own cell only: that the cell is listed,
never that it is last or alone.

It starts no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "longcat-flash-chat.serve-chat-decode-heavy"
CFG = harness.load_json("configs", "longcat-flash-chat.json")
FAMILY = harness.module("families", "longcat_flash")
REFERENCE = harness.module("reference", "longcat_flash")
# the published config.json (catalog row LongCat-Flash-Chat), key for key
PUBLISHED = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000, "attention_method": "MLA",
    "zero_expert_num": 256, "zero_expert_type": "identity", "moe_topk": 12}
# what the configuration changes, and to what
CUT = {"num_layers": 4, "n_routed_experts": 16, "vocab_size": 16384,
       "max_position_embeddings": 4096}
TOL = dict(rtol=1e-4, atol=1e-5)


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "moe_topk",
              "num_attention_heads", "zero_expert_num"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["num_layers_published"], CFG["experts_held_first"]) == \
        (512, 131072, 28, 0)
    for needle in ("28 -> 4", "512 -> 16", "131072 -> 16384",
                   "131072 -> 4096", "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"]
                 if c["name"] == "longcat-flash-chat")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/longcat-flash-chat.json"
    assert CFG["source"] == ("https://huggingface.co/meituan-longcat/"
                             "LongCat-Flash-Chat/blob/main/config.json")
    assert set(CFG["assumed"]) >= {
        "layer_order", "scoring_func", "norm_topk_prob", "selection_bias",
        "zero_experts", "mla_scales", "rotation", "attention_bias",
        "initial_values", "weights_dtype", "weights_seed",
        "weights_seed_why", "published_code"}
    assert CFG["assumed"]["weights_seed"] == 31337
    assert "32 chips share each layer by experts" in CFG["deployment"]
    assert "pipeline stages" in CFG["deployment"]


def test_shapes_against_the_hand_count():
    """ISSUE 52's arithmetic: a sublayer of attention 90,572,800, a dense FFN
    226,492,416, the router 4,719,360 with its bias, four norms 24,576:
    638,874,368 a double layer outside its experts; an expert 37,748,736;
    embedding, head and final norm 201,332,736: 5,172,749,312 held, 10.35 GB
    in bf16; the whole model by the same formulas 560.66 B."""
    s = FAMILY.shapes(CFG)
    attn = (6144 * 1536 + 1536 + 1536 * 12288 + 6144 * 576 + 512
            + 512 * 64 * 256 + 8192 * 6144)
    dense, expert = 3 * 6144 * 12288, 3 * 6144 * 2048
    assert (attn, dense, expert) == (90_572_800, 226_492_416, 37_748_736)
    outside = 2 * (attn + dense) + 6144 * 768 + 768 + 4 * 6144
    assert outside == 638_874_368
    top = 2 * 16384 * 6144 + 6144
    assert top == 201_332_736
    assert s["params"] == 4 * (outside + 16 * expert) + top == 5_172_749_312
    assert 10.34e9 < 2 * s["params"] < 10.35e9
    whole = 28 * (outside + 512 * expert) + 2 * 131072 * 6144 + 6144
    assert s["params_published"] == whole == 560_664_980_480
    # a token routes 12 x 16 / 768 = 0.25 pairs a layer here on average
    assert s["active_params"] == s["params"] - 4 * int(expert * 15.75)
    assert (s["experts"], s["real_experts"], s["zero_experts"],
            s["experts_held"], s["experts_per_token"], s["expert_mlp"],
            s["sparse_layers"], s["double_layers"]) == \
        (768, 512, 256, 16, 12, 2048, 4, 4)
    # ``layers`` counts ATTENTION layers (work/mla_*.py multiply by it); the
    # cached row serves all heads, at the decompressed sizes of sarvam-105b
    assert (s["layers"], s["width"], s["hidden"], s["heads"], s["kv_heads"],
            s["head_dim"], s["v_head_dim"], s["cache_row_dim"], s["latent"],
            s["rope_dim"], s["mlp"], s["vocab"], s["positions"]) == \
        (8, 6144, 6144, 64, 1, 192, 128, 576, 512, 64, 12288, 16384, 4096)
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    assert model.config.held == (0, 16) and model.config.num_experts == 768
    assert (model.config.row_width, model.config.prompt_block) == (640, 2048)
    # a cached token: 8 rows of 640 lanes, 10,240 bytes; 32 slots x 4,096
    assert 8 * 640 * 2 == 10_240 and 8 * 576 * 2 == 9_216
    assert 8 * 640 * 2 * 4096 * 32 == 1_342_177_280


def test_the_family_refuses_what_the_program_does_not_compute():
    for key, value in (("attention_bias", True),
                       ("zero_expert_type", "copy"),
                       ("rope_scaling", {"rope_type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            FAMILY.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError, match="scoring_func"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], scoring_func="sigmoid")), {})
    with pytest.raises(ValueError, match="rematerialisation"):
        FAMILY.build_model(CFG, {"remat": True})


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == ("longcat-flash-chat",
                                                 "serve-chat-decode-heavy")
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 32,
                             "max_len": 4096,
                             "buckets": [256, 512, 1024, 2048],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    assert arr["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 1.0, "min": 32, "max": 2048}
    assert arr["output"] == {"dist": "lognormal", "median": 448,
                             "sigma": 0.4, "min": 128, "max": 640}
    assert arr["max_total"] == 2688 and arr.get("burst_size", 1) == 1
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert {"kernel.mla_decode_roofline", "kernel.mla_decode_share",
            "kernel.moe_experts_roofline", "kernel.moe_experts_share",
            "moe.expert_live_share", "moe.zero_expert_share",
            "device.idle_share.serve", "entry.compiles_in_window.serve",
            "entry.traces_after_warm", "step.decode_ms", "step.prefill_ms",
            "sched.batch_fill"} <= layer
    # another kernel's roofline and share, and a ring this model has not
    assert not {"kernel.decode_attn_roofline", "kernel.decode_attn_share",
                "cache.window_live_share", "kernel.kda_update_share"} & layer
    m = next(m for m in bench["per_layer"]
             if m["name"] == "moe.zero_expert_share")
    spec = harness.load_json("layer_metrics", "moe.zero_expert_share.json")
    assert CELL in m["workloads"] and m["moves"] == spec["moves"] == \
        "itl_p95_ms"
    assert (m["unit"], m["better"], m["source"], m["layer"]) == \
        (spec["unit"], spec["better"], spec["source"], spec["layer"]) == \
        ("%", "higher", "program_counter", "expert layer")


def test_the_schedule_is_decode_heavy_and_fits_a_slot(bench):
    """Answers longer than questions, whatever the seed; everything fits a
    slot and the largest bucket; ids come from the held rows of the
    vocabulary."""
    from benchmarks import traffic_gen

    arr = harness.load_cell(CELL, bench)["traffic_file"]["arrivals"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=16384)
        prompts = sorted(len(p.prompt) for p in planned)
        outputs = sorted(p.max_new_tokens for p in planned)
        assert prompts[len(prompts) // 2] < outputs[len(outputs) // 2]
        assert 32 <= prompts[0] and prompts[-1] <= 2048
        assert 128 <= outputs[0] and outputs[-1] <= 640
        assert max(max(p.prompt) for p in planned[:16]) < 16384
        assert all(len(p.prompt) + p.max_new_tokens <= 2688
                   for p in planned)
    assert len(planned) == max(1, round(arr["rate"] * 51))


def test_the_zero_expert_share_through_its_reader():
    spec = harness.load_json("layer_metrics", "moe.zero_expert_share.json")
    read = harness.module("readers", spec["reader"]).read
    obs = {"counters": {"serving/moe_assignments": 4800,
                        "serving/moe_assignments_zero": 1620}}
    assert read(spec["params"], obs) == pytest.approx(33.75)
    # a program without the counter (the parent commit): nothing to read
    assert read(spec["params"],
                {"counters": {"serving/moe_assignments": 4800}}) is None
    assert read(spec["params"], {"counters": {}}) is None


@pytest.fixture(scope="module")
def built():
    """The tiny program in float32 and the reference's logits of 2 x 48 ids:
    two double layers, 2 of 16 real experts held, 8 identity experts, 4
    choices a token, token blocks of 16 and key blocks of 8."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = FAMILY.tiny(CFG)
    model = FAMILY.build_model(cfg, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 48)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = REFERENCE.forward_logits(params, ids, cfg)

    def step(params, ids, cache):
        with jax.default_matmul_precision("highest"):
            return model.forward_with_cache(params, ids, cache)

    return model, params, ids, ref, jax.jit(step)


def test_full_forward_matches_the_reference(built):
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params, ids, ref, _ = built
    with jax.default_matmul_precision("highest"):
        out = FAMILY.engine_logits(model, params, ids)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


def test_prefill_then_decode_through_the_slot_cache_matches_the_reference(
        built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    latent leaf of ``2 L`` rows, by ``SlotKVCache``'s own tree: bucketed
    prefills (one inside a token block, one of two whole blocks: the walk
    inside the program) written as prefixes into slots, two slots of unequal
    length decoding together with a third inactive. Logits, not tokens; the
    step's counters count all four choices of a token, identity experts
    included."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import write_slot_rows
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step = built
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == slots.row_keys == ("latent",)
    assert slots.recurrent_keys == () and not slots.fused_walk
    assert slots.state["latent"].shape == (4, 3, 64, 128)
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    for row, length, bucket, slot in ((0, 32, 32, 1), (1, 7, 16, 0)):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        counts = [int(n) for n in cache["step_counters"]]
        assert counts[3] == 2 * 4 * length      # layers x k x real tokens
        assert 0 < counts[4] < counts[3] and counts[2] + counts[4] <= counts[3]
        state["latent"] = write_slot_rows(state["latent"], cache["latent"],
                                          slot)
        lengths[slot] = length
    for _ in range(8):
        active = jnp.asarray([True, True, False])
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([ids[1, lengths[0]], ids[0, lengths[1]], 0])
        cache = dict(state, index=idx, valid_len=active.astype(jnp.int32),
                     slot_walk=slot_walk(idx, active))
        logits, cache = step(params, tokens[:, None], cache)
        np.testing.assert_allclose(logits[0, 0], ref[1, lengths[0]], **TOL)
        np.testing.assert_allclose(logits[1, 0], ref[0, lengths[1]], **TOL)
        assert int(cache["step_counters"][3]) == 2 * 4 * 2
        lengths[:2] += 1
        state["latent"] = cache["latent"]
    assert list(lengths) == [15, 40, 0]
    assert not np.asarray(state["latent"][..., 40:]).any()   # the zero lanes


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
    assert (shapes["experts"], shapes["experts_held"], shapes["zero_experts"],
            shapes["layers"], shapes["sparse_layers"], shapes["kv_heads"],
            shapes["cache_row_dim"]) == (24, 2, 8, 4, 2, 1, 40)
    # all four choices of a token a double layer are pairs; those that went
    # to identity experts and to the two held experts are some of them
    assert counters["serving/moe_assignments"] == \
        2 * 4 * counters["serving/slot_iterations_active"]
    assert 0 < counters["serving/moe_assignments_zero"] < \
        counters["serving/moe_assignments"]
    assert counters["serving/moe_assignments_held"] \
        + counters["serving/moe_assignments_zero"] \
        < counters["serving/moe_assignments"]
    assert 0 < counters["serving/moe_experts_streamed"] <= \
        2 * 2 * counters["serving/decode_steps"]


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.zero_expert_share"]["value"] < 100
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
