"""The ninth family, ``gigachat35``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, the work of its one-token delta-rule update against a hand-worked
window, its metric files through their readers, its mix's schedule, the
program against the reference through the slot cache across a bucket boundary,
and a tiny in-process rehearsal of its cell (``rehearse=True``: no device
guard, never a result). What it reads of ``BENCHMARK.json`` it reads through
the ``bench`` fixture, as accepted and with a cell appended (appended.py), and
it speaks of its own cell only: that the cell is listed, never that it is last
or alone.

One module (tests/conftest.py runs every module in a child process); it starts
no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "gigachat3.5-432b-a28b.serve-batched-questions"
CFG = harness.load_json("configs", "gigachat3.5-432b-a28b.json")
FAMILY = harness.module("families", "gigachat35")
REFERENCE = harness.module("reference", "gigachat35")
TOL = dict(rtol=1e-4, atol=2e-5)
# the published config.json (catalog row GigaChat3.5-432B-A28B), key for key
PUBLISHED = {
    "vocab_size": 128256, "max_position_embeddings": 262144,
    "hidden_size": 7168, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_hidden_layers": 40,
    "nextn_is_sparse": False, "num_attention_heads": 64,
    "n_shared_experts": 1, "n_routed_experts": 256,
    "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": 1536,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
    "qk_head_dim": 192, "n_group": 1, "topk_group": 1,
    "num_experts_per_tok": 8, "first_k_dense_replace": 3,
    "norm_topk_prob": True, "rope_interleave": True,
    "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
    "rope_theta": 100000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32768,
                     "type": "yarn"},
    "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm",
    "layernorm_type": "pre_post", "layernorm_gating_weight": 2,
    "gated_attention": True, "use_shared_expert_sigmoid": False,
    "use_mla_scaling_factor": True,
    "linear_attention_type": "GigaChat35GatedDeltaNet",
    "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39],
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "linear_num_key_heads": 32,
    "linear_num_value_heads": 64,
    "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered",
    "linear_sigmoid_gate_scale": 2, "linear_attn_o_norm_eps": 1e-06,
    "swiglu_limit": 10, "tie_word_embeddings": False,
    "num_nextn_predict_layers": 2, "model_type": "gigachat3_5",
    "tf_legacy_loss": False}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
       "full_attention_layers": [1], "n_routed_experts": 16,
       "vocab_size": 16032, "max_position_embeddings": 4096,
       "num_nextn_predict_layers": 0}
# what the cell reports, by ISSUE 59's list less ``kernel.mla_prefill_*``: a
# test under ``paths`` pins those two to Sarvam's cell alone (ROADMAP R1 (14))
LISTED = {
    "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s",
    "kernel.gdn_update_roofline", "kernel.gdn_update_share",
    "mixer.gdn_split_steps", "kernel.mla_decode_roofline",
    "kernel.mla_decode_share", "kernel.moe_experts_roofline",
    "kernel.moe_experts_share", "moe.expert_live_share",
    "device.idle_share.serve", "host.stall_ms.serve",
    "host.gc_pause_ms.serve", "sched.batch_fill", "step.decode_ms",
    "step.prefill_ms", "step.prefill_pad_share",
    "entry.compiles_in_window.serve", "entry.traces_after_warm"}


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "qk_head_dim", "v_head_dim",
              "linear_key_head_dim", "linear_value_head_dim",
              "linear_num_key_heads", "linear_num_value_heads",
              "linear_conv_kernel_dim", "num_experts_per_tok",
              "num_attention_heads", "rope_scaling"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"], CFG["num_hidden_layers_published"]) \
        == (256, 128256, 0, 40)
    for needle in ("40 -> 5", "3 -> 1", "256 -> 16", "128256 -> 16032",
                   "262144 -> 4096", "2 -> 0", "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"]
                 if c["name"] == "gigachat3.5-432b-a28b")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/gigachat3.5-432b-a28b.json"
    assert CFG["source"] == ("https://huggingface.co/ai-sage/"
                             "GigaChat3.5-432B-A28B/blob/main/config.json")
    # every reading ISSUE 59 marks ASSUMED, each with its alternative
    assert set(CFG["assumed"]) >= {
        "norm_scale", "norm_placement", "gdn_equations", "gdn_column_order",
        "gdn_o_norm", "conv_bias", "state_dtype", "gated_attention",
        "latent_norms", "rope", "swiglu_limit", "scoring_func", "router_why",
        "param_count", "initial_values", "weights_dtype", "weights_seed",
        "weights_seed_why", "published_code"}
    for key in ("norm_scale", "norm_placement", "gdn_o_norm",
                "gated_attention", "swiglu_limit", "state_dtype_why",
                "conv_bias_why"):
        assert "other" in CFG["assumed"][key], key
    assert "multi-token-prediction" in CFG["left_out"]
    assert "sixteen chips share each layer by experts" in CFG["deployment"]
    assert "pipeline stages" in CFG["deployment"]
    assert "sixteen times their share" in CFG["deployment"]
    assert "five layers make the host's share" in CFG["deployment"]


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
    """ISSUE 59's arithmetic, part by part: 4,731,722,752 parameters held,
    9.46 GB in bf16; the whole model by the same formulas 430.5 B with 26.4 B
    active, the published 432B-A28B less its two dense multi-token-prediction
    layers."""
    s = FAMILY.shapes(CFG)
    d = 7168
    gdn = (d * 24576 + d * 128 + 4 * 16384 + 64 + 64 + 128 + 8192 * d)
    mla = (d * 1536 + 1536 + 1536 * 64 * 192 + d * 576 + 512
           + 512 * 64 * 256 + 2 * d * 8192)
    norms, dense, expert = 4 * d, 3 * d * 18432, 3 * d * 2048
    sparse = d * 256 + 256 + expert + 16 * expert
    assert (gdn, mla, norms, dense, sparse) == (
        235_864_320, 159_844_352, 28_672, 396_361_728, 750_518_528)
    top = 2 * 16032 * d + d
    assert top == 229_841_920
    layers = (gdn + norms + dense, mla + norms + sparse, gdn + norms + sparse)
    assert layers == (632_254_720, 910_391_552, 986_411_520)
    want = layers[0] + layers[1] + 3 * layers[2] + top
    assert s["params"] == want == 4_731_722_752
    assert 9.46e9 < 2 * s["params"] < 9.47e9
    # a token passes through half a routed expert a sparse layer here:
    # 8 x 16 / 256
    assert s["active_params"] == want - 4 * expert * 16 + 4 * expert // 2
    whole = FAMILY.shapes(dict(
        CFG, num_hidden_layers=40, first_k_dense_replace=3,
        vocab_size=128256, n_routed_experts=256,
        full_attention_layers=PUBLISHED["full_attention_layers"]))
    assert (whole["params"], whole["active_params"]) == \
        (430_549_380_864, 26_436_579_072)
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["expert_mlp"], s["sparse_layers"], s["dense_layers"]) == \
        (256, 16, 8, 2048, 4, 1)
    # ``layers`` counts the layers that hold token rows: ONE of five
    assert (s["layers"], s["total_layers"], s["width"], s["hidden"],
            s["heads"], s["kv_heads"], s["head_dim"], s["v_head_dim"],
            s["cache_row_dim"], s["latent"], s["vocab"], s["positions"]) == \
        (1, 5, 7168, 7168, 64, 1, 192, 128, 576, 512, 16032, 4096)
    assert (s["gdn_layers"], s["gdn_key_heads"], s["gdn_value_heads"],
            s["gdn_key_dim"], s["gdn_value_dim"], s["gdn_conv"],
            s["gdn_state_bytes"]) == (4, 32, 64, 128, 128, 4, 4)
    # a slot: 16,777,216 bytes of state, 393,216 of tails, 5,242,880 of rows
    assert s["state_bytes_per_slot"] == 16_777_216 + 393_216 + 5_242_880 \
        == 22_413_312
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    c = model.config
    assert c.held == (0, 16) and c.num_experts == 256
    assert c.runs() == (("gdn_dense", 0, 0, 1), ("mla_sparse", 0, 0, 1),
                        ("gdn_sparse", 0, 1, 3))
    assert (c.prompt_block, c.key_block, c.gdn_chunk) == (2048, 512, 64)
    assert (c.swiglu_limit, c.routed_scaling_factor, c.rope_theta,
            c.rope_factor, c.rope_original_max) == (10.0, 2.5, 1e5, 8.0, 32768)
    from deepspeed_tpu.ops import gdn as ops_gdn
    from deepspeed_tpu.ops import mla_decode_step, mla_prefill

    assert ops_gdn.supports(c.gdn_key_heads, c.gdn_value_heads,
                            c.gdn_key_dim, c.gdn_value_dim, c.gdn_conv)
    # the served cache routes to the fused absorbed step, and a prefill's own
    # bucket-long cache to the prompt kernel from one key block (512) on: a
    # 256 bucket takes the lax loop
    assert mla_decode_step.supports(4096, c.row_width)
    assert [mla_prefill.supports(t, c.row_width, c.key_block, t)
            for t in (256, 512, 1024, 2048)] == [False, True, True, True]


def test_the_family_refuses_what_the_program_does_not_compute():
    for key, value in (("n_group", 2), ("attention_bias", True),
                       ("use_shared_expert_sigmoid", True),
                       ("nextn_is_sparse", True),
                       ("num_nextn_predict_layers", 2),
                       ("gated_attention", False),
                       ("rope_interleave", False),
                       ("layernorm_type", "pre"),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            FAMILY.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError, match="rope_scaling.type"):
        FAMILY.build_model(dict(CFG, rope_scaling=dict(
            CFG["rope_scaling"], type="linear")), {})
    with pytest.raises(ValueError, match="scoring_func"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], scoring_func="softmax")), {})
    with pytest.raises(ValueError, match="conv_bias"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], conv_bias=True)), {})
    with pytest.raises(ValueError, match="state_dtype"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], state_dtype="bfloat16")), {})
    with pytest.raises(ValueError, match="rematerialisation"):
        FAMILY.build_model(CFG, {"remat": True})


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == ("gigachat3.5-432b-a28b",
                                                 "serve-batched-questions")
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 64,
                             "max_len": 4096,
                             "buckets": [256, 512, 1024, 2048],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    assert arr["burst_size"] == 8
    assert arr["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.9, "min": 32, "max": 2048}
    out = dict(arr["output"])
    # 512, or the lower cap the drain forced (ISSUE 59: 448, then 384)
    assert out.pop("max") in (512, 448, 384)
    assert out == {"dist": "lognormal", "median": 384, "sigma": 0.3,
                   "min": 192}
    assert arr["max_total"] == 2560
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert LISTED - e2e <= layer
    # other families' kernels, and a ring this model has not
    assert not {"kernel.decode_attn_roofline", "kernel.decode_attn_share",
                "kernel.ssm_update_roofline", "kernel.kda_update_roofline",
                "kernel.kda_prefill_share", "kernel.gqa_prefill_share",
                "kernel.mla_prefill_roofline", "kernel.mla_prefill_share",
                "cache.window_live_share", "moe.zero_expert_share"} & layer
    for name in ("kernel.gdn_update_roofline", "kernel.gdn_update_share",
                 "mixer.gdn_split_steps"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == spec["moves"] == \
            "itl_p95_ms"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"])
    for name in ("kernel.gdn_update_roofline", "kernel.gdn_update_share"):
        spec = harness.load_json("layer_metrics", name + ".json")
        assert spec["params"]["pattern"] == r"^%[\w.\-]*dstpu_gdn_update"
    spec = harness.load_json("layer_metrics", "mixer.gdn_split_steps.json")
    assert (spec["reader"], spec["params"]["counter"], spec["better"]) == \
        ("counter", "gdn/traced_split_step", "lower")


def test_the_schedule_is_bursts_of_eight_that_span_the_buckets(bench):
    """Eight requests land together, whatever the seed; the first 16 finished
    are among the first bursts, whose prompts span the buckets; a whole burst
    arrives inside the traced last 3 s early enough for its prefills to end
    there; everything fits a slot."""
    from benchmarks import traffic_gen

    arr = harness.load_cell(CELL, bench)["traffic_file"]["arrivals"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=16032)
        times = [p.arrival_time for p in planned]
        assert len(planned) == max(1, round(arr["rate"] * 51))
        for at in range(0, len(planned) - 7, 8):
            assert len(set(times[at:at + 8])) == 1
        first = [len(p.prompt) for p in planned[:24]]
        buckets = {next(b for b in (256, 512, 1024, 2048) if n <= b)
                   for n in first}
        assert len(buckets) >= 3, first
        assert max(max(p.prompt) for p in planned[:16]) < 16032
        assert all(len(p.prompt) + p.max_new_tokens <= 2560 for p in planned)
        assert all(32 <= len(p.prompt) <= 2048 for p in planned)
        assert all(192 <= p.max_new_tokens <= arr["output"]["max"]
                   for p in planned)
        traced = [t for t in times if 48.0 <= t < 50.0]
        assert len(traced) >= 8, sorted(set(times))[-4:]


def test_gdn_update_work_against_a_hand_worked_window():
    """Window [10, 11): a request commits its decode tokens 1 and 2 in it (the
    first token is the prefill's), another one token; a token outside the
    window adds nothing. A slot-step is 4 layers x 64 value heads x 128 x 128
    elements, read and written once in float32, 7 FLOPs each."""
    s = FAMILY.shapes(CFG)
    obs = {"trace_span": [10.0, 11.0], "shapes": s,
           "requests": [
               {"prompt_len": 300, "token_times": [9.9, 10.1, 10.2, 11.5]},
               {"prompt_len": 1500, "token_times": [9.0, 9.1, 9.2, 10.5]}]}
    n_flops, n_bytes = harness.module("work", "gdn_update").work(obs)
    elements = 3 * 4 * 64 * 128 * 128
    assert n_bytes == elements * 8 == 3 * 33_554_432
    assert n_flops == elements * 7
    # 0.875 FLOPs a byte: the bytes set the least time
    assert n_flops / n_bytes == pytest.approx(0.875)
    assert harness.module("work", "gdn_update").work(
        dict(obs, requests=[])) == (0.0, 0.0)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    def read(name, obs):
        spec = harness.load_json("layer_metrics", name + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    s = FAMILY.shapes(CFG)
    step = ("%dstpu_gdn_update.3 = (bf16[64,64,128]{2,1,0}, "
            "f32[4,64,64,128,128]{4,3,2,1,0}) custom-call(%a, %b)")
    reader = "%fusion.7 = bf16[64,1,8192] fusion(%dstpu_gdn_update.3)"
    other = "%dstpu_mla_decode_step.1 = bf16[64,64,640] custom-call(%q)"
    kda = "%dstpu_kda_update.1 = bf16[16,64,128] custom-call(%q)"
    tr = trace_reduce.Trace(
        {0: [(step, 0.0, 0.001), (reader, 0.001, 0.002),
             (other, 0.002, 0.003), (kda, 0.003, 0.004)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0))
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0], "spans": [],
           "counters": {"gdn/traced_split_step": 0,
                        "gdn/traced_folded_step": 4},
           "requests": [{"prompt_len": 800,
                         "token_times": [0.1, 0.2, 0.3]}]}
    assert read("kernel.gdn_update_share", obs) == pytest.approx(25.0)
    # two slot-steps: 67.1 MB at 819 GB/s of 1 ms
    assert read("kernel.gdn_update_roofline", obs) == pytest.approx(
        100 * 2 * 33_554_432 / 819e9 / 0.001)
    assert read("mixer.gdn_split_steps", obs) == 0
    # a program without the kernel or the counter (another family, the parent
    # commit): nothing to read, and nothing raised
    bare = dict(obs, counters={}, trace=trace_reduce.Trace(
        {0: [(reader, 0.0, 0.004), (other, 0.004, 0.005)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    assert read("kernel.gdn_update_roofline", bare) is None
    assert read("kernel.gdn_update_share", bare) is None
    assert read("mixer.gdn_split_steps", bare) is None


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def built():
    """The tiny program in float32 and the reference's logits of 2 x 48 ids:
    the cell's five layers, 2 key and 4 value delta-rule heads of 16, a
    latent of 32 under 4 heads, token blocks of 16 and key blocks of 8."""
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
        ref = jax.jit(lambda p, x: REFERENCE.forward_logits(p, x, cfg))(
            params, ids)

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
        out = jax.jit(lambda p, x: FAMILY.engine_logits(model, p, x))(
            params, ids)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


def test_prefill_then_decode_through_the_slot_cache_matches_the_reference(
        built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    three leaves, by ``SlotKVCache``'s own tree: bucketed prefills on both
    sides of a bucket boundary (16 real positions fill the bucket of 16, 17
    take the bucket of 32 and two token blocks: the walk inside the program)
    written into slots, latent rows as prefixes and the recurrent leaves
    whole, then two slots of unequal length decoding together with a third
    inactive."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import insert_slot_row, write_slot_rows
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step = built
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == ("latent", "gdn", "gdn_conv")
    assert slots.row_keys == ("latent",)
    assert slots.recurrent_keys == ("gdn", "gdn_conv")
    assert {k: v.shape for k, v in slots.state.items()} == {
        "latent": (1, 3, 64, 128), "gdn": (4, 3, 4, 16, 16),
        "gdn_conv": (4, 3, 3, 8, 16)}
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    for row, length, bucket, slot in ((0, 16, 16, 1), (1, 17, 32, 0)):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        assert int(cache["step_counters"][3]) == 4 * 4 * length
        state["latent"] = write_slot_rows(state["latent"], cache["latent"],
                                          slot)
        for name in ("gdn", "gdn_conv"):
            state[name] = insert_slot_row(state[name], cache[name], slot)
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
        assert int(cache["step_counters"][3]) == 4 * 4 * 2
        # the idle slot's state and tails do not move
        for name in ("gdn", "gdn_conv"):
            np.testing.assert_array_equal(np.asarray(cache[name])[:, 2],
                                          np.asarray(state[name])[:, 2])
        lengths[:2] += 1
        state = {name: cache[name] for name in state}
    assert list(lengths) == [25, 24, 0]


def test_the_geometry_is_read_a_leaf():
    """At the published sizes ``SlotKVCache`` takes the pair of leaves by the
    model's declaration: latent rows of ONE layer that route to the fused
    absorbed step, float32 state and bf16 tails of four; 64 slots of 4,096
    rows are 1.43 GB, 22.4 MB a slot."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model = FAMILY.build_model(CFG, {})
    shapes = jax.eval_shape(
        lambda: model.init_cache(64, 4096, dtype=jnp.bfloat16))

    class Shaped:       # the cache's tree as shapes: nothing is allocated
        config, slot_state_keys, row_state_keys = (
            model.config, model.slot_state_keys, model.row_state_keys)
        fused_row_walk = model.fused_row_walk

        @staticmethod
        def init_cache(slots, max_len, dtype=None):
            return shapes

    slots = SlotKVCache(Shaped, 64, 4096)
    assert slots.pair == 1 and slots.fused_walk
    assert slots.state["latent"].shape == (1, 64, 4096, 640)
    assert slots.state["gdn"].shape == (4, 64, 64, 128, 128)
    assert slots.state["gdn"].dtype == jnp.float32
    assert slots.state["gdn_conv"].shape == (4, 64, 3, 128, 128)
    assert slots.hbm_bytes() == 64 * FAMILY.shapes(CFG)[
        "state_bytes_per_slot"] == 1_434_451_968


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
    assert (shapes["experts"], shapes["experts_held"], shapes["layers"],
            shapes["total_layers"], shapes["sparse_layers"],
            shapes["gdn_layers"], shapes["gdn_key_heads"],
            shapes["gdn_value_heads"], shapes["gdn_key_dim"]) == \
        (16, 2, 1, 5, 4, 4, 2, 4, 16)
    # four experts a token a sparse layer, an eighth of them held here
    assert counters["serving/moe_assignments"] == \
        4 * 4 * counters["serving/slot_iterations_active"]
    assert 0 < counters["serving/moe_assignments_held"] < \
        counters["serving/moe_assignments"]
    assert counters["serving/prefill_rows_run"] > \
        counters["serving/prefill_rows_padding"] > 0
    # on a CPU the delta-rule layers of the decode program are traced split
    assert counters["gdn/traced_split_step"] > 0
    assert counters["gdn/traced_folded_step"] == 0


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    assert line["metrics"]["mixer.gdn_split_steps"]["value"] > 0
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
