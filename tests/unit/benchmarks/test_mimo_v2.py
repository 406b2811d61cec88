"""The eighth family, ``mimo_v2``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, its cell's listing and its mix's parameters, the two ``work`` files
that count the decode step's and the global layers' prompt kernel's useful
work on a hand-made window, the program against the plain reference at tiny
size in float32 (full forward, prefill then decode through ``SlotKVCache``
with prompts longer than two windows, so the rings wrap), and a tiny
in-process rehearsal of its cell (``rehearse=True``: no device guard, never a
result). What it reads of ``BENCHMARK.json`` it reads through the ``bench``
fixture, as accepted and with a cell appended (appended.py), and it speaks of
its own cell only: that the cell is listed, never that it is last or alone.

It starts no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness, trace_reduce
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "mimo-v2.5.serve-long-context-decode"
CFG = harness.load_json("configs", "mimo-v2.5.json")
FAMILY = harness.module("families", "mimo_v2")
REFERENCE = harness.module("reference", "mimo_v2")
PEAK = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
# the published config.json (catalog row MiMo-V2.5), key for key
PUBLISHED = {
    "attention_bias": False, "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True, "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64, "swa_head_dim": 192,
    "swa_v_head_dim": 128, "head_dim": 192, "hidden_act": "silu",
    "hidden_size": 4096, "hybrid_block_size": None,
    "hybrid_layer_pattern": PATTERN, "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05, "max_position_embeddings": 1048576,
    "model_type": "mimo_v2", "moe_intermediate_size": 2048,
    "moe_layer_freq": [0] + [1] * 47, "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": None, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {"rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "routed_scaling_factor": None,
    "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_size": 128, "swa_rope_theta": 10000,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152576}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 7, "hybrid_layer_pattern": PATTERN[:7],
       "moe_layer_freq": [0] + [1] * 6, "n_routed_experts": 16,
       "vocab_size": 19072, "max_position_embeddings": 16384}
TOL = dict(rtol=1e-4, atol=1e-5)
# every list of BENCHMARK.json the cell belongs in (ISSUE 55, step 7)
KIND_WIDE = (
    "entry.compiles_in_window.serve", "entry.trace_ms", "entry.lower_ms",
    "entry.backend_compile_ms", "entry.cache_load_ms", "entry.cache_misses",
    "entry.retraces", "entry.setup_weights_ms", "entry.setup_warmup_ms",
    "entry.setup_warmup_repeat_ms", "entry.traces_after_warm",
    "sched.queue_wait_p95_ms", "sched.batch_fill", "sched.schedule_host_ms",
    "sched.iter_schedule_p95_ms", "step.prefill_ms", "step.decode_ms",
    "step.prefill_chunk_ms", "step.upload_host_ms", "step.launch_host_ms",
    "step.fetch_wait_ms", "step.commit_host_ms", "step.decode_overlap_share",
    "device.idle_share.serve", "step.decode_mfu", "step.prefill_mfu")
LISTED = KIND_WIDE + (
    "kernel.moe_experts_prefill_roofline",
    "step.prefill_pad_share", "kernel.decode_attn_share",
    "kernel.decode_attn_live_share", "cache.window_live_share",
    "kernel.moe_experts_roofline", "kernel.moe_experts_share",
    "moe.expert_live_share", "kernel.window_decode_roofline",
    "kernel.global_prefill_roofline", "kernel.global_prefill_share")
BARRED = ("kernel.decode_attn_roofline", "kernel.gqa_prefill_roofline",
          "kernel.gqa_prefill_share", "kernel.mla_decode_roofline",
          "kernel.kda_update_roofline", "moe.zero_expert_share",
          "entry.setup_first_step_ms", "kernel.flash_roofline")


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "v_head_dim", "swa_head_dim", "swa_v_head_dim",
              "num_attention_heads", "num_key_value_heads",
              "swa_num_key_value_heads", "num_experts_per_tok",
              "sliding_window"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["num_hidden_layers_published"], CFG["experts_held_first"]) \
        == (256, 152576, 48, 0)
    for needle in ("48 -> 7", "256 -> 16", "152576 -> 19072",
                   "1048576 -> 16384", "No width is cut", "3,429,955,392",
                   "308,778,780,864"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"] if c["name"] == "mimo-v2.5")
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/mimo-v2.5.json"
    assert CFG["source"] == ("https://huggingface.co/XiaomiMiMo/MiMo-V2.5/"
                             "blob/main/config.json")
    assert set(CFG["assumed"]) >= {
        "norm_placement", "qk_norm_and_bias", "projection_order", "rotation",
        "value_scale", "sink", "window", "attention_chunk_size", "router",
        "left_out", "initial_values", "weights_dtype", "weights_seed",
        "weights_seed_why", "published_code"}
    assert CFG["assumed"]["weights_seed"] == 31337
    assert "sixteen chips share each layer by experts" in CFG["deployment"]
    assert "this is chip 0 without its exchange" in CFG["deployment"]


def test_shapes_against_the_hand_count():
    """ISSUE 55's arithmetic: a global attention half 89,137,152, a sliding
    one 94,380,096, the dense FFN 201,326,592, a router with its bias
    1,048,832, an expert 25,165,824; embedding, head and final norm
    156,241,920: 3,429,955,392 held, 6.86 GB in bf16; the whole model by the
    same formulas 308,778,780,864 with 15,445,936,320 a token passes."""
    s = FAMILY.shapes(CFG)
    glob = 4096 * 13568 + 8192 * 4096 + 2 * 4096
    slide = 4096 * 14848 + 8192 * 4096 + 64 + 2 * 4096
    dense, router, expert = 3 * 4096 * 16384, 4096 * 256 + 256, \
        3 * 4096 * 2048
    assert (glob, slide, dense, router, expert) == \
        (89_137_152, 94_380_096, 201_326_592, 1_048_832, 25_165_824)
    top = 2 * 19072 * 4096 + 4096
    assert top == 156_241_920
    held = (glob + dense) + 5 * (slide + router + 16 * expert) \
        + (glob + router + 16 * expert) + top
    assert (glob + dense, slide + router + 16 * expert,
            glob + router + 16 * expert) == \
        (290_463_744, 498_082_112, 492_839_168)
    assert s["params"] == held == 3_429_955_392
    assert 6.85e9 < 2 * s["params"] < 6.87e9
    whole = (glob + dense) + 39 * (slide + router + 256 * expert) \
        + 8 * (glob + router + 256 * expert) + 2 * 152576 * 4096 + 4096
    assert s["published_params"] == whole == 308_778_780_864
    full = dict(CFG, hybrid_layer_pattern=PATTERN,
                moe_layer_freq=[0] + [1] * 47, num_hidden_layers=48,
                n_routed_experts=256, vocab_size=152576)
    assert FAMILY.shapes(full)["active_params"] == 15_445_936_320
    assert FAMILY.shapes(full)["params"] == whole
    # a token routes 8 x 16 / 256 = 0.5 pairs a layer here on average
    assert s["active_params"] == s["params"] - 6 * int(expert * 15.5)
    assert (s["layers"], s["hidden"], s["width"], s["heads"], s["kv_heads"],
            s["head_dim"], s["v_head_dim"], s["cache_row_dim"], s["mlp"],
            s["vocab"], s["positions"]) == \
        (7, 4096, 4096, 64, 4, 192, 128, 1280, 16384, 19072, 16384)
    assert (s["global_kv_heads"], s["sliding_kv_heads"], s["window"],
            s["sliding_layers"], s["global_layers"], s["experts"],
            s["experts_held"], s["experts_per_token"], s["expert_mlp"],
            s["sparse_layers"]) == (4, 8, 128, 5, 2, 256, 16, 8, 2048, 6)
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    assert model.config.held == (0, 16) and model.config.num_experts == 256
    assert (model.config.prompt_block, model.config.key_block,
            model.config.routed_scaling_factor) == (2048, 512, 1.0)
    # a cached token's LIVE bytes: 2 global layers x 4 heads x 320 x 2
    assert 2 * s["cache_row_dim"] * 2 == 5_120


def test_the_cell_is_one_chip_and_is_listed_where_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == ("mimo-v2.5",
                                                 "serve-long-context-decode")
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    for name in BARRED:
        assert CELL not in by_name[name]["workloads"], name
    # and nothing but what step 7 names
    assert {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)} \
        == set(LISTED)
    for name, moves, better, work in (
            ("kernel.window_decode_roofline", "itl_p95_ms", "higher",
             "window_decode"),
            ("kernel.global_prefill_roofline", "ttft_p95_ms", "higher",
             "global_prefill"),
            ("kernel.global_prefill_share", "ttft_p95_ms", "lower", None)):
        m, spec = by_name[name], harness.load_json("layer_metrics",
                                                   name + ".json")
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (spec["unit"], spec["better"], spec["source"],
                                spec["layer"], spec["moves"]) == \
            ("%", better, "device_trace", "kernels", moves)
        assert spec["params"].get("work") == work
    assert harness.load_json(
        "layer_metrics", "kernel.window_decode_roofline.json"
    )["params"]["pattern"] == r"^%[\w.\-]*dstpu_decode_step"
    assert harness.load_json(
        "layer_metrics", "kernel.global_prefill_roofline.json"
    )["params"]["pattern"] == r"^%[\w.\-]*dstpu_gqa_prefill"


def test_the_mix_is_long_contexts_that_decode_for_long(bench):
    """The mix's parameters as ISSUE 55 gives them, and its schedule: half
    the prompts 8k and longer, answers of several hundred tokens, everything
    inside a slot and the largest bucket, 24 requests or more."""
    from benchmarks import traffic_gen

    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 16,
                             "max_len": 16384,
                             "buckets": [2048, 4096, 8192, 16384],
                             "trace_seconds": 3.0}
    arr = mix["arrivals"]
    values = [1536, 2560, 3584, 5120, 6656, 8192, 9728, 11264, 13312, 15360]
    assert arr["prompt"] == {"dist": "choice", "values": values}
    assert sum(values) / 10 == 7731.2 and sum(v >= 8192 for v in values) == 5
    assert arr["output"] == {"dist": "lognormal", "median": 512,
                             "sigma": 0.5, "min": 128, "max": 1024}
    assert arr["max_total"] == 16384 and arr.get("burst_size", 1) == 1
    assert "shared_prefix" not in arr
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    assert len(mix["check"]["why"]) > 80 and len(mix["what"]) > 80
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=19072)
        assert len(planned) >= 24
        assert all(len(p.prompt) in values for p in planned)
        assert all(128 <= p.max_new_tokens <= 1024 for p in planned)
        assert all(len(p.prompt) + p.max_new_tokens <= 16384
                   for p in planned)
        assert max(max(p.prompt) for p in planned[:16]) < 19072


# ------------------------------------------------- the two ``work`` files
SHAPES = FAMILY.shapes(CFG)


def _request(n, admitted, first_token, token_times=None):
    return {"prompt_len": n, "admitted": admitted, "first_token": first_token,
            "token_times": token_times or [first_token]}


def _obs(requests, trace=None):
    return {"trace_span": [10.0, 13.0], "shapes": SHAPES, "peak": PEAK,
            "requests": requests, "trace": trace, "counters": {}, "spans": []}


def _read(name, obs):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _trace(events):
    return trace_reduce.Trace({0: events}, [("bench/window", 10.0, 13.0)],
                              (10.0, 13.0))


def test_window_decode_work_on_a_hand_made_window():
    """A decoding slot at n rows reads n rows on each of the 2 global layers
    (4 heads) and min(n, 128) on each of the 5 sliding ones (8 heads), 320
    live elements a head a row, 2 x 320 FLOPs a query head a row; the first
    token of a request is the prefill's, tokens outside the window count
    nothing."""
    work = harness.module("work", "window_decode").work
    # prompt 1000: tokens 1, 2 inside the window at 1001 and 1002 rows;
    # prompt 50: token 1 inside at 51 rows (a ring not yet full)
    reqs = [_request(1000, 9.0, 9.5, [9.5, 10.5, 11.0, 13.5]),
            _request(50, 11.0, 11.5, [11.5, 12.0])]
    rows, ring = 1001 + 1002 + 51, 128 + 128 + 51
    flops, nbytes = work(_obs(reqs))
    assert flops == (2 * rows + 5 * ring) * 64 * 2 * 320
    assert nbytes == (2 * rows * 4 + 5 * ring * 8) * 320 * 2
    assert work(_obs([])) == (0.0, 0.0)
    # memory binds it: 40 FLOPs a byte and under
    assert flops / nbytes < PEAK["bf16_tflops"] * 1e3 / PEAK["hbm_gbps"]
    # through the reader: the kernel at its least time reads 100%, slower
    # reads lower; a program without the kernel has nothing to read
    least = nbytes / (PEAK["hbm_gbps"] * 1e9)
    kernel = ("%dstpu_decode_step.7 = (bf16[16,64,128], bf16[2,16,4,16384,"
              "256], bf16[2,16,4,16384,128]) custom-call(%a)")
    other = "%fusion.3 = bf16[16,64,128] fusion(%dstpu_decode_step.7)"
    for slowdown in (1.0, 3.0):
        obs = _obs(reqs, _trace([(kernel, 10.2, 10.2 + least * slowdown),
                                 (other, 11.0, 11.5)]))
        assert _read("kernel.window_decode_roofline", obs) == \
            pytest.approx(100.0 / slowdown)
    assert _read("kernel.window_decode_roofline",
                 _obs(reqs, _trace([(other, 11.0, 11.5)]))) is None
    # K-EXAONE's shapes() states what the file reads too (one head count)
    exa = harness.module("families", "exaone_moe").shapes(
        harness.load_json("configs", "k-exaone-236b-a23b.json"))
    f2, b2 = work(dict(_obs(reqs), shapes=exa))
    assert f2 == (1 * rows + 4 * ring) * 64 * 2 * 256
    assert b2 == (1 * rows + 4 * ring) * 8 * 256 * 2


def test_global_prefill_work_on_a_hand_made_window():
    """``work/gqa_prefill.py``'s rule at two widths: a prompt of n tokens
    whose prefill lies whole in the window attends n (n + 1) / 2 pairs a
    head on each of the 2 global layers at 2 x (192 + 128) FLOPs, and reads
    its rows once a token block of 2,048 at 4 heads x 320 elements; a cut
    one counts nothing, and a window without a whole prefill reads None."""
    work = harness.module("work", "global_prefill").work
    n = 5000
    whole = (n * (n + 1) // 2 * 2 * 64 * 2 * 320,
             (2048 + 4096 + 5000) * 2 * 4 * 320 * 2)
    assert work(_obs([_request(n, 10.5, 11.0)])) == pytest.approx(whole)
    for cut in ([_request(n, 9.9, 10.4)], [_request(n, 12.8, 13.2)],
                [_request(n, 12.0, None)], []):
        assert work(_obs(cut)) == (0.0, 0.0)
    n = 15360
    flops = n * (n + 1) // 2 * 2 * 64 * 2 * 320
    least = flops / (PEAK["bf16_tflops"] * 1e12)
    kernel = "%dstpu_gqa_prefill.5 = bf16[1,2048,8192]{2,1,0} custom-call(%q)"
    loop = ('%fusion.378 = f32[4,16,2048] fusion(%p), metadata={op_name="jit('
            'prefill)/dstpu_gqa_prefill/while/body/reduce_max"}')
    for slowdown in (1.0, 1.7):
        took = least * slowdown
        obs = _obs([_request(n, 10.1, 10.3 + took)],
                   _trace([(kernel, 10.2, 10.2 + took), (loop, 12.0, 12.1)]))
        assert _read("kernel.global_prefill_roofline", obs) == \
            pytest.approx(100.0 / slowdown)
        assert _read("kernel.global_prefill_share", obs) == \
            pytest.approx(100.0 * took / (took + 0.1))
    # a window in which the kernel did not run, and a program without the
    # kernel (the parent's), have nothing to read: None, not 0
    none = _obs([_request(n, 9.0, 10.4)], _trace([(loop, 12.0, 12.1)]))
    assert _read("kernel.global_prefill_roofline", none) is None
    assert _read("kernel.global_prefill_share", none) is None
    assert _read("kernel.global_prefill_roofline", _obs([], None)) is None


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def built():
    """The tiny program in float32 and the reference's logits of 2 x 48 ids:
    the cell's seven layers, keys 24 and values 16 wide, 1 and 2 key-value
    heads, window 8, token blocks of 16 and key blocks of 8."""
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
    four leaves, by ``SlotKVCache``'s own tree and its geometry a leaf:
    bucketed prefills (one of two whole token blocks, 32 positions, four
    windows: the walk inside the program and a ring that has wrapped; one of
    19 real positions in a bucket of 32) written into slots, rows as
    prefixes and rings whole, then two slots of unequal length decoding
    together with a third inactive, eight steps, so each ring wraps again."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import insert_slot_row, write_slot_rows
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step = built
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == ("k", "v", "k_win", "v_win")
    assert slots.recurrent_keys == ("k_win", "v_win")
    assert (slots.window, slots.window_layers, slots.pair) == (8, 5, 1)
    assert {k: v.shape for k, v in slots.state.items()} == {
        "k": (2, 3, 1, 64, 24), "v": (2, 3, 1, 64, 16),
        "k_win": (5, 3, 2, 8, 24), "v_win": (5, 3, 2, 8, 16)}
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    for row, length, bucket, slot in ((0, 32, 32, 1), (1, 19, 32, 0)):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        assert int(cache["step_counters"][3]) == 6 * 4 * length
        for name in ("k", "v"):
            state[name] = write_slot_rows(state[name], cache[name], slot)
        for name in ("k_win", "v_win"):
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
        assert int(cache["step_counters"][3]) == 6 * 4 * 2
        lengths[:2] += 1
        state = {name: cache[name] for name in state}
    assert list(lengths) == [27, 40, 0]


def test_the_geometry_is_read_a_leaf():
    """At the published sizes ``SlotKVCache`` reads pair, heads, window and
    the two fused-walk flags from each leaf: rows of 4 heads at keys of 256
    lanes and values of 128, rings of 8 heads, both routes the fused step's
    where there are two slots or more."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model = FAMILY.build_model(CFG, {})
    shapes = jax.eval_shape(
        lambda: model.init_cache(16, 16384, dtype=jnp.bfloat16))

    class Shaped:       # the cache's tree as shapes: nothing is allocated
        config, slot_state_keys, window_state_keys = (
            model.config, model.slot_state_keys, model.window_state_keys)

        @staticmethod
        def init_cache(slots, max_len, dtype=None):
            return shapes

    slots = SlotKVCache(Shaped, 16, 16384)
    assert (slots.pair, slots.window, slots.window_layers) == (1, 128, 5)
    assert slots.fused_walk and slots.fused_window_walk
    assert slots.state["k"].shape == (2, 16, 4, 16384, 256)
    assert slots.state["v_win"].shape == (5, 16, 8, 128, 128)
    # 16 slots x 16,384 rows and the rings: 1.61 GB + 63 MB
    assert slots.hbm_bytes() == 16 * (16384 * 6144 + 3932160)


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
            shapes["sparse_layers"], shapes["global_kv_heads"],
            shapes["sliding_kv_heads"], shapes["head_dim"],
            shapes["v_head_dim"], shapes["window"]) == \
        (16, 2, 7, 6, 1, 2, 24, 16, 8)
    # four experts a token a sparse layer, an eighth of them held here
    assert counters["serving/moe_assignments"] == \
        6 * 4 * counters["serving/slot_iterations_active"]
    assert 0 < counters["serving/moe_assignments_held"] < \
        counters["serving/moe_assignments"] / 3
    assert counters["serving/moe_assignments_zero"] == 0


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    assert set(line["metrics"]) <= set(LISTED)
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
