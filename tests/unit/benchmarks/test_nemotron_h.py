"""The eleventh family, ``nemotron_h``, in the benchmark: its configuration
file against the published keys and its stated cut, its sizes against the hand
count at the cut and at the published keys, the work of its latent experts
against a hand-worked window, its metric files through their readers, its
mix's schedule, the program against the reference (full forward, and prefill
then decode through the slot cache), the four shares of an expert layer against
the uncut one, the decode step's compact buffer against the full one, and a
tiny in-process rehearsal of its cell (``rehearse=True``: no device guard,
never a result). What it reads of ``BENCHMARK.json`` it reads through the
``bench`` fixture, as accepted and with a cell appended (appended.py), and it
speaks of its own cell only: that the cell is listed, never that it is last or
alone.

One module; it starts no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
NAME = "nemotron-3-super-120b-a12b"
CELL = NAME + ".serve-agent-fanout"
CFG = harness.load_json("configs", NAME + ".json")
FAMILY = harness.module("families", "nemotron_h")
REFERENCE = harness.module("reference", "nemotron_h")
TOL = dict(rtol=1e-4, atol=2e-5)
PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
           "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the published config.json (catalog row
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16), key for key
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
# what the configuration changes, and to what
CUT = {"num_hidden_layers": 11, "hybrid_override_pattern": "MEMEMEMEM*E",
       "n_routed_experts": 128, "vocab_size": 32768,
       "num_nextn_predict_layers": 0, "mtp_hybrid_override_pattern": "",
       "max_position_embeddings": 4096}
# what the cell reports, by ISSUE 65's list
LISTED = {
    "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s",
    "kernel.moe_latent_experts_roofline", "kernel.moe_latent_experts_share",
    "moe.decode_buffer_fill", "moe.held_experts_read_share",
    "moe.expert_live_share", "kernel.ssm_update_roofline",
    "kernel.ssm_update_share", "device.idle_share.serve", "sched.batch_fill",
    "step.decode_ms", "step.prefill_ms", "step.prefill_pad_share",
    "entry.compiles_in_window.serve", "entry.traces_after_warm",
    "host.stall_ms.serve", "host.gc_pause_ms.serve"}
NEW = ("kernel.moe_latent_experts_roofline", "kernel.moe_latent_experts_share",
       "moe.decode_buffer_fill", "moe.held_experts_read_share")


def test_the_configuration_file_holds_the_published_keys(bench):
    assert len(PATTERN) == 88 and PATTERN[27:38] == "MEMEMEMEM*E"
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    # every key that differs from the source is listed, and no width is
    assert sorted(CFG["reduced"]) == sorted(CUT)
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "moe_latent_size", "moe_shared_expert_intermediate_size",
              "head_dim", "mamba_head_dim", "mamba_num_heads",
              "ssm_state_size", "n_groups", "conv_kernel", "expand",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads"}
    assert not widths & set(CFG["reduced"])
    assert (CFG["n_routed_experts_published"], CFG["vocab_size_published"],
            CFG["experts_held_first"], CFG["num_hidden_layers_published"],
            CFG["hybrid_override_pattern_published"]) == \
        (512, 131072, 0, 88, PATTERN)
    for needle in ("88 -> 11", "characters 27 to 37", "512 -> 128",
                   "131072 -> 32768", "262144 -> 4096", "1 -> 0",
                   "No width is cut"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"] if c["name"] == NAME)
    assert entry["source"] == CFG["source"] and \
        entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmarks/configs/{NAME}.json"
    assert CFG["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
        "/blob/main/config.json")
    # every reading ISSUE 65 marks ASSUMED, each with its alternative
    assert set(CFG["assumed"]) >= {
        "layer_form", "time_step_limit", "gated_norm", "positions", "latent",
        "scoring_func", "router_why", "param_count", "initial_values",
        "weights_dtype", "state_dtype", "weights_seed", "weights_seed_why",
        "published_code"}
    for key in ("time_step_limit", "gated_norm", "positions", "latent",
                "router_why", "state_dtype_why", "layer_form"):
        assert "other reading" in CFG["assumed"][key], key
    assert "multi-token-prediction" in CFG["left_out"]
    assert "four chips share each layer by experts" in CFG["deployment"]
    assert "pipeline stages" in CFG["deployment"]
    assert "1,024-wide latent rows" in CFG["deployment"]
    assert "four times their share" in CFG["deployment"]


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
    """ISSUE 65's arithmetic, part by part: 4,380 M in the layers and 268 M
    in embedding and head at the cut, 9.30 GB in bf16; the whole model by the
    same formulas 120.7 B with 12.8 B a token, the published 120B-A12B."""
    s = FAMILY.shapes(CFG)
    d = 4096
    mamba = (d + d * (2 * 8192 + 2 * 8 * 128 + 128) + 5 * 10240 + 3 * 128
             + 8192 + 8192 * d)
    attn = d + d * 128 * (2 * 32 + 2 * 2)
    outside = d + d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376
    expert = 2 * 1024 * 2688
    assert (mamba, attn, outside, expert) == (
        109_640_064, 35_655_680, 54_530_560, 5_505_024)
    assert (s["mamba_layer_params"], s["attn_layer_params"],
            s["moe_layer_params"], s["expert_params"]) == \
        (mamba, attn, outside, expert)
    layers = 5 * mamba + 5 * (outside + 128 * expert) + attn
    top = 2 * 32768 * d + d
    assert layers == 4_379_724_160 and top == 268_439_552
    assert s["params"] == layers + top == 4_648_163_712
    assert 9.29e9 < 2 * s["params"] < 9.30e9
    # a token passes through 22 x 128 / 512 = 5.5 routed experts a sparse
    # layer here
    assert s["active_params"] == s["params"] - 5 * 128 * expert \
        + 5 * 11 * expert // 2
    whole = FAMILY.shapes(dict(
        CFG, hybrid_override_pattern=PATTERN, num_hidden_layers=88,
        n_routed_experts=512, vocab_size=131072))
    assert (whole["mamba_layers"], whole["sparse_layers"],
            whole["attn_layers"]) == (40, 40, 8)
    assert 120.6e9 < whole["params"] < 120.7e9
    assert 12.7e9 < whole["active_params"] < 12.8e9
    assert (s["experts"], s["experts_held"], s["experts_per_token"],
            s["expert_mlp"], s["latent"], s["sparse_layers"]) == \
        (512, 128, 22, 2688, 1024, 5)
    # ``layers`` counts the layers that hold token rows: ONE of eleven
    assert (s["layers"], s["total_layers"], s["hidden"], s["heads"],
            s["kv_heads"], s["head_dim"], s["mlp"], s["vocab"],
            s["positions"]) == (1, 11, 4096, 32, 2, 128, 5376, 32768, 4096)
    assert (s["mamba_layers"], s["ssm_heads"], s["ssm_head_dim"],
            s["ssm_state"], s["ssm_groups"], s["conv_width"],
            s["ssm_state_bytes"]) == (5, 128, 64, 128, 8, 10240, 4)
    # a slot: 5 x (4,194,304 bytes of state + 61,440 of tails), and 1 KB a
    # token of rows on the one attention layer
    assert s["state_bytes_per_slot"] == 5 * (4_194_304 + 61_440) \
        == 21_278_720
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    c = model.config
    assert c.held == (0, 128) and c.num_experts == 512
    # an expert layer is numbered in its stack and holds no leaf
    assert model.runs() == tuple(
        (kind, i, i, 1) for kind, i in (
            ("mamba", 0), ("moe", 0), ("mamba", 1), ("moe", 1), ("mamba", 2),
            ("moe", 2), ("mamba", 3), ("moe", 3), ("mamba", 4),
            ("attention", 0), ("moe", 4)))
    assert (c.prompt_block, c.mamba_chunk_size,
            c.routed_scaling_factor, c.eps) == (512, 128, 5.0, 1e-5)
    from deepspeed_tpu.moe.grouped import compact_rows
    from deepspeed_tpu.ops import ssm

    # the decode step folds at eight groups, and its expert buffer has a
    # compact form: 768 rows for the worst case's 1,408
    assert ssm.step_folds(c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                          c.mamba_n_groups)
    assert compact_rows(64, 22, 128, 512) == 768 < 64 * 22


def test_the_family_refuses_what_the_program_does_not_compute():
    for key, value in (("n_group", 2), ("attention_bias", True),
                       ("mlp_bias", True), ("mamba_proj_bias", True),
                       ("use_conv_bias", False),
                       ("num_nextn_predict_layers", 1),
                       ("mtp_hybrid_override_pattern", "*E"),
                       ("mlp_hidden_act", "silu"), ("n_shared_experts", 2),
                       ("norm_eps", 1e-6), ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            FAMILY.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError, match="mamba_n_groups"):
        FAMILY.build_model(dict(CFG, n_groups=3), {})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        FAMILY.build_model(dict(CFG, num_hidden_layers=12), {})
    with pytest.raises(ValueError, match="state_dtype"):
        FAMILY.build_model(dict(CFG, assumed=dict(
            CFG["assumed"], state_dtype="bfloat16")), {})
    with pytest.raises(ValueError, match="rematerialisation"):
        FAMILY.build_model(CFG, {"remat": True})


def test_the_cell_is_one_chip_and_lists_what_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert (cell["config"], cell["traffic"]) == (NAME, "serve-agent-fanout")
    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    server = dict(mix["server"])
    buckets = server.pop("buckets")
    assert server == {"dtype": "bf16", "num_slots": 64, "max_len": 4096,
                      "trace_seconds": 3.0}
    # whole 128-token chunks of the Mamba-2 prompt form, up to 3,072
    assert buckets == sorted(buckets) and buckets[-1] == 3072
    assert all(b % 128 == 0 for b in buckets)
    arr = mix["arrivals"]
    assert arr["burst_size"] == 4
    assert arr["prompt"] == {"dist": "lognormal", "median": 640,
                             "sigma": 0.8, "min": 128, "max": 3072}
    out = dict(arr["output"])
    # 640, or the lower cap the drain forced
    assert 320 < out.pop("max") <= 640
    assert out == {"dist": "lognormal", "median": 320, "sigma": 0.4,
                   "min": 96}
    assert arr["max_total"] == 3712
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    e2e = {m["name"] for m in harness.metrics_of(CELL, "end_to_end", bench)}
    assert e2e == {"serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                   "setup_s"}
    layer = {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)}
    assert LISTED - e2e <= layer
    # work/moe_experts.py counts three matrices of width x expert_mlp, six
    # times a latent expert's work; other families' kernels
    assert not {"kernel.moe_experts_roofline", "kernel.moe_experts_share",
                "kernel.decode_attn_roofline", "kernel.gdn_update_roofline",
                "kernel.kda_update_roofline", "kernel.gqa_prefill_share",
                "kernel.mla_prefill_roofline", "kernel.kda_prefill_share",
                "cache.window_live_share", "moe.zero_expert_share"} & layer
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert CELL in m["workloads"] and m["moves"] == spec["moves"] == \
            "itl_p95_ms"
        assert (m["unit"], m["better"], m["source"], m["layer"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"])
    for name in NEW[:2]:
        spec = harness.load_json("layer_metrics", name + ".json")
        # XLA's kernel, or a grouped matmul of the repo's own by its name
        assert spec["params"]["pattern"] == (
            r"^%ragged-dot-(?!metadata)|^%[\w.\-]*dstpu_moe_experts")
    spec = harness.load_json("layer_metrics",
                             "kernel.moe_latent_experts_roofline.json")
    assert (spec["params"]["work"], spec["params"]["phase"]) == \
        ("moe_experts", "decode")
    spec = harness.load_json("layer_metrics",
                             "moe.held_experts_read_share.json")
    assert (spec["reader"], spec["params"]["numerator"],
            spec["params"]["denominators"], spec["better"]) == (
        "counter_ratio", "serving/moe_experts_streamed",
        ["serving/moe_experts_held_steps"], "lower")
    spec = harness.load_json("layer_metrics", "moe.decode_buffer_fill.json")
    assert (spec["params"]["numerator"], spec["params"]["denominators"],
            spec["better"]) == ("serving/moe_assignments_held",
                                ["serving/moe_buffer_rows"], "higher")


def test_the_schedule_is_bursts_of_four_that_span_the_buckets(bench):
    """Four requests land together, whatever the seed; the first 16 finished
    are among the first bursts, whose prompts span the buckets; everything
    fits a slot."""
    from benchmarks import traffic_gen

    mix = harness.load_cell(CELL, bench)["traffic_file"]
    arr, buckets = mix["arrivals"], mix["server"]["buckets"]
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=32768)
        times = [p.arrival_time for p in planned]
        assert len(planned) == max(1, round(arr["rate"] * 51))
        for at in range(0, len(planned) - 3, 4):
            assert len(set(times[at:at + 4])) == 1
        first = [len(p.prompt) for p in planned[:24]]
        assert len({next(b for b in buckets if n <= b) for n in first}) >= 3, \
            first
        assert max(max(p.prompt) for p in planned[:16]) < 32768
        assert all(len(p.prompt) + p.max_new_tokens <= 3712 for p in planned)
        assert all(128 <= len(p.prompt) <= 3072 for p in planned)
        assert all(96 <= p.max_new_tokens <= arr["output"]["max"]
                   for p in planned)


def test_latent_experts_work_against_a_hand_worked_window():
    """Window [10, 11): 20 decode steps start in it; over the run a step read
    310 held experts and computed 440 pairs on average (5 sparse layers); one
    prompt of 1,000 tokens was admitted in it. An expert is TWO matrices of
    1,024 x 2,688."""
    s = FAMILY.shapes(CFG)
    spans = [{"name": "decode_step", "start": 10.0 + 0.05 * i,
              "end": 10.01 + 0.05 * i} for i in range(20)]
    spans += [{"name": "decode_step", "start": 9.5, "end": 9.51},
              {"name": "prefill", "start": 10.2, "end": 10.4}]
    obs = {"trace_span": [10.0, 11.0], "shapes": s, "spans": spans,
           "counters": {"serving/decode_steps": 1000,
                        "serving/moe_experts_touched": 310_000,
                        "serving/moe_assignments_held": 440_000,
                        "serving/moe_assignments": 1_760_000},
           "requests": [{"prompt_len": 1000, "admitted": 10.2},
                        {"prompt_len": 3000, "admitted": 9.0}]}
    # one work file for every family's grouped matmul: the family states an
    # expert's form (benchmarks/work/moe_experts.py)
    assert (s["expert_matrices"], s["expert_in_width"]) == (2, 1024)
    n_flops, n_bytes = harness.module("work", "moe_experts").work(obs)
    elems = 2 * 1024 * 2688
    pairs = 20 * 440 + 1000 * 22 * 0.25 * 5
    read = 20 * 310 + 1 * 5 * 128
    assert n_flops == pytest.approx(2.0 * elems * pairs)
    assert n_bytes == pytest.approx(2.0 * elems * read)
    # a sixth of what it counts for the same window where an expert is the
    # default three matrices of width x expert_mlp
    wide = dict(obs, shapes={k: v for k, v in dict(s, width=4096).items()
                             if k not in ("expert_matrices",
                                          "expert_in_width")})
    f3, b3 = harness.module("work", "moe_experts").work(wide)
    assert f3 == pytest.approx(6 * n_flops) and b3 == pytest.approx(6 * n_bytes)


def test_the_new_metric_files_through_their_readers():
    from benchmarks import trace_reduce

    def read(name, obs):
        spec = harness.load_json("layer_metrics", name + ".json")
        return harness.module("readers", spec["reader"]).read(
            spec["params"], obs)

    s = FAMILY.shapes(CFG)
    dot = ("%ragged-dot-none.3 = bf16[768,2688]{1,0} custom-call(%xs, %w, "
           "%metadata)")
    meta = "%ragged-dot-metadata.3 = s32[645]{0} custom-call(%groups)"
    reader = "%fusion.9 = f32[768,1024] fusion(%ragged-dot-none.4)"
    step = ("%dstpu_ssm_update.2 = (bf16[64,64,128]{2,1,0}, "
            "f32[5,64,64,128,128]{4,3,2,1,0}) custom-call(%a, %b)")
    tr = trace_reduce.Trace(
        {0: [(dot, 0.0, 0.002), (meta, 0.002, 0.003), (reader, 0.003, 0.004),
             (step, 0.004, 0.008)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0),
        # the roofline reads the decode program's events alone
        {0: [("jit_decode(4070338962791433473)", 0.0, 0.008)]})
    obs = {"trace": tr, "peak": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
           "shapes": s, "trace_span": [0.0, 1.0],
           "spans": [{"name": "decode_step", "start": 0.5, "end": 0.51}],
           "counters": {"serving/decode_steps": 10,
                        "serving/moe_experts_touched": 3200,
                        "serving/moe_experts_streamed": 3200,
                        "serving/moe_experts_held_steps": 6400,
                        "serving/moe_assignments_held": 4400,
                        "serving/moe_assignments": 17600,
                        "serving/moe_buffer_rows": 38400},
           "requests": []}
    assert read("kernel.moe_latent_experts_share", obs) == pytest.approx(25.0)
    # one step: 320 experts of 11.0 MB each read at 819 GB/s, of 2 ms
    assert read("kernel.moe_latent_experts_roofline", obs) == pytest.approx(
        100 * 320 * 2 * 2 * 1024 * 2688 / 819e9 / 0.002)
    assert read("moe.held_experts_read_share", obs) == pytest.approx(50.0)
    assert read("moe.decode_buffer_fill", obs) == pytest.approx(
        100 * 4400 / 38400)
    # a program without the kernel or the counters (another family, the
    # parent commit): nothing to read, and nothing raised
    bare = dict(obs, counters={"serving/moe_experts_streamed": 5,
                               "serving/moe_assignments_held": 5},
                trace=trace_reduce.Trace(
        {0: [(reader, 0.0, 0.004), (step, 0.004, 0.005)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0)))
    for name in NEW:
        assert read(name, bare) is None, name


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def built():
    """The tiny program in float32 and the reference's logits of 2 x 48 ids:
    seven layers ``MEM*EME``, 8 Mamba-2 heads of 16 in 4 groups, 3 of 16
    experts a token of which experts 4 to 7 are held, a latent of 32 on a
    stream of 64, token blocks of 16."""
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

    return model, params, ids, ref, jax.jit(step), cfg


def test_full_forward_matches_the_reference(built):
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params, ids, ref, _, _ = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: FAMILY.engine_logits(model, p, x))(
            params, ids)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


def test_one_group_or_one_norm_for_four_fails_the_comparison(built):
    """The draws tell the readings apart: the reference with ONE group of
    heads' B and C for all, or one gated norm over all of ``d_inner``, is
    another model's logits."""
    import jax
    import jax.numpy as jnp

    model, params, ids, ref, _, cfg = built
    c = model.config
    d_in, n = c.d_inner, c.mamba_d_state

    def shared_group(w):      # every group reads group 0's B and C
        z, x, b, cm, dt = jnp.split(
            w, [d_in, 2 * d_in, 2 * d_in + 4 * n, 2 * d_in + 8 * n], axis=-1)
        b, cm = (jnp.tile(v[..., :n], 4) for v in (b, cm))
        return jnp.concatenate([z, x, b, cm, dt], axis=-1)

    # at a stream of 64 the projection's columns are an eighth of what they
    # are at 4,096: drawn eight times as wide the state weighs as it does
    # at the published sizes
    wide = 8.0 * params["mamba"]["in_proj"]
    with jax.default_matmul_precision("highest"):
        apart, shared = (REFERENCE.forward_logits(
            dict(params, mamba=dict(params["mamba"], in_proj=w)), ids[:1], cfg)
            for w in (wide, shared_group(wide)))
    assert float(jnp.abs(apart - shared).max()) > 1e-2
    import numpy as np

    from deepspeed_tpu.models import mamba

    y, z = (jnp.asarray(np.random.RandomState(s).randn(2, 3, d_in),
                        jnp.float32) for s in (1, 2))
    w = params["mamba"]["gate_norm"][0]
    apart = mamba.gated_norm(y, z, w, c.eps, c.mamba_n_groups)
    whole = mamba.gated_norm(y, z, w, c.eps, 1)
    assert float(jnp.abs(apart - whole).max()) > 1e-2


def test_prefill_then_decode_through_the_slot_cache_matches_the_reference(
        built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    four leaves, by ``SlotKVCache``'s own tree: bucketed prefills on both
    sides of a bucket boundary (16 real positions fill the bucket of 16, 17
    take the bucket of 32 and two token blocks: the walk inside the program)
    written into slots, key-value rows as prefixes and the recurrent leaves
    whole, then two slots of unequal length decoding together with a third
    inactive, which is switched on for the last steps."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import insert_slot_row, write_slot_rows
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step, _ = built
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == ("k", "v", "ssm", "conv")
    assert slots.row_keys == ("k", "v")
    assert slots.recurrent_keys == ("ssm", "conv")
    assert {k: v.shape for k, v in slots.state.items()} == {
        "k": (1, 3, 2, 64, 16), "v": (1, 3, 2, 64, 16),
        "ssm": (3, 3, 8, 16, 16), "conv": (3, 3, 16, 128)}
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    for row, length, bucket, slot in ((0, 16, 16, 1), (1, 17, 32, 0)):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        # three sparse layers, three experts a token, real positions only
        assert int(cache["step_counters"][3]) == 3 * 3 * length
        assert cache["step_counters"].shape == (7,)
        for name in ("k", "v"):
            state[name] = write_slot_rows(state[name], cache[name], slot)
        for name in ("ssm", "conv"):
            state[name] = insert_slot_row(state[name], cache[name], slot)
        lengths[slot] = length
    for i in range(8):
        on = [True, True, i >= 6]
        if i == 6:      # a third request joins: a prefix of row 0
            cache = model.init_cache(1, 16, dtype=jnp.float32)
            cache["valid_len"] = jnp.asarray(9)
            _, cache = step(params, ids[:1, :16], cache)
            for name in ("k", "v"):
                state[name] = write_slot_rows(state[name], cache[name], 2)
            for name in ("ssm", "conv"):
                state[name] = insert_slot_row(state[name], cache[name], 2)
            lengths[2] = 9
        active = jnp.asarray(on)
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([ids[1, lengths[0]], ids[0, lengths[1]],
                              ids[0, lengths[2]]])
        cache = dict(state, index=idx, valid_len=active.astype(jnp.int32),
                     slot_walk=slot_walk(idx, active))
        logits, cache = step(params, tokens[:, None], cache)
        np.testing.assert_allclose(logits[0, 0], ref[1, lengths[0]], **TOL)
        np.testing.assert_allclose(logits[1, 0], ref[0, lengths[1]], **TOL)
        live = sum(on)
        counts = [int(n) for n in cache["step_counters"]]
        assert counts[3] == 3 * 3 * live
        # the buffer's rows (worst case: the compact form would be larger)
        # and the experts held, three sparse layers
        assert counts[5:] == [3 * 3 * 3, 3 * 4]
        if on[2]:
            np.testing.assert_allclose(logits[2, 0], ref[0, lengths[2]], **TOL)
        else:       # the idle slot's state and tails do not move
            for name in ("ssm", "conv"):
                np.testing.assert_array_equal(np.asarray(cache[name])[:, 2],
                                              np.asarray(state[name])[:, 2])
        lengths[np.asarray(on)] += 1
        state = {name: cache[name] for name in state}
    assert list(lengths) == [25, 24, 11]


def test_the_four_shares_add_up_to_the_uncut_layer(built):
    """The router, the choice and the normalisation run over all 16 experts;
    each share sums its own four; ``W_up`` is linear, so the shares' routed
    parts, with the shared expert counted once, give the reference's layer
    with all 16 held."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import moe_ffn
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig

    model, params, _, _, _, cfg = built
    rng = np.random.RandomState(3)
    blk = {n: v[1] for n, v in params["moe"].items()}
    lat, m = model.config.moe_latent_size, model.config.moe_intermediate_size
    up = jnp.asarray(rng.randn(16, lat, m) * 0.2, jnp.float32)
    down = jnp.asarray(rng.randn(16, m, lat) * 0.2, jnp.float32)
    u = jnp.asarray(rng.randn(2, 12, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        shared = jnp.square(jax.nn.relu(u @ blk["shared_up"])) \
            @ blk["shared_down"]
        total = -3 * shared
        for r in range(4):
            c = NemotronHConfig.tiny(held=(4 * r, 4))
            part, counts = moe_ffn.ffn(
                u, dict(blk, expert_up=up[4 * r:4 * r + 4],
                        expert_down=down[4 * r:4 * r + 4]),
                moe_ffn.SPARSE, None, c)
            assert int(counts[3]) == 2 * 12 * 3
            total = total + part
        whole = REFERENCE._latent_moe(
            u, blk, (up[None], down[None]), 0,
            dict(cfg, n_routed_experts=16, experts_held_first=0))
    assert float(jnp.abs(whole - shared).max()) > 1e-2   # the routed part
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("live", [6, 60])
def test_a_decode_step_and_a_prompt_block_give_the_same_bits(live):
    """64 tokens, 3 of 64 experts each, 8 held, as a decode step of 64 slots
    (``T == 1``: the worst-case buffer of 192 rows, no switch) and as one
    prompt block (``T == 64``: the compact buffer of 128 rows with 6 tokens
    live, the full one when 60 outgrow it): the same bits either way, and
    the step's counters say what it ran."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import moe_ffn
    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    c = NemotronHConfig.tiny(num_experts=64, num_experts_per_tok=3,
                             held=(8, 8), hybrid_override_pattern="E")
    model = NemotronHModel(c, compute_dtype=jnp.float32)
    blk = {n: v[0] for n, v in model.init(
        jax.random.PRNGKey(1))["moe"].items()}
    # a router that crowds this share's experts when many tokens are live
    blk["select_bias"] = blk["select_bias"].at[8:16].set(1.0)
    u = jnp.asarray(np.random.RandomState(live).randn(64, 1, 64), jnp.float32)
    valid = (jnp.arange(64) < live)[:, None]
    layer = jax.jit(lambda u, valid: moe_ffn.ffn(
        u, blk, moe_ffn.SPARSE, valid, c, buffer_counters=True))
    y, n = layer(u, valid)
    y_block, n_block = layer(u.reshape(1, 64, 64), valid.reshape(1, 64))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(y_block).reshape(64, 1, 64))
    # a slot that does not decode gets the shared expert and no routed one
    shared = jnp.square(jax.nn.relu(u @ blk["shared_up"])) @ blk["shared_down"]
    routed = np.asarray(y - shared)
    assert np.abs(routed[:live]).max() > 1e-5 > np.abs(routed[live:]).max()
    assert [int(v) for v in n[:5]] == [int(v) for v in n_block[:5]]
    assert (int(n[5]), int(n[6])) == (192, 8)
    # PROMPT_COUNTERS behind them: one block, spilled when its pairs outgrow
    # the compact buffer, not empty
    held = int(n[2])
    assert [int(v) for v in n_block[7:]] == [1, int(held > 128), 0]
    assert (held <= 128) == (live == 6)


def test_the_geometry_is_read_a_leaf():
    """At the published sizes ``SlotKVCache`` takes the leaves by the model's
    declaration: key-value rows of ONE layer of two heads, float32 state and
    bf16 tails of five; 64 slots of 4,096 rows are 1.36 + 0.27 GB."""
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
    assert slots.state["k"].shape == slots.state["v"].shape == \
        (1, 64, 2, 4096, 128)
    assert slots.state["ssm"].shape == (5, 64, 128, 64, 128)
    assert slots.state["ssm"].dtype == jnp.float32
    assert slots.state["conv"].shape == (5, 64, 240, 128)
    rows = 2 * 64 * 2 * 4096 * 128 * 2
    assert slots.hbm_bytes() == rows + 64 * FAMILY.shapes(CFG)[
        "state_bytes_per_slot"] == 268_435_456 + 1_361_838_080


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
            shapes["mamba_layers"], shapes["ssm_groups"],
            shapes["latent"]) == (16, 4, 1, 7, 3, 3, 4, 32)
    # three experts a token a sparse layer, a quarter of them held here
    assert counters["serving/moe_assignments"] == \
        3 * 3 * counters["serving/slot_iterations_active"]
    assert 0 < counters["serving/moe_assignments_held"] < \
        counters["serving/moe_assignments"]
    # four experts held a sparse layer a step; four slots x three a token
    assert counters["serving/moe_experts_held_steps"] == \
        3 * 4 * counters["serving/decode_steps"]
    assert counters["serving/moe_buffer_rows"] == \
        3 * 4 * 3 * counters["serving/decode_steps"]
    assert counters["serving/moe_experts_streamed"] <= \
        counters["serving/moe_experts_held_steps"]
    assert counters["serving/prefill_rows_run"] > \
        counters["serving/prefill_rows_padding"] > 0
    # on a CPU the Mamba layers of the decode program are traced split, and a
    # latent layer was traced
    assert counters["ssm/traced_split_step"] > 0
    assert counters["ssm/traced_step_folded_groups"] == 0
    assert counters["moe/traced_latent"] > 0


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["moe.expert_live_share"]["value"] <= 100
    assert 0 < line["metrics"]["moe.held_experts_read_share"]["value"] <= 100
    assert 0 < line["metrics"]["moe.decode_buffer_fill"]["value"] <= 100
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"] if sources[m] == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
