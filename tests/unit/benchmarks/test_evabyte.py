"""The tenth family, ``evabyte``, in the benchmark: its configuration file
against the published keys and its stated cut, its sizes against the hand
count, its cell's listing and its mix's parameters, the two ``work`` files on
a hand-made window, the program against the plain reference at the family's
tiny size in float32 (full forward on all eight heads; prefill then decode
through the slot programs' pieces across a bucket boundary, chunk boundaries
and two window boundaries), and a tiny in-process rehearsal of its cell
(``rehearse=True``: no device guard, never a result). What it reads of
``BENCHMARK.json`` it reads through the ``bench`` fixture, as accepted and
with a cell appended (appended.py), and it speaks of its own cell only: that
the cell is listed, never that it is last or alone.

It starts no subprocess and describes no TPU topology.
"""
import json
import time

import pytest

from benchmarks import harness, trace_reduce
from benchmarks import run as bench_run

BENCH = harness.benchmark_json()
CELL = "evabyte.serve-byte-files"
CFG = harness.load_json("configs", "evabyte.json")
FAMILY = harness.module("families", "evabyte")
REFERENCE = harness.module("reference", "evabyte")
PEAK = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
# the published config.json (catalog row EvaByte), key for key
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048}
CUT = {"num_hidden_layers": 8}
TOL = dict(rtol=1e-4, atol=1e-5)
KIND_WIDE = (
    "entry.compiles_in_window.serve", "entry.trace_ms", "entry.lower_ms",
    "entry.backend_compile_ms", "entry.cache_load_ms", "entry.cache_misses",
    "entry.retraces", "entry.setup_weights_ms", "entry.setup_warmup_ms",
    "entry.setup_warmup_repeat_ms", "entry.traces_after_warm",
    "sched.queue_wait_p95_ms", "sched.batch_fill", "sched.schedule_host_ms",
    "sched.iter_schedule_p95_ms", "step.prefill_ms", "step.decode_ms",
    "step.prefill_chunk_ms", "step.upload_host_ms", "step.launch_host_ms",
    "step.fetch_wait_ms", "step.commit_host_ms", "step.decode_overlap_share",
    "device.idle_share.serve", "host.stall_ms.serve",
    "host.gc_pause_ms.serve", "step.decode_mfu", "step.prefill_mfu")
OWN = ("kernel.eva_decode_roofline", "kernel.eva_decode_share",
       "kernel.eva_prefill_roofline", "kernel.eva_prefill_share",
       "cache.eva_live_share", "mixer.eva_split_steps")
LISTED = KIND_WIDE + ("step.prefill_pad_share",) + OWN
# a row a token is not this cache: the engine's count by lengths is barred
BARRED = ("kernel.decode_attn_live_share", "kernel.decode_attn_share",
          "kernel.decode_attn_roofline", "cache.window_live_share",
          "kernel.gqa_prefill_roofline", "kernel.flash_roofline",
          "kernel.moe_experts_roofline", "moe.expert_live_share")


def test_the_configuration_file_holds_the_published_keys(bench):
    for key, value in PUBLISHED.items():
        assert key in CFG, key
        assert CFG[key] == (CUT[key] if key in CUT else value), key
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["num_hidden_layers_published"] == 32
    for needle in ("32 -> 8", "No width is cut", "1,630,932,992",
                   "6,488,330,240", "202,391,552", "536.9 MB"):
        assert needle in CFG["reduced_why"], needle
    entry = next(c for c in bench["configs"] if c["name"] == "evabyte")
    assert entry["source"] == CFG["source"] == (
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json")
    assert entry["reduced"] == CFG["reduced"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/evabyte.json"
    # every ASSUMED reading of ISSUE 61 with its alternative, and what waits
    assert set(CFG["assumed"]) >= {
        "norm", "residual", "rotation", "summary_position",
        "pooling_parameters", "pooling_scale", "pooled_key_offset",
        "one_softmax", "visibility", "head_layout", "initial_values",
        "weights_dtype", "left_out", "published_code"}
    for key in ("norm", "rotation", "summary_position", "pooling_parameters",
                "pooling_scale", "pooled_key_offset", "head_layout"):
        assert "Alternative" in CFG["assumed"][key], key
    assert "self-speculative decoding" in CFG["assumed"]["left_out"]
    assert "four pipeline stages" in CFG["deployment"]
    assert "LAST stage" in CFG["deployment"]


def test_shapes_against_the_hand_count():
    """ISSUE 61's arithmetic: the four attention matrices 67,108,864, the
    pooling's direction and offset 8,192, the gated MLP 135,266,304, two
    norms 8,192: a layer 202,391,552; embedding, head of eight times 320
    columns and final norm 11,800,576."""
    s = FAMILY.shapes(CFG)
    attn, pool, mlp, norms = 4 * 4096 * 4096, 2 * 32 * 128, \
        3 * 4096 * 11008, 2 * 4096
    assert (attn, pool, mlp, norms) == (67_108_864, 8_192, 135_266_304, 8_192)
    layer = attn + pool + mlp + norms
    top = 320 * 4096 + 4096 * 2560 + 4096
    assert (layer, top) == (202_391_552, 11_800_576)
    assert s["params"] == s["active_params"] == 8 * layer + top == \
        1_630_932_992
    assert s["published_params"] == 32 * layer + top == 6_488_330_240
    assert 3.26e9 < 2 * s["params"] < 3.27e9
    assert (s["layers"], s["hidden"], s["heads"], s["kv_heads"],
            s["head_dim"], s["mlp"], s["vocab"], s["positions"], s["window"],
            s["chunk"], s["pred_heads"]) == \
        (8, 4096, 32, 32, 128, 11008, 320, 32768, 2048, 16, 8)
    assert "cache_row_dim" not in s
    model = FAMILY.build_model(CFG, {})
    assert model.num_params() == s["params"]
    # a slot at 32,768 positions: window rows and summary rows alike
    import jax
    import jax.numpy as jnp

    cache = jax.eval_shape(
        lambda: model.init_cache(1, 32768, dtype=jnp.bfloat16))
    assert cache["k_win"].shape == cache["k_sum"].shape == \
        (8, 1, 32, 2048, 128)
    per_slot = sum(cache[k].size * 2 for k in model.slot_state_keys)
    assert per_slot == 536_870_912


def test_the_cell_is_one_chip_and_is_listed_where_it_reports(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("evabyte", "serve-byte-files", 1)
    assert len(cell["why"]) <= 200
    for e in bench["end_to_end"]:
        if e["name"] in ("serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms"):
            assert CELL in e["workloads"]
        elif "workloads" in e:
            assert CELL not in e["workloads"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in LISTED:
        assert CELL in by_name[name]["workloads"], name
    for name in BARRED:
        assert CELL not in by_name[name]["workloads"], name
    assert {m["name"] for m in harness.metrics_of(CELL, "per_layer", bench)} \
        == set(LISTED)
    for name in OWN:
        m, spec = by_name[name], harness.load_json("layer_metrics",
                                                   name + ".json")
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (spec["unit"], spec["better"], spec["source"],
                                spec["layer"], spec["moves"])
        assert CELL in m["workloads"]
    for name, pattern, work, moves in (
            ("kernel.eva_decode_roofline", "dstpu_eva_decode_step",
             "eva_decode", "itl_p95_ms"),
            ("kernel.eva_prefill_roofline", "dstpu_eva_prefill",
             "eva_prefill", "ttft_p95_ms")):
        spec = harness.load_json("layer_metrics", name + ".json")
        assert spec["params"]["pattern"] == r"^%[\w.\-]*" + pattern
        assert (spec["params"]["work"], spec["moves"], spec["unit"]) == \
            (work, moves, "%")
        share = harness.load_json("layer_metrics",
                                  name.replace("roofline", "share") + ".json")
        assert share["params"]["pattern"] == spec["params"]["pattern"]
    assert harness.load_json("layer_metrics", "mixer.eva_split_steps.json")[
        "params"]["counter"] == "eva/traced_split_step"
    ratio = harness.load_json("layer_metrics", "cache.eva_live_share.json")
    assert (ratio["params"]["numerator"], ratio["params"]["denominators"]) \
        == ("serving/eva_rows_live", ["serving/eva_rows_fetched"])


def test_the_mix_is_files_of_bytes_in_bursts_of_four(bench):
    """The mix's parameters as ISSUE 61 gives them, and its schedule: 28
    requests or more in the window, everything inside a slot and the largest
    bucket, every bucket whole windows."""
    from benchmarks import traffic_gen

    mix = harness.load_cell(CELL, bench)["traffic_file"]
    assert mix["kind"] == "serve_open_loop"
    assert mix["server"] == {"dtype": "bf16", "num_slots": 12,
                             "max_len": 32768,
                             "buckets": [4096, 8192, 16384, 24576, 32768],
                             "trace_seconds": 3.0}
    assert all(b % CFG["window_size"] == 0 for b in mix["server"]["buckets"])
    arr = mix["arrivals"]
    assert arr["prompt"] == {"dist": "lognormal", "median": 8192,
                             "sigma": 0.8, "min": 2048, "max": 30720}
    out = arr["output"]
    assert (out["dist"], out["median"], out["sigma"], out["min"]) == \
        ("lognormal", 448, 0.3, 256) and out["max"] in (768, 640, 512)
    assert arr["max_total"] == 32768 and arr["burst_size"] == 4
    assert "shared_prefix" not in arr and 0.3 <= arr["rate"] <= 1.5
    assert 0 < mix["check"]["mean_gap_tol"] < mix["check"]["logit_tol"]
    assert len(mix["check"]["why"]) > 80 and len(mix["what"]) > 80
    for seed in (1, 2**31 + 5):
        planned = traffic_gen.open_loop_requests(arr, seed=seed, seconds=51,
                                                 vocab_size=320)
        assert len(planned) >= 28
        assert all(2048 <= len(p.prompt) <= 30720 for p in planned)
        assert all(1 <= p.max_new_tokens <= out["max"] for p in planned)
        assert all(len(p.prompt) + p.max_new_tokens <= 32768
                   for p in planned)
        assert max(max(p.prompt) for p in planned[:16]) < 320
    # the requests the check replays reach past one, four and eight windows
    first = sorted(len(p.prompt) for p in planned[:16])
    assert first[0] < 4096 and any(n > 8192 for n in first) \
        and any(n > 16384 for n in first)


# ------------------------------------------------- the two ``work`` files
SHAPES = FAMILY.shapes(CFG)


def _request(n, admitted, first_token, token_times=None):
    return {"prompt_len": n, "admitted": admitted, "first_token": first_token,
            "token_times": token_times or [first_token]}


def _obs(requests, trace=None, counters=None):
    return {"trace_span": [10.0, 13.0], "shapes": SHAPES, "peak": PEAK,
            "requests": requests, "trace": trace, "counters": counters or {},
            "spans": []}


def _read(name, obs):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _trace(events):
    return trace_reduce.Trace({0: events}, [("bench/window", 10.0, 13.0)],
                              (10.0, 13.0))


def test_eva_decode_work_on_a_hand_made_window():
    """A decoding slot at context p reads ``(p mod W) + 1`` window rows and
    ``128 floor(p / W)`` summary rows of keys and of values on each of the 8
    layers, 32 x 128 elements a row, 4 FLOPs an element, and writes two rows
    of each; the first token of a request is the prefill's, tokens outside
    the window count nothing."""
    work = harness.module("work", "eva_decode").work
    # prompt 12000: tokens 1, 2 inside the window at contexts 12000, 12001
    # (window 5: 1761 and 1762 rows, 640 summaries); prompt 100: token 1
    # inside at context 100 (the first window: no summary)
    reqs = [_request(12000, 9.0, 9.5, [9.5, 10.5, 11.0, 13.5]),
            _request(100, 11.0, 11.5, [11.5, 12.0])]
    rows = (1761 + 640) + (1762 + 640) + 101
    flops, nbytes = work(_obs(reqs))
    assert flops == 4 * rows * 8 * 32 * 128
    assert nbytes == 2 * 2 * (rows + 2 * 3) * 8 * 32 * 128
    assert work(_obs([])) == (0.0, 0.0)
    # memory binds it: one FLOP a byte
    assert flops / nbytes < PEAK["bf16_tflops"] * 1e3 / PEAK["hbm_gbps"]
    least = nbytes / (PEAK["hbm_gbps"] * 1e9)
    kernel = ("%dstpu_eva_decode_step.7 = (bf16[12,32,128], bf16[8,12,32,"
              "2048,128], bf16[8,12,32,2048,128]) custom-call(%a)")
    other = "%fusion.3 = bf16[12,32,128] fusion(%dstpu_eva_decode_step.7)"
    for slowdown in (1.0, 3.0):
        obs = _obs(reqs, _trace([(kernel, 10.2, 10.2 + least * slowdown),
                                 (other, 11.0, 11.5)]))
        assert _read("kernel.eva_decode_roofline", obs) == \
            pytest.approx(100.0 / slowdown)
        assert _read("kernel.eva_decode_share", obs) == pytest.approx(
            100.0 * least * slowdown / (least * slowdown + 0.5))
    none = _obs(reqs, _trace([(other, 11.0, 11.5)]))
    assert _read("kernel.eva_decode_roofline", none) is None
    assert _read("kernel.eva_decode_share", none) is None
    # the counters' metrics: nothing to read of a program without them
    assert _read("cache.eva_live_share", _obs(reqs)) is None
    assert _read("mixer.eva_split_steps", _obs(reqs)) is None
    obs = _obs(reqs, counters={"serving/eva_rows_live": 900,
                               "serving/eva_rows_fetched": 1000,
                               "eva/traced_split_step": 0})
    assert _read("cache.eva_live_share", obs) == pytest.approx(90.0)
    assert _read("mixer.eva_split_steps", obs) == 0


def test_eva_prefill_work_on_a_hand_made_window():
    """A prompt of n bytes whose prefill lies whole in the window passes
    ``ceil(n / W)`` blocks: block i's r real positions attend r (r + 1) / 2
    pairs inside it and r x 128 i summaries, 4 x 128 FLOPs a pair a head a
    layer; a cut prefill counts nothing, and a window without a whole one
    reads None."""
    work = harness.module("work", "eva_prefill").work
    n = 5000        # blocks of 2048, 2048 and 904
    pairs = 2 * (2048 * 2049 // 2) + 904 * 905 // 2 \
        + 2048 * 128 + 904 * 256
    rows = 4 * 5000 + 2 * (128 + 256)
    assert work(_obs([_request(n, 10.5, 11.0)])) == pytest.approx(
        (4.0 * pairs * 8 * 32 * 128, 2.0 * rows * 8 * 32 * 128))
    for cut in ([_request(n, 9.9, 10.4)], [_request(n, 12.8, 13.2)],
                [_request(n, 12.0, None)], []):
        assert work(_obs(cut)) == (0.0, 0.0)
    flops, _ = work(_obs([_request(20000, 10.1, 11.0)]))
    least = flops / (PEAK["bf16_tflops"] * 1e12)
    kernel = "%dstpu_eva_prefill.5 = bf16[1,2048,4096]{2,1,0} custom-call(%q)"
    loop = "%fusion.378 = f32[1,32,128,16] fusion(%p)"
    for slowdown in (1.0, 1.7):
        took = least * slowdown
        obs = _obs([_request(20000, 10.1, 10.3 + took)],
                   _trace([(kernel, 10.2, 10.2 + took), (loop, 12.0, 12.1)]))
        assert _read("kernel.eva_prefill_roofline", obs) == \
            pytest.approx(100.0 / slowdown)
        assert _read("kernel.eva_prefill_share", obs) == \
            pytest.approx(100.0 * took / (took + 0.1))
    none = _obs([_request(n, 9.0, 10.4)], _trace([(loop, 12.0, 12.1)]))
    assert _read("kernel.eva_prefill_roofline", none) is None
    assert _read("kernel.eva_prefill_share", none) is None
    assert _read("kernel.eva_prefill_roofline", _obs([], None)) is None


# ------------------------------------------- the program and the reference
@pytest.fixture(scope="module")
def built():
    """The family's tiny program in float32 (two layers, window 16, chunks of
    4) and the reference's eight heads' logits of 2 x 64 ids: four
    windows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = FAMILY.tiny(CFG)
    model = FAMILY.build_model(cfg, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 320, (2, 64)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: REFERENCE.forward_all_heads(p, x, cfg))(
            params, ids)

    def step(params, ids, cache):
        with jax.default_matmul_precision("highest"):
            return model.forward_with_cache(params, ids, cache)

    return model, params, ids, ref, jax.jit(step), cfg


def test_full_forward_matches_the_reference_on_all_eight_heads(built):
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, params, ids, ref, _, cfg = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: model.all_logits(
            p, model.forward_hidden(p, x)))(params, ids)
        head0 = FAMILY.engine_logits(model, params, ids)
        served = REFERENCE.forward_logits(params, ids, cfg)
    assert ref.shape == (2, 64, 8, 320)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)
    np.testing.assert_allclose(head0, ref[:, :, 0], **TOL)
    np.testing.assert_allclose(served, ref[:, :, 0], **TOL)
    assert float(REFERENCE.loss(params, ids[:, :-1], ids[:, 1:], cfg)) > 1.0


def test_prefill_then_decode_through_the_slot_cache_matches_the_reference(
        built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    four leaves: bucketed prefills (37 real positions in a bucket of 48:
    three blocks, the last one mid-chunk; 16 in a bucket of 16: ends on a
    window's last row) written into slots whole, then the two slots decoding
    together with a third inactive, 20 steps: across chunk boundaries and two
    window boundaries for the first slot."""
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.attention import insert_slot_row
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step, _ = built
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == slots.recurrent_keys == \
        ("k_win", "v_win", "k_sum", "v_sum")
    assert (slots.restart_window, slots.summary_chunk, slots.pair) == \
        (16, 4, 1)
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    for row, length, bucket, slot in ((0, 37, 48, 1), (1, 16, 16, 0)):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        pad = jnp.zeros((1, bucket), jnp.int32).at[:, :length].set(
            ids[row:row + 1, :length])
        logits, cache = step(params, pad, cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1, 0],
                                   **TOL)
        for name in state:
            state[name] = insert_slot_row(state[name], cache[name], slot)
        lengths[slot] = length
    for _ in range(20):
        active = jnp.asarray([True, True, False])
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([ids[1, lengths[0]], ids[0, lengths[1]], 0])
        cache = dict(state, index=idx, valid_len=active.astype(jnp.int32),
                     slot_walk=slot_walk(idx, active))
        logits, cache = step(params, tokens[:, None], cache)
        np.testing.assert_allclose(logits[0, 0], ref[1, lengths[0], 0], **TOL)
        np.testing.assert_allclose(logits[1, 0], ref[0, lengths[1], 0], **TOL)
        lengths[:2] += 1
        state = {name: cache[name] for name in state}
    assert list(lengths) == [36, 57, 0]


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
    assert (shapes["layers"], shapes["window"], shapes["chunk"],
            shapes["pred_heads"], shapes["vocab"]) == (2, 16, 4, 8, 320)
    assert counters["eva/traced_split_step"] > 0 == \
        counters["eva/traced_fused_step"]
    assert 0 < counters["serving/eva_rows_live"] <= \
        counters["serving/eva_rows_fetched"]


def test_the_rehearsal_prints_the_cells_metrics(rehearsed, bench):
    """The result lines of that run, whatever else ``BENCHMARK.json`` lists
    behind this cell."""
    cell, out = rehearsed
    line = bench_run.result_line(cell, bench, out, trace=True)
    assert 0 < line["metrics"]["cache.eva_live_share"]["value"] <= 100
    assert line["metrics"]["mixer.eva_split_steps"]["value"] > 0   # a CPU
    assert 0 < line["metrics"]["step.prefill_pad_share"]["value"] < 100
    assert set(line["metrics"]) <= set(LISTED) | {
        "serve_tokens_per_s", "ttft_p95_ms", "itl_p95_ms", "setup_s"}
    # no device plane on this backend: the trace readers leave theirs out
    sources = {m["name"]: m["source"] for m in bench["per_layer"]}
    assert not [m for m in line["metrics"]
                if sources.get(m) == "device_trace"]
    line0 = bench_run.result_line(cell, bench, out, trace=False)
    assert set(line0["metrics"]) == {"serve_tokens_per_s", "ttft_p95_ms",
                                     "itl_p95_ms", "setup_s"}
    json.dumps(line), json.dumps(line0)
