"""The metrics that read ONE jitted program of a traced window: the expert
matmuls' rooflines by program (decode, prefill) and by the work's kernel
whoever wrote it, and each program's share of the chip's peak
(``step.*_mfu``). Hand-made events, as tests/unit/benchmarks/test_benchmark.py.

One module: no subprocess, no TPU topology, nothing at module level that
loads libtpu.
"""
import re

import pytest

from benchmarks import flops, harness, trace_reduce
from tests.unit.benchmarks import appended

PEAK = {"bf16_tflops": 197.0, "hbm_gbps": 819.0}
# the device's module line names a run of a jitted program so
DECODE, PREFILL, TRAIN = ("jit_decode(4070338962791433473)",
                          "jit_prefill_1024(1153246073422034925)",
                          "jit_train_step(7112950127338211009)")
CHUNK = "jit_chunk_prefill_512(330981127)"
XLA = "%ragged-dot-none.3 = bf16[1408,2688]{1,0} custom-call(%xs, %w, %meta)"
OURS = ("%dstpu_moe_experts_decode.2 = bf16[1408,2688]{1,0} "
        "custom-call(%xs, %w, %sizes)")
META = "%ragged-dot-metadata.1 = s32[645]{0} custom-call(%groups)"
# a kernel's result among another event's operands does not make it one
READS_XLA = "%fusion.9 = f32[1408,1024] fusion(%ragged-dot-none.4)"
READS_OURS = "%fusion.8 = f32[1408,1024] fusion(%dstpu_moe_experts_decode.2)"
NEW = ("kernel.moe_experts_prefill_roofline",
       "kernel.moe_latent_experts_prefill_roofline", "step.decode_mfu",
       "step.prefill_mfu", "step.train_mfu")
EXPERT_METRICS = {"exaone_moe": "kernel.moe_experts",
                  "nemotron_h": "kernel.moe_latent_experts"}
CONFIGS = {"exaone_moe": "k-exaone-236b-a23b",
           "nemotron_h": "nemotron-3-super-120b-a12b"}


def _shapes(family):
    cfg = harness.load_json("configs", CONFIGS[family] + ".json")
    return harness.module("families", family).shapes(cfg)


def _expert_elems(s):
    """The elements of one expert, by the family's own statement of it."""
    return (s.get("expert_matrices", 3)
            * s.get("expert_in_width", s["hidden"]) * s["expert_mlp"])


def _read(metric, obs):
    spec = harness.load_json("layer_metrics", metric + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _obs(family, device, programs, **more):
    """A window [0, 1) on both clocks: 10 decode steps start in it, and a
    prompt of 1,000 tokens is admitted in it."""
    tr = trace_reduce.Trace({0: device}, [("bench/window", 0.0, 1.0)],
                            (0.0, 1.0), {0: programs})
    obs = {"trace": tr, "peak": PEAK, "shapes": _shapes(family),
           "trace_span": [0.0, 1.0],
           "spans": [{"name": "decode_step", "start": 0.05 * i,
                      "end": 0.05 * i + 0.01} for i in range(10)],
           "counters": {"serving/decode_steps": 100,
                        "serving/moe_experts_touched": 3200,
                        "serving/moe_assignments_held": 4400,
                        "serving/moe_assignments": 17600},
           "requests": [{"prompt_len": 1000, "admitted": 0.5,
                         "token_times": [0.6, 0.7, 0.8, 1.2]},
                        {"prompt_len": 3000, "admitted": -4.0,
                         "token_times": [-3.0, 0.1, 0.2]}]}
    return dict(obs, **more)


# ----------------------------------- the work's kernel, whoever wrote it
@pytest.mark.parametrize("family", sorted(EXPERT_METRICS))
def test_the_experts_metrics_read_xlas_kernel_and_our_own_alike(family):
    """The same seconds under XLA's ``ragged-dot-<epilogue>`` and under a
    Pallas call named ``dstpu_moe_experts<_suffix>`` read the same roofline
    and share; ``ragged-dot-metadata`` and an event whose OPERAND carries
    either name count nothing."""
    metric = EXPERT_METRICS[family]
    got = {}
    for kernel, reader in ((XLA, READS_XLA), (OURS, READS_OURS)):
        obs = _obs(family, [(kernel, 0.0, 0.002), (META, 0.002, 0.003),
                            (reader, 0.003, 0.008)], [(DECODE, 0.0, 0.01)])
        got[kernel] = (_read(metric + "_roofline", obs),
                       _read(metric + "_share", obs))
        silent = _obs(family, [(META, 0.002, 0.003), (reader, 0.003, 0.008)],
                      [(DECODE, 0.0, 0.01)])
        for name in ("_roofline", "_share", "_prefill_roofline"):
            assert _read(metric + name, silent) is None, (kernel, name)
    assert got[XLA] == got[OURS]
    roofline, share = got[XLA]
    assert share == pytest.approx(25.0)         # 2 of 8 busy milliseconds
    # ten steps of 32 experts read once each, at 819 GB/s, of 2 ms
    s = _shapes(family)
    elems = _expert_elems(s)
    assert roofline == pytest.approx(100 * 10 * 32 * 2 * elems / 819e9 / 0.002)


@pytest.mark.parametrize("pattern_of", [
    "kernel.moe_experts_roofline", "kernel.moe_experts_share",
    "kernel.moe_latent_experts_roofline", "kernel.moe_latent_experts_share",
    "kernel.moe_experts_prefill_roofline",
    "kernel.moe_latent_experts_prefill_roofline"])
def test_the_experts_pattern_is_one_and_anchors_at_the_result(pattern_of):
    rx = re.compile(harness.load_json(
        "layer_metrics", pattern_of + ".json")["params"]["pattern"])
    for name in (XLA, OURS, "%dstpu_moe_experts.1 = bf16[8] custom-call(%x)",
                 "%wrapped.dstpu_moe_experts_prefill = bf16[8] custom-call()",
                 "%ragged-dot-bias.12 = bf16[8] custom-call(%x)"):
        assert rx.search(name), name
    for name in (META, READS_XLA, READS_OURS, "ragged-dot-none.3",
                 "%dstpu_moe_route.1 = s32[8] custom-call(%x)",
                 "%fusion.2 = bf16[8] fusion(%p), "
                 'metadata={op_name="jit(decode)/dstpu_moe_experts/dot"}'):
        assert not rx.search(name), name


# ------------------------------------------- one program of the window
def _two_programs(family):
    """Decode ran 0.0 to 0.1 with 4 ms of the experts' kernel, a prefill 0.2
    to 0.5 with 60 ms of it (and 20 ms more of it outside any program's
    event: nobody's), and a chunked prefill 0.6 to 0.7 with 10 ms."""
    return _obs(family,
                [(XLA, 0.010, 0.014), ("%fusion.1 = bf16[8] fusion()", 0.014,
                                       0.050),
                 (XLA, 0.200, 0.260), ("%fusion.2 = bf16[8] fusion()", 0.260,
                                       0.500),
                 (XLA, 0.510, 0.530), (XLA, 0.600, 0.610)],
                [(DECODE, 0.0, 0.1), (PREFILL, 0.2, 0.5), (CHUNK, 0.6, 0.7)])


@pytest.mark.parametrize("family", sorted(EXPERT_METRICS))
def test_each_phase_reads_its_own_seconds_and_its_own_work(family):
    obs = _two_programs(family)
    tr, work = obs["trace"], harness.module("work", "moe_experts").work
    dec = harness.load_json("layer_metrics", EXPERT_METRICS[family]
                            + "_roofline.json")["params"]
    pre = harness.load_json("layer_metrics", EXPERT_METRICS[family]
                            + "_prefill_roofline.json")["params"]
    assert (dec["phase"], pre["phase"]) == ("decode", "prefill")
    assert dec["pattern"] == pre["pattern"] and dec["work"] == pre["work"]
    s_dec = trace_reduce.matched_seconds(tr, dec["pattern"], dec["program"])
    s_pre = trace_reduce.matched_seconds(tr, pre["pattern"], pre["program"])
    s_all = trace_reduce.matched_seconds(tr, dec["pattern"])
    assert s_dec == pytest.approx(0.004) and s_pre == pytest.approx(0.070)
    # what ran under no program's event is in the unsplit reading alone
    assert s_all == pytest.approx(s_dec + s_pre + 0.020)
    (f_dec, b_dec), (f_pre, b_pre) = work(obs, "decode"), work(obs, "prefill")
    f_all, b_all = work(obs)
    assert f_all == pytest.approx(f_dec + f_pre) and f_dec > 0 < f_pre
    assert b_all == pytest.approx(b_dec + b_pre) and b_dec > 0 < b_pre
    assert work(obs, None) == (f_all, b_all)
    with pytest.raises(ValueError):
        work(obs, "train")
    # decode: 10 steps of 32 experts and 44 pairs; prefill: one prompt of
    # 1,000 tokens, a quarter of its pairs held, every held expert read
    s = obs["shapes"]
    elems = _expert_elems(s)
    assert (f_dec, b_dec) == (pytest.approx(2 * elems * 440),
                              pytest.approx(2 * elems * 320))
    assert (f_pre, b_pre) == (
        pytest.approx(2 * elems * 1000 * s["experts_per_token"] * 0.25
                      * s["sparse_layers"]),
        pytest.approx(2 * elems * s["sparse_layers"] * s["experts_held"]))
    least = lambda f, b: flops.roofline_seconds(f, b, PEAK)[0]
    assert _read(EXPERT_METRICS[family] + "_roofline", obs) == pytest.approx(
        100 * least(f_dec, b_dec) / 0.004)
    assert _read(EXPERT_METRICS[family] + "_prefill_roofline", obs) == \
        pytest.approx(100 * least(f_pre, b_pre) / 0.070)
    # the whole window's share is of every program's busy time
    assert _read(EXPERT_METRICS[family] + "_share", obs) == pytest.approx(
        100 * 0.094 / 0.370)


@pytest.mark.parametrize("family", sorted(EXPERT_METRICS))
def test_decode_work_follows_the_windows_live_slot_steps(family):
    """With ``serving/slot_iterations_active`` the decode work is the run's
    mean a live slot-step times the window's: the run had 500 slot-steps in
    its 100 steps, and decode steps committed 4 tokens inside the window
    (indices 1 and 2 of both requests; a first token is its prefill's)."""
    obs = _two_programs(family)
    work = harness.module("work", "moe_experts").work
    counters = dict(obs["counters"], **{"serving/slot_iterations_active": 500})
    f_dec, b_dec = work(dict(obs, counters=counters), "decode")
    elems = _expert_elems(obs["shapes"])
    assert f_dec == pytest.approx(2 * elems * 4400 * 4 / 500)
    assert b_dec == pytest.approx(2 * elems * 3200 * 4 / 500)
    # ten mean steps would have counted 440 pairs and 320 experts: this
    # window is a twelfth as busy as the run, and the spans cannot say so
    assert work(obs, "decode")[0] == pytest.approx(2 * elems * 440)
    # the prompts' work does not read the counter
    assert work(dict(obs, counters=counters), "prefill") == \
        work(obs, "prefill")


def test_a_program_filter_finds_nothing_in_a_trace_without_a_module_line():
    obs = _two_programs("exaone_moe")
    bare = trace_reduce.Trace(obs["trace"].device_ops, obs["trace"].host,
                              obs["trace"].window)
    assert bare.device_programs == {}
    assert trace_reduce.matched_seconds(bare, "^%ragged-dot-") == \
        pytest.approx(0.094)
    assert trace_reduce.matched_seconds(bare, "^%ragged-dot-",
                                        r"^jit_decode\b") == 0.0
    assert trace_reduce.program_seconds(bare, r"^jit_decode\b") == 0.0
    for metric in ("kernel.moe_experts_roofline", "step.decode_mfu",
                   "kernel.moe_experts_prefill_roofline", "step.prefill_mfu"):
        assert _read(metric, dict(obs, trace=bare)) is None


def test_program_seconds_clip_to_the_window_and_average_over_the_chips():
    tr = trace_reduce.Trace(
        {0: [("%fusion.1 = f32[2] fusion()", 0.5, 0.9)],
         1: [("%fusion.1 = f32[2] fusion()", 0.5, 0.7)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0),
        {0: [(TRAIN, -0.2, 0.3), (TRAIN, 0.3, 0.8), (DECODE, 0.8, 0.9)],
         1: [(TRAIN, 0.1, 0.5), (TRAIN, 0.9, 1.4)]})
    # device 0: 0.3 + 0.5; device 1: 0.4 + 0.1
    assert trace_reduce.program_seconds(tr, r"^jit_train_step\b") == \
        pytest.approx((0.8 + 0.5) / 2)
    assert trace_reduce.program_seconds(tr, r"^jit_decode\b") == \
        pytest.approx(0.1 / 2)
    assert trace_reduce.program_seconds(tr, r"^jit_prefill_\d+") == 0.0
    # an operation ran under the program whose event holds its START
    by = trace_reduce.time_by_name(tr, program=r"^jit_train_step\b")
    assert by == {"%fusion.1 = f32[2] fusion()": pytest.approx(0.4 / 2)}


@pytest.mark.parametrize("name,finds", [
    (DECODE, "step.decode_mfu"), (PREFILL, "step.prefill_mfu"),
    (CHUNK, "step.prefill_mfu"), (TRAIN, "step.train_mfu"),
    ("jit_decode", "step.decode_mfu"),
    ("jit_decode_probe(12)", None), ("jit_prefill(5)", None),
    ("jit_generate_prefill(5)", None), ("jit_verify_4(9)", None),
    ("jit_train_step_probe(3)", None), ("jit_swap_in(2)", None)])
def test_a_program_pattern_finds_its_program_alone(name, finds):
    for metric in ("step.decode_mfu", "step.prefill_mfu", "step.train_mfu"):
        program = harness.load_json(
            "layer_metrics", metric + ".json")["params"]["program"]
        assert bool(re.search(program, name)) == (metric == finds), metric


# ----------------------------------------- a program's share of the peak
def test_train_mfu_on_a_made_up_step():
    """Two traced steps of 16 rows x 1,024 on each of two chips, 0.4 s a
    step on one and 0.5 s on the other: the model FLOPs of one chip's rows
    over the peak times the mean of 0.8 and 1.0 s."""
    large = harness.module("families", "gpt2").shapes(
        harness.load_json("configs", "gpt2-large.json"))
    tr = trace_reduce.Trace(
        {0: [("%fusion.1 = f32[2] fusion()", 0.0, 0.8)],
         1: [("%fusion.1 = f32[2] fusion()", 0.0, 1.0)]},
        [("bench/window", 0.0, 1.2)], (0.0, 1.2),
        {0: [(TRAIN, 0.0, 0.4), (TRAIN, 0.4, 0.8)],
         1: [(TRAIN, 0.0, 0.5), (TRAIN, 0.5, 1.0)]})
    obs = {"trace": tr, "peak": PEAK, "shapes": large,
           "train": {"rows_per_device_step": 16, "seq_len": 1024,
                     "traced_steps": 2}}
    needed = 5210411520.0 * 16 * 1024 * 2     # test_benchmark's hand count
    assert flops.train_model_flops(obs) == needed
    assert _read("step.train_mfu", obs) == pytest.approx(
        100 * needed / (197e12 * 0.9))
    # at the ledger's rate the same arithmetic gives the builders' figure
    assert 100 * 20852 * 5210411520.0 / 197e12 == pytest.approx(55.15, abs=.01)


def test_serve_mfu_on_a_made_up_window():
    """Dense shapes by hand: 1,000 parameters of which a 10 x 20 head, 2
    heads of 4 over 3 layers. In the window [0, 1): decode commits tokens 1
    and 2 of a prompt of 10 (contexts 11 and 12) and token 1 of a prompt of
    5 (context 6); a prompt of 7 is admitted."""
    s = {"layers": 3, "hidden": 20, "heads": 2, "kv_heads": 2, "head_dim": 4,
         "vocab": 10, "active_params": 1000, "params": 1000}
    tr = trace_reduce.Trace(
        {0: [("%fusion.1 = f32[2] fusion()", 0.0, 0.5)]},
        [("bench/window", 0.0, 1.0)], (0.0, 1.0),
        {0: [(DECODE, 0.0, 0.1), (DECODE, 0.1, 0.2), (PREFILL, 0.2, 0.45)]})
    obs = {"trace": tr, "peak": PEAK, "shapes": s, "trace_span": [0.0, 1.0],
           "requests": [
               {"prompt_len": 10, "admitted": -1.0,
                "token_times": [-0.5, 0.1, 0.2, 1.5]},
               {"prompt_len": 5, "admitted": -0.2,
                "token_times": [-0.1, 0.15]},
               {"prompt_len": 7, "admitted": 0.2, "token_times": [0.45]}]}
    # decode: 3 tokens through all 1,000 parameters; 11 + 12 + 6 rows a layer
    dec = 2 * 1000 * 3 + 2 * 2 * (4 + 4) * 3 * (11 + 12 + 6)
    assert flops.decode_model_flops(obs) == dec
    # prefill: 7 tokens through 800, the head once; 1 + 2 + ... + 7 rows
    pre = 2 * 800 * 7 + 2 * 200 * 1 + 2 * 2 * (4 + 4) * 3 * 28
    assert flops.prefill_model_flops(obs) == pre
    assert _read("step.decode_mfu", obs) == pytest.approx(
        100 * dec / (197e12 * 0.2))
    assert _read("step.prefill_mfu", obs) == pytest.approx(
        100 * pre / (197e12 * 0.25))
    # a program that ran with no work of its kind counted: nothing, not 0
    idle = dict(obs, requests=[])
    assert _read("step.decode_mfu", idle) is None
    assert _read("step.prefill_mfu", idle) is None


@pytest.mark.parametrize("shapes,upto,want", [
    # every layer global: 1 + 2 + ... + 100, in 4 layers
    ({"layers": 4}, 100, 4 * 5050),
    # ``layers`` counts other mixers too: the attention layers alone
    ({"layers": 40, "attn_layers": 4}, 100, 4 * 5050),
    # 4 sliding layers see min(c, 8), 1 global all: 36 + 92 * 8 = 772
    ({"layers": 5, "window": 8, "sliding_layers": 4, "global_layers": 1},
     100, 4 * 772 + 5050),
    # inside the window a sliding layer is a global one
    ({"layers": 5, "window": 128, "sliding_layers": 4, "global_layers": 1},
     100, 5 * 5050),
    # every layer an exact window of 8 and one pooled row per 4 tokens
    # before it: 772 + (1 + ... + 92) / 4
    ({"layers": 2, "window": 8, "chunk": 4}, 100, 2 * (772 + 4278 / 4)),
    ({"layers": 2, "window": 8}, 100, 2 * 772),
], ids=["global", "attn-layers", "sliding-and-global", "inside-the-window",
        "window-and-pooled", "window-alone"])
def test_attended_rows_by_hand(shapes, upto, want):
    assert flops.attended_rows(shapes, upto) == pytest.approx(want)
    assert flops.attended_rows(shapes, 0) == 0.0


@pytest.mark.parametrize("cell", [w["name"] for w in
                                  appended.ACCEPTED["workloads"]])
def test_no_cells_shapes_can_count_more_than_the_usual_count(cell):
    """A serve cell's decode FLOPs a token lie between 2 a parameter less
    the embedding and head, and 2 a parameter plus every attention layer
    over the longest context."""
    loaded = harness.load_cell(cell, appended.ACCEPTED)
    cfg = loaded["config_file"]
    s = harness.module("families", cfg["family"]).shapes(cfg)
    n = min(s["positions"], 4096)
    obs = {"shapes": s, "trace_span": [0.0, 1.0],
           "requests": [{"prompt_len": n - 2, "admitted": 0.5,
                         "token_times": [0.6, 0.7]}]}
    dh = s["head_dim"]
    attn = 2.0 * s["heads"] * (dh + s.get("v_head_dim", dh)) * s.get(
        "attn_layers", s["layers"])
    got = flops.decode_model_flops(obs)
    assert 2.0 * s["active_params"] < got <= \
        2.0 * s["active_params"] + attn * n
    pre = flops.prefill_model_flops(obs)
    assert 0 < pre <= (n - 2) * (2.0 * s["active_params"] + attn * n / 2)


# ------------------------------------------------- files, cells and lists
@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("bench,cell_name", appended.CELL_CASES)
def test_a_new_metric_loads_in_every_cell_it_lists(bench, cell_name, metric):
    entry = next(m for m in bench["per_layer"] if m["name"] == metric)
    spec = harness.load_json("layer_metrics", metric + ".json")
    cell = harness.load_cell(cell_name, bench)
    kind, cfg = cell["traffic_file"]["kind"], cell["config_file"]
    shapes = harness.module("families", cfg["family"]).shapes(cfg)
    listed = cell_name in entry["workloads"]
    # every cell of its kind lists a step's share of the peak; an experts'
    # prefill roofline is listed wherever its decode twin is
    if metric.startswith("step."):
        assert listed == ((kind == "train_job")
                          == (metric == "step.train_mfu"))
    else:
        decode = metric.replace("_prefill_roofline", "_roofline")
        twin = next(m for m in bench["per_layer"] if m["name"] == decode)
        assert listed == (cell_name in twin["workloads"])
        assert listed <= ("experts_held" in shapes)
    if not listed:
        return
    for key in ("layer", "unit", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert (spec["unit"], spec["source"]) == ("%", "device_trace")
    assert entry["moves"] in {m["name"] for m in harness.metrics_of(
        cell_name, "end_to_end", bench)}
    reader = harness.module("readers", spec["reader"])
    assert reader.read(spec["params"], {}) is None
    if spec["reader"] == "trace_program_mfu":
        assert callable(getattr(flops, spec["params"]["flops"]))
        assert "mfu" in metric.split(".")[-1].split("_")
    else:
        assert callable(harness.module("work", spec["params"]["work"]).work)


def test_the_roofline_reader_returns_nothing_where_the_work_counts_nothing():
    """The kernel ran but its ``work`` counted no FLOP and no byte (no decode
    step started in the window, no prompt admitted): ``None``, never 0: the
    driver reads a 0 as a roofline that fell silent."""
    obs = _two_programs("exaone_moe")
    empty = dict(obs, spans=[], requests=[])
    assert harness.module("work", "moe_experts").work(empty) == (0.0, 0.0)
    for metric in ("kernel.moe_experts_roofline",
                   "kernel.moe_experts_prefill_roofline"):
        assert _read(metric, obs) > 0
        assert _read(metric, empty) is None
    reader = harness.module("readers", "trace_kernel_roofline")
    unsplit = {"pattern": "^%ragged-dot-", "work": "moe_experts"}
    assert reader.read(unsplit, obs) > 0
    assert reader.read(unsplit, empty) is None
    # the share needs no work and still reads
    assert _read("kernel.moe_experts_share", empty) == pytest.approx(
        100 * 0.094 / 0.370)
