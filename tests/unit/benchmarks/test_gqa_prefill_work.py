"""The softmax grouped-query layer's prompt kernel in the benchmark: the
useful work of the traced window's prefills (``benchmarks/work/
gqa_prefill.py``) on made-up observations, and the two metric files that read
``dstpu_gqa_prefill`` through their readers. What it reads of
``BENCHMARK.json`` it reads through the ``bench`` fixture, as accepted and
with a cell appended (appended.py), and it speaks of its own cell only.

One module; it starts no subprocess, describes no TPU topology and runs no
model.
"""
import pytest

from benchmarks import harness, trace_reduce

CELL = "solar-open2-250b.serve-agent-contexts"
SHAPES = harness.module("families", "solar_kda").shapes(
    harness.load_json("configs", "solar-open2-250b.json"))
PEAK = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
NAMES = ("kernel.gqa_prefill_roofline", "kernel.gqa_prefill_share")
WORK = harness.module("work", "gqa_prefill").work
# FLOPs a (query, key) pair of one head: the score and the sum at 128 each;
# bytes a cached row: 8 key-value heads' key and value at 128 x 2 bytes
PAIR, HEADS, ROW = 2 * (128 + 128), 1 * 64, 8 * 2 * 128 * 2


def _request(n, admitted, first_token):
    return {"prompt_len": n, "admitted": admitted, "first_token": first_token,
            "token_times": [first_token]}


def _obs(requests, trace=None):
    return {"trace_span": [10.0, 13.0], "shapes": SHAPES, "peak": PEAK,
            "requests": requests, "trace": trace, "counters": {}, "spans": []}


@pytest.mark.parametrize("case", ["whole", "cut-at-the-start",
                                  "cut-at-the-end", "outside", "unfinished",
                                  "two-whole"])
def test_a_prefill_counts_whole_or_not_at_all(case):
    """A prompt of n tokens attends n (n + 1) / 2 pairs a head on the ONE
    softmax layer of the cell's four, and reads its key and value rows once
    a token block of 2,048; one the window cuts at either edge counts
    nothing."""
    n = 5000
    pairs = n * (n + 1) // 2
    # three token blocks read 2,048, 4,096 and 5,000 rows
    whole = (pairs * HEADS * PAIR, (2048 + 4096 + 5000) * ROW)
    requests, want = {
        "whole": ([_request(n, 10.5, 11.0)], whole),
        "cut-at-the-start": ([_request(n, 9.9, 10.4)], (0.0, 0.0)),
        "cut-at-the-end": ([_request(n, 12.8, 13.2)], (0.0, 0.0)),
        "outside": ([_request(n, 3.0, 3.5), _request(n, 14.0, 14.5)],
                    (0.0, 0.0)),
        "unfinished": ([_request(n, 12.0, None), _request(n, None, None)],
                       (0.0, 0.0)),
        "two-whole": ([_request(n, 10.0, 10.5), _request(100, 12.0, 12.01),
                       _request(n, 9.0, 10.2)],
                      (whole[0] + 5050 * HEADS * PAIR, whole[1] + 100 * ROW)),
    }[case]
    assert WORK(_obs(requests)) == pytest.approx(want, rel=1e-12)
    assert (SHAPES["attn_layers"], SHAPES["heads"], SHAPES["kv_heads"],
            SHAPES["head_dim"]) == (1, 64, 8, 128)


def _read(name, obs):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _trace(events):
    return trace_reduce.Trace({0: events}, [("bench/window", 10.0, 13.0)],
                              (10.0, 13.0))


KERNEL = ("%dstpu_gqa_prefill.5 = bf16[1,2048,8192]{2,1,0} custom-call("
          "%layer, %first, %valid, %q, %k, %v)")
# a reader of the kernel's result, the sibling kernels and the loop's fusion
# inside the named scope: none of them is the kernel
OTHERS = ("%fusion.9 = bf16[1,2048,8192] fusion(%dstpu_gqa_prefill.5)",
          "%dstpu_kda_prefill.12 = (f32[1,2048,8192]) custom-call(%a)",
          "%dstpu_decode_step.3 = (bf16[16,64,128]) custom-call(%a)",
          '%fusion.378 = f32[8,8,2048] fusion(%p), metadata={op_name="jit('
          'prefill)/dstpu_gqa_prefill/while/body/reduce_max"}')


@pytest.mark.parametrize("slowdown", [1.0, 2.2, 6.3])
def test_an_ideal_kernel_reads_100_percent_and_never_more(slowdown):
    """Device time = useful FLOPs over ``peaks.json``'s peak reads 100%; a
    kernel that also computes padding and masked halves, or waits for its
    softmax, takes longer and reads lower (the loop's three fusions read 16%
    by this count); a prefill the window cuts adds time and no work, so the
    share only falls."""
    n = 15872
    flops = n * (n + 1) // 2 * HEADS * PAIR
    least = flops / (PEAK["bf16_tflops"] * 1e12)
    assert 0.0209 < least < 0.0210        # 4.13 TFLOP at the MXU's peak
    took = least * slowdown
    events = [(KERNEL, 10.2, 10.2 + took)] + [
        (text, 12.0 + i * 0.1, 12.05 + i * 0.1)
        for i, text in enumerate(OTHERS)]
    obs = _obs([_request(n, 10.1, 10.2 + took + 0.3)], _trace(events))
    assert WORK(obs)[0] == flops
    assert _read(NAMES[0], obs) == pytest.approx(100.0 / slowdown)
    assert _read(NAMES[1], obs) == pytest.approx(
        100.0 * took / (took + 4 * 0.05))
    cut = _obs(obs["requests"] + [_request(n, 12.9, 13.4)], _trace(
        events + [(KERNEL.replace(".5 ", ".4 "), 12.9, 13.0)]))
    assert _read(NAMES[0], cut) == pytest.approx(
        100.0 * least / (took + 0.1))
    # the bytes never bound it: 71,168 rows of 4 KB, 0.36 ms
    assert WORK(obs)[1] / (PEAK["hbm_gbps"] * 1e9) < 0.02 * least


def test_a_program_without_the_kernel_has_nothing_to_read():
    """The parent commit's loop: its fusions carry no such result name (and
    the scope, where a route sets it, sits in ``op_name``)."""
    events = [(text, 10.5 + i * 0.5, 10.9 + i * 0.5)
              for i, text in enumerate(OTHERS[1:])]
    obs = _obs([_request(15872, 10.1, 12.0)], _trace(events))
    assert [_read(name, obs) for name in NAMES] == [None, None]
    assert [_read(name, _obs([], None)) for name in NAMES] == [None, None]


def test_the_metric_files_load_for_the_cell_and_for_no_other(bench):
    for name in NAMES:
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert entry["workloads"] == [CELL]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == \
            (spec["unit"], spec["better"], spec["source"], spec["layer"],
             spec["moves"]) == \
            ("%", "higher" if name.endswith("roofline") else "lower",
             "device_trace", "kernels", "ttft_p95_ms")
        assert spec["params"]["pattern"] == r"^%[\w.\-]*dstpu_gqa_prefill"
        harness.module("readers", spec["reader"])
    assert harness.load_json(
        "layer_metrics", NAMES[0] + ".json")["params"]["work"] == "gqa_prefill"
    for cell in bench["workloads"]:
        reported = {m["name"] for m in harness.metrics_of(
            cell["name"], "per_layer", bench)}
        assert set(NAMES) <= reported if cell["name"] == CELL \
            else not set(NAMES) & reported
    # the cell reports the end-to-end metric both move
    assert "ttft_p95_ms" in {m["name"] for m in harness.metrics_of(
        CELL, "end_to_end", bench)}
