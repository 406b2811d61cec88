"""The prompt kernel of latent attention in the benchmark: the useful work of
the traced window's prefills (``benchmarks/work/mla_prefill.py``) on made-up
observations, and the two metric files that read ``dstpu_mla_prefill``
through their readers. What it reads of ``BENCHMARK.json`` it reads through
the ``bench`` fixture, as accepted and with a cell appended (appended.py), and
it speaks of its own cell only.

One module; it starts no subprocess, describes no TPU topology and runs no
model.
"""
import pytest

from benchmarks import harness, trace_reduce

CELL = "sarvam-105b.serve-long-documents"
SHAPES = harness.module("families", "sarvam_mla").shapes(
    harness.load_json("configs", "sarvam-105b.json"))
PEAK = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
NAMES = ("kernel.mla_prefill_roofline", "kernel.mla_prefill_share")
WORK = harness.module("work", "mla_prefill").work
# FLOPs a (query, key) pair of one head: the score at 128 + 64, the sum at 128
PAIR = 2 * (192 + 128)


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
    """A prompt of n tokens attends n (n + 1) / 2 pairs a head a layer, and
    reads its latent rows once a token block of 2,048; one the window cuts at
    either edge counts nothing."""
    n = 5000
    pairs = n * (n + 1) // 2
    # three token blocks read 2,048, 4,096 and 5,000 rows of 576 x 2 bytes
    whole = (pairs * 5 * 64 * PAIR, (2048 + 4096 + 5000) * 5 * 576 * 2)
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
                      (whole[0] + 5050 * 5 * 64 * PAIR,
                       whole[1] + 100 * 5 * 576 * 2)),
    }[case]
    assert WORK(_obs(requests)) == pytest.approx(want, rel=1e-12)
    assert SHAPES["head_dim"] + SHAPES["v_head_dim"] == 192 + 128


def _read(name, obs):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _trace(events):
    return trace_reduce.Trace({0: events}, [("bench/window", 10.0, 13.0)],
                              (10.0, 13.0))


KERNEL = ("%dstpu_mla_prefill.24 = bf16[1,2048,8192]{2,1,0} custom-call("
          "%a, %b, %c, %d, %e, %q, %latent, %wkv_b)")
# a reader of the kernel's result, the sibling step and the loop's fusion
# inside the named scope: none of them is the kernel
OTHERS = ("%fusion.7 = bf16[1,2048,4096] fusion(%dstpu_mla_prefill.24)",
          "%dstpu_mla_decode_step.3 = (bf16[16,64,512]) custom-call(%a)",
          '%fusion.386 = f32[64,2048] fusion(%p), metadata={op_name="jit('
          'prefill)/dstpu_mla_prefill/while/body/dot_general"}')


@pytest.mark.parametrize("slowdown", [1.0, 2.5, 4.0])
def test_an_ideal_kernel_reads_100_percent_and_never_more(slowdown):
    """Device time = useful FLOPs over ``peaks.json``'s peak reads 100%; a
    kernel that also computes padding, masked halves and the up-projection
    takes longer and reads lower; a prefill the window cuts adds time and no
    work, so the share only falls."""
    n = 12288
    flops = n * (n + 1) // 2 * 5 * 64 * PAIR
    least = flops / (PEAK["bf16_tflops"] * 1e12)
    assert 0.078 < least < 0.079          # 15.5 TFLOP at the MXU's peak
    took = least * slowdown
    events = [(KERNEL, 10.2, 10.2 + took)] + [
        (text, 12.0 + i * 0.1, 12.05 + i * 0.1)
        for i, text in enumerate(OTHERS)]
    obs = _obs([_request(n, 10.1, 10.2 + took + 0.3)], _trace(events))
    assert _read(NAMES[0], obs) == pytest.approx(100.0 / slowdown)
    assert _read(NAMES[1], obs) == pytest.approx(
        100.0 * took / (took + 3 * 0.05))
    cut = _obs(obs["requests"] + [_request(n, 12.9, 13.4)], _trace(
        events + [(KERNEL.replace(".24", ".23"), 12.9, 13.0)]))
    assert _read(NAMES[0], cut) == pytest.approx(
        100.0 * least / (took + 0.1))
    # the bytes never bound it: 43,008 rows of five layers, 0.3 ms
    assert WORK(obs)[1] / (PEAK["hbm_gbps"] * 1e9) < 0.01 * least


def test_a_program_without_the_kernel_has_nothing_to_read():
    """The parent commit's loop: its fusions carry the scope in ``op_name``
    and not in their result names."""
    events = [(text, 10.5 + i, 10.9 + i) for i, text in enumerate(OTHERS[1:])]
    obs = _obs([_request(12288, 10.1, 12.0)], _trace(events))
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
        assert spec["params"]["pattern"] == r"^%[\w.\-]*dstpu_mla_prefill"
        harness.module("readers", spec["reader"])
    assert harness.load_json(
        "layer_metrics", NAMES[0] + ".json")["params"]["work"] == "mla_prefill"
    for cell in bench["workloads"]:
        reported = {m["name"] for m in harness.metrics_of(
            cell["name"], "per_layer", bench)}
        assert set(NAMES) <= reported if cell["name"] == CELL \
            else not set(NAMES) & reported
    # the cell reports the end-to-end metric both move
    assert "ttft_p95_ms" in {m["name"] for m in harness.metrics_of(
        CELL, "end_to_end", bench)}
