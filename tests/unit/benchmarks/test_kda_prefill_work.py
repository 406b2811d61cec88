"""The delta-rule prompt kernel in the benchmark: the work of the traced
window's prefills (``benchmarks/work/kda_prefill.py``) on made-up
observations, and the two metric files that read ``dstpu_kda_prefill``
through their readers. What it reads of ``BENCHMARK.json`` it reads through
the ``bench`` fixture, as accepted and with a cell appended (appended.py), and
it speaks of its own cell only.

One module; it starts no subprocess, describes no TPU topology and runs no
model.
"""
import pytest

from benchmarks import harness, trace_reduce

CELL = "solar-open2-250b.serve-agent-contexts"
SHAPES = harness.module("families", "solar_kda").shapes(
    harness.load_json("configs", "solar-open2-250b.json"))
PEAK = harness.load_json("peaks.json")["devices"]["TPU v5 lite"]
NAMES = ("kernel.kda_prefill_roofline", "kernel.kda_prefill_share")
WORK = harness.module("work", "kda_prefill").work
HEADS = 3 * 64            # delta-rule layers x heads
# a position of one head: the recurrence's FLOPs; q, k, v, o at 2 bytes and g
# at 4; and a token block's state, read and written in float32
FLOPS, ROW, STATE = 7 * 128 * 128, 2 * 4 * 128 + 4 * 128, 2 * 4 * 128 * 128


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
    """A prompt of n tokens passes n positions a head a layer through the
    recurrence and carries its state through three token blocks of 2,048; one
    the window cuts at either edge counts nothing."""
    n = 5000
    whole = (n * HEADS * FLOPS, n * HEADS * ROW + 3 * HEADS * STATE)
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
                      (whole[0] + 100 * HEADS * FLOPS,
                       whole[1] + 100 * HEADS * ROW + HEADS * STATE)),
    }[case]
    assert WORK(_obs(requests)) == pytest.approx(want, rel=1e-12)
    assert (SHAPES["kda_layers"] * SHAPES["kda_heads"], SHAPES["kda_key_dim"],
            SHAPES["kda_value_dim"]) == (HEADS, 128, 128)


def _read(name, obs):
    spec = harness.load_json("layer_metrics", name + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _trace(events):
    return trace_reduce.Trace({0: events}, [("bench/window", 10.0, 13.0)],
                              (10.0, 13.0))


KERNEL = ("%dstpu_kda_prefill.12 = (f32[1,2048,8192]{2,1,0}, "
          "f32[1,64,128,128]{3,2,1,0}) custom-call(%len, %q, %k, %v, %g, "
          "%beta, %state)")
# a reader of the kernel's result, the decode step's kernel and the chunked
# form's reduction inside the named scope: none of them is the kernel
OTHERS = ("%fusion.7 = bf16[1,2048,8192] fusion(%dstpu_kda_prefill.12)",
          "%dstpu_kda_update.3 = (bf16[16,64,128]) custom-call(%a)",
          "%multiply_reduce_fusion.6 = (f32[1,32,64,64,64]) fusion(%p), "
          'metadata={op_name="jit(prefill)/dstpu_kda_prefill/reduce"}')


@pytest.mark.parametrize("slowdown", [1.0, 4.0, 20.0])
def test_an_ideal_kernel_reads_100_percent_and_never_more(slowdown):
    """Device time = the operands' bytes over ``peaks.json``'s bandwidth reads
    100% (the recurrence is bound by memory: 75 FLOPs a byte against the
    chip's 240); a kernel that also computes padding, scores and a solve in
    float32 takes longer and reads lower; a prefill the window cuts adds time
    and no work, so the share only falls."""
    n = 15872
    flops, moved = WORK(_obs([_request(n, 10.1, 12.9)]))
    least = moved / (PEAK["hbm_gbps"] * 1e9)
    assert least > flops / (PEAK["bf16_tflops"] * 1e12)
    assert 0.0059 < least < 0.0061        # 4.9 GB at the chip's bandwidth
    took = least * slowdown
    events = [(KERNEL, 10.2, 10.2 + took)] + [
        (text, 12.0 + i * 0.1, 12.05 + i * 0.1)
        for i, text in enumerate(OTHERS)]
    obs = _obs([_request(n, 10.1, 10.2 + took + 0.3)], _trace(events))
    assert _read(NAMES[0], obs) == pytest.approx(100.0 / slowdown)
    assert _read(NAMES[1], obs) == pytest.approx(
        100.0 * took / (took + 3 * 0.05))
    cut = _obs(obs["requests"] + [_request(n, 12.9, 13.4)], _trace(
        events + [(KERNEL.replace(".12", ".11"), 12.9, 13.0)]))
    assert _read(NAMES[0], cut) == pytest.approx(
        100.0 * least / (took + 0.1))


def test_a_program_without_the_kernel_has_nothing_to_read():
    """The parent commit's chunked form: its operations carry the scope in
    ``op_name`` and not in their result names."""
    events = [(text, 10.5 + i, 10.9 + i) for i, text in enumerate(OTHERS[1:])]
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
        assert spec["params"]["pattern"] == r"^%[\w.\-]*dstpu_kda_prefill"
        harness.module("readers", spec["reader"])
    assert harness.load_json(
        "layer_metrics", NAMES[0] + ".json")["params"]["work"] == "kda_prefill"
    for cell in bench["workloads"]:
        reported = {m["name"] for m in harness.metrics_of(
            cell["name"], "per_layer", bench)}
        assert set(NAMES) <= reported if cell["name"] == CELL \
            else not set(NAMES) & reported
    # the cell reports the end-to-end metric both move
    assert "ttft_p95_ms" in {m["name"] for m in harness.metrics_of(
        CELL, "end_to_end", bench)}
