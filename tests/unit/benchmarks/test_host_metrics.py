"""The four ``host.*`` per-layer metrics (ISSUE 57): the metric files load,
agree with their ``BENCHMARK.json`` entries, read nothing from nothing, and
read a number, never None, from a tiny in-process rehearsal of each kind
(``rehearse=True``: no device guard, never a result).

One module, as tests/unit/benchmarks/test_benchmark.py: no subprocess, no
TPU topology, nothing at module level that loads libtpu.
"""
import time

import pytest

from benchmarks import harness

METRICS = {
    "host.stall_ms.serve": ("host/stall_ms", "serve_open_loop", "ttft_p95_ms"),
    "host.stall_ms.train": ("host/stall_ms", "train_job",
                            "train_tokens_per_s"),
    "host.gc_pause_ms.serve": ("host/gc_pause_ms", "serve_open_loop",
                               "ttft_p95_ms"),
    "host.gc_pause_ms.train": ("host/gc_pause_ms", "train_job",
                               "train_tokens_per_s"),
}


# ``test_mimo_v2.py`` holds that cell's per-layer metrics to an exact set, and
# an accepted benchmark file is not this PR's to edit: the two serve metrics
# leave the cell out until a ``benchmark`` PR loosens that (PERF.md, section 7)
PINNED = {"mimo-v2.5.serve-long-context-decode"}


def _read(metric, obs):
    spec = harness.load_json("layer_metrics", metric + ".json")
    return harness.module("readers", spec["reader"]).read(spec["params"], obs)


def _cells_of(kind, bench):
    return [w["name"] for w in bench["workloads"]
            if harness.load_cell(w["name"], bench)["traffic_file"]["kind"]
            == kind]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_the_metric_file_agrees_with_its_entry(metric, bench):
    counter, kind, moves = METRICS[metric]
    spec = harness.load_json("layer_metrics", metric + ".json")
    assert spec["reader"] == "counter"          # no reader code of its own
    assert spec["params"]["counter"] == counter
    entry, = [m for m in bench["per_layer"] if m["name"] == metric]
    for key in ("layer", "unit", "better", "source", "moves"):
        assert spec[key] == entry[key], key
    assert (entry["layer"], entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("host process", "ms", "lower",
                                "program_counter", moves)
    # every cell of the kind but the pinned one, and no other; each reports
    # what it moves
    assert sorted(entry["workloads"]) == sorted(
        set(_cells_of(kind, bench)) - PINNED)
    for cell in entry["workloads"]:
        assert moves in {m["name"] for m in harness.metrics_of(
            cell, "end_to_end", bench)}


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_nothing_read_from_nothing_and_a_zero_is_a_reading(metric):
    counter = METRICS[metric][0]
    assert _read(metric, {}) is None
    assert _read(metric, {"counters": {}}) is None       # the parent's run
    assert _read(metric, {"counters": {counter: 0}}) == 0
    assert _read(metric, {"counters": {counter: 104.5}}) == 104.5


@pytest.fixture(scope="module", params=["train_job", "serve_open_loop"])
def rehearsed(request):
    from tests.unit.benchmarks.appended import ACCEPTED

    kind = request.param
    cell = harness.load_cell(_cells_of(kind, ACCEPTED)[0], ACCEPTED)
    out = harness.module("kinds", kind).run(
        cell, seed=2**31 + 57, seconds=0.6, trace=True,
        clock0=time.perf_counter(), rehearse=True)
    return kind, cell, out


def test_a_rehearsal_of_each_kind_reads_both_and_never_none(rehearsed):
    """A sound run reads 0. A tiny run on a loaded CPU is not held to that:
    a phase of 50 ms there is the machine's, and the train kind's pause
    between its window and its traced steps (the profiler's start) is a
    caller's gap like any other. What is held: a number, 0 or more, under
    both names, on the line ``run.py`` prints."""
    from benchmarks import run as bench_run

    kind, cell, out = rehearsed
    mine = [m for m, (_, k, _) in METRICS.items() if k == kind]
    assert len(mine) == 2
    for metric in mine:
        value = _read(metric, out["observations"])
        assert value is not None and value >= 0, metric
    line = bench_run.result_line(cell, harness.benchmark_json(), out,
                                 trace=True)
    for metric in mine:
        assert line["metrics"][metric]["unit"] == "ms"
        assert line["metrics"][metric]["value"] >= 0
    others = [m for m in METRICS if m not in mine]
    assert not set(others) & set(line["metrics"])
