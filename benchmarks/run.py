#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix, traffic kind, model family,
plain reference and per-layer readers by name (benchmarks/README.md), runs
the kind's runner in this one process, prints what it noted on earlier lines
and, as the last line of stdout, the one JSON object of the contract:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics.

Fails, with a non-zero exit and no result line, when JAX finds no TPU, a TPU
that ``benchmarks/peaks.json`` does not know, or fewer chips than the cell
asks for.
"""
import argparse
import json
import os
import sys
import time

CLOCK0 = time.perf_counter()   # process start, as near as Python can say
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def say(**kw):
    print(json.dumps(kw, sort_keys=True, default=str), flush=True)


def per_layer_metrics(cell_name, bench, observations):
    """Each per-layer metric of the cell through its own reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    from benchmarks import harness

    out = {}
    for m in harness.metrics_of(cell_name, "per_layer", bench):
        spec = harness.load_json("layer_metrics", m["name"] + ".json")
        value = harness.module("readers", spec["reader"]).read(
            spec.get("params", {}), observations)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(cell, bench, outcome, trace):
    """The contract's object from a runner's outcome."""
    from benchmarks import harness, trace_reduce

    device = dict(outcome["device"])
    line = {"correct": outcome["correct"], "attempted": outcome["attempted"],
            "failed": outcome["failed"], "device": device}
    if trace:
        obs = outcome["observations"]
        line["metrics"] = per_layer_metrics(cell["name"], bench, obs)
        reduced = obs.get("trace")
        if reduced is not None:
            device["busy_s"] = trace_reduce.busy_seconds(reduced)
            device["window_s"] = reduced.window_s
            line["breakdown"] = trace_reduce.breakdown(reduced)
    else:
        units = {m["name"]: m["unit"]
                 for m in harness.metrics_of(cell["name"], "end_to_end", bench)}
        line["metrics"] = {k: {"value": v, "unit": units[k]}
                           for k, v in outcome["end_to_end"].items()
                           if k in units}
        missing = sorted(set(units) - set(line["metrics"]))
        if missing:
            raise RuntimeError(f"the run produced no value for {missing}")
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks import harness

    bench = harness.benchmark_json()
    cell = harness.load_cell(args.workload, bench)
    cache = harness.setup_compile_cache()   # before the first use of JAX
    runner = harness.module("kinds", cell["traffic_file"]["kind"])
    outcome = runner.run(cell, seed=args.seed, seconds=args.seconds,
                         trace=bool(args.trace), clock0=CLOCK0)
    say(workload=cell["name"], seed=args.seed, seconds=args.seconds,
        trace=args.trace, compile_cache=cache, device=outcome["device"],
        end_to_end=outcome["end_to_end"], **outcome["notes"])
    line = result_line(cell, bench, outcome, bool(args.trace))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
