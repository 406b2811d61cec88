"""Continuous-batching serving bench (ISSUE 2 / ISSUE 4 acceptance
numbers only).

Default: bench.py's serving-comparison section standalone — aggregate
tokens/sec + p50/p95 per-request latency of the continuous-batching
runtime (deepspeed_tpu/serving) vs run-to-completion static batching at
the same slot count, under a mixed-length Poisson arrival trace.

``--speculative {off,ngram,draft}``: the ISSUE-4 comparison instead —
speculative decoding (prompt-lookup n-gram or draft-model drafting)
vs plain continuous batching on the same templated high-acceptance
trace, reporting decode tokens/sec, p50/p95 latency, acceptance rate,
tokens per verify invocation, and the zero-recompile check.

``--prefix-cache {on,off}``: the ISSUE-6 comparison instead — block-paged
KV with radix prefix sharing (on) vs the plain slot-paged engine (off is
the default continuous-vs-static bench) on a shared-prefix multi-tenant
trace, reporting TTFT p50/p95, prefill tokens computed, cache hit rate,
COW/eviction counters, and the zero-recompile + lossless checks.

``--slo``: the ISSUE-8 comparison instead — SLO-aware serving (chunked
prefill under a per-iteration token budget, priority classes with
aging, preemption with host KV swap) vs the FIFO monolithic-prefill
engine on a bimodal long-prompt trace, reporting decode-TPOT
(inter-token latency) and TTFT p50/p95/p99 overall and per priority
class, throughput, preemption/chunk counters, and the zero-recompile +
lossless checks in BOTH cache modes.

Usage: python scripts/serve_continuous_bench.py [--speculative MODE]
                                                [--prefix-cache {on,off}]
                                                [--slo]
Prints one JSON object (the matching entry of bench.py).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--speculative", choices=("off", "ngram", "draft"),
                   default="off",
                   help="compare speculative decoding (n-gram prompt-"
                        "lookup or draft-model drafting) against plain "
                        "continuous batching instead of continuous-vs-"
                        "static")
    p.add_argument("--prefix-cache", choices=("on", "off"), default="off",
                   help="compare the block-paged radix prefix cache "
                        "against the cache-off engine on a shared-prefix "
                        "multi-tenant trace instead of continuous-vs-"
                        "static")
    p.add_argument("--slo", action="store_true",
                   help="compare SLO-aware serving (chunked prefill + "
                        "priority classes + preemption w/ host KV swap) "
                        "against the FIFO monolithic-prefill engine on a "
                        "bimodal long-prompt trace, both cache modes, "
                        "instead of continuous-vs-static")
    args = p.parse_args()
    exclusive = [args.prefix_cache == "on", args.speculative != "off",
                 args.slo]
    if sum(exclusive) > 1:
        p.error("--prefix-cache on, --speculative, and --slo are separate "
                "comparisons; pass one of them")

    from bench import (_bench_continuous_serving,
                       _bench_prefix_cache_serving,
                       _bench_slo_serving,
                       _bench_speculative_serving, _on_tpu)

    on_tpu = _on_tpu()
    if args.slo:
        out = _bench_slo_serving(on_tpu)
    elif args.prefix_cache == "on":
        out = _bench_prefix_cache_serving(on_tpu)
    elif args.speculative != "off":
        out = _bench_speculative_serving(on_tpu, mode=args.speculative)
    else:
        out = _bench_continuous_serving(on_tpu)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
