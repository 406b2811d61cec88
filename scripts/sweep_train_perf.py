"""Training-throughput sweep on the real chip: (attn_impl, remat, mb x gas).

Dogfoods the bench methodology (best-of-windows, see bench.py) across the
knobs VERDICT r1 called out: whether the Pallas FA2 kernel beats XLA dense
attention, whether remat is needed at all at 125M, and the microbatch split.
Prints one JSON line per config; run me on the chip.
"""

from __future__ import annotations

import json
import os
import sys


sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_config(attn_impl, remat, remat_policy, batch, gas, loss_chunk=0,
               steps=8, windows=3):
    from scripts.bench_common import train_tokens_per_sec

    return train_tokens_per_sec(
        attn_impl=attn_impl, remat=remat, remat_policy=remat_policy,
        batch=batch, gas=gas, loss_chunk=loss_chunk, steps=steps,
        windows=windows)


def main():
    grid = [
        # (attn_impl, remat, policy, mb, gas[, loss_chunk])
        ("dense", True, "dots_no_batch", 8, 8),
        ("dense", True, "dots_no_batch", 16, 4),
        ("dense", True, "dots_no_batch", 4, 16),   # r1 champion re-measure
        ("flash", True, "dots_no_batch", 8, 8),
        ("dense", True, "nothing", 8, 8),
        ("dense", True, "dots_no_batch", 32, 2),
        ("dense", True, "dots_no_batch", 8, 8, 512),   # chunked LM loss
        ("flash", False, None, 8, 8),                  # sweep-1 runner-up
        ("flash", True, "save_attn", 4, 16),           # idx 8: selective remat
        ("flash", True, "save_attn", 8, 8),            # idx 9
        ("flash", True, "save_attn", 16, 4),           # idx 10
        ("flash", False, None, 16, 4),                 # idx 11
        ("flash", False, None, 16, 4, 512),            # idx 12: chunked CE
        ("flash", False, None, 32, 2, 512),            # idx 13
        ("flash", False, None, 8, 8, 512),             # idx 14
    ]
    if len(sys.argv) > 1:  # allow running a subset: indices as args
        grid = [grid[int(i)] for i in sys.argv[1:]]
    results = []
    for g in grid:
        try:
            toks = run_config(*g)
            results.append((g, round(toks)))
        except Exception as e:
            results.append((g, f"ERROR {type(e).__name__}: {e}"))
        print(json.dumps({"config": list(results[-1][0]), "tok_s": results[-1][1]}),
              flush=True)
    best = max((r for r in results if isinstance(r[1], (int, float))),
               key=lambda r: r[1], default=None)
    print("BEST:", best)


if __name__ == "__main__":
    main()
