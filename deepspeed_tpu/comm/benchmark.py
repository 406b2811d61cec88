"""Collective micro-benchmark (`dstpu_bench`).

Reference analog: ``bin/ds_bench`` → deepspeed communication benchmarks —
sweep message sizes through the collectives and report algorithm/bus
bandwidth.  Here each collective is a jitted `shard_map` program over the
local mesh, so the numbers reflect the real XLA/ICI path the framework
trains with.
"""

from __future__ import annotations

import argparse
import time
from typing import List


def _human(nbytes: float) -> str:
    for unit in ("B", "KB", "MB", "GB"):
        if nbytes < 1024:
            return f"{nbytes:.1f}{unit}"
        nbytes /= 1024
    return f"{nbytes:.1f}TB"


def run_collective_bench(op: str = "all_reduce", sizes: List[int] = None,
                         trials: int = 10, dtype_str: str = "float32"):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.parallel.topology import DATA_AXIS

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(devices, (DATA_AXIS,))
    dtype = getattr(jnp, dtype_str)
    sizes = sizes or [2 ** p for p in range(12, 27, 2)]  # 4KB..512MB elems/4
    results = []
    # one local function + out_specs per collective, one shard_map site
    local_fns = {
        "all_reduce": (lambda a: jax.lax.psum(a, DATA_AXIS), P(DATA_AXIS)),
        "all_gather": (lambda a: jax.lax.all_gather(a, DATA_AXIS, tiled=True),
                       P()),
        "reduce_scatter": (lambda a: jax.lax.psum_scatter(a, DATA_AXIS,
                                                          tiled=True),
                           P(DATA_AXIS)),
        "all_to_all": (lambda a: jax.lax.all_to_all(
            a.reshape(n, -1), DATA_AXIS, 0, 0,
            tiled=False).reshape(a.shape), P(DATA_AXIS)),
    }
    if op not in local_fns:
        raise ValueError(f"unknown op '{op}'")
    local_fn, out_specs = local_fns[op]
    for numel in sizes:
        x = jnp.ones((n, numel // n if op != "all_gather" else numel), dtype)
        fn = shard_map(local_fn, mesh=mesh, in_specs=P(DATA_AXIS),
                       out_specs=out_specs)
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(x))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(trials):
            out = jfn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / trials
        nbytes = numel * x.dtype.itemsize
        # bus bandwidth correction factors (NCCL-tests convention)
        factor = {"all_reduce": 2 * (n - 1) / n, "all_gather": (n - 1) / n,
                  "reduce_scatter": (n - 1) / n, "all_to_all": (n - 1) / n}[op]
        busbw = nbytes * factor / dt
        results.append((numel, nbytes, dt, busbw))
    return results


def main(argv=None):
    parser = argparse.ArgumentParser(description="dstpu collective benchmark")
    parser.add_argument("--op", default="all_reduce",
                        choices=["all_reduce", "all_gather", "reduce_scatter",
                                 "all_to_all"])
    parser.add_argument("--trials", type=int, default=10)
    parser.add_argument("--dtype", default="float32")
    parser.add_argument("--maxsize", type=int, default=24,
                        help="max message size as log2(elements)")
    args = parser.parse_args(argv)
    sizes = [2 ** p for p in range(12, args.maxsize + 1, 2)]
    print(f"{'size':>10} {'bytes':>10} {'time(us)':>12} {'busbw(GB/s)':>12}")
    for numel, nbytes, dt, busbw in run_collective_bench(
            args.op, sizes, args.trials, args.dtype):
        print(f"{numel:>10} {_human(nbytes):>10} {dt * 1e6:>12.1f} "
              f"{busbw / 1e9:>12.2f}")
    return 0


if __name__ == "__main__":
    main()
