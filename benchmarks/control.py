#!/usr/bin/env python3
"""The control of the kinds' logit comparisons: the plain reference with every
weight matrix in int8 (per output channel, symmetric, dequantized to float32)
stands in the program's place, and has to come out as not ``correct``. int8 is
the precision next below the bf16 the cells state, and the step a later PR
would be tempted by. (Rounding the weights to bf16 is no control on the chip:
XLA may drop a float32 -> bfloat16 -> float32 round trip.)

No run of the benchmark runs this. ``python3 benchmarks/control.py <config>
<seed>...`` reads it on the chip at a configuration's own size; each mix's
``check.why`` and PERF.md give what it read beside the limits. The tests hold
the arithmetic at tiny size.
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def int8_weights(params):
    """``params`` with every leaf of two or more dimensions rounded to 127
    levels either side of zero, one scale for each slice along the axis before
    last (a matmul's input axis)."""
    import jax
    import jax.numpy as jnp

    def quantize(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        scale = jnp.where(scale == 0, 1.0, scale)
        return jnp.clip(jnp.round(w / scale), -127, 127) * scale
    return jax.tree_util.tree_map(quantize, params)


def compare(ref_logits, logits, first: int, count: int) -> dict:
    """What the kinds compare, with ``logits`` in the program's place, over
    one row ``[T, V]``. ``train_job``: the largest |difference| anywhere.
    ``serve_open_loop``: over ``count`` positions from ``first`` (a request's
    output tokens), how far the token greedy decoding would pick from
    ``logits`` sits below the reference's best: the largest gap, the sum of
    the gaps and how many positions picked another token."""
    import jax.numpy as jnp

    rows, picked = ref_logits[first:first + count], logits[first:first + count]
    gap = rows.max(-1) - jnp.take_along_axis(
        rows, jnp.argmax(picked, -1)[:, None], -1)[:, 0]
    return {"logit_err": float(jnp.max(jnp.abs(ref_logits - logits))),
            "worst_gap": float(gap.max()), "gap_sum": float(gap.sum()),
            "flipped": int((gap > 0).sum()), "checked": int(count)}


def main(argv=None) -> int:
    from benchmarks import harness, traffic_gen

    argv = sys.argv[1:] if argv is None else argv
    name, seeds = argv[0], [int(x) for x in argv[1:]]
    harness.setup_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    cfg = harness.load_json("configs", name + ".json")
    family = harness.module("families", cfg["family"])
    reference = harness.module("reference", cfg["family"])
    shapes = family.shapes(cfg)
    model = family.build_model(cfg, {})
    ref = jax.jit(lambda p, x: reference.forward_logits(p, x, cfg)[0])
    lower = jax.jit(int8_weights)
    print(json.dumps({"config": name, "device": jax.devices()[0].device_kind}),
          flush=True)
    for seed in seeds:
        params = jax.jit(model.init)(
            jax.random.PRNGKey(traffic_gen.fold_seed(seed)))
        low = lower(params)
        rng = np.random.RandomState(traffic_gen.fold_seed(seed, 5))
        out = {"seed": seed, "requests": []}
        # sixteen rows, as the serve kind replays sixteen requests: a prompt
        # of the chat mix's median and about its mean output
        for _ in range(16):
            ids = jnp.asarray(rng.randint(
                0, shapes["vocab"], (1, shapes["positions"])).astype(np.int32))
            out["requests"].append(compare(ref(params, ids), ref(low, ids),
                                           first=191, count=116))
        rows = out["requests"]
        checked = sum(r["checked"] for r in rows)
        out["serve"] = {"worst_gap": max(r["worst_gap"] for r in rows),
                        "mean_gap": sum(r["gap_sum"] for r in rows) / checked,
                        "flipped": sum(r["flipped"] for r in rows),
                        "checked": checked}
        ids = jnp.asarray(traffic_gen.arith_rows(
            rng, shapes["vocab"], (1, shapes["positions"]))["input_ids"])
        out["train"] = {"logit_err": compare(
            ref(params, ids), ref(low, ids), 0, 1)["logit_err"]}
        out["requests"] = [[r["checked"], r["worst_gap"], r["gap_sum"]]
                           for r in rows]
        print(json.dumps(out), flush=True)
        del params, low
    return 0


if __name__ == "__main__":
    sys.exit(main())
