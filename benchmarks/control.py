#!/usr/bin/env python3
"""The control of the kinds' logit comparisons: the plain reference with every
weight matrix in int8 (per output channel, symmetric, dequantized to float32)
stands in the program's place, and has to come out as not ``correct``. int8 is
the precision next below the bf16 the cells state, and the step a later PR
would be tempted by. (Rounding the weights to bf16 is no control on the chip:
XLA may drop a float32 -> bfloat16 -> float32 round trip.)

No run of the benchmark runs this. ``python3 benchmarks/control.py <config>
[--mix <mix>] <seed>...`` reads it on the chip at a configuration's own size;
each mix's ``check.why`` and PERF.md give what it read beside the limits. With
``--mix`` the sixteen rows compare the positions of that mix's first sixteen
requests (prompt and output lengths from its schedule), as a run of the cell
replays them; without, a prompt of 192 and 116 tokens behind it. The tests
hold the arithmetic at tiny size.

It keeps ONE copy of the weights (a configuration here fills half a chip): the
reference runs first on the weights as drawn and keeps, of each row, the
positions the comparison reads; then the one tree is rounded where it lies,
leaf by leaf, and the reference runs again on the same rows.
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

    return jax.tree_util.tree_map(_quantize, params)


def _quantize(w):
    import jax.numpy as jnp

    if w.ndim < 2:
        return w
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def int8_weights_in_place(params):
    """:func:`int8_weights` a leaf at a time, each leaf's buffer given to its
    rounded self (donated on a TPU; a CPU copies), so the process never holds
    more than the one tree and a leaf's temporaries. ``params`` is spent: the
    caller keeps the returned tree alone."""
    import jax

    donate = (0,) if jax.default_backend() == "tpu" else ()
    quantize = jax.jit(_quantize, donate_argnums=donate)
    leaves, tree = jax.tree_util.tree_flatten(params)
    for i, w in enumerate(leaves):
        leaves[i] = quantize(w)
    return jax.tree_util.tree_unflatten(tree, leaves)


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
    import argparse

    from benchmarks import harness, traffic_gen

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config")
    ap.add_argument("seeds", nargs="+", type=int)
    ap.add_argument("--mix")
    args = ap.parse_args(argv)
    name = args.config
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
    # (first position compared, how many) a row: the logits that picked a
    # request's output tokens
    if args.mix is None:
        spans = [(191, 116)] * 16
    else:
        planned = traffic_gen.open_loop_requests(
            harness.load_json("traffic", args.mix + ".json")["arrivals"],
            seed=1, seconds=harness.benchmark_json()["run_seconds"],
            vocab_size=shapes["vocab"])
        spans = [(len(p.prompt) - 1, p.max_new_tokens) for p in planned[:16]]

    def read(params, ids, span):
        # fenced: a row's whole logits go before the next row's are made
        return jax.block_until_ready(
            ref(params, ids)[span[0]:span[0] + span[1]])
    print(json.dumps({"config": name, "device": jax.devices()[0].device_kind,
                      "spans": spans}), flush=True)
    for seed in args.seeds:
        params = jax.jit(model.init)(
            jax.random.PRNGKey(traffic_gen.fold_seed(seed)))
        rng = np.random.RandomState(traffic_gen.fold_seed(seed, 5))
        # sixteen rows, as the serve kind replays sixteen requests; one
        # learnable row for the train comparison, which reads every position
        served = [jnp.asarray(rng.randint(
            0, shapes["vocab"], (1, shapes["positions"])).astype(np.int32))
            for _ in spans]
        trained = jnp.asarray(traffic_gen.arith_rows(
            rng, shapes["vocab"], (1, shapes["positions"]))["input_ids"])
        want = [read(params, ids, span) for ids, span in zip(served, spans)]
        want_trained = ref(params, trained)
        params = int8_weights_in_place(params)
        rows = [compare(w, read(params, ids, span), 0, span[1])
                for w, ids, span in zip(want, served, spans)]
        checked = sum(r["checked"] for r in rows)
        out = {"seed": seed,
               "serve": {"worst_gap": max(r["worst_gap"] for r in rows),
                         "mean_gap": sum(r["gap_sum"] for r in rows) / checked,
                         "flipped": sum(r["flipped"] for r in rows),
                         "checked": checked},
               "train": {"logit_err": compare(
                   want_trained, ref(params, trained), 0, 1)["logit_err"]},
               "requests": [[r["checked"], r["worst_gap"], r["gap_sum"]]
                            for r in rows],
               "memory_peak_bytes": harness.memory_peak_bytes(
                   jax.devices()[:1])}
        print(json.dumps(out), flush=True)
        del params, want, want_trained
    return 0


if __name__ == "__main__":
    sys.exit(main())
