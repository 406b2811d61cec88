#!/usr/bin/env python
"""Measured kernel-plan micro-autotuner (ISSUE 12 satellite; VERDICT
next-round #4).

Times candidate plans for the Pallas serving kernels on the RUNNING
backend and writes the committed plan artifact
(``AUTOTUNE_KERNELS_MEASURED.json``) that ops/autotune.py serves back
to the kernels at trace time:

  * ``decode_step``        — ``(bg, cs, vmem_mb, mha)`` per slot-paged
    geometry (ops/decode_step.fused_decode_step);
  * ``block_decode_step``  — ``(vmem_mb, mha)`` per block-paged
    geometry, bf16 AND quantized pools
    (ops/decode_step.fused_block_decode_step);
  * ``int8_matmul_dma``    — ``(bd, be)`` divisor tiles per weight
    shape (ops/int8_matmul.int8_matmul_dma).

The HAND-PICKED plan is always candidate 0 and the chosen plan is the
measured argmin, so a committed entry beats-or-ties the constants by
construction in its own windows (``us`` vs ``hand_us`` record both).
Timing methodology is bench.py's: per-candidate MEDIAN over several
best-of windows with block_until_ready fences.

Usage:
    python scripts/autotune_kernels.py --preset cpu-smoke   # sandbox
    python scripts/autotune_kernels.py --preset 125m        # on TPU
    python scripts/autotune_kernels.py --preset 7b          # on TPU

The cpu-smoke preset exists to keep the artifact format, the loading
path, and the beats-or-ties invariant exercised per-commit; interpret-
mode timings do NOT transfer to TPU, which is why ops/autotune.lookup
gates entries on the artifact's recorded backend.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops import autotune
from deepspeed_tpu.ops.decode_step import (_VMEM_LIMIT, _plan,
                                           fused_block_decode_step,
                                           fused_decode_step,
                                           supports, supports_block)
from deepspeed_tpu.ops.int8_matmul import (_aligned_divisors,
                                           _hand_dma_plan,
                                           int8_matmul_dma)
from deepspeed_tpu.serving.kv_quant import quantized_pool_like


def time_call(fn, *args, windows: int = 3, calls: int = 3) -> float:
    """Median over ``windows`` of (best-effort) per-call seconds, each
    window timing ``calls`` back-to-back invocations behind a
    block_until_ready fence. One untimed warmup call absorbs
    trace/compile."""
    jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / calls)
    return statistics.median(samples)


def _us(seconds: float) -> float:
    return round(seconds * 1e6, 1)


# ---------------------------------------------------------------- decode
def tune_decode_step(b, hkv, s_max, dh, *, dtype=jnp.bfloat16,
                     windows=3, calls=3):
    """One slot-paged geometry: hand plan first, then a small
    (bg, cs, mha) grid. Returns (key, entry)."""
    assert supports(hkv, hkv, s_max, dh), (hkv, s_max, dh)
    itemsize = jnp.dtype(dtype).itemsize
    from deepspeed_tpu.ops.attention import kv_pack_factor

    pair = kv_pack_factor(dh)
    rng = np.random.RandomState(0)
    l = 1
    k_full = jnp.asarray(
        rng.randn(l, b, hkv, s_max // pair, dh * pair), dtype) * 0.1
    v_full = jnp.asarray(
        rng.randn(l, b, hkv, s_max // pair, dh * pair), dtype) * 0.1
    q = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    kn = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    vn = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    idx = jnp.asarray(rng.randint(s_max // 2, s_max - 8, size=(b,)),
                      jnp.int32)

    hand_bg, hand_cs = _plan(b, hkv, s_max, dh, itemsize)
    hand = {"bg": hand_bg, "cs": hand_cs, "vmem_mb": _VMEM_LIMIT >> 20,
            "mha": "mxu"}
    cands = [hand]
    bgs = sorted({g for g in (b, b // 2, 1) if g >= 1 and b % g == 0})
    css = [c for c in (128, 256, 512) if s_max % c == 0]
    for bg in bgs:
        for cs in css:
            for mha in ("mxu", "vpu"):
                c = {"bg": bg, "cs": cs, "vmem_mb": _VMEM_LIMIT >> 20,
                     "mha": mha}
                if c not in cands:
                    cands.append(c)

    results = []
    for cand in cands:
        fn = jax.jit(functools.partial(
            lambda q, k, v, kn, vn, idx, _p: fused_decode_step(
                q, k, v, kn, vn, 0, idx, plan=_p)[0], _p=cand))
        results.append((time_call(fn, q, k_full, v_full, kn, vn, idx,
                                  windows=windows, calls=calls), cand))
    results.sort(key=lambda r: r[0])
    best_t, best = results[0]
    hand_t = next(t for t, c in results if c == hand)
    entry = dict(best, us=_us(best_t), hand_us=_us(hand_t),
                 n_candidates=len(cands))
    return autotune.decode_key(b, hkv, s_max, dh, itemsize), entry


def tune_block_decode(b, hkv, bs, dh, *, dtype=jnp.bfloat16, kv_dtype=None,
                      mb=4, windows=3, calls=3):
    """One block-paged geometry (bf16 or quantized pool): the chunk
    size IS the pool block size, so only (vmem_mb, mha) are tunable."""
    assert supports_block(hkv, hkv, bs, dh), (hkv, bs, dh)
    from deepspeed_tpu.ops.attention import kv_pack_factor

    pair = kv_pack_factor(dh)
    rng = np.random.RandomState(0)
    n = b * mb + 1
    base = jnp.asarray(
        rng.randn(1, n + 1, hkv, bs // pair, dh * pair), dtype) * 0.1
    if kv_dtype is not None:
        k_pool = quantized_pool_like(base, dh, kv_dtype)
        v_pool = quantized_pool_like(base, dh, kv_dtype)
        itemsize = 1
    else:
        k_pool, v_pool = base, base + 0.01
        itemsize = jnp.dtype(dtype).itemsize
    q = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    kn = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    vn = jnp.asarray(rng.randn(b, 1, hkv, dh), dtype)
    idx = jnp.asarray(rng.randint(bs, mb * bs - 1, size=(b,)), jnp.int32)
    tbl = jnp.asarray(rng.permutation(n)[:b * mb].reshape(b, mb),
                      jnp.int32)

    hand = {"vmem_mb": _VMEM_LIMIT >> 20, "mha": "mxu"}
    cands = [hand] + [{"vmem_mb": v, "mha": m}
                      for v in (_VMEM_LIMIT >> 20, 64)
                      for m in ("mxu", "vpu")
                      if {"vmem_mb": v, "mha": m} != hand]
    results = []
    for cand in cands:
        fn = jax.jit(functools.partial(
            lambda q, k, v, kn, vn, idx, tbl, _p: fused_block_decode_step(
                q, k, v, kn, vn, 0, idx, tbl, plan=_p)[0], _p=cand))
        results.append((time_call(fn, q, k_pool, v_pool, kn, vn, idx, tbl,
                                  windows=windows, calls=calls), cand))
    results.sort(key=lambda r: r[0])
    best_t, best = results[0]
    hand_t = next(t for t, c in results if c == hand)
    entry = dict(best, us=_us(best_t), hand_us=_us(hand_t),
                 kv_dtype=kv_dtype or "compute", n_candidates=len(cands))
    return autotune.block_decode_key(b, hkv, bs, dh, itemsize), entry


# ------------------------------------------------------------ int8 matmul
def tune_int8_matmul(d, e, *, b=8, dtype=jnp.bfloat16, windows=3, calls=3):
    """One [D, E] int8 weight shape: hand plan + the distinct plans a
    few VMEM caps yield + a couple of narrower-row alternatives."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(b, d), dtype)
    q = jnp.asarray(rng.randint(-127, 128, size=(d, e)), jnp.int8)
    s = jnp.asarray(rng.rand(1, e) * 0.01 + 1e-3, jnp.float32)

    hand = _hand_dma_plan(d, e)
    assert hand is not None, (d, e)
    cands = [hand]
    for cap in (1_250_000, 2_500_000, 5_000_000):
        p = _hand_dma_plan(d, e, cap)
        if p is not None and p not in cands:
            cands.append(p)
    # narrower rows (half/quarter E) with fatter bd, if they divide
    for be in _aligned_divisors(e):
        if be in (hand[1],) or be * 4 < hand[1]:
            continue
        for bd in reversed(_aligned_divisors(d)):
            if bd * be <= 2_500_000:
                p = (bd, be)
                if p not in cands:
                    cands.append(p)
                break
        if len(cands) >= 6:
            break

    results = []
    for cand in cands:
        fn = functools.partial(int8_matmul_dma, plan=tuple(cand))
        results.append((time_call(fn, x, q, s, windows=windows,
                                  calls=calls), tuple(cand)))
    results.sort(key=lambda r: r[0])
    best_t, best = results[0]
    hand_t = next(t for t, c in results if c == tuple(hand))
    entry = {"bd": best[0], "be": best[1], "us": _us(best_t),
             "hand_us": _us(hand_t), "n_candidates": len(cands)}
    return autotune.matmul_key(d, e), entry


# ------------------------------------------------------------------ main
PRESETS = {
    # tiny interpret-mode shapes: keeps the artifact format + loading
    # path + beats-or-ties invariant exercised on the CPU sandbox
    "cpu-smoke": {
        "decode": [(4, 4, 256, 64)],
        "block": [(2, 4, 16, 64, None), (2, 4, 16, 64, "int8")],
        "matmul": [(256, 512)],
        "windows": 2, "calls": 2,
    },
    # GPT-2-125M serving geometry (B=8 decode, prompt 512 cache 640)
    "125m": {
        "decode": [(8, 12, 640, 64), (1, 12, 640, 64)],
        "block": [(8, 12, 128, 64, None), (8, 12, 128, 64, "int8"),
                  (8, 12, 128, 64, "fp8")],
        "matmul": [(768, 2304), (768, 768), (768, 3072), (3072, 768)],
        "windows": 5, "calls": 8,
    },
    # 6.7B geometry (Dh=128, LLaMA-ish MLP dims)
    "7b": {
        "decode": [(1, 32, 2048, 128), (8, 32, 2048, 128)],
        "block": [(8, 32, 128, 128, None), (8, 32, 128, 128, "int8"),
                  (8, 32, 128, 128, "fp8")],
        "matmul": [(4096, 12288), (4096, 4096), (4096, 11008),
                   (11008, 4096)],
        "windows": 5, "calls": 8,
    },
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", choices=sorted(PRESETS), default=None,
                    help="shape set (default: cpu-smoke off-TPU, 125m on)")
    # artifact_path() honors DSTPU_KERNEL_PLANS, whose documented
    # empty-string value DISABLES lookups — never let it eat the write
    ap.add_argument("--out",
                    default=autotune.artifact_path()
                    or autotune._REPO_ARTIFACT)
    args = ap.parse_args(argv)
    backend = jax.default_backend()
    preset = args.preset or ("125m" if backend == "tpu" else "cpu-smoke")
    cfg = PRESETS[preset]
    w, c = cfg["windows"], cfg["calls"]

    plans = {"decode_step": {}, "block_decode_step": {},
             "int8_matmul_dma": {}}
    for (b, hkv, s_max, dh) in cfg["decode"]:
        key, ent = tune_decode_step(b, hkv, s_max, dh, windows=w, calls=c)
        plans["decode_step"][key] = ent
        print(f"decode_step {key}: {ent}")
    for (b, hkv, bs, dh, kvd) in cfg["block"]:
        key, ent = tune_block_decode(b, hkv, bs, dh, kv_dtype=kvd,
                                     windows=w, calls=c)
        # quantized and bf16 pools share a key only if itemsizes match;
        # keep the better-measured entry on collision
        old = plans["block_decode_step"].get(key)
        if old is None or ent["us"] < old["us"]:
            plans["block_decode_step"][key] = ent
        print(f"block_decode_step {key}: {ent}")
    for (d, e) in cfg["matmul"]:
        key, ent = tune_int8_matmul(d, e, windows=w, calls=c)
        plans["int8_matmul_dma"][key] = ent
        print(f"int8_matmul_dma {key}: {ent}")

    art = {
        "metric": "kernel_plan_autotune",
        "backend": backend,
        "device": str(jax.devices()[0].device_kind),
        "preset": preset,
        "method": f"median_of_{w}x{c}call_windows_vs_hand_candidate0",
        "plans": plans,
    }
    with open(args.out, "w") as f:
        json.dump(art, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
