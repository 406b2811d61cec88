"""Decode-latency profiling on the real chip: batch-1 and batch-8 decode
ms/token via the bench.py shape-differencing methodology (dispatch and
prefill cost cancel), across decode_unroll settings.

Usage: python scripts/profile_decode.py [--quick]
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model  # noqa: E402


def timed(engine, ids, n_new, trials):
    engine.generate(ids, max_new_tokens=n_new)  # compile
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        engine.generate(ids, max_new_tokens=n_new)
        ts.append(time.perf_counter() - t0)
    return sorted(ts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--unrolls", default="1,2,4,12")
    ap.add_argument("--batches", default="1,8")
    ap.add_argument("--dtype", default="bf16")
    args = ap.parse_args()

    prompt_len, decode_len, trials = (64, 8, 3) if args.quick else (512, 64, 9)
    cfg = GPT2Config.gpt2_125m()
    rng = np.random.RandomState(0)
    results = {}
    for unroll in [int(u) for u in args.unrolls.split(",")]:
        for b in [int(x) for x in args.batches.split(",")]:
            ids = rng.randint(0, cfg.vocab_size, size=(b, prompt_len)).astype(np.int32)
            engine = deepspeed_tpu.init_inference(
                GPT2Model(cfg, decode_unroll=unroll), dtype=args.dtype,
                max_out_tokens=prompt_len + decode_len + 1)
            pre = timed(engine, ids, 1, trials)
            full = timed(engine, ids, decode_len + 1, trials)
            dec = full[0] - pre[0]
            # a noisy window can make the difference non-positive —
            # report the sample as invalid, never negative
            results[f"unroll{unroll}_b{b}"] = {
                "decode_ms_per_token": round(dec * 1e3 / decode_len, 3) if dec > 0 else None,
                "agg_tokens_per_sec": round(b * decode_len / dec, 1) if dec > 0 else None,
                "prefill_best_ms": round(pre[0] * 1e3, 2),
            }
            print(f"unroll={unroll} b={b}: {results[f'unroll{unroll}_b{b}']}",
                  flush=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
