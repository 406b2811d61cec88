"""GPT-2-1.3B serving latency on one chip — bf16 vs int8 weight-only.

The >=1B-param serving half of the BASELINE ladder ("the inference engine
serves the resulting checkpoint"): batch-1 prefill + per-token decode
latency through `init_inference`'s compiled prefill+decode programs.
Params are random-init ON DEVICE (weight values don't change the timing;
no host transfer involved). Writes SERVE_1B3.json at the repo root.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main():
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.utils import groups

    cfg = GPT2Config.gpt2_1b3()
    prompt_len, decode_len, trials = 512, 64, 9
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, size=(1, prompt_len)).astype(np.int32)
    out = {"metric": "gpt2_1b3_serving", "prompt_len": prompt_len,
           "decode_len": decode_len, "batch": 1}
    for dtype in ("bf16", "int8"):
        groups.reset()
        engine = deepspeed_tpu.init_inference(
            GPT2Model(cfg), dtype=dtype,
            max_out_tokens=prompt_len + decode_len + 1)
        engine.generate(ids, max_new_tokens=1)
        engine.generate(ids, max_new_tokens=decode_len + 1)

        def timed(new_tokens):
            t0 = time.perf_counter()
            engine.generate(ids, max_new_tokens=new_tokens)
            return time.perf_counter() - t0

        prefill = sorted(timed(1) for _ in range(trials))
        full = sorted(timed(decode_len + 1) for _ in range(trials))
        decode_best = full[0] - prefill[0]
        out[dtype] = {
            "prefill_p50_ms": round(prefill[len(prefill) // 2] * 1e3, 1),
            "prefill_best_ms": round(prefill[0] * 1e3, 1),
            "decode_ms_per_token": round(decode_best * 1e3 / decode_len, 3)
            if decode_best > 0 else None,
            "decode_tokens_per_sec": round(decode_len / decode_best, 1)
            if decode_best > 0 else None,
        }
        del engine
    print(json.dumps(out))
    with open(os.path.join(_REPO, "SERVE_1B3.json"), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
