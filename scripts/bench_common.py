"""Shared single-chip training-throughput harness for the sweep scripts.

One copy of the methodology (engine build → warmup/compile → best-of-N
short windows, fenced by a `jax.device_get` value fetch). bench.py intentionally keeps its own inline copy so the driver can
run it with zero repo-internal imports beyond the package.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np


def train_tokens_per_sec(*, attn_impl: str, remat: bool, remat_policy,
                         batch: int, gas: int, seq: int = 1024,
                         steps: int = 8, windows: int = 3,
                         zero_stage: int = 0, loss_chunk: int = 0) -> float:
    """GPT-2-125M bf16 training throughput for one knob setting."""
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.utils import groups

    groups.reset()
    cfg = GPT2Config.gpt2_125m(max_seq_len=seq)
    if loss_chunk:
        cfg = dataclasses.replace(cfg, loss_chunk=loss_chunk)
    model = GPT2Model(cfg, remat=remat, remat_policy=remat_policy,
                      attn_impl=attn_impl)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": batch * gas,
        "gradient_accumulation_steps": gas,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4,
                                                  "weight_decay": 0.01}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "zero_optimization": {"stage": zero_stage},
    })
    rng = np.random.RandomState(0)

    def make_batch():
        ids = rng.randint(0, cfg.vocab_size,
                          size=(gas, batch, seq + 1)).astype(np.int32)
        return {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]}

    for _ in range(2):
        loss = engine.train_batch_from_stacked(make_batch())
    float(jax.device_get(loss))
    best = float("inf")
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = engine.train_batch_from_stacked(make_batch())
        float(jax.device_get(loss))
        best = min(best, time.perf_counter() - t0)
    return batch * gas * seq * steps / best


RESULT_TAG = "PHASE_RESULT:"


def emit_phase_result(result) -> None:
    import json

    print(RESULT_TAG + json.dumps(result), flush=True)


def run_phase_isolated(script_path, name, attempts=3, timeout=2400):
    """Run `python script_path --phase name` in fresh subprocesses until one
    succeeds (emits a RESULT_TAG line). A RESOURCE_EXHAUSTED leaves the
    JAX client unusable, so in-process retries are useless — each attempt
    needs a clean process. A chip belongs to one process at a time: the
    caller must not have touched JAX, or the child cannot get the chip."""
    import json
    import subprocess
    import sys
    import time

    last = None
    for attempt in range(attempts):
        try:
            proc = subprocess.run(
                [sys.executable, script_path, "--phase", name],
                capture_output=True, text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            last = f"timeout after {timeout}s"
        else:
            for line in proc.stdout.splitlines():
                if line.startswith(RESULT_TAG):
                    out = json.loads(line[len(RESULT_TAG):])
                    print(f"[{name}] attempt {attempt}: ok", flush=True)
                    return out
            tail = (proc.stdout + proc.stderr)[-600:]
            last = (f"rc={proc.returncode}: "
                    f"{tail.splitlines()[-1] if tail else ''}")
        print(f"[{name}] attempt {attempt} failed: {last}", flush=True)
        if attempt + 1 < attempts:
            time.sleep(15)
    return {"error": f"all {attempts} attempts failed; last: {str(last)[:300]}"}
