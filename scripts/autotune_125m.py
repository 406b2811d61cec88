"""Dogfood the autotuner on the GPT-2-125M bench config (8-device mesh).

Compile-time search over the template knobs that matter for the bench
(micro-batch x gas x remat at ZeRO-2); the chosen config and every trial's
memory/roofline verdict are committed as AUTOTUNE_125M.json. Runs on the
virtual CPU mesh (self-bootstrapping subprocess, like scripts/memplan.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def run():
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning import Autotuner
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config.gpt2_125m(), compute_dtype=jnp.bfloat16)
    tuner = Autotuner(model, {
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "gradient_clipping": 1.0,
    }, seq_len=1024, vocab_size=50257, hbm_bytes=16e9,
        peak_flops=197e12, hbm_bw=819e9)
    best = tuner.tune(zero_stages=(2,), space={
        "micro_batch": [4, 8], "gas": [16],
        "offload": [False], "remat": [None, "dots_no_batch"]})
    out = {
        "best": best,
        "model_info": tuner.model_info(),
        "trials": [dataclasses.asdict(r) for r in tuner.results],
    }
    print("AUTOTUNE_JSON " + json.dumps(out))


def main():
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["DSTPU_ACCELERATOR"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = (f"import sys; sys.path.insert(0, {_REPO!r}); "
            f"from scripts.autotune_125m import run; run()")
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO, env=env,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=3000)
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        raise SystemExit(f"autotune child failed rc={proc.returncode}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("AUTOTUNE_JSON "))
    out = json.loads(line[len("AUTOTUNE_JSON "):])
    with open(os.path.join(_REPO, "AUTOTUNE_125M.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["best"], indent=1))
    print("wrote AUTOTUNE_125M.json")


if __name__ == "__main__":
    main()
