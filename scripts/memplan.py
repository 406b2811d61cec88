"""MEMPLAN generator: AOT per-chip memory plan for the LLaMA-7B ZeRO-3
north-star config (BASELINE.md) on an 8-device mesh — WITHOUT needing 8
chips. XLA's compile-time ``memory_analysis()`` is exact about the buffer
plan, so infeasibility shows up here, not at step 1 on a pod.

Runs on the virtual CPU mesh (self-bootstrapping like __graft_entry__).
Writes MEMPLAN.md at the repo root.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def _child_env(n):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["DSTPU_ACCELERATOR"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n}").strip()
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def analyze(seq_len: int = 2048, micro_batch: int = 1, n_devices: int = 8,
            remat_policy: str = "dots_no_batch"):
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.utils import groups

    groups.reset()
    topo = build_topology(world_size=n_devices)
    cfg = LlamaConfig.llama_7b()
    model = LlamaModel(cfg, remat=True, remat_policy=remat_policy)
    dp = topo.data_parallel_size
    engine, *_ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_batch_size": micro_batch * dp,
        "gradient_accumulation_steps": 1,
        "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        "zero_optimization": {"stage": 3,
                              "stage3_param_persistence_threshold": int(1e5)},
    }, )
    step_fn = engine._build_train_step()
    batch = {
        "input_ids": jax.ShapeDtypeStruct((1, micro_batch * dp, seq_len), np.int32),
        "labels": jax.ShapeDtypeStruct((1, micro_batch * dp, seq_len), np.int32),
    }
    lr = jax.ShapeDtypeStruct((), np.float32)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = step_fn.lower(engine.state, batch, lr, rng).compile()
    ma = compiled.memory_analysis()
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
        jax.eval_shape(model.init, jax.random.PRNGKey(0))))
    out = {
        "n_params": n_params,
        "n_devices": n_devices,
        "seq_len": seq_len,
        "micro_batch_per_dp": micro_batch,
        "zero_stage": 3,
        "remat_policy": remat_policy,
        # memory_analysis reports PER-DEVICE bytes (verified: a [8,1024]
        # f32 sharded 8-ways reports argument_size 4096 = global/8)
        "argument_bytes": int(getattr(ma, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
        "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
    }
    print("MEMPLAN_JSON " + json.dumps(out))


def main(n: int = 8):
    code = (f"import sys; sys.path.insert(0, {_REPO!r}); "
            f"from scripts.memplan import analyze; analyze(n_devices={n})")
    proc = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                          env=_child_env(n), stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True, timeout=3000)
    if proc.returncode != 0:
        sys.stdout.write(proc.stdout)
        raise SystemExit(f"memplan child failed rc={proc.returncode}")
    line = next(l for l in proc.stdout.splitlines()
                if l.startswith("MEMPLAN_JSON "))
    d = json.loads(line[len("MEMPLAN_JSON "):])
    n = d["n_devices"]  # single source of truth for per-device divisions
    per_dev = {
        "params_master_fp32_gb": round(4 * d["n_params"] / n / 1e9, 2),
        "adam_moments_fp32_gb": round(8 * d["n_params"] / n / 1e9, 2),
        "compute_bf16_shard_gb": round(2 * d["n_params"] / n / 1e9, 2),
        # argument/temp bytes are already per-device (see analyze())
        "args_gb": round(d["argument_bytes"] / 1e9, 2),
        "temps_gb": round(d["temp_bytes"] / 1e9, 2),
    }
    total = per_dev["args_gb"] + per_dev["temps_gb"]
    onebatch = d["micro_batch_per_dp"]
    md = f"""# MEMPLAN — LLaMA-7B ZeRO-3 memory plan (AOT, {n}-device mesh)

Generated by `scripts/memplan.py` via XLA's compile-time
`compiled.memory_analysis()` — the exact buffer plan for ONE full training
step (fused gas-scan -> ZeRO-3 collectives -> clip -> AdamW), compiled for
a {n}-device `jax.sharding.Mesh` without any TPU hardware.
North-star config per BASELINE.md: ZeRO-3, bf16 compute, fp32 master +
Adam moments sharded over 'data', seq {d['seq_len']}, micro-batch
{onebatch}/device, remat `{d['remat_policy']}`.

| quantity | value |
|---|---|
| params | {d['n_params']/1e9:.2f} B |
| devices | {d['n_devices']} |
| per-device fp32 master shard | {per_dev['params_master_fp32_gb']} GB |
| per-device Adam moment shards | {per_dev['adam_moments_fp32_gb']} GB |
| per-device bf16 compute shard | {per_dev['compute_bf16_shard_gb']} GB |
| per-device program arguments (state+batch) | {per_dev['args_gb']} GB |
| per-device HLO temporaries | {per_dev['temps_gb']} GB |
| **per-device total (args + temps)** | **{total:.2f} GB** |

Raw XLA memory_analysis (PER-DEVICE bytes): `{json.dumps({k: d[k] for k in ('argument_bytes', 'output_bytes', 'temp_bytes', 'alias_bytes')})}`

Reading: at {n} devices the plan needs ~{total:.1f} GB/chip — v5p-class HBM
territory; a 16 GB v5e needs a larger mesh (the fp32 state in `args` and
the grad/activation share of `temps` shrink as 1/devices) and/or
`offload_optimizer` (drops the 12 bytes/param fp32 state from `args` to
host — see scripts/run_1b3_offload.py for that path measured on a real
chip at 1.3B). Caveats: this is XLA:CPU's buffer plan — a conservative
upper bound. The TPU compiler re-plans with its own scheduler; in
particular the large `temps` term includes ZeRO-3's gathered bf16 params,
which the TPU scheduler overlaps per-layer inside the scanned stack.

## Measured on one real chip: GPT-2-1.3B + ZeRO-Offload

See `BENCH_1B3.json` (generated by `scripts/run_1b3_offload.py`): 1.3B
params train on ONE 16 GB chip with host-resident optimizer state
(12 bytes/param = 15.7 GB in host RAM — impossible in HBM), the
ZeRO-Offload headline capability (reference
docs/_posts/2021-03-08-zero3-offload.md:65).
"""
    with open(os.path.join(_REPO, "MEMPLAN.md"), "w") as f:
        f.write(md)
    print(md)
    print("wrote MEMPLAN.md")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 8)
