"""Elastic restart end-to-end (reference elasticity/elastic_agent.py:28
DSElasticAgent): a 2-worker group loses a worker mid-training; the agent
tears the group down and restarts at world-size 1; the surviving run
resumes from the universal (sharding-agnostic) checkpoint with the
elasticity-chosen batch config for the NEW world size."""

import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

WORKER = """
import json, os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DSTPU_ACCELERATOR"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np

rank = int(os.environ["RANK"])
world = int(os.environ["WORLD_SIZE"])
ckpt = os.environ["CKPT_DIR"]
log = os.environ["RUN_LOG"]

if rank != 0:
    # non-zero rank participates then dies mid-training on round 1
    import time
    time.sleep(float(os.environ.get("DIE_AFTER_S", "2")))
    sys.exit(9)

from deepspeed_tpu.elasticity import compute_elastic_config
import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

ELASTIC = {{"elasticity": {{"enabled": True, "max_train_batch_size": 64,
                            "micro_batch_sizes": [4, 8], "min_gpus": 1,
                            "max_gpus": 4}}}}
batch, _valid, micro = compute_elastic_config(ELASTIC, world_size=world)

cfg = GPT2Config(vocab_size=64, max_seq_len=32, num_layers=1,
                 hidden_size=32, num_heads=2)
# this process's share of the elastic global batch (each worker is a
# 1-device jax process here; a real pod run passes the global triple)
engine, *_ = deepspeed_tpu.initialize(
    model=GPT2Model(cfg, compute_dtype=jax.numpy.float32), config={{
        "train_batch_size": batch // world,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": batch // (micro * world),
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-3}}}},
        "steps_per_print": 0}})

start_step = 0
if os.path.exists(os.path.join(ckpt, "latest")):
    _, client = engine.load_checkpoint(ckpt)
    start_step = int(client["step"])

rng = np.random.RandomState(start_step)
gas = engine.gradient_accumulation_steps()
TOTAL = 6
for step in range(start_step, TOTAL):
    s = (rng.randint(0, 32, size=(gas, micro, 1)) + np.arange(33)) % 64
    b = {{"input_ids": s[:, :, :-1].astype(np.int32),
          "labels": s[:, :, 1:].astype(np.int32)}}
    loss = float(np.asarray(engine.train_batch_from_stacked(b)))
    engine.save_checkpoint(ckpt, client_state={{"step": step + 1}})
    with open(log, "a") as f:
        f.write(json.dumps({{"world": world, "step": step + 1,
                             "batch": batch, "micro": micro,
                             "loss": loss}}) + "\\n")
    if rank == 0 and world > 1 and step + 1 >= 2:
        sys.exit(7)   # group failure surfaces after the peer died
sys.exit(0)
"""


def test_elastic_restart_resumes_at_new_world_size(tmp_path):
    from deepspeed_tpu.elasticity.elastic_agent import ElasticAgent

    worker_py = tmp_path / "worker.py"
    worker_py.write_text(WORKER.format(repo=REPO))
    ckpt = str(tmp_path / "ckpt")
    log = str(tmp_path / "runs.jsonl")
    world_sizes = [2, 1]   # node lost between rounds
    round_no = {"i": 0}

    def spawn():
        world = world_sizes[min(round_no["i"], len(world_sizes) - 1)]
        round_no["i"] += 1
        procs = []
        for rank in range(world):
            env = dict(os.environ,
                       RANK=str(rank), WORLD_SIZE=str(world),
                       CKPT_DIR=ckpt, RUN_LOG=log,
                       XLA_FLAGS="")  # one device per worker process
            procs.append(subprocess.Popen([sys.executable, str(worker_py)],
                                          env=env))
        return procs

    def monitor(procs):
        rcs = [p.wait(timeout=600) for p in procs]
        return max(abs(rc) for rc in rcs)

    agent = ElasticAgent(spawn, monitor, max_restarts=2, restart_delay_s=0.1)
    assert agent.run() == 0
    assert agent.restart_count == 1

    runs = [json.loads(l) for l in open(log)]
    # round 1 trained at world 2 with the elasticity batch for 2 workers;
    # round 2 resumed at world 1 with a REVALIDATED batch config
    assert runs[0]["world"] == 2 and runs[-1]["world"] == 1
    assert runs[0]["batch"] % (runs[0]["micro"] * 2) == 0
    assert runs[-1]["batch"] % runs[-1]["micro"] == 0
    # resume continued the step count — no restart from zero
    steps = [r["step"] for r in runs]
    world1_steps = [r["step"] for r in runs if r["world"] == 1]
    world2_steps = [r["step"] for r in runs if r["world"] == 2]
    assert world1_steps[0] == max(world2_steps) + 1
    assert steps[-1] == 6
    assert all(np.isfinite(r["loss"]) for r in runs)
