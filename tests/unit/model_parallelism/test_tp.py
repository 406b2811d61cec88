"""Tensor-parallel tests: sharding placement + numerical equivalence with
pure-DP execution (the reference only tests TP indirectly through megatron
fixtures; here equivalence is asserted directly)."""

import functools
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model  # noqa: E402
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel  # noqa: E402
from deepspeed_tpu.parallel.topology import build_topology  # noqa: E402


def lm_batches(n, gas=1, b=16, t=32, vocab=512, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        start = rng.randint(0, vocab, size=(gas, b, 1))
        step = rng.randint(1, 5, size=(gas, b, 1))
        ids = (start + step * np.arange(t + 1)) % vocab
        out.append({"input_ids": ids[:, :, :-1].astype(np.int32),
                    "labels": ids[:, :, 1:].astype(np.int32)})
    return out


def run_training(model_factory, tp=1, sp=1, stage=0, steps=4, seed=0):
    from deepspeed_tpu.utils import groups

    groups.reset()
    topo = build_topology(tp=tp, sp=sp)
    engine, *_ = deepspeed_tpu.initialize(
        model=model_factory(), topology=topo, config={
            "train_batch_size": 16,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": stage,
                                  "stage3_param_persistence_threshold": 0},
            "tensor_parallel": {"tp_size": tp},
            "sequence_parallel": {"sp_size": sp},
            "steps_per_print": 0,
        })
    losses = []
    for batch in lm_batches(steps, seed=seed):
        losses.append(float(jax.device_get(engine.train_batch_from_stacked(batch))))
    return engine, losses


@functools.lru_cache(maxsize=None)
def gpt2_run(tp=1, sp=1):
    """Tiny GPT-2's engine and losses on a mesh, trained once a process for
    the tests that read them (tp=1 and tp=2 were each built by two)."""
    return run_training(lambda: GPT2Model(GPT2Config.tiny()), tp=tp, sp=sp)


def test_tp_shards_model_axis():
    engine, _ = gpt2_run(tp=2)
    spec = engine.state.params["blocks"]["mlp_fc_w"].sharding.spec
    assert "model" in str(spec), f"mlp weight not TP-sharded: {spec}"
    spec_attn = engine.state.params["blocks"]["qkv_w"].sharding.spec
    assert "model" in str(spec_attn)


def test_tp_matches_dp_numerics():
    _, dp_losses = gpt2_run()
    _, tp_losses = gpt2_run(tp=2)
    np.testing.assert_allclose(dp_losses, tp_losses, rtol=2e-4)


def test_tp_with_zero3():
    engine, losses = run_training(lambda: GPT2Model(GPT2Config.tiny()), tp=2, stage=3)
    assert losses[-1] < losses[0]
    spec = str(engine.state.params["blocks"]["mlp_fc_w"].sharding.spec)
    assert "model" in spec and "data" in spec, spec


def test_sp_matches_dp_numerics():
    _, dp_losses = gpt2_run()
    _, sp_losses = gpt2_run(sp=2)
    np.testing.assert_allclose(dp_losses, sp_losses, rtol=2e-4)


def test_llama_trains():
    engine, losses = run_training(lambda: LlamaModel(LlamaConfig.tiny()), tp=2, stage=2)
    assert losses[-1] < losses[0]


def test_llama_gqa_heads():
    cfg = LlamaConfig.tiny()
    assert cfg.num_kv_heads == 2 and cfg.num_heads == 4
    model = LlamaModel(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    batch = lm_batches(1)[0]
    loss, _ = jax.jit(lambda p, b: model.apply(p, b))(
        params, jax.tree_util.tree_map(lambda x: x[0], batch))
    assert np.isfinite(float(jax.device_get(loss)))


def test_llama_remat_matches_no_remat():
    from deepspeed_tpu.utils import groups

    cfg = LlamaConfig.tiny()
    batch = jax.tree_util.tree_map(lambda x: x[0], lm_batches(1)[0])
    m1 = LlamaModel(cfg, remat=False)
    m2 = LlamaModel(cfg, remat=True, remat_policy="dots")
    p = jax.jit(m1.init)(jax.random.PRNGKey(0))

    def grad_norm(model):
        g = jax.grad(lambda p: model.apply(p, batch)[0])(p)
        return float(jax.device_get(
            sum(jax.numpy.sum(x ** 2) for x in jax.tree_util.tree_leaves(g))))

    np.testing.assert_allclose(grad_norm(m1), grad_norm(m2), rtol=1e-5)
