"""Random-LTD token routing THROUGH the model (round-4 VERDICT missing #1).

Reference behavior: data_routing/basic_layer.py RandomLayerTokenDrop drops
a scheduled random subset of tokens inside every non-reserved transformer
layer during training; scheduler.py ramps the kept-token count. Here the
kept count rides model.apply(ltd_keep=...) as a static shape.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.utils import groups


def _batch(cfg, b, t, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, size=(b, t + 1)).astype(np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def test_model_ltd_keep_drops_tokens():
    """ltd_keep < T changes the forward (tokens actually routed), keeps
    the loss finite, and ltd_keep >= T is the exact baseline."""
    cfg = GPT2Config(vocab_size=256, max_seq_len=64, num_layers=4,
                     hidden_size=64, num_heads=4)
    model = GPT2Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 64)
    rngs = {"dropout": jax.random.PRNGKey(1)}

    # one program a setting (op by op, four layers' forward and backward
    # compiled an operation at a time: most of a minute)
    def loss(**kw):
        return jax.jit(lambda p: model.apply(p, batch, rngs=rngs, train=True,
                                             **kw)[0])

    base = loss()(params)
    full = loss(ltd_keep=64)(params)
    half = loss(ltd_keep=32)(params)
    assert float(full) == float(base)          # keep >= T: path disabled
    assert np.isfinite(float(half))
    assert float(half) != float(base)          # tokens were actually dropped
    # deterministic under the same rng
    half2 = loss(ltd_keep=32)(params)
    assert float(half) == float(half2)
    # grads flow through the routed path
    g = jax.jit(jax.grad(lambda p: model.apply(
        p, batch, rngs=rngs, train=True, ltd_keep=32)[0]))(params)
    gn = sum(float(jnp.abs(x).sum()) for x in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gn) and gn > 0


def test_model_ltd_eval_and_inference_unaffected():
    cfg = GPT2Config(vocab_size=256, max_seq_len=64, num_layers=3,
                     hidden_size=64, num_heads=4)
    model = GPT2Model(cfg, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, 2, 64)
    e0, _ = model.apply(params, batch, train=False)
    e1, _ = model.apply(params, batch, train=False, ltd_keep=16)
    assert float(e0) == float(e1)  # eval never drops


def test_engine_ltd_schedule_e2e():
    """Engine wiring: the scheduler's kept count follows the configured
    ramp, the step runs with reduced token routing, and loss stays sane
    vs a no-LTD run on the same data."""
    groups.reset()
    cfg = GPT2Config(vocab_size=256, max_seq_len=64, num_layers=4,
                     hidden_size=64, num_heads=4)

    def make_engine(ltd):
        config = {
            "train_batch_size": 8, "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 0,
        }
        if ltd:
            config["data_efficiency"] = {
                "enabled": True,
                "data_routing": {
                    "enabled": True,
                    "random_ltd": {
                        "enabled": True,
                        "random_ltd_schedule": {
                            "min_value": 16, "max_value": 64,
                            "schedule_config": {
                                "total_layer_tokens_steps": 4,
                                "seq_per_step": 16}},
                    },
                },
            }
        groups.reset()
        model = GPT2Model(cfg, compute_dtype=jnp.float32)
        engine, *_ = deepspeed_tpu.initialize(model=model, config=config)
        return engine

    eng = make_engine(ltd=True)
    assert eng._use_random_ltd
    losses, keeps = [], []
    for step in range(6):
        loss = eng.train_batch_from_stacked(
            {k: v[None] for k, v in _batch(cfg, 8, 64, seed=step).items()})
        losses.append(float(jax.device_get(loss)))
        keeps.append(eng.random_ltd_scheduler.get_current_seq())
    # ramp 16 -> 64 over 4 steps in granules of 16, then saturate
    assert keeps[0] == 16 and keeps[-1] == 64
    assert keeps == sorted(keeps)
    assert all(np.isfinite(l) for l in losses)

    ref = make_engine(ltd=False)
    ref_losses = []
    for step in range(6):
        loss = ref.train_batch_from_stacked(
            {k: v[None] for k, v in _batch(cfg, 8, 64, seed=step).items()})
        ref_losses.append(float(jax.device_get(loss)))
    # dropping tokens must not blow the loss up: same ballpark as no-LTD
    assert abs(losses[-1] - ref_losses[-1]) < 1.5
