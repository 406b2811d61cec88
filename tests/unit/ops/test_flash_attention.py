"""Pallas flash-attention tests (interpret mode on CPU — same kernel lines
the TPU runs; analog of reference tests/unit/ops/transformer/ numeric
comparisons vs dense torch attention)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import multihead_attention
from deepspeed_tpu.ops.flash_attention import flash_attention


def qkv(b=2, t=64, h=2, dh=16, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, dh), dtype) * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t", [32, 64, 96])
def test_flash_forward_matches_dense(causal, t):
    q, k, v = qkv(t=t)
    ref = multihead_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 32, 16, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = qkv(t=64, seed=1)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal, None, 32, 32, True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(multihead_attention(q, k, v, causal=causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


def test_flash_custom_scale():
    q, k, v = qkv(seed=2)
    ref = multihead_attention(q, k, v, causal=True, scale=0.1)
    out = flash_attention(q, k, v, True, 0.1, 32, 32, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = qkv(dtype=jnp.bfloat16, seed=3)
    ref = multihead_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 32, 32, True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=5e-2, atol=5e-2)


def test_flash_odd_block_sizes():
    # t not divisible by preferred blocks → _pick_block halves until it fits
    q, k, v = qkv(t=48, seed=4)
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    ref = multihead_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_gpt2_flash_matches_dense_forward():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg = GPT2Config.tiny(max_seq_len=64)
    dense = GPT2Model(cfg, compute_dtype=jnp.float32)
    flash = GPT2Model(cfg, compute_dtype=jnp.float32, attn_impl="flash")
    params = jax.jit(dense.init)(jax.random.PRNGKey(0))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 33)).astype(np.int32)
    batch = {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}
    l1, _ = dense.apply(params, batch)
    l2, _ = flash.apply(params, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_registry_exposes_flash_attention():
    from deepspeed_tpu.ops import all_ops, get_op_builder

    assert "flash_attention" in all_ops()
    builder = get_op_builder("flash_attention")()
    assert builder.is_compatible()
    mod = builder.load()
    assert hasattr(mod, "flash_attention")


@pytest.mark.parametrize("tp,stage", [(2, 1), (1, 3)])
def test_flash_composes_with_tp_and_zero(tp, stage):
    """Flash attention inside the fused train step on a tp>1 (model-axis)
    and a ZeRO-3 (data-axis) mesh — the bench's default attention path.
    Here the kernel is interpreted, which GSPMD could partition by itself;
    the compiled kernel cannot, so sp_attention runs it under shard_map
    (tests/unit/ops/test_tpu_compile.py compiles that for a 2x2 v5e)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.parallel.topology import build_topology
    from deepspeed_tpu.utils import groups

    groups.reset()
    topo = build_topology(tp=tp)
    model = GPT2Model(GPT2Config.tiny(), attn_impl="flash")
    engine, *_ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": stage},
        "tensor_parallel": {"tp_size": tp},
        "steps_per_print": 0})
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(3):
        ids = (rng.randint(0, 256, (1, 16, 1)) + np.arange(33)) % 512
        b = {"input_ids": ids[:, :, :-1].astype(np.int32),
             "labels": ids[:, :, 1:].astype(np.int32)}
        losses.append(float(jax.device_get(engine.train_batch_from_stacked(b))))
    assert losses[-1] < losses[0], losses
