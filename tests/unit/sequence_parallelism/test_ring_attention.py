"""Ring / Ulysses sequence-parallel attention tests.

The reference has no SP (SURVEY §5.7) — equivalence is asserted against the
dense jnp attention, forward AND gradients, which is stronger than the
reference's block-sparse kernel tests (numeric vs dense torch)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.ops.attention import multihead_attention
from deepspeed_tpu.ops.ring_attention import (ring_attention,
    ring_flash_attention, ulysses_attention)
from deepspeed_tpu.parallel.topology import build_topology
from deepspeed_tpu.utils import groups


def qkv(b=2, t=32, h=4, dh=8, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, t, h, dh), dtype) * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense_forward(sp, causal):
    groups.reset()
    topo = build_topology(sp=sp)
    q, k, v = qkv()
    ref = multihead_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: ring_attention(
        q, k, v, mesh=topo.mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_matches_dense_gradients():
    groups.reset()
    topo = build_topology(sp=4)
    q, k, v = qkv(seed=1)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh=topo.mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(multihead_attention(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense_forward(causal):
    groups.reset()
    topo = build_topology(sp=2)
    q, k, v = qkv(seed=2)
    ref = multihead_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: ulysses_attention(
        q, k, v, mesh=topo.mesh, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_matches_dense_gradients():
    groups.reset()
    topo = build_topology(sp=2)
    q, k, v = qkv(seed=3)

    g1 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        ulysses_attention(q, k, v, mesh=topo.mesh) ** 2), argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        multihead_attention(q, k, v, causal=True) ** 2), argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_ring_bf16_runs():
    groups.reset()
    topo = build_topology(sp=2)
    q, k, v = qkv(dtype=jnp.bfloat16)
    out = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh=topo.mesh))(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = multihead_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


# ------------------------------------------------------------- model-level
@functools.lru_cache(maxsize=None)   # the dense run is three tests' reference
def _train(attn_impl, sp, steps=3):
    groups.reset()
    topo = build_topology(sp=sp)
    model = GPT2Model(GPT2Config.tiny(), compute_dtype=jnp.float32,
                      attn_impl=attn_impl)
    engine, *_ = deepspeed_tpu.initialize(model=model, topology=topo, config={
        "train_batch_size": 16,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "sequence_parallel": {"sp_size": sp},
        "steps_per_print": 0,
    })
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        start = rng.randint(0, 512, size=(1, 16, 1))
        d = rng.randint(1, 5, size=(1, 16, 1))
        ids = ((start + d * np.arange(33)) % 512).astype(np.int32)
        losses.append(float(jax.device_get(engine.train_batch_from_stacked(
            {"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]}))))
    return losses


def test_gpt2_ring_attention_matches_dense_training():
    dense = _train("dense", sp=1)
    ring = _train("ring", sp=2)
    np.testing.assert_allclose(dense, ring, rtol=2e-4)


def test_gpt2_ulysses_matches_dense_training():
    dense = _train("dense", sp=1)
    uly = _train("ulysses", sp=2)
    np.testing.assert_allclose(dense, uly, rtol=2e-4)


@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_dense_forward(sp, causal):
    """Ring with the Pallas flash kernel per hop (custom-vjp reverse ring)
    must match dense attention exactly like the jnp ring does."""
    groups.reset()
    topo = build_topology(sp=sp)
    q, k, v = qkv()
    ref = multihead_attention(q, k, v, causal=causal)
    out = jax.jit(lambda q, k, v: ring_flash_attention(
        q, k, v, topo.mesh, causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_flash_matches_dense_gradients(causal):
    """The reverse ring's hops run the one-kernel backward, by their plan
    and not by an accident of these sizes: the counters say so."""
    groups.reset()
    topo = build_topology(sp=4)
    q, k, v = qkv(seed=1)

    def loss_rf(q, k, v):
        return jnp.sum(ring_flash_attention(q, k, v, topo.mesh, causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(multihead_attention(q, k, v, causal=causal) ** 2)

    from deepspeed_tpu.telemetry.registry import get_registry

    counters = [get_registry().counter(name) for name in
                ("flash/traced_bwd_fused", "flash/traced_bwd_split")]
    before = [c.value for c in counters]
    g1 = jax.jit(jax.grad(loss_rf, argnums=(0, 1, 2)))(q, k, v)
    # the reverse ring traces its own block's hop and the one its scan
    # repeats: each a row of one grid step, so each hop's dq, dk and dv come
    # from ONE kernel (ops/flash_attention._bwd_tiles), against the global
    # lse and not causal behind hop 0
    assert [c.value - b for c, b in zip(counters, before)] == [2, 0]
    g2 = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_gpt2_ring_flash_matches_dense_training():
    dense = _train("dense", sp=1)
    rf = _train("ring_flash", sp=2)
    np.testing.assert_allclose(dense, rf, rtol=2e-4)
