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


# ----------------------------------------------- the tile program's choices
def _flash_counts(*names):
    from deepspeed_tpu.telemetry.registry import get_registry

    return tuple(get_registry().counter(name).value for name in names)


def _walk_counts():
    return _flash_counts("flash/traced_short_seq", "flash/traced_grid_walk")


def _bwd_counts():
    """(backwards traced as one kernel, as two)."""
    return _flash_counts("flash/traced_bwd_fused", "flash/traced_bwd_split")


def _flash_tiles(q, k, v, do, causal, block_q, block_k, walk_budget=None,
                 scale=None):
    """Forward and backward through the internal tile-layout calls, the way
    ``flash_attention`` makes them, with the walk side's VMEM budget forced
    where a case wants the chunked walk at a tiny size. [B, T, H, Dh] in and
    out: (out, dq, dk, dv), and the rows a tile that the shapes chose."""
    from deepspeed_tpu.ops import flash_attention as fa

    b, t, h, dh = q.shape
    rows = fa._tile_rows(b * h, dh, t)
    qp, kp, vp, dop = (fa._pack(fa._reshape_bh(x), rows)
                       for x in (q, k, v, do))
    kw = dict(rows=rows, causal=causal, scale=scale or dh ** -0.5,
              block_q=block_q, block_k=block_k, interpret=True)
    if walk_budget is not None:
        kw["walk_budget"] = walk_budget
    outp, lse = fa._fwd_tiles(qp, kp, vp, **kw)
    delta = fa._delta_tiles(dop, outp, rows, lse.shape[-1])
    grads = fa._bwd_tiles(qp, kp, vp, dop, lse, delta, **kw)
    return [fa._unshape_bh(fa._unpack(x, rows), b, h)
            for x in (outp,) + tuple(grads)], rows


def _dense_with_grads(q, k, v, do, causal, scale=None):
    """In float32, whatever the storage: what bf16 results are held to."""
    f32 = lambda x: x.astype(jnp.float32)
    out, vjp = jax.vjp(
        lambda q, k, v: multihead_attention(q, k, v, causal=causal,
                                            scale=scale),
        f32(q), f32(k), f32(v))
    return [out, *vjp(f32(do))]


def _assert_matches_dense(got, want, out_tol=2e-5, grad_tol=2e-4):
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(got[0]), f32(want[0]), rtol=out_tol,
                               atol=out_tol, err_msg="out")
    for name, a, b in zip(("dq", "dk", "dv"), got[1:], want[1:]):
        np.testing.assert_allclose(f32(a), f32(b), rtol=grad_tol,
                                   atol=grad_tol, err_msg=name)


# name, (b, tq, tk, h, dh), blocks, walk budget in bytes, rows a tile, walk
TILE_CASES = [
    # gpt2-xl's 50 rows in small: 2 x 5 heads pair across the batch boundary
    ("paired_across_batch", (2, 64, 64, 5, 16), (32, 32), None, 2, "short"),
    ("odd_rows_fall_back", (1, 64, 64, 3, 16), (32, 32), None, 1, "short"),
    ("head_size_128", (1, 64, 64, 2, 128), (32, 32), None, 1, "short"),
    ("blocks_differ", (2, 128, 128, 2, 16), (32, 64), None, 2, "short"),
    ("wide_query_block", (2, 128, 128, 2, 16), (64, 32), None, 2, "short"),
    # K and V (2 x 128 x 32 floats = 32 KB) above a budget of 8 KB: chunks
    ("above_budget", (2, 128, 128, 2, 16), (32, 32), 8192, 2, "grid"),
    ("above_budget_one_row", (1, 128, 128, 3, 16), (32, 32), 4096, 1, "grid"),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("name,dims,blocks,budget,rows,walk", TILE_CASES,
                         ids=[c[0] for c in TILE_CASES])
def test_tile_program_matches_dense(name, dims, blocks, budget, rows, walk,
                                    causal):
    b, tq, tk, h, dh = dims
    q, _, _ = qkv(b=b, t=tq, h=h, dh=dh, seed=5)
    k, v, _ = qkv(b=b, t=tk, h=h, dh=dh, seed=6)
    do, _, _ = qkv(b=b, t=tq, h=h, dh=dh, seed=7)
    short0, grid0 = _walk_counts()
    fused0, split0 = _bwd_counts()
    got, got_rows = _flash_tiles(q, k, v, do, causal, *blocks,
                                 walk_budget=budget)
    short1, grid1 = _walk_counts()
    fused1, split1 = _bwd_counts()
    assert got_rows == rows
    # each kernel says which walk it was traced with: a row that is one grid
    # step has the forward and ONE backward kernel, any other walk has two
    assert (short1 - short0, grid1 - grid0) == \
        ((2, 0) if walk == "short" else (0, 3))
    assert (fused1 - fused0, split1 - split0) == \
        ((1, 0) if walk == "short" else (0, 1))
    _assert_matches_dense(got, _dense_with_grads(q, k, v, do, causal))


@pytest.mark.parametrize("budget", [None, 8192], ids=["short", "grid"])
def test_ring_hop_keys_longer_than_queries(budget):
    """A ring hop: not causal, 64 queries against 128 keys; down the one
    backward kernel where the keys stay resident, down two in chunks."""
    q, _, do = qkv(b=2, t=64, h=3, dh=16, seed=8)
    k, v, _ = qkv(b=2, t=128, h=3, dh=16, seed=9)
    fused0, split0 = _bwd_counts()
    got, rows = _flash_tiles(q, k, v, do, False, 32, 32, walk_budget=budget)
    fused1, split1 = _bwd_counts()
    assert rows == 2
    assert (fused1 - fused0, split1 - split0) == \
        ((1, 0) if budget is None else (0, 1))
    _assert_matches_dense(got, _dense_with_grads(q, k, v, do, False))


# name, storage, scale (None: head size ** -0.5, a power of two), p and ds in
# float32 (DSTPU_FLASH_F32_PRECISE), tolerance against dense (out, gradients)
WALK_STORAGE = [
    ("f32", jnp.float32, None, False, (2e-5, 2e-4)),
    # any scale is folded into a float32 tile; in bf16 0.3 multiplies the
    # scores instead, and dq carries it at the end as dk does
    ("f32_scale_0.3", jnp.float32, 0.3, False, (2e-5, 2e-4)),
    ("bf16", jnp.bfloat16, None, False, (3e-2, 3e-2)),
    ("bf16_precise", jnp.bfloat16, None, True, (3e-2, 3e-2)),
    ("bf16_scale_0.3", jnp.bfloat16, 0.3, False, (3e-2, 3e-2)),
]


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("name,dtype,scale,precise,dense_tol", WALK_STORAGE,
                         ids=[c[0] for c in WALK_STORAGE])
def test_all_walks_agree(name, dtype, scale, precise, dense_tol, causal,
                         monkeypatch):
    """The same inputs and blocks down the resident walk unrolled by hand
    (one backward kernel for dq, dk and dv), down the same walk with the
    backward split in two (the fused form refused), down the resident walk
    as a fori_loop (a row of more blocks than are unrolled) and down the
    chunked one: only the loop, who carries the softmax state, and the
    order of dq's float32 sums differ."""
    from deepspeed_tpu.ops import flash_attention as fa

    if precise:
        monkeypatch.setenv("DSTPU_FLASH_F32_PRECISE", "1")
    q, k, v = qkv(b=2, t=128, h=5, dh=16, seed=10, dtype=dtype)
    do, _, _ = qkv(b=2, t=128, h=5, dh=16, seed=11, dtype=dtype)
    walk = lambda **kw: _flash_tiles(q, k, v, do, causal, 32, 32,
                                     scale=scale, **kw)[0]
    counts = [_bwd_counts()]
    fused = walk()
    counts.append(_bwd_counts())
    chunked = walk(walk_budget=4096)
    monkeypatch.setattr(fa, "_FUSED_VMEM", 0)    # no row fits: two kernels
    split = walk()
    monkeypatch.setattr(fa, "_MAX_UNROLL", 4)    # the row has 16 blocks
    monkeypatch.setattr(fa, "_MAX_TILE", 64)     # and two tiles of queries
    looped = walk()
    counts.append(_bwd_counts())
    assert [(b[0] - a[0], b[1] - a[1]) for a, b in zip(counts, counts[1:])] \
        == [(1, 0), (0, 3)]
    want = _dense_with_grads(q, k, v, do, causal, scale)
    _assert_matches_dense(fused, want, *dense_tol)
    _assert_matches_dense(looped, want, *dense_tol)
    # the same visits in the same order: dk and dv are the split kernel's
    for a, b in zip(fused[2:], split[2:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # dq's float32 sums run in another order; in bf16 one rounding of a
    # result is all any two walks may differ by
    for other in (split, looped, chunked):
        for name_, a, b in zip(("out", "dq", "dk", "dv"), fused, other):
            tol = (2 ** -7 if dtype == jnp.bfloat16
                   else 1e-5 if name_ == "dq" else 1e-6)
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=tol, atol=tol, err_msg=name_)


@pytest.mark.parametrize("b,h", [(2, 5), (1, 3)], ids=["paired", "one_row"])
def test_public_call_pairs_rows_over_batch_times_heads(b, h):
    """flash_attention itself on gpt2-xl's row pattern, default blocks."""
    q, k, v = qkv(b=b, t=64, h=h, dh=16, seed=12)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    flash = loss(lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                                 None, True))
    dense = loss(lambda q, k, v: multihead_attention(q, k, v, causal=True))
    short0, grid0 = _walk_counts()
    fused0, split0 = _bwd_counts()
    g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    short1, grid1 = _walk_counts()
    assert short1 > short0 and grid1 == grid0
    assert _bwd_counts() == (fused0 + 1, split0)
    g2 = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b_ in zip("qkv", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4,
                                   atol=2e-4, err_msg=f"d{name}")


def test_parts_keep_their_flat_contract():
    """ring attention's building blocks: flat [BH, T, Dh] operands, lse
    [BH, T, 1] float32 and global."""
    from deepspeed_tpu.ops.flash_attention import (flash_bwd_parts,
                                                   flash_fwd_parts)

    rng = np.random.RandomState(13)
    qf, kf, vf, dof = (jnp.asarray(rng.randn(10, 64, 16), jnp.float32) * 0.5
                       for _ in range(4))
    out, lse = flash_fwd_parts(qf, kf, vf, causal=True, interpret=True)
    assert out.shape == (10, 64, 16) and lse.shape == (10, 64, 1)
    assert lse.dtype == jnp.float32
    s = jnp.einsum("bqd,bkd->bqk", qf, kf) * 16 ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
    np.testing.assert_allclose(np.asarray(lse[..., 0]),
                               np.asarray(jax.nn.logsumexp(s, axis=-1)),
                               rtol=2e-5, atol=2e-5)
    delta = jnp.sum(dof * out, axis=-1, keepdims=True)
    dq, dk, dv = flash_bwd_parts(qf, kf, vf, dof, lse, delta, causal=True,
                                 interpret=True)
    assert dq.shape == dk.shape == dv.shape == (10, 64, 16)
