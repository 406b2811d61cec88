"""Flash-decode: single-token attention against the KV cache, in Pallas.

Reference counterpart: the fused ``softmax_context`` decode kernel
(csrc/transformer/inference/csrc/softmax.cu + pt_binding.cpp) — one fused
pass over the cache per token instead of materialized score tensors.

Why a kernel when XLA already fuses the einsum path
(ops/attention.decode_attention): two reasons, both measured at
GPT-2-125M batch-8 decode (round 4):

1. **Static-shape cache reads.** The XLA einsum contracts against the
   FULL [B, H, S_max, Dh] cache every step regardless of how many
   positions are valid; with scalar-prefetch the kernel's index_map
   clamps dead key blocks to the last live one (consecutive identical
   fetches are deduped by the pipeline), so HBM traffic tracks the
   VALID prefix (~idx) instead of S_max.
2. **Layout control at batch > 1.** The batched einsum pair
   (QK^T then PV) measured ~2x off the weight+cache streaming roofline
   at B=8; the kernel streams each (batch, kv-head)'s contiguous
   [S, Dh] block once, with the online-softmax state in VMEM.

GQA native: q heads grouped per kv head ([rep, Dh] q tile against the
[S, Dh] cache of their shared kv head). Serving-only: no VJP (training
uses ops/flash_attention.py).

Status per variant (round-4 measurements):
  * wide-GQA (rep >= 8) MXU-slab kernel — the PRODUCTION route
    (ops/attention.decode_attention gates on rep).
  * MHA head-batched VPU kernel (``_mha_kernel``) — measured SLOWER than
    the XLA einsum it would replace (1.94 vs 1.42 ms/tok at 125M B=8)
    because the decode loop's cache carry is laid out for einsum lane
    parallelism and the pallas operand pays a relayout copy per step.
    Kept test-covered but UNROUTED, pending carry-layout control
    (round 5); delete it instead if that lever never lands.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
DEFAULT_BLOCK_S = 512


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_s: int, ns: int, scale: float):
    sj = pl.program_id(1)

    @pl.when(sj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]
    live = sj * block_s <= idx

    @pl.when(live)
    def _step():
        q = q_ref[...]                                   # [rep, Dh]
        k = k_ref[...]                                   # [BS, Dh]
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [rep, BS] f32
        pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= idx, s, _NEG_INF)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * corr + p.sum(axis=-1))[:, None]
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new[:, None]

    @pl.when(sj == ns - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...][:, 0], 1e-20)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _mha_kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                *, block_s: int, ns: int, scale: float):
    """Head-batched MHA variant: one grid cell per (batch, key-block)
    computes ALL heads' scores with VPU elementwise-multiply + reduce —
    at rep==1 the MXU variant degenerates to [1, Dh] dots and per-cell
    overhead dominates (measured 5x slower than the XLA einsum at 125M
    B=8); here each cell streams the whole [H, BS, Dh] cache block once
    and the math vectorizes over (heads x positions) lanes."""
    sj = pl.program_id(1)

    @pl.when(sj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[0]
    live = sj * block_s <= idx

    @pl.when(live)
    def _step():
        q = q_ref[...].astype(jnp.float32)               # [H, Dh]
        k = k_ref[...].astype(jnp.float32)               # [H, BS, Dh]
        v = v_ref[...].astype(jnp.float32)
        s = (q[:, None, :] * k).sum(axis=-1) * scale     # [H, BS] on the VPU
        pos = sj * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(pos <= idx, s, _NEG_INF)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * corr + p.sum(axis=-1))[:, None]
        acc_ref[...] = acc_ref[...] * corr[:, None] + \
            (p[:, :, None] * v).sum(axis=1)              # [H, Dh]
        m_ref[...] = m_new[:, None]

    @pl.when(sj == ns - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...][:, 0], 1e-20)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)


def _pick(n: int, pref: int) -> int:
    if n <= pref:
        return n
    while n % pref:
        pref //= 2
    return max(pref, 1)


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 cache_index, *, scale: Optional[float] = None,
                 block_s: int = DEFAULT_BLOCK_S,
                 interpret: Optional[bool] = None) -> jax.Array:
    """``q [B, 1, Hq, Dh]`` against head-major ``[B, Hkv, S, Dh]`` caches
    whose position ``cache_index`` holds q's own K/V (already written).
    Returns ``[B, 1, Hq, Dh]``."""
    b, t, hq, dh = q.shape
    assert t == 1, "flash_decode is the single-token path"
    hkv, s_max = k_cache.shape[1], k_cache.shape[2]
    rep = hq // hkv
    sc = scale if scale is not None else dh ** -0.5
    bs = _pick(s_max, block_s)
    ns = s_max // bs
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    idx = jnp.asarray(cache_index, jnp.int32).reshape(1)

    if rep == 1:
        # MHA: head-batched VPU kernel — grid over (batch, key blocks)
        qf = q.reshape(b, hq, dh)
        kernel = functools.partial(_mha_kernel, block_s=bs, ns=ns, scale=sc)

        def live_block4(bi, sj, idx_ref):
            return (bi, 0, jnp.minimum(sj, idx_ref[0] // bs), 0)

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, ns),
            in_specs=[
                pl.BlockSpec((None, hq, dh),
                             lambda bi, sj, idx_ref: (bi, 0, 0)),
                pl.BlockSpec((None, hkv, bs, dh), live_block4),
                pl.BlockSpec((None, hkv, bs, dh), live_block4),
            ],
            out_specs=pl.BlockSpec((None, hq, dh),
                                   lambda bi, sj, idx_ref: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),   # running max
                pltpu.VMEM((hq, 1), jnp.float32),   # running sum
                pltpu.VMEM((hq, dh), jnp.float32),  # output accumulator
            ],
        )
        out = pl.pallas_call(
            kernel,
            name="dstpu_flash_decode_mha",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
            interpret=interpret,
        )(idx, qf, k_cache, v_cache)
        return out[:, None]

    # GQA: [B, 1, Hq, Dh] -> [B*Hkv, rep, Dh]; the [rep, Dh] q tile feeds
    # the MXU a real slab per kv head
    qf = q.reshape(b, hkv, rep, dh).reshape(b * hkv, rep, dh)
    kf = k_cache.reshape(b * hkv, s_max, dh)
    vf = v_cache.reshape(b * hkv, s_max, dh)
    kernel = functools.partial(_kernel, block_s=bs, ns=ns, scale=sc)

    def live_block(bh, sj, idx_ref):
        # clamp dead key blocks onto the last live one: the pipeline dedups
        # consecutive identical fetches, so HBM traffic follows the valid
        # prefix, not S_max
        return (bh, jnp.minimum(sj, idx_ref[0] // bs), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, ns),
        in_specs=[
            pl.BlockSpec((None, rep, dh), lambda bh, sj, idx_ref: (bh, 0, 0)),
            pl.BlockSpec((None, bs, dh), live_block),
            pl.BlockSpec((None, bs, dh), live_block),
        ],
        out_specs=pl.BlockSpec((None, rep, dh),
                               lambda bh, sj, idx_ref: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),   # running max
            pltpu.VMEM((rep, 1), jnp.float32),   # running sum
            pltpu.VMEM((rep, dh), jnp.float32),  # output accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="dstpu_flash_decode_gqa",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * hkv, rep, dh), q.dtype),
        interpret=interpret,
    )(idx, qf, kf, vf)
    return out.reshape(b, hkv * rep, dh)[:, None]
