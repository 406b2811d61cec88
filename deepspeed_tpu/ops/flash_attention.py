"""Pallas flash attention (TPU kernel) — FlashAttention-2 style.

Reference counterpart: the fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` training softmax,
``csrc/transformer/inference/csrc/softmax.cu``) — on TPU the fused,
memory-efficient form is a Pallas kernel tiled for the MXU: O(block) VMEM
per grid step instead of materializing the [T, T] score matrix in HBM.

Layout: inputs [B, T, H, Dh] (framework-standard). The key/value walk is a
GRID dimension (not an in-kernel loop over a VMEM-resident K/V copy), so
VMEM holds only (block_q x Dh) + (block_k x Dh) tiles at any sequence
length — double-buffered full-T K/V residency OOM'd scoped VMEM at
seq 8192. Online-softmax state (m, l, acc) lives in VMEM scratch carried
across the innermost (sequential) grid dimension; causal skipping masks
whole blocks above the diagonal via ``pl.when``. The backward pass is the
standard two-kernel FA2 recomputation (dq; dk/dv) using the saved
log-sum-exp rows, with the same grid structure. Matmuls run in the storage
dtype (bf16 on the training path — full MXU rate) with f32 accumulation.
Precision note: the P·V, dS·K, P^T·dO and dS^T·Q products therefore see
their p/ds operand ROUNDED to the storage dtype before the MXU — the
standard FA2-on-bf16 tradeoff, but a change vs all-f32 operands; set
``DSTPU_FLASH_F32_PRECISE=1`` to keep those operands in f32 (half MXU
rate) for tolerance-sensitive runs.
Known tradeoff: causally-masked grid steps skip COMPUTE via ``pl.when`` but
still fetch their K/V tiles (Pallas grids are rectangular) — ~2x the K/V
bandwidth of a bounded walk on the causal path; measured wins at seq
1024-8192 absorb it (tiles are small vs the T^2 compute), revisit with a
per-qi bounded inner loop if a profile ever shows fetch-bound behavior.
Composes with ring attention (ops/ring_attention.py) for sequence lengths
beyond one chip.

Exposed as ``flash_attention(q, k, v, causal=...)`` with a custom_vjp;
``interpret=True`` (CPU tests) runs the same kernels in the Pallas
interpreter, so TPU and test paths share every line of kernel code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512-blocks amortize per-grid-step overhead (measured 2026-07-31 on-chip:
# (512,512) >> (256,256) > (128,128) for fwd+bwd at seq 2048; (1024,1024)
# regresses — the [bq,bk] f32 score tile outgrows VMEM headroom)
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _dot_f32(a, b, dims):
    """MXU-native matmul: inputs stay in their storage dtype (bf16 on the
    training path — full MXU rate), accumulation in f32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _mm_dtype(storage_dtype):
    """Dtype for the computed p/ds operands of the second-stage matmuls:
    the storage dtype (full MXU rate) unless DSTPU_FLASH_F32_PRECISE=1
    opts back into all-f32 operands (see module docstring)."""
    import os

    if os.environ.get("DSTPU_FLASH_F32_PRECISE") == "1":
        return jnp.float32
    return storage_dtype


def _causal_mask(s, qi, kj, block_q, block_k):
    bq, bk = s.shape
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(k_pos <= q_pos, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                causal: bool, scale: float, block_q: int, block_k: int,
                nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: key block strictly above the diagonal contributes nothing
    live = (kj * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...]                                  # [BQ, Dh]
        k = k_ref[...]                                  # [BK, Dh]
        v = v_ref[...]
        s = _dot_f32(q, k, ((1,), (1,))) * scale        # [BQ, BK] f32
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k)
        m_prev = m_ref[...][:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = (l_ref[...][:, 0] * corr + p.sum(axis=-1))[:, None]
        acc_ref[...] = acc_ref[...] * corr[:, None] + \
            _dot_f32(p.astype(_mm_dtype(v.dtype)), v, ((1,), (0,)))
        m_ref[...] = m_new[:, None]

    @pl.when(kj == nk - 1)
    def _finish():
        l_safe = jnp.maximum(l_ref[...][:, 0], 1e-20)
        o_ref[...] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # trailing unit dim: rank-2 (bq, 1) tiles satisfy the TPU block-shape
        # constraint (1-D tiles fail Mosaic lowering)
        lse_ref[...] = (m_ref[...][:, 0] + jnp.log(l_safe))[:, None]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, causal: bool, scale: float, block_q: int,
                   block_k: int, nk: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = (kj * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, 0]
        delta = delta_ref[...][:, 0]
        s = _dot_f32(q, k, ((1,), (1,))) * scale
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        dq_acc_ref[...] += _dot_f32(ds.astype(_mm_dtype(k.dtype)), k, ((1,), (0,)))

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[...] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, causal: bool,
                    scale: float, block_q: int, block_k: int, nq: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: query block strictly before this key block sees none of it
    live = (qi * block_q + block_q - 1 >= kj * block_k) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, 0]
        delta = delta_ref[...][:, 0]
        s = _dot_f32(q, k, ((1,), (1,))) * scale        # [BQ, BK]
        if causal:
            s = _causal_mask(s, qi, kj, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dv_acc_ref[...] += _dot_f32(p.astype(_mm_dtype(do.dtype)), do, ((0,), (0,)))
        dp = _dot_f32(do, v, ((1,), (1,)))
        ds = p * (dp - delta[:, None])
        dk_acc_ref[...] += _dot_f32(ds.astype(_mm_dtype(q.dtype)), q, ((0,), (0,)))

    @pl.when(qi == nq - 1)
    def _finish():
        # s was computed from UNSCALED q, so dk carries the softmax scale
        dk_ref[...] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc_ref[...].astype(dv_ref.dtype)


def _reshape_bh(x):
    b, t, h, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)


def _unshape_bh(x, b, h):
    bh, t, dh = x.shape
    return x.reshape(b, h, t, dh).transpose(0, 2, 1, 3)


def _pick_block(t: int, pref: int) -> int:
    blk = min(pref, t)
    while t % blk:
        blk //= 2
    return max(blk, 1)


def vma_typing_supported() -> bool:
    """The installed JAX carries shard_map varying-axis (vma) typing
    (aval ``.vma`` + ``ShapeDtypeStruct(vma=...)``), which ``_sds`` below
    declares on pallas_call outputs; callers (ops/ring_attention.py) keep
    asking so that strict checking stays a decision made in one place."""
    return True


def _sds(*operands_then_args):
    """ShapeDtypeStruct factory that propagates shard_map varying-axes (vma)
    typing from the kernel operands — pallas_call under `shard_map` with
    check_vma requires outputs to declare how they vary over mesh axes
    (e.g. the Ulysses head-scatter path)."""
    *operands, shape, dtype = operands_then_args
    vma = frozenset()
    typeof = getattr(jax, "typeof", None)  # absent on older jax: no vma
    for op in (operands if typeof is not None else ()):
        vma |= frozenset(getattr(typeof(op), "vma", ()) or ())
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _grid_params(seq_semantics=("parallel", "parallel", "arbitrary")):
    return pltpu.CompilerParams(dimension_semantics=seq_semantics)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    interpret: Optional[bool] = None):
    """q/k/v: [B, T, H, Dh] → [B, T, H, Dh]. MHA (same head counts)."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def flash_fwd_parts(qf, kf, vf, *, causal, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Kernel-level forward on FLAT [BH, T, Dh] operands → (out, lse).

    Public building block for sequence-parallel composition (ring attention
    merges per-hop (out, lse) pairs exactly); ``flash_attention`` wraps it
    with the [B, T, H, Dh] layout and custom_vjp."""
    bh, t, dh = qf.shape
    sc = scale if scale is not None else dh ** -0.5
    bq = _pick_block(t, block_q)
    bk = _pick_block(kf.shape[1], block_k)
    nq, nk = t // bq, kf.shape[1] // bk
    interp = _interpret_default() if interpret is None else interpret
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=sc,
                               block_q=bq, block_k=bk, nk=nk)
    kw = {} if interp else {"compiler_params": _grid_params()}
    shp = functools.partial(_sds, qf, kf, vf)
    return pl.pallas_call(
        kernel,
        name="dstpu_flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda bh_, qi, kj: (bh_, qi, 0)),
            pl.BlockSpec((None, bk, dh), lambda bh_, qi, kj: (bh_, kj, 0)),
            pl.BlockSpec((None, bk, dh), lambda bh_, qi, kj: (bh_, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, dh), lambda bh_, qi, kj: (bh_, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda bh_, qi, kj: (bh_, qi, 0)),
        ],
        out_shape=[
            shp((bh, t, dh), qf.dtype),
            shp((bh, t, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum l
            pltpu.VMEM((bq, dh), jnp.float32),   # output accumulator
        ],
        interpret=interp,
        **kw,
    )(qf, kf, vf)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    qf, kf, vf = _reshape_bh(q), _reshape_bh(k), _reshape_bh(v)
    out, lse = flash_fwd_parts(qf, kf, vf, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    # Residuals tagged for remat: the "flash_res" checkpoint-name lets the
    # save_attn policy (runtime/activation_checkpointing.py) SAVE them, so a
    # rematted transformer block never re-runs this kernel in backward —
    # flash residuals are O(T) (out + lse), unlike dense attention's O(T^2).
    from jax.ad_checkpoint import checkpoint_name

    res = tuple(checkpoint_name(x, "flash_res") for x in (qf, kf, vf, out, lse))
    return _unshape_bh(out, b, h), res + ((b, h),)


def _flash_fwd_vjp(q, k, v, causal, scale, block_q, block_k, interpret):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, res


def flash_bwd_parts(qf, kf, vf, dof, lse, delta, *, causal, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Kernel-level backward on FLAT operands → (dq, dk, dv).

    ``lse``/``delta`` are the GLOBAL log-sum-exp rows / do·out sums, so
    sequence-parallel callers can run this per K/V hop and the per-hop
    grads sum to the exact global gradient (p = exp(s - lse_global))."""
    bh, t, dh = qf.shape
    sc = scale if scale is not None else dh ** -0.5
    bq = _pick_block(t, block_q)
    bk = _pick_block(kf.shape[1], block_k)
    nq, nk = t // bq, kf.shape[1] // bk
    interp = _interpret_default() if interpret is None else interpret
    kw = {} if interp else {"compiler_params": _grid_params()}
    shp = functools.partial(_sds, qf, kf, vf, dof)

    dq_kernel = functools.partial(_bwd_dq_kernel, causal=causal, scale=sc,
                                  block_q=bq, block_k=bk, nk=nk)
    dq = pl.pallas_call(
        dq_kernel,
        name="dstpu_flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda b_, qi, kj: (b_, qi, 0)),
            pl.BlockSpec((None, bk, dh), lambda b_, qi, kj: (b_, kj, 0)),
            pl.BlockSpec((None, bk, dh), lambda b_, qi, kj: (b_, kj, 0)),
            pl.BlockSpec((None, bq, dh), lambda b_, qi, kj: (b_, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda b_, qi, kj: (b_, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda b_, qi, kj: (b_, qi, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, dh), lambda b_, qi, kj: (b_, qi, 0)),
        out_shape=shp((bh, t, dh), qf.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32)],
        interpret=interp,
        **kw,
    )(qf, kf, vf, dof, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, causal=causal, scale=sc,
                                   block_q=bq, block_k=bk, nq=nq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="dstpu_flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((None, bq, dh), lambda b_, kj, qi: (b_, qi, 0)),
            pl.BlockSpec((None, bk, dh), lambda b_, kj, qi: (b_, kj, 0)),
            pl.BlockSpec((None, bk, dh), lambda b_, kj, qi: (b_, kj, 0)),
            pl.BlockSpec((None, bq, dh), lambda b_, kj, qi: (b_, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda b_, kj, qi: (b_, qi, 0)),
            pl.BlockSpec((None, bq, 1), lambda b_, kj, qi: (b_, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, dh), lambda b_, kj, qi: (b_, kj, 0)),
            pl.BlockSpec((None, bk, dh), lambda b_, kj, qi: (b_, kj, 0)),
        ],
        out_shape=[
            shp((kf.shape[0], kf.shape[1], dh), kf.dtype),
            shp((kf.shape[0], kf.shape[1], dh), vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, dh), jnp.float32),
            pltpu.VMEM((bk, dh), jnp.float32),
        ],
        interpret=interp,
        **kw,
    )(qf, kf, vf, dof, lse, delta)
    return dq, dk, dv


def _flash_bwd_vjp(causal, scale, block_q, block_k, interpret, res, g):
    qf, kf, vf, outf, lse, (b, h) = res
    dof = _reshape_bh(g)
    delta = jnp.sum(dof.astype(jnp.float32) * outf.astype(jnp.float32),
                    axis=-1, keepdims=True)                 # [bh, t, 1]
    dq, dk, dv = flash_bwd_parts(qf, kf, vf, dof, lse, delta, causal=causal,
                                 scale=scale, block_q=block_q,
                                 block_k=block_k, interpret=interpret)
    return (_unshape_bh(dq, b, h), _unshape_bh(dk, b, h), _unshape_bh(dv, b, h))


flash_attention.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)
