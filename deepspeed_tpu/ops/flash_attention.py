"""Pallas flash attention (TPU kernel) — FlashAttention-2 style.

Reference counterpart: the fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` training softmax,
``csrc/transformer/inference/csrc/softmax.cu``) — on TPU the fused,
memory-efficient form is a Pallas kernel tiled for the MXU: O(block) VMEM
instead of materializing the [T, T] score matrix in HBM.

Layout: inputs [B, T, H, Dh] (framework-standard), flattened to (batch x
head) rows. Three kernels: forward, and the FA2 recomputation backward from
the saved log-sum-exp rows, as ONE kernel where a row is one grid step (the
dk/dv kernel with dq as a third result: sequence 2048 and under, every
training call of the cells) and as the standard two (dq; dk/dv) everywhere
else. All share one tile program, whose parameters come from the operands'
shapes alone:

* **Rows a tile.** Where two rows' heads fit the 128 lanes (Dh <= 64) and
  the row count is even, rows 2i and 2i+1 of the flattened (batch x head)
  dimension sit side by side in one ``[T, 2*Dh]`` tile, so every load,
  store and DMA is lane-dense. In the kernel the pair's blocks are
  *stacked* along the sublanes, each with the other row's lanes zeroed
  where it feeds a contraction (the MXU's 128-deep contraction is half
  empty at Dh = 64 either way): one matmul and one softmax serve both
  rows, and each row takes its half of the 128-wide results at the end.
  An odd row count or Dh > 64 runs one row a tile.
* **The walk.** One side of the score matrix is the *tile* side (queries,
  in forward and dq; keys, in dk/dv), the other the *walk* side. Where
  the walk side's two operands fit ``_WALK_VMEM_BUDGET`` whole (K and V
  at T = 1024, Dh = 64: 128 KB a row each; up to T = 8192 at 128 lanes)
  they stay resident in VMEM for a row and a loop inside the kernel walks
  their blocks; under ``causal`` it stops at the diagonal (dk/dv: starts
  there) and only the blocks that touch the diagonal are masked. A row of
  ``_MAX_UNROLL`` blocks or fewer (T <= 2048 in 512-blocks) is one grid
  step whose walk is unrolled by hand with every bound static: one basic
  block, in which the compiler overlaps a step's matmuls with its
  neighbour's softmax. That, not the residency, is what
  the chip pays for: the same walk as a ``fori_loop`` is no faster than
  the grid it replaces. Longer rows loop with dynamic bounds. Where the
  walk side does not fit (T = 16384), the same loop walks one chunk of it
  a grid step, the softmax state rides VMEM scratch across the chunks, and
  a causally dead chunk is neither computed nor fetched (its index is
  clamped to the nearest live one).
* **Statistics.** ``m`` / ``l`` are loop values; ``lse`` and ``delta``
  live in HBM as lane-dense rows ``[groups, T/128, rows, 128]`` (a
  ``(T, 1)`` array pads every float to a 128-lane row and moves four
  bytes a DMA row). The dk/dv kernel computes the *transposed* scores
  ``K Q^T``, against which a statistics row broadcasts as it lies and
  ``P^T dO`` / ``dS^T Q`` are plain matmuls; forward and dq turn rows
  into columns with a 128 x 128 transpose a statistics chunk.
* **One backward kernel.** Each of the two backward kernels recomputes
  the scores, their exponentials and ``dO V^T`` for itself: seven stacked
  matmuls a block visit where the mathematics needs five. Where both are
  one grid step a row with every bound static, and the row fits
  ``_FUSED_VMEM``, the dk/dv kernel's visit also adds ``dS^T K`` to its
  query block's dq, a float32 value carried across the key blocks and
  written once at the end. The stacked keys hold zeros in the other row's
  lanes, so that one product contracts over the whole stack and lands each
  row's dq in its own lanes. dk and dv are the split kernel's bit for bit;
  dq differs by the order of its float32 sums alone (on the chip, in bf16
  storage, by no bit at the cells' shapes).

The numbers that chose each parameter (TPU v5e, PR 31) stand at the
constants below; PERF.md section 6 has the table of single changes.

Matmuls run in the storage dtype (bf16 on the training path — full MXU
rate) with f32 accumulation; softmax statistics and ``exp`` are f32.
Precision note: the P·V, dS·K (fused: dS^T·K), P^T·dO and dS^T·Q products
see their p/ds operand ROUNDED to the storage dtype before the MXU — the standard
FA2-on-bf16 tradeoff; set ``DSTPU_FLASH_F32_PRECISE=1`` to keep those
operands in f32 (half MXU rate) for tolerance-sensitive runs. The softmax
scale is folded into the query (dk/dv: key) tile once a block where that
is exact (a power of two, as at Dh = 64, or f32 storage) and multiplies
the scores otherwise.
Composes with ring attention (ops/ring_attention.py) for sequence lengths
beyond one chip.

Exposed as ``flash_attention(q, k, v, causal=...)`` with a custom_vjp;
``interpret=True`` (CPU tests) runs the same kernels in the Pallas
interpreter, so TPU and test paths share every line of kernel code.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Blocks, both sides of the score matrix, all three kernels. TPU v5e, device
# time a call of forward / dq / dk-dv in us at [40, 1024, 64] bf16 causal,
# two rows a tile, unrolled (my chip runs, PR 31; PERF.md section 6 has the
# whole table): 512 x 512 101 / 130 / 170, 256 x 256 99 / 121 / 147, 128 x
# 128 168 / 148 / 133 (the 512 x 512 grid walk this replaces: 228 / 152 /
# 229). 256 is 7% faster and was dropped all the same: its ten block visits
# a row make an unrolled kernel of 370-490 operations (512: 170-260), and
# every warm start lowers each kernel to Mosaic two or three times, about a
# millisecond an operation on the chip's host: +3.5 s of warm set-up on 21
# at 256, under one second at 512. The backward as one kernel (my chip runs,
# PR 62; dq + dk-dv -> fused, us a call): [40, 1024, 64] 129 + 170 -> 211
# with dS^T K as a matmul that contracts dimension 0 of both operands (Mosaic
# turns dS, [1024, 512] a visit, through the transpose unit, which the kernel
# left idle), 212 with dq^T = K^T dS (K^T made once a key block, dq^T turned
# once a query block); [50, 1024, 64] 161 + 212 -> 264 / 264; not causal
# [40, 1024, 64] 170 + 226 -> 280 / 281; [64, 2048, 128] 672 + 891 -> 1118 /
# 1116. Five units of 8.05 GFLOP of MXU passes for seven: 204 us at 197
# TFLOP/s, so the fused kernel issues at 97% of the MXU's rate. The two
# orientations are one speed; the first is kept, being the fewer lines
_BLOCK = 512
# A row whose score matrix has this many blocks or fewer (sequence 2048 and
# under) is one grid step unrolled by hand, every bound static: at sequence
# 1024 in 256-blocks 99 / 121 / 147 us unrolled and 262 / 178 / 231 in a
# fori_loop, which serialises each step's matmuls behind its softmax; at
# [64, 2048, 128] 511 / 672 / 891 unrolled, 932 / 947 / 1219 looped
_MAX_UNROLL = 16
# bytes of the walk side's two operands (K and V, or Q and dO) that may
# stay resident in VMEM for a row: [8192, 128] bf16 twice. Pallas double-
# buffers them, and that compiles and runs under the 16 MiB scoped default
# (forward 1213 us resident against 2578 in chunks of 2048, same run)
_WALK_VMEM_BUDGET = 4 * 1024 * 1024
_MAX_TILE = 1024        # tile-side rows a grid step of a walk not unrolled
# bytes ``_fused_bytes`` may count for a row of the one-kernel backward. The
# count is a bound from the shapes, not the compiler's own: compiled for the
# described v5e under the 16 MiB scoped default, rows counted at 12.0 (both
# train cells; [64, 2048, 128] bf16), 14.0 and 15.5 MiB fit, one at 16.0 fits
# causal and is refused not causal by 0.4 MiB, and 19.0 and more are refused
_FUSED_VMEM = 14 * 1024 * 1024
_LANES = 128
_NEG_INF = -1e30


def _dot_f32(a, b, dims):
    """MXU-native matmul: inputs stay in their storage dtype (bf16 on the
    training path — full MXU rate), accumulation in f32."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a @ b.T
_NN = ((1,), (0,))      # a @ b
_TN = ((0,), (0,))      # a.T @ b


def _mm_dtype(storage_dtype):
    """Dtype for the computed p/ds operands of the second-stage matmuls:
    the storage dtype (full MXU rate) unless DSTPU_FLASH_F32_PRECISE=1
    opts back into all-f32 operands (see module docstring)."""
    import os

    if os.environ.get("DSTPU_FLASH_F32_PRECISE") == "1":
        return jnp.float32
    return storage_dtype


# ------------------------------------------------------------ tile helpers
# A tile of ``rows`` (batch x head) rows is worked on *stacked*: its rows'
# blocks one under the other along the sublanes, ``[rows*n, ...]``, each
# segment with the other rows' lanes zeroed where it feeds a contraction.
# One matmul and one softmax then serve the whole tile (the MXU streams
# the same rows either way), and a block visit is the same dozen
# operations whatever ``rows`` is, which is what keeps an unrolled kernel
# short enough to lower.
def _stack_rows(x, rows, dh, scale=None):
    """``x`` ([n, rows*dh]) -> [rows*n, rows*dh]: segment r is ``x`` with
    every lane but row r's zeroed, times ``scale`` where it is folded in."""
    if scale is not None:
        x = (x * scale).astype(x.dtype)
    if rows == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    zero = jnp.zeros_like(x)
    return jnp.concatenate(
        [jnp.where((lane >= r * dh) & (lane < (r + 1) * dh), x, zero)
         for r in range(rows)], axis=0)


def _unstack_rows(x, rows, dh):
    """[rows*n, rows*dh] -> [n, rows*dh]: row r's lanes of segment r, side
    by side (the other lanes of a segment hold another row's products)."""
    if rows == 1:
        return x
    n = x.shape[0] // rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, x.shape[1]), 1)
    out = x[:n]
    for r in range(1, rows):
        out = jnp.where(lane >= r * dh, x[r * n:(r + 1) * n], out)
    return out


def _stacked_diff(n_q, n_k, rows, *, keys_on_lanes):
    """Key position minus query position inside a block, for the causal
    mask, once a segment: ``[rows*n_q, n_k]``, or ``[rows*n_k, n_q]`` where
    keys run down the sublanes (the transposed scores of dk/dv)."""
    shape, k_axis = ((n_q, n_k), 1) if keys_on_lanes else ((n_k, n_q), 0)
    diff = (jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - k_axis))
    return jnp.concatenate([diff] * rows, axis=0) if rows > 1 else diff


def _store_stat_rows(ref, first, cols, rows):
    """A stacked column ``cols`` ([rows*n, 1]) -> the lane-dense chunks
    ``first ...`` of ``ref`` ([chunks, rows, sw]): one square transpose a
    chunk, lane r of its operand holding row r's values."""
    sw = ref.shape[-1]
    n = cols.shape[0] // rows
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, sw), 1)
    x = jnp.broadcast_to(cols[:n], (n, sw))
    for r in range(1, rows):
        x = jnp.where(lane == r, cols[r * n:(r + 1) * n], x)
    for c in range(n // sw):
        ref[first + c] = x[c * sw:(c + 1) * sw].T[:rows]


def _stat_cols(ref, first, n_chunks):
    """The chunks ``first .. first+n_chunks`` of ``ref`` ([chunks, rows,
    sw]) as one stacked column [rows*n_chunks*sw, 1]."""
    rows, sw = ref.shape[1:]
    sub = jax.lax.broadcasted_iota(jnp.int32, (sw, sw), 0)
    chunks = []
    for c in range(n_chunks):
        stat = ref[first + c]                            # [rows, sw]
        x = jnp.broadcast_to(stat[0:1], (sw, sw))
        for r in range(1, rows):
            x = jnp.where(sub == r, stat[r:r + 1], x)
        chunks.append(x.T)                   # column r: row r's values
    whole = jnp.concatenate(chunks, axis=0) if n_chunks > 1 else chunks[0]
    return jnp.concatenate([whole[:, r:r + 1] for r in range(rows)], axis=0)


def _stat_rows(ref, first, n_chunks, n_keys):
    """Those chunks as stacked rows [rows*n_keys, n_chunks*sw]: segment r
    repeats row r's values down ``n_keys`` sublanes."""
    chunks = [ref[first + c] for c in range(n_chunks)]
    whole = jnp.concatenate(chunks, axis=1) if n_chunks > 1 else chunks[0]
    rows, n = whole.shape
    segs = [jnp.broadcast_to(whole[r:r + 1], (n_keys, n)) for r in range(rows)]
    return jnp.concatenate(segs, axis=0) if rows > 1 else segs[0]


def _stretches(causal, tile0, tile_n, walk0, walk_n, n_blocks, *,
               walk_is_keys):
    """Which of a chunk's ``n_blocks`` walk-side blocks (each ``walk_n``
    positions, the first at ``walk0``) a tile-side block at ``tile0`` of
    ``tile_n`` positions has to visit: ``(lo, hi, masked)`` stretches, in
    the order walked. Not causal: all of them, none masked. Keys walked
    (forward, dq): first the blocks that lie under the diagonal whole,
    then those that touch it; queries walked (dk/dv): the other way."""
    if not causal:
        return [(0, n_blocks, False)]

    def clamp(x):
        if isinstance(x, int):
            return min(max(x, 0), n_blocks)
        return jnp.clip(x, 0, n_blocks)

    if walk_is_keys:
        # key block j is whole iff its last key <= the first query,
        # live iff its first key <= the last query
        whole = clamp((tile0 + 1 - walk0) // walk_n)
        live = clamp((tile0 + tile_n - 1 - walk0) // walk_n + 1)
        return [(0, whole, False), (whole, live, True)]
    # query block i is dead iff its last query < the first key, whole iff
    # its first query >= the last key
    dead = clamp((tile0 - walk0) // walk_n)
    whole_from = clamp(-((walk0 - tile0 - tile_n + 1) // walk_n))
    return [(dead, whole_from, True), (whole_from, n_blocks, False)]


def _loop(lo, hi, body, carry):
    """``fori_loop``, unrolled by hand where both bounds are static: the
    steps of a short walk then lie in one basic block, and the compiler
    overlaps one step's matmuls with another's softmax."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= _MAX_UNROLL:
        for j in range(lo, hi):
            carry = body(j, carry)
        return carry
    return jax.lax.fori_loop(lo, hi, body, carry)


def _walk(stretches, step, carry):
    """``step(j, carry, masked)`` over every block of every stretch."""
    for lo, hi, masked in stretches:
        carry = _loop(lo, hi, lambda j, c, m=masked: step(j, c, m), carry)
    return carry


def _rows_at(i, n):
    """Rows ``[i*n, (i+1)*n)`` of a ref's sublane dimension."""
    return pl.ds(i * n if isinstance(i, int) else pl.multiple_of(i * n, n), n)


# ---------------------------------------------------------- one block visit
# Each is a jitted function of values, so that a kernel whose walk is
# unrolled by hand traces the visit once and not once a block, in this and
# in every later trace of the program. (Jitting the whole call as well made
# warm set-up 2.4 s *longer* on one chip: PERF.md section 6.) ``diff`` is
# key position minus query position inside a block and ``offset`` the first
# query's position minus the first key's: a key is visible where
# ``diff <= offset``; ``diff`` None: all are.
_STEP_STATICS = ("sc", "mm")


def _scores(a, b, diff, offset, sc):
    s = _dot_f32(a, b, _NT)
    if sc is not None:
        s = s * sc
    return s if diff is None else jnp.where(diff <= offset, s, _NEG_INF)


@functools.partial(jax.jit, static_argnames=_STEP_STATICS)
def _fwd_step(q, k, v, m_prev, l_prev, acc, diff, offset, *, sc, mm):
    s = _scores(q, k, diff, offset, sc)                  # [rows*bq, bk]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    return m_new, l_new, acc * corr + _dot_f32(p.astype(mm), v, _NN)


@functools.partial(jax.jit, static_argnames=_STEP_STATICS)
def _dq_step(q, do, k, v, lse, delta, acc, diff, offset, *, sc, mm):
    p = jnp.exp(_scores(q, k, diff, offset, sc) - lse)   # [rows*bq, bk]
    ds = p * (_dot_f32(do, v, _NT) - delta)
    return acc + _dot_f32(ds.astype(mm), k, _NN)


@functools.partial(jax.jit, static_argnames=_STEP_STATICS)
def _dkv_step(k, v, q, do, lse, delta, dk, dv, dq, diff, offset, *, sc, mm):
    """The transposed scores K Q^T: keys run down the sublanes, and the
    statistics are rows. ``dq`` not None (the one-kernel backward): the
    visit's scores, exponentials and V dO^T serve the query side too. The
    stacked keys carry zeros in the other row's lanes, so ``dS^T K``
    contracts over the whole stack and lands each row's dq in its own lanes
    of [bq, w]."""
    pt = jnp.exp(_scores(k, q, diff, offset, sc) - lse)  # [rows*bk, bq]
    dv = dv + _dot_f32(pt.astype(mm), do, _NN)
    dst = (pt * (_dot_f32(v, do, _NT) - delta)).astype(mm)
    return (dk + _dot_f32(dst, q, _NN), dv,
            None if dq is None else dq + _dot_f32(dst, k, _TN))


# ------------------------------------------------------------------ kernels
def _origins(static, tile, chunk):
    """(first tile-side position, chunk index, first walk-side position) of
    a grid step; plain zeros where the grid has one tile and one chunk a
    row (``static``), so that every bound of the walk is static too."""
    if static:
        return 0, 0, 0
    c = pl.program_id(2)
    return pl.program_id(1) * tile, c, c * chunk


def _end_of_chunk(n_chunks, c, finish):
    """Write the results where this grid step saw the last chunk."""
    if n_chunks == 1:
        finish()
    else:
        pl.when(c == n_chunks - 1)(finish)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *state, causal, scale,
                fold, rows, dh, bq, bk, n_chunks, static):
    tq, w = q_ref.shape
    kc = k_ref.shape[0]
    n_sub, n_kb, sw_n = tq // bq, kc // bk, bq // lse_ref.shape[-1]
    q0, c, k0 = _origins(static, tq, kc)
    mm = _mm_dtype(v_ref.dtype)
    sc = None if fold else scale
    diff = _stacked_diff(bq, bk, rows, keys_on_lanes=True)

    if n_chunks > 1:                # state rides scratch across the chunks
        m_ref, l_ref, acc_ref = state

        @pl.when(c == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def sub_block(i, _):
        r0 = q0 + i * bq
        rs = _rows_at(i, bq)
        q = _stack_rows(q_ref[rs, :], rows, dh, scale if fold else None)
        if n_chunks > 1:
            carry = (m_ref[...], l_ref[...], acc_ref[...])
        else:
            carry = (jnp.full((rows * bq, 1), _NEG_INF, jnp.float32),
                     jnp.zeros((rows * bq, 1), jnp.float32),
                     jnp.zeros((rows * bq, w), jnp.float32))

        def step(j, carry, masked):
            ks = _rows_at(j, bk)
            return _fwd_step(q, k_ref[ks, :], v_ref[ks, :], *carry,
                             diff if masked else None, r0 - (k0 + j * bk),
                             sc=sc, mm=mm)

        m, l, acc = _walk(_stretches(causal, r0, bq, k0, bk, n_kb,
                                     walk_is_keys=True), step, carry)
        if n_chunks > 1:
            m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

        def finish():
            safe = jnp.maximum(l, 1e-20)
            o_ref[rs, :] = _unstack_rows(acc / safe, rows, dh).astype(
                o_ref.dtype)
            _store_stat_rows(lse_ref, i * sw_n, m + jnp.log(safe), rows)

        _end_of_chunk(n_chunks, c, finish)
        return 0

    _loop(0, n_sub, sub_block, 0)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *state, causal, scale, fold, rows, dh, bq, bk, n_chunks,
                   static):
    tq, w = q_ref.shape
    kc = k_ref.shape[0]
    n_sub, n_kb, sw_n = tq // bq, kc // bk, bq // lse_ref.shape[-1]
    q0, c, k0 = _origins(static, tq, kc)
    mm = _mm_dtype(k_ref.dtype)
    sc = None if fold else scale
    diff = _stacked_diff(bq, bk, rows, keys_on_lanes=True)

    if n_chunks > 1:
        (acc_ref,) = state

        @pl.when(c == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

    def sub_block(i, _):
        r0 = q0 + i * bq
        rs = _rows_at(i, bq)
        q = _stack_rows(q_ref[rs, :], rows, dh, scale if fold else None)
        do = _stack_rows(do_ref[rs, :], rows, dh)
        lse = _stat_cols(lse_ref, i * sw_n, sw_n)
        delta = _stat_cols(delta_ref, i * sw_n, sw_n)
        acc = (acc_ref[...] if n_chunks > 1
               else jnp.zeros((rows * bq, w), jnp.float32))

        def step(j, acc, masked):
            ks = _rows_at(j, bk)
            return _dq_step(q, do, k_ref[ks, :], v_ref[ks, :], lse, delta,
                            acc, diff if masked else None,
                            r0 - (k0 + j * bk), sc=sc, mm=mm)

        acc = _walk(_stretches(causal, r0, bq, k0, bk, n_kb,
                               walk_is_keys=True), step, acc)
        if n_chunks > 1:
            acc_ref[...] = acc

        def finish():
            dq_ref[rs, :] = (_unstack_rows(acc, rows, dh) * scale).astype(
                dq_ref.dtype)

        _end_of_chunk(n_chunks, c, finish)
        return 0

    _loop(0, n_sub, sub_block, 0)


def _bwd_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *state, causal, scale, fold, rows, dh,
                    bq, bk, n_chunks, static, fused=False):
    """Keys on the tile side, queries walked; the scores are K Q^T.
    ``fused`` (a row is one grid step, every bound static): ``state`` is a
    third result, dq, and a visit adds its ``dS^T K`` to the float32 block
    of its queries, carried across the key blocks as a value."""
    tk, w = k_ref.shape
    qc = q_ref.shape[0]
    n_sub, n_qb, sw_n = tk // bk, qc // bq, bq // lse_ref.shape[-1]
    k0, c, q0 = _origins(static, tk, qc)
    mm = _mm_dtype(q_ref.dtype)
    sc = None if fold else scale
    diff = _stacked_diff(bq, bk, rows, keys_on_lanes=False)
    if fused:
        (dq_ref,) = state
        dq = [jnp.zeros((bq, w), jnp.float32)] * n_qb
    elif n_chunks > 1:
        dk_acc_ref, dv_acc_ref = state

        @pl.when(c == 0)
        def _init():
            dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
            dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    def sub_block(jb, _):
        c0 = k0 + jb * bk
        cs = _rows_at(jb, bk)
        k = _stack_rows(k_ref[cs, :], rows, dh, scale if fold else None)
        v = _stack_rows(v_ref[cs, :], rows, dh)
        if n_chunks > 1:
            carry = (dk_acc_ref[...], dv_acc_ref[...])
        else:
            carry = (jnp.zeros((rows * bk, w), jnp.float32),) * 2

        def step(i, carry, masked):
            rs = _rows_at(i, bq)
            dk, dv, dq_i = _dkv_step(
                k, v, q_ref[rs, :], do_ref[rs, :],
                _stat_rows(lse_ref, i * sw_n, sw_n, bk),
                _stat_rows(delta_ref, i * sw_n, sw_n, bk), *carry,
                dq[i] if fused else None, diff if masked else None,
                (q0 + i * bq) - c0, sc=sc, mm=mm)
            if fused:
                dq[i] = dq_i
            return dk, dv

        dk, dv = _walk(_stretches(causal, c0, bk, q0, bq, n_qb,
                                  walk_is_keys=False), step, carry)
        if n_chunks > 1:
            dk_acc_ref[...], dv_acc_ref[...] = dk, dv

        def finish():
            # the scores came from UNSCALED q, so dk carries the scale
            dk_ref[cs, :] = (_unstack_rows(dk, rows, dh) * scale).astype(
                dk_ref.dtype)
            dv_ref[cs, :] = _unstack_rows(dv, rows, dh).astype(dv_ref.dtype)

        _end_of_chunk(n_chunks, c, finish)
        return 0

    _loop(0, n_sub, sub_block, 0)
    if fused:
        # dq came from the stacked keys, scaled already where folded
        for i in range(n_qb):
            dq_ref[_rows_at(i, bq), :] = (
                dq[i] if fold else dq[i] * scale).astype(dq_ref.dtype)


# ------------------------------------------------------------------ layouts
def _reshape_bh(x):
    b, t, h, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, dh)


def _unshape_bh(x, b, h):
    bh, t, dh = x.shape
    return x.reshape(b, h, t, dh).transpose(0, 2, 1, 3)


def _tile_rows(bh: int, dh: int, t: int) -> int:
    """(batch x head) rows a tile: two where two heads fit the 128 lanes
    and the rows pair up, else one (and for a sequence too odd for a
    statistics chunk of a sublane tile's width)."""
    return 2 if 2 * dh <= _LANES and bh % 2 == 0 and t % 8 == 0 else 1


def _pack(xf, rows):
    """[BH, T, Dh] -> [BH/rows, T, rows*Dh]: ``rows`` consecutive rows of
    the flattened (batch x head) dimension side by side in the lanes."""
    if rows == 1:
        return xf
    bh, t, dh = xf.shape
    return (xf.reshape(bh // rows, rows, t, dh).transpose(0, 2, 1, 3)
            .reshape(bh // rows, t, rows * dh))


def _unpack(xp, rows):
    if rows == 1:
        return xp
    g, t, w = xp.shape
    return (xp.reshape(g, t, rows, w // rows).transpose(0, 2, 1, 3)
            .reshape(g * rows, t, w // rows))


def _stat_width(t: int, block_q: Optional[int]) -> int:
    """Lanes of a statistics chunk: 128 wherever the sequence allows, and
    always a divisor of every kernel's query block (``_plan``)."""
    return math.gcd(_pick_block(t, block_q) if block_q else t, _LANES)


def _pack_stat(s, rows, sw):
    """[BH, T, 1] -> lane-dense [BH/rows, T/sw, rows, sw]."""
    bh, t, _ = s.shape
    return (s.reshape(bh // rows, rows, t // sw, sw).transpose(0, 2, 1, 3))


def _unpack_stat(sp):
    g, n, rows, sw = sp.shape
    return sp.transpose(0, 2, 1, 3).reshape(g * rows, n * sw, 1)


def _delta_tiles(dop, outp, rows, sw):
    """rowsum(do * out) a (batch x head) row, from tile-layout operands,
    laid out like lse: ``[G, T/sw, rows, sw]`` f32."""
    g, t, w = dop.shape
    prod = dop.astype(jnp.float32) * outp.astype(jnp.float32)
    return (prod.reshape(g, t // sw, sw, rows, w // rows).sum(axis=-1)
            .transpose(0, 1, 3, 2))


def _pick_block(t: int, pref: int) -> int:
    blk = min(pref, t)
    while t % blk:
        blk //= 2
    return max(blk, 1)


def _plan(t_tile, t_walk, w, itemsize, block_tile, block_walk, walk_budget):
    """(tile rows a grid step, tile block, walk rows a grid step, walk
    block, unrolled?) for a kernel whose tile side has ``t_tile`` positions
    and whose walk side has ``t_walk``. The walk side stays whole where its
    two operands fit ``walk_budget`` bytes, else a grid step sees the
    largest power-of-two share of it that does. A row whose blocks number
    ``_MAX_UNROLL`` or fewer is one grid step, unrolled by hand."""
    resident = 2 * t_walk * w * itemsize <= walk_budget
    b_tile = _pick_block(t_tile, block_tile or _BLOCK)
    b_walk = _pick_block(t_walk, block_walk or _BLOCK)
    if not resident:
        chunk = t_walk
        while (2 * chunk * w * itemsize > walk_budget
               and chunk % (2 * b_walk) == 0):
            chunk //= 2
        return b_tile, b_tile, chunk, b_walk, False
    if (t_tile // b_tile) * (t_walk // b_walk) <= _MAX_UNROLL:
        return t_tile, b_tile, t_walk, b_walk, True
    tile = t_tile
    while tile > _MAX_TILE and tile % (2 * b_tile) == 0:
        tile //= 2
    return tile, b_tile, t_walk, b_walk, False


def _count_traced(this: str, other: str) -> None:
    """Say in the program's registry which of two forms a traced flash
    kernel took; both counters exist from the first call on."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    reg.counter(other)
    reg.counter(this).inc()


def _count_walk(n_chunks: int) -> None:
    """Which walk: the walk side resident, or in chunks a grid step."""
    names = ("flash/traced_short_seq", "flash/traced_grid_walk")
    _count_traced(*(names if n_chunks == 1 else names[::-1]))


def _count_bwd(fused: bool) -> None:
    """Which backward: one kernel for dq, dk and dv, or two."""
    names = ("flash/traced_bwd_fused", "flash/traced_bwd_split")
    _count_traced(*(names if fused else names[::-1]))


def _fused_bytes(t, tk, w, item, rows, bq, bk) -> int:
    """VMEM the one-kernel backward holds for a row: q, dO, dq and k, v,
    dk, dv whole and double-buffered, dq's float32 blocks, and a visit's
    float32 scores with the three values derived from them."""
    return (2 * (3 * t + 4 * tk) * w * item + t * w * 4
            + 4 * rows * bk * bq * 4)


def _fold_scale(scale: float, dtype) -> bool:
    """Folding the softmax scale into an operand tile changes no bit where
    the scale is a power of two, and only f32 rounding in f32 storage."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


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


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _side_specs(tile, walk, n_chunks, w, rows, sw, *, causal, walk_is_keys):
    """BlockSpecs of a [G, T, w] operand and a [G, T/sw, rows, sw]
    statistic on the tile side (one tile a step of grid axis 1) and on the
    walk side (one chunk a step of axis 2). A causally dead chunk gets the
    index of the nearest live one, so it is not fetched again."""
    def walk_idx(ti, c):
        if not causal:
            return c
        if walk_is_keys:        # chunks past the tile's last query are dead
            return jnp.minimum(c, ((ti + 1) * tile - 1) // walk)
        # and so are chunks that end before its first key
        return jnp.maximum(c, jnp.minimum((ti * tile) // walk, n_chunks - 1))

    tile_op = pl.BlockSpec((None, tile, w), lambda g, ti, c: (g, ti, 0))
    walk_op = pl.BlockSpec((None, walk, w),
                           lambda g, ti, c: (g, walk_idx(ti, c), 0))
    tile_stat = pl.BlockSpec((None, tile // sw, rows, sw),
                             lambda g, ti, c: (g, ti, 0, 0))
    walk_stat = pl.BlockSpec((None, walk // sw, rows, sw),
                             lambda g, ti, c: (g, walk_idx(ti, c), 0, 0))
    return tile_op, walk_op, tile_stat, walk_stat


# --------------------------------------------------------------- the calls
def _fwd_tiles(qp, kp, vp, *, rows, causal, scale, block_q, block_k,
               interpret, walk_budget=_WALK_VMEM_BUDGET):
    """Forward on tile-layout operands ``[G, T, rows*Dh]`` -> (out in the
    same layout, lse as lane-dense ``[G, T/sw, rows, sw]`` f32)."""
    g, t, w = qp.shape
    tk = kp.shape[1]
    dh = w // rows
    tq, bq, kc, bk, static = _plan(t, tk, w, qp.dtype.itemsize, block_q,
                                   block_k, walk_budget)
    n_chunks, sw = tk // kc, _stat_width(t, block_q)
    _count_walk(n_chunks)
    interp = _interpret_default() if interpret is None else interpret
    kernel = functools.partial(
        _fwd_kernel, causal=causal, scale=scale,
        fold=_fold_scale(scale, qp.dtype), rows=rows, dh=dh, bq=bq, bk=bk,
        n_chunks=n_chunks, static=static)
    q_spec, kv_spec, stat_spec, _ = _side_specs(
        tq, kc, n_chunks, w, rows, sw, causal=causal, walk_is_keys=True)
    kw = {} if interp else {"compiler_params": _grid_params()}
    shp = functools.partial(_sds, qp, kp, vp)
    scratch = [] if n_chunks == 1 else [
        pltpu.VMEM((rows * bq, 1), jnp.float32),    # running max m
        pltpu.VMEM((rows * bq, 1), jnp.float32),    # running sum l
        pltpu.VMEM((rows * bq, w), jnp.float32),    # output accumulator
    ]
    return pl.pallas_call(
        kernel,
        name="dstpu_flash_fwd",
        grid=(g, t // tq, n_chunks),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, stat_spec],
        out_shape=[shp((g, t, w), qp.dtype),
                   shp((g, t // sw, rows, sw), jnp.float32)],
        scratch_shapes=scratch,
        interpret=interp,
        **kw,
    )(qp, kp, vp)


def _bwd_tiles(qp, kp, vp, dop, lse, delta, *, rows, causal, scale, block_q,
               block_k, interpret, walk_budget=_WALK_VMEM_BUDGET):
    """Backward on tile-layout operands -> (dq, dk, dv) in that layout;
    ``lse`` / ``delta`` lane-dense ``[G, T/sw, rows, sw]``. One kernel where
    both sides' plans make a row one grid step and the row fits
    ``_FUSED_VMEM`` (the dk/dv call with dq as a third result), else two."""
    g, t, w = qp.shape
    tk = kp.shape[1]
    dh = w // rows
    sw = lse.shape[-1]
    item = qp.dtype.itemsize
    interp = _interpret_default() if interpret is None else interpret
    kw = {} if interp else {"compiler_params": _grid_params()}
    shp = functools.partial(_sds, qp, kp, vp, dop)
    common = dict(causal=causal, scale=scale,
                  fold=_fold_scale(scale, qp.dtype), rows=rows, dh=dh)

    tq, bq, kc, bk, static = _plan(t, tk, w, item, block_q, block_k,
                                   walk_budget)
    dkv_plan = _plan(tk, t, w, item, block_k, block_q, walk_budget)
    fused = (static and dkv_plan[-1]
             and _fused_bytes(t, tk, w, item, rows, bq, bk) <= _FUSED_VMEM)
    _count_bwd(fused)
    if not fused:
        n_chunks = tk // kc
        _count_walk(n_chunks)
        q_spec, kv_spec, stat_spec, _ = _side_specs(
            tq, kc, n_chunks, w, rows, sw, causal=causal, walk_is_keys=True)
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, bq=bq, bk=bk,
                              n_chunks=n_chunks, static=static, **common),
            name="dstpu_flash_bwd_dq",
            grid=(g, t // tq, n_chunks),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
            out_specs=q_spec,
            out_shape=shp((g, t, w), qp.dtype),
            scratch_shapes=([] if n_chunks == 1
                            else [pltpu.VMEM((rows * bq, w), jnp.float32)]),
            interpret=interp,
            **kw,
        )(qp, kp, vp, dop, lse, delta)

    tkt, bk, qc, bq, static = dkv_plan
    n_chunks = t // qc
    _count_walk(n_chunks)
    k_spec, q_spec, _, stat_spec = _side_specs(
        tkt, qc, n_chunks, w, rows, sw, causal=causal, walk_is_keys=False)
    results = [(k_spec, shp((g, tk, w), kp.dtype)),
               (k_spec, shp((g, tk, w), vp.dtype))]
    if fused:   # the whole row of queries is this grid step's: so is its dq
        results.append((q_spec, shp((g, t, w), qp.dtype)))
    grads = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, bq=bq, bk=bk, n_chunks=n_chunks,
                          static=static, fused=fused, **common),
        name="dstpu_flash_bwd_dkv",
        grid=(g, tk // tkt, n_chunks),
        in_specs=[k_spec, k_spec, q_spec, q_spec, stat_spec, stat_spec],
        out_specs=[spec for spec, _ in results],
        out_shape=[shape for _, shape in results],
        scratch_shapes=([] if n_chunks == 1
                        else [pltpu.VMEM((rows * bk, w), jnp.float32)] * 2),
        interpret=interp,
        **kw,
    )(kp, vp, qp, dop, lse, delta)
    if fused:
        dk, dv, dq = grads
    else:
        dk, dv = grads
    return dq, dk, dv


def flash_fwd_parts(qf, kf, vf, *, causal, scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Kernel-level forward on FLAT [BH, T, Dh] operands → (out, lse), lse
    [BH, T, 1] f32.

    Public building block for sequence-parallel composition (ring attention
    merges per-hop (out, lse) pairs exactly); ``flash_attention`` wraps the
    same call with the [B, T, H, Dh] layout and custom_vjp."""
    bh, _, dh = qf.shape
    rows = _tile_rows(bh, dh, qf.shape[1])
    out, lse = _fwd_tiles(
        _pack(qf, rows), _pack(kf, rows), _pack(vf, rows), rows=rows,
        causal=causal, scale=scale if scale is not None else dh ** -0.5,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return _unpack(out, rows), _unpack_stat(lse)


def flash_bwd_parts(qf, kf, vf, dof, lse, delta, *, causal, scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Kernel-level backward on FLAT operands → (dq, dk, dv).

    ``lse``/``delta`` ([BH, T, 1]) are the GLOBAL log-sum-exp rows / do·out
    sums, so sequence-parallel callers can run this per K/V hop and the
    per-hop grads sum to the exact global gradient (p = exp(s - lse_global))."""
    bh, t, dh = qf.shape
    rows = _tile_rows(bh, dh, t)
    sw = _stat_width(t, block_q)
    f32 = jnp.float32
    dq, dk, dv = _bwd_tiles(
        _pack(qf, rows), _pack(kf, rows), _pack(vf, rows), _pack(dof, rows),
        _pack_stat(lse.astype(f32), rows, sw),
        _pack_stat(delta.astype(f32), rows, sw), rows=rows, causal=causal,
        scale=scale if scale is not None else dh ** -0.5, block_q=block_q,
        block_k=block_k, interpret=interpret)
    return _unpack(dq, rows), _unpack(dk, rows), _unpack(dv, rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q/k/v: [B, T, H, Dh] → [B, T, H, Dh]. MHA (same head counts).
    ``block_q`` / ``block_k`` override the blocks chosen from the shapes."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    b, t, h, dh = q.shape
    rows = _tile_rows(b * h, dh, t)
    qp, kp, vp = (_pack(_reshape_bh(x), rows) for x in (q, k, v))
    out, lse = _fwd_tiles(
        qp, kp, vp, rows=rows, causal=causal,
        scale=scale if scale is not None else dh ** -0.5, block_q=block_q,
        block_k=block_k, interpret=interpret)
    # Residuals tagged for remat: the "flash_res" checkpoint-name lets the
    # save_attn policy (runtime/activation_checkpointing.py) SAVE them, so a
    # rematted transformer block never re-runs this kernel in backward —
    # flash residuals are O(T) (out + lse), unlike dense attention's O(T^2).
    # They stay in the tile layout: lane-dense, nothing padded in HBM.
    from jax.ad_checkpoint import checkpoint_name

    res = tuple(checkpoint_name(x, "flash_res") for x in (qp, kp, vp, out, lse))
    return _unshape_bh(_unpack(out, rows), b, h), res + ((b, h),)


def _flash_bwd_vjp(causal, scale, block_q, block_k, interpret, res, g):
    qp, kp, vp, outp, lse, (b, h) = res
    rows, sw = lse.shape[2:]
    dop = _pack(_reshape_bh(g), rows)
    dq, dk, dv = _bwd_tiles(
        qp, kp, vp, dop, lse, _delta_tiles(dop, outp, rows, sw), rows=rows,
        causal=causal,
        scale=scale if scale is not None else (qp.shape[-1] // rows) ** -0.5,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return tuple(_unshape_bh(_unpack(x, rows), b, h) for x in (dq, dk, dv))


flash_attention.defvjp(_flash_fwd, _flash_bwd_vjp)
