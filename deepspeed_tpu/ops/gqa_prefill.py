"""Prompt attention of a softmax GROUPED-QUERY layer over a cache that already
holds the block's rows, one Pallas invocation a layer a token block: a block
of queries at consecutive positions against the layer's key and value rows
``[0, end of block)``, read from the stacked cache leaves where they lie, the
scores of a (query tile, key block) never outside VMEM.

    score_h(i, j) = s q_h(i) . k_g(j), j <= i;   o_h(i) = sum_j p_h(i, j) v_g(j)

with ``g = h // rep`` the key-value head that ``rep`` query heads share.

**The grid** is (batch row, key-value head, query tile, key block), the key
block innermost and sequential: the running maximum, the running sum and the
float32 accumulator of a query tile ride VMEM scratch across its key blocks.
The ``rep`` query heads of a key-value head share a grid cell, their tiles
STACKED ALONG THE ROWS of the scratches: the cell fetches its key block's
``k`` and ``v`` (``[key_block, head_dim]`` each) once for all of them. The
queries come as the projection leaves them (``[B, T, heads x head_dim]``, a
position a row): a tile's block holds the group's heads side by side in the
lanes, and a head's rows are a choice of whole tiles, no relayout. The result
goes back the same way, as the output projection takes it.

**Inside a cell** the stacked rows are walked in chunks of ``_CHUNK_ROWS``,
unrolled in one basic block: a chunk's scores ``[128, key_block]`` float32 are
64 vector registers, so maximum, ``exp``, sum and the rounding to bf16 never
leave them, and the next chunk's score matmul is issued before this chunk's
softmax, so the MXU works while the vector units do. Whole tiles' scores at
once (``[4096, 512]``, 8 MB through VMEM and back for every pass) ran at 46%
of the MXU's peak on the chip, the chunks at 73% (PERF.md, PR 50).

**What is skipped**, as ops/mla_prefill.py skips it: a query tile takes no key
block that lies wholly above its diagonal, and only a tile the diagonal
crosses builds a mask. A tile with no real position (at or past the row's
``valid``) does nothing and comes back as zeros: nothing real attends its
rows. A key block past a tile's reach is neither computed nor fetched (its
block index is clamped to the last needed one, so the pipeline re-uses the
buffer it has). The layer, the first position and the valid length ride
scalar prefetch: one kernel body serves every bucket, every token block of a
long prompt and a chunk continued at ``first > 0``.

**The numbers are the loop's** (ops/attention.blocked_prompt_attention): bf16
operands on the MXU, float32 scores times ``head_dim ** -0.5``, float32
maximum, sum and correction, probabilities rounded to the cache's dtype
before the second product, one division by the sum at the end.

Serving's prefill only: no VJP. ``generate()``'s prompts of any length, packed
rows, a CPU and shapes :func:`supports` refuses take the ``lax`` loop.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_step import _NEG, _VMEM_LIMIT
from deepspeed_tpu.ops.flash_attention import _NN, _NT, _dot_f32

LANES = 128
# Positions of a query tile (a grid cell's), and the rows of it that one pair
# of matmuls takes: a chunk's scores are 64 vector registers, so its softmax
# never leaves them (PERF.md, PR 50 has the chip's readings by both)
_QUERY_TILE = 512
_CHUNK_ROWS = 128


def query_tile(t: int) -> int:
    """Positions of a query tile for a token block of ``t``."""
    return min(t, _QUERY_TILE)


def _chunk_rows(tq: int) -> int:
    """Rows of a chunk of a query tile of ``tq`` positions."""
    return min(tq, _CHUNK_ROWS)


def supports(s_max: int, row_width: int, head_dim: int, key_block: int,
             t: int, heads: int, kv_heads: int,
             v_head_dim: Optional[int] = None) -> bool:
    """Shapes the kernel takes: unpacked cached rows (a row is one head's
    ``head_dim``) of whole 128-lane tiles, a row count of whole key blocks,
    a token block of whole query tiles of whole chunks, tiles and key blocks
    of whole sublane tiles (16 rows of bf16), query heads in whole groups;
    value rows of another width (``v_head_dim``) in whole tiles too."""
    tq = query_tile(t)
    return (row_width == head_dim and head_dim % LANES == 0
            and (v_head_dim or head_dim) % LANES == 0
            and key_block % 16 == 0 and s_max % key_block == 0
            and tq % 16 == 0 and t % tq == 0 and tq % _chunk_rows(tq) == 0
            and heads % kv_heads == 0)


def count_traced(kernel: bool) -> None:
    """Say in the program's registry which way a softmax layer's prompt block
    was traced: ``gqa/traced_prefill_kernel`` (this file's call) or
    ``gqa/traced_blocked_block`` (the ``lax`` loop). Both exist from the
    first call on."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = [reg.counter("gqa/traced_" + n)
                for n in ("blocked_block", "prefill_kernel")]
    counters[bool(kernel)].inc()


# Jitted, so that a kernel traces each once a signature and not once a chunk
@functools.partial(jax.jit, static_argnames=("scale",))
def _scores(q, k, *, scale: float):
    """``q [rows, d]`` against ``k [bk, d]``: float32 scores times the
    scale."""
    return _dot_f32(q, k, _NT) * scale


@jax.jit
def _chunk_step(s, v, m_prev, l_prev, acc, diff, offset):
    """One (chunk of query rows, key block) of the running softmax from its
    scores ``s [rows, bk]``; ``diff [rows, bk]`` key column minus query row
    and ``offset`` the first query's position minus the first key's: a key is
    visible where ``diff <= offset``; ``diff`` None: all are."""
    if diff is not None:
        s = jnp.where(diff <= offset, s, _NEG)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    return m_new, l_new, acc * corr + _dot_f32(p.astype(v.dtype), v, _NN)


def _reach(first, valid, j, tq: int, bk: int, n_kb: int):
    """How many key blocks query tile ``j`` of a token block takes: up to
    its last position's, none where it holds no real position."""
    return jnp.where(j * tq < valid,
                     jnp.minimum((first + (j + 1) * tq - 1) // bk + 1, n_kb),
                     0)


def _kernel(layer_ref, first_ref, valid_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, rep: int, n: int, scale: float):
    """A grid cell: batch row ``b``, one key-value head, query tile ``j``,
    key block ``kb``. ``first_ref [B]`` the position of the token block's
    first query, ``valid_ref [B]`` how many of its positions are real. The
    scratches hold the ``rep`` heads' tiles stacked, head-major; a chunk is
    ``n`` of their rows."""
    del layer_ref                    # the index maps read it
    b, j, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    tq, bk, d, dv = (q_ref.shape[0], k_ref.shape[0], k_ref.shape[1],
                     v_ref.shape[1])
    lo = first_ref[b] + j * tq                    # the tile's first position
    blocks = _reach(first_ref[b], valid_ref[b], j, tq, bk,
                    pl.num_programs(3))

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def scores(c):
        h, r = divmod(c * n, tq)
        return _scores(q_ref[r:r + n, h * d:(h + 1) * d], k_ref[...],
                       scale=scale)

    def tile(masked: bool):
        diff = None
        if masked:      # key column c, query row i: c + kb bk <= i + lo
            diff = (jax.lax.broadcasted_iota(jnp.int32, (n, bk), 1)
                    - jax.lax.broadcasted_iota(jnp.int32, (n, bk), 0))
        chunks = rep * tq // n
        # the next chunk's scores are issued before this chunk's softmax:
        # the MXU works on them while the vector units work on these
        s_next = scores(0)
        for c in range(chunks):
            s = s_next
            if c + 1 < chunks:
                s_next = scores(c + 1)
            rs = pl.ds(c * n, n)
            m_ref[rs], l_ref[rs], acc_ref[rs] = _chunk_step(
                s, v_ref[...], m_ref[rs], l_ref[rs], acc_ref[rs], diff,
                lo + c * n % tq - kb * bk)

    needed = kb < blocks
    crossed = kb * bk + bk - 1 > lo
    pl.when(jnp.logical_and(needed, crossed))(lambda: tile(True))
    pl.when(jnp.logical_and(needed, jnp.logical_not(crossed)))(
        lambda: tile(False))

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        for h in range(rep):
            l = l_ref[h * tq:(h + 1) * tq]
            # a tile with no real position was never visited: zeros, not 0/0
            o_ref[:, h * dv:(h + 1) * dv] = (
                acc_ref[h * tq:(h + 1) * tq] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)


def gqa_prefill(q: jax.Array, k_full: jax.Array, v_full: jax.Array, layer,
                first, valid=None, *, key_block: int,
                scale: Optional[float] = None,
                interpret: Optional[bool] = None):
    """One layer's prompt attention of one token block over the FULL stacked
    key and value caches, whose rows already hold the block's own.

    q:       ``[B, T, Hq, Dh]``
    k_full:  ``[L, B, Hkv, S, Dh]`` the stacked cache, unpacked rows;
    v_full:  ``[L, B, Hkv, S, Dv]``, the values' own width
    scale:   of the scores; ``None``: ``Dh ** -0.5``
    layer:   scalar int32, the cache's layer
    first:   scalar or ``[B]`` int32: the position of the block's first query
    valid:   scalar or ``[B]`` int32: how many of the block's positions are
             real; ``None``: all ``T``

    Returns ``[B, T, Hq, Dv]`` in ``q``'s dtype; the rows of a query tile
    with no real position are zeros."""
    b, t, hq, d = q.shape
    l, _, hkv, s_max, w = k_full.shape
    dv = v_full.shape[4]
    tq, bk = query_tile(t), key_block
    assert k_full.shape == v_full.shape[:4] + (w,) == (l, b, hkv, s_max, w) \
        and supports(s_max, w, d, bk, t, hq, hkv, dv), \
        (q.shape, k_full.shape, v_full.shape, bk)
    rep, n_kb = hq // hkv, s_max // bk
    i32 = jnp.int32
    first = jnp.broadcast_to(jnp.asarray(first, i32), (b,))
    valid = jnp.full((b,), t, i32) if valid is None else jnp.clip(
        jnp.broadcast_to(jnp.asarray(valid, i32), (b,)), 0, t)
    scalars = [jnp.asarray(layer, i32).reshape(1), first, valid]

    def rows_at(bi, g, j, kb, layer_ref, first_ref, valid_ref):
        last = _reach(first_ref[bi], valid_ref[bi], j, tq, bk, n_kb) - 1
        return layer_ref[0], bi, g, jnp.minimum(kb, jnp.maximum(last, 0)), 0

    def tile_at(bi, g, j, kb, *_):
        return bi, j, g

    tile = pl.BlockSpec((None, tq, rep * d), tile_at)
    rows = pl.BlockSpec((None, None, None, bk, d), rows_at)
    out_tile, v_rows = tile, rows
    if dv != d:
        out_tile = pl.BlockSpec((None, tq, rep * dv), tile_at)
        v_rows = pl.BlockSpec((None, None, None, bk, dv), rows_at)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, hkv, t // tq, n_kb),
        in_specs=[tile, rows, v_rows],
        out_specs=out_tile,
        scratch_shapes=[
            pltpu.VMEM((rep * tq, 1), jnp.float32),       # running max
            pltpu.VMEM((rep * tq, 1), jnp.float32),       # running sum
            pltpu.VMEM((rep * tq, dv), jnp.float32),      # accumulator
        ])
    interp = jax.default_backend() != "tpu" if interpret is None \
        else interpret
    kw = {} if interp else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}
    out = pl.pallas_call(
        functools.partial(
            _kernel, rep=rep, n=_chunk_rows(tq),
            scale=float(d ** -0.5 if scale is None else scale)),
        name="dstpu_gqa_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, hq * dv), q.dtype),
        interpret=interp,
        **kw,
    )(*scalars, q.reshape(b, t, hq * d), k_full, v_full)
    return out.reshape(b, t, hq, dv)
