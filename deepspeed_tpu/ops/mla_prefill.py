"""Prompt attention of multi-head LATENT attention in its decompressed form,
one Pallas invocation a layer a token block: a block of queries at head sizes
``nope + rope`` / ``v`` against the latent cache's rows ``[0, end of block)``,
the keys and values of a key block made from its cached rows inside the
kernel, the scores of a (query tile, key block) never outside VMEM.

A latent cache holds ONE row a token a layer that every head shares
(models/sarvam_mla.py): the normed latent ``c~`` (``kv_lora_rank`` wide), the
rotated key ``k_r`` behind it, zero lanes up to a whole number of 128. Head
``h``'s keys and values are an up-projection of ``c~``:

    [k_nope_h | v_h] = c~ Wkv_b[h]
    score_h(i, j) = s (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i

**The grid** is (batch row, head, key block), the key block innermost and
sequential: the running maximum, the running sum and the ``[T, v]`` float32
accumulator of the whole token block ride VMEM scratch across a head's key
blocks. A grid cell fetches its key block's rows (``[key_block, W]``) and the
head's columns of ``Wkv_b`` (``[latent, nope + v]``), makes ``k_nope`` and
``v`` of the block ONCE, and walks the token block's query tiles against
them: the FLOPs of the ``lax`` loop's up-projection, no buffer of
decompressed keys in HBM.

**What is skipped.** A query tile takes no key block that lies wholly above
its diagonal, and only a tile the diagonal crosses builds a mask. A tile with
no real position (at or past the row's ``valid``) does nothing and comes back
as zeros: nothing real attends its rows. A key block past the last live
tile's reach is neither computed nor fetched (its block index is clamped to
the last needed one, so the pipeline re-uses the buffer it has). The first
position, the valid length and the key-block count ride scalar prefetch: one
kernel body serves every bucket, every token block of a long prompt and a
chunk continued at ``first > 0``.

**Inside a cell** a query tile is walked in chunks of ``_CHUNK_ROWS`` rows in
one basic block: a chunk's scores ``[128, key_block]`` float32 are 64 vector
registers, so mask, maximum, ``exp``, sum and the rounding to bf16 never leave
them, and the next chunk's two score matmuls are issued before this chunk's
softmax, so the MXU works while the vector units do (ops/gqa_prefill.py walks
its tiles the same way, and the running-softmax step of a chunk is that
file's). A whole tile's scores at once (``[512, 512]``, 1 MB through VMEM and
back for every pass) left 17.6 of a layer's 69.6 ms over a 16,384 prompt as
softmax work beside an idle MXU (PERF.md, PR 47 and PR 54). The chunks are ONE
traced body of a ``fori_loop`` that the lowering unrolls: unrolled in the
trace they cost every prefill program a second and more of set-up, and left
rolled the scores ride the loop's carry through VMEM.

**A key block below every tile's diagonal, all tiles live**, is the common
cell of a long prompt (28 of 32 in the last token block of 16,384): all the
chunks of all its tiles are one walk, so the next chunk's scores are issued
across tile boundaries too. (Whole tiles unrolled so, with the overlap left to
the compiler, were the 17.6 ms that stayed exposed.) Every other cell takes a
tile at a time, each under its own condition, its chunks one walk; a tile the
diagonal crosses builds its mask a chunk at a time from one ``[128,
key_block]`` iota difference and the chunk's offset.

**The numbers are the loop's** (models/sarvam_mla.py ``_prompt_attention``):
bf16 operands on the MXU, float32 accumulation of the scores and of ``p v``,
float32 maximum, sum and correction, probabilities rounded to bf16 before the
second product, the scale applied to float32 scores, one division by the sum
at the end. The rotated key's product (``rope`` wide, one row for all heads)
is its own dot beside the ``nope``-wide one, summed in float32.

Serving's prefill only: no VJP. ``generate()``'s prompts of any length, a CPU
and shapes :func:`supports` refuses take the ``lax`` loop over the same leaf.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_step import _NEG, _VMEM_LIMIT
from deepspeed_tpu.ops.flash_attention import _NN, _NT, _dot_f32, _rows_at
from deepspeed_tpu.ops.gqa_prefill import _chunk_step

# Rows of a query tile (the unit that is skipped, masked or walked whole
# against a key block: PERF.md, PR 47 has the chip's readings by tile), and
# the rows of it that one pair of score matmuls takes: a chunk's scores are 64
# vector registers, so its softmax never leaves them (PERF.md, PR 54 has the
# chip's readings by chunk)
_QUERY_TILE = 512
_CHUNK_ROWS = 128


def query_tile(t: int) -> int:
    """Rows of a query tile for a token block of ``t``."""
    return min(t, _QUERY_TILE)


def _chunk_rows(tq: int) -> int:
    """Rows of a chunk of a query tile of ``tq`` rows."""
    return min(tq, _CHUNK_ROWS)


def supports(s_max: int, width: int, key_block: int, t: int) -> bool:
    """Shapes the kernel takes: cached rows of whole 128-lane tiles, a row
    count of whole key blocks, a token block of whole query tiles of whole
    chunks, tiles and key blocks of whole sublane tiles (16 rows of bf16)."""
    tq = query_tile(t)
    return (width % 128 == 0 and key_block % 16 == 0
            and s_max % key_block == 0 and tq % 16 == 0 and t % tq == 0
            and tq % _chunk_rows(tq) == 0)


def count_traced() -> None:
    """Say in the program's registry that a latent-attention layer's prompt
    block was traced with this file's kernel: ``mla/traced_prefill_kernel``,
    beside ``mla/traced_decompressed_block`` for the ``lax`` loop
    (ops/mla_decode_step.count_form)."""
    from deepspeed_tpu.telemetry.registry import get_registry

    get_registry().counter("mla/traced_prefill_kernel").inc()


# Jitted, so that a kernel traces it once a signature and not once a chunk
@functools.partial(jax.jit, static_argnames=("n", "scale"))
def _scores(q, k_nope, k_rope, *, n: int, scale: float):
    """``q [rows, n + rope]`` against ``k_nope [bk, n]`` and the rotated key
    ``k_rope [bk, rope]``: the two products summed in float32, times the
    scale."""
    return (_dot_f32(q[:, :n], k_nope, _NT)
            + _dot_f32(q[:, n:], k_rope, _NT)) * scale


def _kernel(layer_ref, wl_ref, first_ref, valid_ref, blocks_ref, q_ref,
            rows_ref, w_ref, o_ref, m_ref, l_ref, acc_ref, kv_ref, *,
            r: int, rope: int, n: int, tq: int, cr: int, scale: float):
    """A grid cell: batch row ``b``, one head, key block ``kb``.
    ``first_ref [B]`` the position of the block's first query,
    ``valid_ref [B]`` how many of its positions are real, ``blocks_ref [B]``
    how many key blocks its live tiles reach. A query tile is ``tq`` rows, a
    chunk ``cr`` of them."""
    del layer_ref, wl_ref            # the index maps read them
    b, kb = pl.program_id(0), pl.program_id(2)
    t, bk = q_ref.shape[0], rows_ref.shape[0]
    n_tiles = t // tq
    first, valid = first_ref[b], valid_ref[b]

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def walk(c0, chunks: int, masked: bool):
        """Chunks ``[c0, c0 + chunks)`` of the token block's query rows
        against the key block, in one basic block."""
        def scores(c):
            return _scores(q_ref[_rows_at(c0 + c, cr), :], kv_ref[:, :n],
                           rows_ref[:, r:r + rope], n=n, scale=scale)

        diff = None
        if masked:      # key column c, query row i: c + kb bk <= i + q0
            diff = (jax.lax.broadcasted_iota(jnp.int32, (cr, bk), 1)
                    - jax.lax.broadcasted_iota(jnp.int32, (cr, bk), 0))

        def step(c, s):
            rs = _rows_at(c0 + c, cr)
            m_ref[rs], l_ref[rs], acc_ref[rs] = _chunk_step(
                s, kv_ref[:, n:], m_ref[rs], l_ref[rs], acc_ref[rs], diff,
                first + (c0 + c) * cr - kb * bk if masked else 0)

        def ahead(c, s):
            # the next chunk's scores are issued before this chunk's
            # softmax: the MXU works on them while the vector units work on
            # these
            s_next = scores(c + 1)
            step(c, s)
            return s_next

        # one traced body that the lowering unrolls (a rolled loop carries
        # the scores through VMEM: 91 ms a layer for 58, PERF.md, PR 54)
        s = scores(0)
        if chunks > 1:
            s = jax.lax.fori_loop(0, chunks - 1, ahead, s, unroll=True)
        step(chunks - 1, s)

    @pl.when(kb < blocks_ref[b])
    def _():
        # the block's keys and values, once for every query tile
        kv_ref[...] = _dot_f32(rows_ref[:, :r], w_ref[...], _NN).astype(
            kv_ref.dtype)
        last_key = kb * bk + bk - 1
        below_all = jnp.logical_and(last_key <= first, valid >= t)
        pl.when(below_all)(lambda: walk(0, t // cr, False))

        @pl.when(jnp.logical_not(below_all))
        def _():
            def each(j, _):
                lo = first + j * tq               # the tile's first position
                needed = jnp.logical_and(j * tq < valid,
                                         kb * bk <= lo + tq - 1)
                crossed = last_key > lo
                pl.when(jnp.logical_and(needed, crossed))(
                    lambda: walk(j * (tq // cr), tq // cr, True))
                pl.when(jnp.logical_and(needed, jnp.logical_not(crossed)))(
                    lambda: walk(j * (tq // cr), tq // cr, False))
                return 0

            jax.lax.fori_loop(0, n_tiles, each, 0)

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        # a tile with no real position was never visited: zeros, not 0 / 0
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)).astype(
            o_ref.dtype)


def mla_prefill(q_nope: jax.Array, q_rope: jax.Array, latent: jax.Array,
                wkv_b: jax.Array, layer, first, valid=None, *,
                latent_width: int, scale: float, key_block: int,
                w_layer=None, interpret: Optional[bool] = None):
    """One layer's prompt attention of one token block over the FULL stacked
    latent cache, whose rows already hold the block's own.

    q_nope:   ``[B, T, H, nope]``
    q_rope:   ``[B, T, H, rope]``, rotated
    latent:   ``[L, B, S, W]`` the stacked cache: ``latent_width`` lanes of
              ``c~``, ``rope`` of the rotated key behind them
    wkv_b:    ``[latent_width, H * (nope + v)]``, head ``h``'s ``W_UK`` its
              first ``nope`` columns and ``W_UV`` the ``v`` behind them; or
              the layer-stacked ``[Lw, latent_width, H * (nope + v)]`` with
              ``w_layer`` the layer to read (the kernel then fetches its
              columns where they lie: no slice of the stack is written out)
    layer:    scalar int32, the cache's layer
    first:    scalar or ``[B]`` int32: the position of the block's first query
    valid:    scalar or ``[B]`` int32: how many of the block's positions are
              real; ``None``: all ``T``

    Returns ``[B, T, H, v]``; the rows of a query tile with no real position
    are zeros."""
    b, t, h, n = q_nope.shape
    rope = q_rope.shape[-1]
    l, _, s_max, w = latent.shape
    r = latent_width
    if wkv_b.ndim == 2:
        wkv_b, w_layer = wkv_b[None], 0
    vd = wkv_b.shape[-1] // h - n
    tq, bk = query_tile(t), key_block
    assert latent.shape == (l, b, s_max, w) and supports(s_max, w, bk, t), \
        (q_nope.shape, latent.shape, bk)
    assert wkv_b.shape[1:] == (r, h * (n + vd)) and r + rope <= w and \
        wkv_b.dtype == latent.dtype, (wkv_b.shape, wkv_b.dtype, r, rope, w)
    n_kb = s_max // bk
    i32 = jnp.int32
    first = jnp.broadcast_to(jnp.asarray(first, i32), (b,))
    valid = jnp.full((b,), t, i32) if valid is None else jnp.clip(
        jnp.broadcast_to(jnp.asarray(valid, i32), (b,)), 0, t)
    # key blocks up to the last live tile's last position
    reach = first + (valid + tq - 1) // tq * tq - 1
    blocks = jnp.where(valid > 0, jnp.minimum(reach // bk + 1, n_kb), 0)
    scalars = [jnp.asarray(layer, i32).reshape(1),
               jnp.asarray(w_layer, i32).reshape(1), first, valid, blocks]
    # heads lead: a head's queries are one [T, nope + rope] block
    q = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)

    def rows_at(bi, hi, kb, layer_ref, wl_ref, first_ref, valid_ref,
                blocks_ref):
        last = jnp.maximum(blocks_ref[bi] - 1, 0)
        return layer_ref[0], bi, jnp.minimum(kb, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, h, n_kb),
        in_specs=[
            pl.BlockSpec((None, None, t, n + rope),
                         lambda bi, hi, kb, *_: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, bk, w), rows_at),
            pl.BlockSpec((None, r, n + vd),
                         lambda bi, hi, kb, layer_ref, wl_ref, *_:
                         (wl_ref[0], 0, hi)),
        ],
        out_specs=pl.BlockSpec((None, t, vd), lambda bi, hi, kb, *_:
                               (bi, 0, hi)),
        scratch_shapes=[
            pltpu.VMEM((t, 1), jnp.float32),              # running max
            pltpu.VMEM((t, 1), jnp.float32),              # running sum
            pltpu.VMEM((t, vd), jnp.float32),             # accumulator
            pltpu.VMEM((bk, n + vd), latent.dtype),       # the block's k, v
        ])
    interp = jax.default_backend() != "tpu" if interpret is None \
        else interpret
    kw = {} if interp else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)}
    out = pl.pallas_call(
        functools.partial(_kernel, r=r, rope=rope, n=n, tq=tq,
                          cr=_chunk_rows(tq), scale=float(scale)),
        name="dstpu_mla_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h * vd), q_nope.dtype),
        interpret=interp,
        **kw,
    )(*scalars, q, latent, wkv_b)
    return out.reshape(b, t, h, vd)
