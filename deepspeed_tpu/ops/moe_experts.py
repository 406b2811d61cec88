"""A decode step's routed experts: one Pallas call, ``dstpu_moe_experts_decode``.

A decode step hands a sparse layer one token a slot, ``N`` of them (16 to 64
in the cells), and every token ``k`` experts of the ``count`` held here: one
to three rows an expert. Against a matrix streamed from HBM a bf16 matmul of
that few rows is bound by the matrix's bytes (``N`` FLOP a byte of weight,
where the chip has :data:`FLOP_PER_BYTE` to give), so the rows ride free and
the step's slots ARE the row tile. The call

  * walks the experts that got at least one pair (``sizes > 0``; their list
    is made from ``sizes`` inside the call, on the scalar core: no sort of
    the step's ``N * k`` pairs, no sorted buffer, no gather back) and streams
    each one's matrices from HBM exactly once, a tile of ``block_m`` hidden
    units at a time, by DMAs of its own into two buffers: the tile behind is
    in flight while this one is multiplied. An expert no token chose is
    never read, and the walk's length is the touched experts' times the
    tiles: no grid step is spent on the others;
  * multiplies ALL ``N`` rows of ``x`` by every touched expert, gate, up,
    activation and down fused (``h = act(x Wg, x Wu)``, or ``act(x Wu)`` of a
    two-matrix expert, in float32; it never leaves VMEM and is rounded to
    the operands' dtype before the last matmul), and adds the result to a
    float32 accumulator ``[N, d]`` weighted by ``weights[n, e]``: the routing
    weight where token ``n`` chose ``e``, 0 elsewhere, and SELECTED there,
    not multiplied in (``0 * NaN`` is NaN: nothing of a row that did not
    choose the expert reaches it);
  * takes layer-stacked weights ``[L, count, ...]`` as they lie: ``layer``
    goes into the DMA's index, nothing of a layer's size is sliced or copied.

A step with no pair held does no DMA and returns zeros.

The sum over a token's experts runs in expert order and each expert's result
is weighed in float32 before any rounding; ``jax.lax.ragged_dot``'s route
(moe/grouped.held_experts) rounds an expert's result to the operands' dtype
first and sums in the order of a token's ``k``. The two differ by rounding
alone.

Serving only: no VJP.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what a v5e gives a byte it reads from HBM: 197 TFLOP/s over 819 GB/s. A
# row of x costs 2 FLOP a weight, one FLOP a byte of bf16, so a streamed
# matrix is bound by its bytes while the rows are fewer than this
FLOP_PER_BYTE = 240
# the rows up to which they ride free, in whole passes of the 128-row MXU
# (measured at the widest cell's 7,168 x 2,048 with every slot live: the
# call streams at 91.8% of the chip's bytes a second at 64 rows and at 128,
# where ragged_dot takes 2.4 times as long; PERF.md, PR 67)
FREE_ROWS = FLOP_PER_BYTE // LANES * LANES
# both buffers of every matrix's tile together (on the v5e tiles of 128
# hidden units stream 4% faster than tiles of 256 at 6,144 and 7,168 rows a
# matrix and tiles of 256 some 2% slower than 512 at 4,096: PERF.md, PR 67);
# the call's own VMEM limit, which leaves room for x, the accumulator and a
# tile's float32 results at the widest cell (64 rows of 7,168: 1.8 MB each)
# and at FREE_ROWS of them (test_tpu_compile.py compiles that case)
_TILE_BUFFERS = 12 * 1024 * 1024
_VMEM_LIMIT = 32 * 1024 * 1024


def supports(n: int, d: int, m: int) -> bool:
    """Shapes the call takes on the chip: rows that ride free, widths in
    whole rows of lanes."""
    return n <= FREE_ROWS and d % LANES == 0 and m % LANES == 0


def default_route(n: int, d: int, m: int) -> str:
    """How a decode step's expert layer runs where the caller names no
    route: ``"fused"``, this file's call, on a TPU at shapes it
    :func:`supports`; ``"grouped"``, ``jax.lax.ragged_dot`` over the sorted
    pairs (moe/grouped.held_experts), elsewhere."""
    return "fused" if jax.default_backend() == "tpu" and supports(n, d, m) \
        else "grouped"


def block_m(d: int, m: int, matrices: int, itemsize: int) -> int:
    """Hidden units a tile: the largest divisor of ``m`` in whole rows of
    lanes whose two buffers a matrix fit :data:`_TILE_BUFFERS` (896 at
    1,024 x 2,688 twice, 128 at 7,168 or 6,144 x 2,048 three times, 256 at
    4,096 x 2,048 and at 4,096 x 1,280); all of ``m`` where it has no such
    divisor (the tests' widths)."""
    fits = [t for t in range(LANES, m + 1, LANES) if m % t == 0
            and 2 * matrices * d * t * itemsize <= _TILE_BUFFERS]
    return max(fits, default=LANES if m % LANES == 0 else m)


def _kernel(layer_ref, sizes_ref, x_ref, w_ref, *refs, count: int, tm: int,
            tiles: int, mats: int, act: Callable):
    """``refs``: the ``mats`` matrices in HBM (gate, up, down; or up, down),
    the output, the touched experts' list (SMEM), a pair of tile buffers a
    matrix, the accumulator, the DMA semaphores ``[matrix, buffer]``."""
    hbm, (o_ref, ids), bufs = refs[:mats], refs[mats:mats + 2], \
        refs[mats + 2:2 * mats + 2]
    acc, sem = refs[2 * mats + 2:]
    layer = layer_ref[0]

    def note(e, n):
        # written wherever the walk stands, kept where the expert has a pair
        ids[n] = e
        return n + (sizes_ref[e] > 0).astype(jnp.int32)

    steps = jax.lax.fori_loop(0, count, note, 0) * tiles

    def expert_of(s):
        return ids[s if tiles == 1 else s // tiles]

    def copies(s, slot):
        e = expert_of(s)
        cols = pl.ds(0 if tiles == 1 else jax.lax.rem(s, tiles) * tm, tm)
        # a tile is columns of gate and up, rows of down
        return [pltpu.make_async_copy(
            w.at[layer, e, cols] if i == mats - 1 else w.at[layer, e, :, cols],
            buf.at[slot], sem.at[i, slot])
            for i, (w, buf) in enumerate(zip(hbm, bufs))]

    acc[...] = jnp.zeros_like(acc)

    @pl.when(steps > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def step(s, carry):
        slot = jax.lax.rem(s, 2)

        @pl.when(s + 1 < steps)
        def _():
            # into the buffer the step before this one multiplied
            for c in copies(s + 1, 1 - slot):
                c.start()

        for c in copies(s, slot):
            c.wait()
        x = x_ref[...]

        def dot(a, buf):
            return jnp.dot(a, buf[slot].astype(a.dtype),
                           preferred_element_type=jnp.float32)

        h = act(*(dot(x, buf) for buf in bufs[:-1]))
        y = dot(h.astype(x.dtype), bufs[-1])
        # the expert's column of the weights: SMEM scalars cannot gather
        w = w_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        we = jnp.sum(jnp.where(lane == expert_of(s), w, 0.0), axis=1,
                     keepdims=True)
        acc[...] += jnp.where(we != 0.0, we * y, 0.0)
        return carry

    jax.lax.fori_loop(0, steps, step, 0)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def experts_decode(x, weights, sizes, w_gate, w_up, w_down, layer=None, *,
                   act: Callable, interpret: Optional[bool] = None):
    """``sum over e of weights[:, e] * Expert_e(x)`` over the experts with
    ``sizes[e] > 0`` -> ``[N, d]`` in ``x``'s dtype.

    ``x [N, d]``; ``weights [N, count]`` float32, 0 where token ``n`` has no
    pair with ``e``; ``sizes [count]`` int32, the pairs an expert got (an
    expert with none is not read); ``w_gate, w_up [count, d, m]``, ``w_down
    [count, m, d]``, or each layer-stacked ``[L, count, ..]`` with ``layer``
    a scalar index. ``Expert_e(x) = act(x Wg_e, x Wu_e) Wd_e``, or ``act(x
    Wu_e) Wd_e`` where ``w_gate`` is ``None``; ``act`` takes and gives
    float32 ``[N, tile]``, elementwise, a tile of :func:`block_m` hidden
    units at a time."""
    n, d = x.shape
    hbm = [w for w in (w_gate, w_up, w_down) if w is not None]
    if w_up.ndim == 3:
        assert layer is None, "a layer index needs layer-stacked weights"
        hbm = [w[None] for w in hbm]
    count, m = hbm[0].shape[1], hbm[0].shape[3]
    assert all(w.shape[1:] == (count, d, m) for w in hbm[:-1]) \
        and hbm[-1].shape[1:] == (count, m, d), (x.shape, w_up.shape,
                                                 w_down.shape)
    assert weights.shape == (n, count) and sizes.shape == (count,), \
        (weights.shape, sizes.shape, count)
    tm = block_m(d, m, len(hbm), w_up.dtype.itemsize)
    # whole sublanes of the operands' dtype: a row more than the slots is
    # weighed 0
    rows = -(-n // (sub := 32 // x.dtype.itemsize)) * sub
    if rows > n:
        x = jnp.pad(x, ((0, rows - n), (0, 0)))
        weights = jnp.pad(weights, ((0, rows - n), (0, 0)))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, count=count, tm=tm, tiles=m // tm,
                          mats=len(hbm), act=act),
        name="dstpu_moe_experts_decode",
        in_specs=[smem, smem, vmem, vmem]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        scratch_shapes=[pltpu.SMEM((count,), jnp.int32)]
        + [pltpu.VMEM((2, d, tm), w.dtype) for w in hbm[:-1]]
        + [pltpu.VMEM((2, tm, d), hbm[-1].dtype),
           pltpu.VMEM((rows, d), jnp.float32),
           pltpu.SemaphoreType.DMA((len(hbm), 2))],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1),
      sizes.astype(jnp.int32), x, weights.astype(jnp.float32), *hbm)
    return out[:n]
