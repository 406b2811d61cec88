"""Int8 weight-streaming matmul kernel for memory-bound decode.

Reference counterpart: the dequant-fused int8 GEMV path in
``csrc/transformer/inference`` (pt_binding.cpp vector_matmul + the
dequantization kernels in dequantize.cu) — the reference streams int8
weights through a fused dequant+GEMV so HBM traffic stays 1 byte/weight.

Why a Pallas kernel: XLA will not reliably fuse an ``int8 -> bf16``
convert into a dot operand — measured at GPT-2-125M decode, the
``qdot`` einsum path (convert materialized per layer) made int8 SLOWER
than bf16 (0.53 vs 0.43 ms/tok) because each weight pays int8-read +
bf16-write + bf16-read. Here the int8 tile is DMA'd into VMEM as int8
(1 byte/weight of HBM traffic — the whole point of weight-only
quantization) and upcast in-register on its way into the MXU; the
per-output-column scale multiplies the f32 accumulator once at the end.

Decode shapes: activations are tiny ([B<=16, D]); weights dominate.
The grid walks (E tiles x D tiles) with D innermost so each output tile
accumulates across the contraction in VMEM scratch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Fat tiles: decode matmuls are weight-streaming-bound and the per-grid-cell
# overhead is what erased the int8 bandwidth win in the first cut (~430
# cells/step at 125M measured ≈ bf16). Blocks are picked as the LARGEST
# divisors of (E, D) under a VMEM byte budget — at 125M every block matmul
# becomes 1 grid cell ([768, 2304] int8 = 1.7 MB); at 6.7B shapes ~2-8
# cells. The Pallas pipeline double-buffers every block, so an N-byte
# int8 tile costs 2N of VMEM before the f32 accumulator and activation
# blocks — budget 4 MB to stay under the ~16 MB/core VMEM.
MAX_TILE_BYTES = 4 * 1024 * 1024
MAX_BLOCK_E = 8192


def _kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, nd: int, out_dtype):
    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 tile upcasts in-register to the ACTIVATION dtype (an fp32-
    # compute serving config must not silently mix f32 x bf16 operands):
    # HBM saw 1 byte/weight either way
    w = q_ref[...].astype(x_ref.dtype)               # [BD, BE]
    x = x_ref[...]                                   # [B, BD]
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * s_ref[...].astype(jnp.float32)) \
            .astype(out_dtype)


def _divisor_block(n: int, quantum: int, cap: int) -> int:
    """Largest divisor of ``n`` that is a multiple of ``quantum`` and
    <= cap; falls back to halving ``cap`` when no such divisor exists
    (then requiring only divisibility of n)."""
    best = 0
    m = 1
    while quantum * m <= min(n, cap):
        if n % (quantum * m) == 0:
            best = quantum * m
        m += 1
    if best:
        return best
    blk = min(n, cap)
    while n % blk:
        blk //= 2
    return max(blk, 1)


def plan_blocks(d: int, e: int):
    """(bd, be, grid_cells) for a [D, E] weight. Callers (models/base.qdot)
    only route through the kernel when the plan is a FEW fat cells:
    per-grid-cell overhead measured ~2 us, which erases the int8 bandwidth
    win once divisor-hostile dims shatter the grid (LLaMA's 11008 = 2^8*43
    yields 256-wide blocks -> ~2000 cells/step at 6.7B, a net regression
    vs the einsum). The manual-DMA whole-matmul kernel
    (:func:`int8_matmul_dma`) removes the per-cell cost and is what
    production routes through now."""
    be = _divisor_block(e, 128, MAX_BLOCK_E)
    bd = _divisor_block(d, 128, max(MAX_TILE_BYTES // be, 512))
    return bd, be, (d // bd) * (e // be)


def _aligned_divisors(n):
    return [m for m in range(128, n + 1, 128) if n % m == 0]


def _hand_dma_plan(d: int, e: int, cap: int = 2_500_000):
    """Hand-picked (bd, be) divisor tiles for the manual-DMA kernel.
    Offsets/extents must align to the HBM tiling (128 on both edges
    here: the bf16 activation slice shares bd), but tiles only need to
    DIVIDE the dims — not be powers of two — so divisor-hostile dims
    still tile fat (11008 = 2^7*86). DMA throughput is set by the ROW
    length (a [bd, be] tile is bd strided rows of be bytes; be == E is
    one contiguous block — measured 8x the bandwidth of 256-byte rows),
    so maximize be FIRST, then bd under the VMEM cap."""
    best = None
    for be in _aligned_divisors(e):
        for bd in _aligned_divisors(d):
            if bd * be > cap:
                continue
            key = (be, bd)  # row length dominates; then tile size
            if best is None or key > best[0]:
                best = (key, bd, be)
    if best is None:
        # no 128-aligned divisor tiling under the cap (e.g. a dim that is
        # not a multiple of 128): callers fall back to the einsum path
        return None
    return best[1], best[2]


def _dma_plan(d: int, e: int, cap: int = 2_500_000):
    """(bd, be) tiles: the MEASURED artifact entry (ops/autotune.py,
    ISSUE 12 satellite) when one exists for this backend+shape and
    validates (128-aligned divisors of the live dims), else the
    hand-picked :func:`_hand_dma_plan`. An entry may carry either
    explicit ``bd``/``be`` tiles or just a re-tuned VMEM ``cap``."""
    from deepspeed_tpu.ops import autotune

    ent = autotune.lookup("int8_matmul_dma", autotune.matmul_key(d, e))
    if ent:
        try:
            if "bd" in ent and "be" in ent:
                bd, be = int(ent["bd"]), int(ent["be"])
                if (bd in _aligned_divisors(d)
                        and be in _aligned_divisors(e)):
                    return bd, be
            elif "cap" in ent:
                plan = _hand_dma_plan(d, e, int(ent["cap"]))
                if plan is not None:
                    return plan
        except Exception:
            pass
    return _hand_dma_plan(d, e, cap)


def _dma_kernel(layer_ref, x_ref, s_ref, w_any, o_ref, wbuf, acc_ref, sem,
                *, b, d, e, bd, be, out_dtype, stacked):
    """One invocation covers the whole [B, D] @ [D, E] int8 matmul:
    static-unrolled walk over (e-tile, d-tile) with double-buffered
    manual DMA of int8 weight tiles — no per-grid-cell dispatch cost
    (the gridded kernel's ~2 us/cell erased the int8 bandwidth win on
    divisor-hostile shapes; VERDICT r4 #2). With ``stacked``, the weight
    operand is the FULL [L, D, E] tensor and ``layer_ref`` picks the
    layer inside the DMA index: a host-side slice of an int8 custom-call
    operand materializes a full per-step copy of the weight (measured as
    round 4's '66% of streaming bound' int8 ceiling at 6.7B)."""
    nd, ne = d // bd, e // be
    order = [(ei, di) for ei in range(ne) for di in range(nd)]
    layer = layer_ref[0]

    def dma(slot, t):
        ei, di = order[t]
        src = w_any.at[layer] if stacked else w_any
        return pltpu.make_async_copy(
            src.at[pl.ds(di * bd, bd), pl.ds(ei * be, be)],
            wbuf.at[slot], sem.at[slot])

    scales = s_ref[layer] if stacked else s_ref[0]      # [E] f32
    dma(0, 0).start()
    for t, (ei, di) in enumerate(order):
        slot = t % 2
        if t + 1 < len(order):
            dma(1 - slot, t + 1).start()
        dma(slot, t).wait()
        if di == 0:
            acc_ref[...] = jnp.zeros_like(acc_ref)
        w = wbuf[slot].astype(x_ref.dtype)        # int8 -> x dtype in-register
        xs = x_ref[:, pl.ds(di * bd, bd)]
        acc_ref[...] += jax.lax.dot_general(
            xs, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if di == nd - 1:
            o_ref[:, pl.ds(ei * be, be)] = (
                acc_ref[...] * scales[None, ei * be:(ei + 1) * be].astype(
                    jnp.float32)).astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "plan"))
def int8_matmul_dma(x: jax.Array, q: jax.Array, s: jax.Array,
                    layer=None, interpret: Optional[bool] = None,
                    plan: Optional[tuple] = None) -> jax.Array:
    """``(x [B, D]) @ (q [D, E] int8) * (s [..., E] f32) -> [B, E]`` as ONE
    Pallas invocation with manually-driven DMA over divisor tiles.

    ``q`` may be the FULL layer-stacked ``[L, D, E]`` tensor with
    ``layer`` a scalar index (``s`` then ``[L, 1, E]``): the kernel
    DMA-slices the layer itself, which keeps the scan body free of
    host-side int8 slices (XLA materializes a sliced custom-call operand
    as a full copy — 1.5x the weight traffic per decode step, measured
    at 6.7B).

    Reference counterpart: the fused dequant GEMM/GEMV paths in
    ``csrc/transformer/inference`` (dequantize.cu:230 + the int8 paths in
    pt_binding.cpp:1747-1806) — HBM sees 1 byte/weight, the upcast rides
    the register file. Requires D % 128 == 0 and E % 128 == 0 (int8 HBM
    tile + bf16 activation-slice alignment); ``qdot`` falls back to the
    einsum otherwise. ``plan`` (static ``(bd, be)`` tuple) overrides the
    tile plan — the autotune micro-bench harness's candidate; production
    callers leave it None and get the measured-artifact-or-hand-picked
    resolution of ``_dma_plan``.
    """
    b, d = x.shape
    stacked = q.ndim == 3
    if stacked:
        nl, d2, e = q.shape
        assert layer is not None, "stacked int8_matmul_dma needs layer"
    else:
        d2, e = q.shape
        nl = 1
    assert d == d2, (x.shape, q.shape)
    if plan is None:
        plan = _dma_plan(d, e)
    assert plan is not None, (d, e)
    bd, be = plan
    assert d % bd == 0 and e % be == 0, (plan, d, e)
    s = s.reshape(nl, e)
    layer_a = jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_dma_kernel, b=b, d=d, e=e, bd=bd, be=be,
                               out_dtype=x.dtype, stacked=stacked)
    return pl.pallas_call(
        kernel,
        name="dstpu_int8_matmul_dma",
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # layer
            pl.BlockSpec(memory_space=pltpu.VMEM),   # x
            pl.BlockSpec(memory_space=pltpu.VMEM),   # scales
            pl.BlockSpec(memory_space=pl.ANY),       # int8 weights (HBM)
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, e), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bd, be), jnp.int8),       # weight tile slots
            pltpu.VMEM((b, be), jnp.float32),        # accumulator
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(layer_a, x, s.astype(jnp.float32), q.astype(jnp.int8))


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x: jax.Array, q: jax.Array, s: jax.Array,
                interpret: Optional[bool] = None) -> jax.Array:
    """``(x [B, D] bf16) @ (q [D, E] int8) * (s [..., E] f32) -> [B, E]``.

    The GRIDDED variant — superseded in production by
    :func:`int8_matmul_dma` (qdot routes there; this one pays ~2 us per
    grid cell). Kept as the pipeline-managed formulation for comparison
    benchmarks and interpret-mode coverage.

    ``s`` may carry leading unit dims (the engine stores per-layer scales
    as [1, E]); it is flattened to [E].
    """
    b, d = x.shape
    d2, e = q.shape
    assert d == d2, (x.shape, q.shape)
    s = s.reshape(e)
    # bd is BOTH x's last dim block (must be 128-divisible) and the weight
    # block's sublane dim — plan_blocks uses quantum 128 for either
    bd, be, _cells = plan_blocks(d, e)
    nd, ne = d // bd, e // be
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kernel = functools.partial(_kernel, nd=nd, out_dtype=x.dtype)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    return pl.pallas_call(
        kernel,
        name="dstpu_int8_matmul",
        grid=(ne, nd),
        in_specs=[
            pl.BlockSpec((b, bd), lambda ei, di: (0, di)),
            pl.BlockSpec((bd, be), lambda ei, di: (di, ei)),
            pl.BlockSpec((1, be), lambda ei, di: (0, ei)),
        ],
        out_specs=pl.BlockSpec((b, be), lambda ei, di: (0, ei)),
        out_shape=jax.ShapeDtypeStruct((b, e), x.dtype),
        scratch_shapes=[pltpu.VMEM((b, be), jnp.float32)],
        interpret=interpret,
        **kw,
    )(x, q.astype(jnp.int8), s.reshape(1, e))
