"""Fused single-token decode step of multi-head LATENT attention in its
absorbed form: the new token's row written into the latent cache and all
heads' attention over the slot's live rows, one Pallas invocation a layer.

A latent cache holds ONE row a token a layer that every head shares
(models/sarvam_mla.py): the normed key-value latent ``c~`` (``kv_lora_rank``
wide), the rotated key ``k_r`` behind it, zero lanes up to a whole number of
128 (a DMA's minor dimension must tile; 512 + 64 -> 640). With the
up-projection absorbed into the query and the output,

    score_h(j) = s (q^_h . c~(j) + q_rope_h . k_r(j)) = s (qcat_h . row(j))
    u_h = sum_j p_h(j) row(j)[:latent]

the row serves as the key (all of it: the zero lanes meet zero lanes of
``qcat``) and as the value (its first ``latent`` lanes), so a step reads each
cached row ONCE. It is ops/decode_step.py's per-slot walk with one key-value
head, ``rep`` = all query heads, and K and V the same buffer: the active slots
in ``slot_walk`` order, nothing of an inactive slot read or written, the new
token's row written in place through an 8-row window, the online softmax in
VMEM started from the new token. A loop step covers ``cs`` rows of each of a
group's ``bg`` slots, fetched in ``cs // 128`` DMAs of 128 rows a slot (a
row's tail rounds up to 128 whatever the step), and runs ``[heads, W] x [W,
cs]`` for the scores and ``[heads, cs] x [cs, W]`` for ``u`` on the MXU, a
slot at a time. ``(bg, cs)`` come from the cache's geometry
(:func:`_walk_plan`, with the measurements): 1,024 rows of ONE slot a step
where a slot can hold 16k rows, 256 rows of four where it holds 4k.

By bytes the step is memory-bound: a row of 1,152 live bytes (1,280 fetched)
meets 64 heads x 2 x (576 + 512) FLOPs, 121 FLOPs a byte against the v5e's
240. By the MXU it is nearer even: 64 heads fill half of a 128-row pass, so
a 128 x 128 tile of the rows is loaded for 64 rows of work, and a step's
chain (wait -> scores -> maximum -> exp -> weighted sum) leaves the MXU idle
under the softmax: one slot's 8k rows alone stream at 46% of 819 GB/s, two
slots' at 63%, sixteen at 74%. Serving-only: per-slot lengths, no VJP.
``generate()`` and a CPU take the einsum route over the same leaf
(models/sarvam_mla.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_step import (_NEG, _SCORE_TILE, _SLOT_CHUNK,
                                           SlotWalk, _compiler_params,
                                           count_walk, slot_walk)

# cache rows a loop step of the walk covers where a slot can hold thousands
_STEP_ROWS = 1024


def supports(s_max: int, width: int) -> bool:
    """Shapes the walk can stream: rows of whole 128-lane tiles, a row count
    of whole chunks."""
    return s_max % _SLOT_CHUNK == 0 and width % 128 == 0


def count_form(absorbed_step: bool) -> None:
    """Say in the program's registry which form a latent-attention layer was
    traced with: the fused absorbed step (this file's kernel), or a
    decompressed prompt block (models/sarvam_mla.py); both counters exist
    from the first call on. A one-token layer on the einsum route (a CPU,
    ``generate()``) counts as neither."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    step, block = (reg.counter("mla/traced_absorbed_step"),
                   reg.counter("mla/traced_decompressed_block"))
    (step if absorbed_step else block).inc()


def _walk_plan(b: int, s_max: int, hq: int):
    """(bg, cs) of the walk from the geometry the call is handed, by
    measurement on the v5e: ``cs`` cache rows of each of ``bg`` slots of like
    length a loop step, in DMAs of ``_SLOT_CHUNK`` rows under every plan
    (``decode_rows_fetched`` counts what it counted).

    One layer's call alone, us (PERF.md, PR 60: 200 calls in one jitted loop
    over the donated cache, median of five, live slots scattered among stale
    lengths, each plan wrapped anew; ``bg x cs``; ``(b, s_max, w, hq)``):

    =========================  =====  =====  =====  =====  =====  =====  =====  =====  =====  ======  ======
    geometry, slots live       4x128  2x128  1x128  2x256  1x256  4x256  2x512  1x512  4x512  1x1024  2x1024
    =========================  =====  =====  =====  =====  =====  =====  =====  =====  =====  ======  ======
    Sarvam, 1 at 8k               84     58     64     44     41     63     36     31     55      27      33
      (16, 16384, 640, 64)
    the same, 2 at 6k, 14k       137     95    143     69     87    101     59     61     87      50      55
    the same, 3 at 4k-15k        147    127    198     92    118    107     76     80     94      67      72
    the same, 6 at 2k-15k        192    194    352    140    207    143    116    138    130     115     110
    the same, 16 at 2k-15k       374    441    882    320    514    284    260    338    263     275     249
    8k rows, 4 at 1.5k-7k         78                                 62     51     57             50
      (32, 8192, 640, 64)
    the same, 12 at 0.5k-7.5k    155                                122    114    144            123
    LongCat, 9 at 0.2k-0.9k       35     36     58     32     44     32     32     39     35      39      34
      (32, 4096, 640, 64)
    the same, 32 at 0.2k-2k      117    146    288    115    189     98    101    142     94     130     101
    GigaChat, 22 at 0.2k-0.9k     62     73    130     63     97     56     59     81     57      81      64
      (64, 4096, 640, 64)
    =========================  =====  =====  =====  =====  =====  =====  =====  =====  =====  ======  ======

    (``1x2048`` reads as ``1x1024`` at 16k rows, 27 / 48 / 65 / 110 / 256,
    and loses at 4k, 48 / 137 / 104.) A step of 128 rows of one slot costs
    1.0 us where its bytes take 0.2, and a step of 1,024 costs 3.4 (0.42 a
    128 rows), so the walk wants long steps; and the latent step is NOT the
    GQA walk of the same bytes (``decode_step._slot_plan``, which would hand
    all three ``(1, 512)`` and lose 10 and 30% at the two geometries of
    4,096 rows): 64 heads against 640 lanes is a chain long enough that two
    or four slots' independent chains in one step fill each other's gaps
    (``1x128`` 882 us, ``4x128`` 374 with sixteen live), while a masked
    companion costs a fifth of a step (``2x1024`` 33 for 27 with one live).
    So a loop step covers ``_STEP_ROWS`` rows in all, as many as keep the
    float32 scores ``[bg, hq, cs]`` at twice ``_SCORE_TILE`` at 64 heads, and
    a sixteenth of what a slot can hold from each slot: ``(1, 1024)`` where a
    slot holds 16k rows (a few long slots live: nothing masked is computed),
    ``(2, 512)`` at 8k, ``(4, 256)`` at 4k (many short slots: company, and a
    short tail). Under 4,096 rows a slot nothing was measured and the plan
    is what it was, four slots x 128 rows. ``bg`` divides ``b``.

    The weighted sum contracted into the ``value_width`` lanes alone (a
    ``[bg, hq, 512]`` accumulator; all 640 lanes are accumulated and 128
    dropped at the end) was read in the same runs at ``1x1024``: 26.2 / 49.6
    / 66.2 / 112.3 / 269.8 for 27.4 / 49.8 / 66.8 / 114.8 / 275.2, 0.4% at
    two slots live and 2% at most past one: under ISSUE 60's 3%, not kept."""
    if s_max < 32 * _SLOT_CHUNK:
        step, cs = 4 * _SLOT_CHUNK, _SLOT_CHUNK
    else:
        step = _STEP_ROWS
        while step > _SLOT_CHUNK and step * hq * 4 > 2 * _SCORE_TILE:
            step //= 2
        cs = step
        while cs > _SLOT_CHUNK and 16 * cs > s_max:
            cs //= 2
    return next(g for g in (4, 2, 1) if g * cs <= step and b % g == 0), cs


def _attend(qv, rows, valid, m_ref, l_ref, acc_ref, scale: float):
    """One chunk of the online softmax: ``qv [bg, H, W]``, ``rows [bg, CS,
    W]`` (key and value at once), ``valid [bg, H, CS]`` bool. bf16 products,
    float32 accumulation, on the MXU."""
    s = jax.lax.dot_general(qv, rows, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, _NEG)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :, None])
    l_ref[...] = l_ref[...] * corr + p.sum(-1)
    pv = jax.lax.dot_general(p.astype(rows.dtype), rows,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, :, None] + pv
    m_ref[...] = m_new


def _kernel(layer_ref, idx_ref, order_ref, n_ref, q_ref, new_ref, _in_ref,
            out_ref, cache_ref, buf, win, qrow, nrow, m_ref, l_ref, acc_ref,
            wsem, rsem, *, b: int, bg: int, cs: int, hq: int, w: int,
            wv: int, scale: float):
    """ops/decode_step._slot_kernel over one leaf: ``idx_ref [B]`` each
    slot's length, ``order_ref [B]`` the slots with the active ones first by
    descending length, ``n_ref [1]`` how many are active. Everything goes by
    sorted position ``p`` (row ``j`` of group ``g`` is slot ``order[g * bg +
    j]``); a loop step covers a chunk of ``cs`` rows, fetched in ``cs //
    _SLOT_CHUNK`` DMAs of ``_SLOT_CHUNK`` rows, each with a semaphore of its
    own into its rows of the buffer, started and waited on only while the row
    has cache rows left for THAT part, so what a row fetches does not depend
    on ``cs``; a group walks to its first row's chunk count; the next
    group's first chunk is prefetched under the last chunk of this one. The
    chunk buffer is zeroed on entry: a row, or a part of one, whose DMA was
    skipped is multiplied by a probability of zero, and ``0 * NaN`` is
    NaN."""
    layer = layer_ref[0]
    n_act = n_ref[0]
    dma = _SLOT_CHUNK         # rows a DMA: what a row's tail rounds up to
    parts = cs // dma         # DMAs a row a chunk

    def slot_at(p):
        return order_ref[jnp.minimum(p, b - 1)]

    def nch_at(p, rows=cs):
        """Chunks of cache rows sorted position ``p`` walks (``rows=dma``:
        the DMAs it starts)."""
        return jnp.where(p < n_act,
                         (idx_ref[slot_at(p)] + rows - 1) // rows, 0)

    # ---- the active slots' new row into the cache: an 8-row window read,
    # one row replaced, written back; async under the walk
    def win_copy(p, back: bool):
        s = slot_at(p)
        w0 = (idx_ref[s] // 8) * 8
        hbm = cache_ref.at[layer, pl.ds(s, 1), pl.ds(w0, 8), :]
        here = win.at[pl.ds(p, 1), :, :]
        return pltpu.make_async_copy(here, hbm, wsem.at[p]) if back \
            else pltpu.make_async_copy(hbm, here, wsem.at[p])

    def each_active(fn):
        def step(p, _):
            fn(p)
            return 0
        jax.lax.fori_loop(0, n_act, step, 0)

    def insert_token(p):
        win_copy(p, False).wait()
        s = slot_at(p)
        sel = (jax.lax.broadcasted_iota(jnp.int32, (1, 8, w), 1)
               == jax.lax.rem(idx_ref[s], 8))
        win[pl.ds(p, 1)] = jnp.where(sel, new_ref[pl.ds(s, 1)],
                                     win[pl.ds(p, 1)])
        win_copy(p, True).start()

    # ---- the walk
    def dma_at(c, u):
        """Part ``u`` of chunk ``c`` among a row's DMAs."""
        return c * parts + u

    def chunk_copy(p, j, u, c, slot):
        return pltpu.make_async_copy(
            cache_ref.at[layer, pl.ds(slot_at(p), 1),
                         pl.ds(dma_at(c, u) * dma, dma), :],
            # into the part's own rows of the buffer
            buf.at[slot, pl.ds(j, 1), pl.ds(u * dma, dma), :],
            rsem.at[slot, j * parts + u])

    def each_row(g, c, fn):
        """``fn(p, j, u)`` for the rows of group ``g`` that hold chunk ``c``
        and the parts of it that they hold."""
        for j in range(bg):
            p = g * bg + j
            held = nch_at(p, dma)
            for u in range(parts):
                @pl.when(dma_at(c, u) < held)
                def _():
                    fn(p, j, u)

    def start_chunk(g, c, slot):
        each_row(g, c, lambda *r: chunk_copy(*r, c, slot).start())

    out_ref[...] = jnp.zeros_like(out_ref)
    buf[...] = jnp.zeros_like(buf)
    each_active(lambda p: win_copy(p, False).start())
    start_chunk(0, 0, 0)
    each_active(insert_token)    # overlaps with chunk 0's flight

    def group(g, t):
        nch_g = nch_at(g * bg)
        lens = jnp.zeros((bg, hq, cs), jnp.int32)
        rows = jax.lax.broadcasted_iota(jnp.int32, lens.shape, 0)
        for j in range(bg):      # SMEM scalars cannot gather: bg selects
            p = g * bg + j
            s = slot_at(p)
            lens = jnp.where(rows == j,
                             jnp.where(p < n_act, idx_ref[s], 0), lens)
            qrow[pl.ds(j, 1)] = q_ref[pl.ds(s, 1)]
            nrow[pl.ds(j, 1)] = new_ref[pl.ds(s, 1)]
        qv = qrow[...]                                   # [bg, H, W]
        attend = functools.partial(_attend, qv, m_ref=m_ref, l_ref=l_ref,
                                   acc_ref=acc_ref, scale=scale)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the new token first: one live position of an 8-row chunk
        attend(jnp.broadcast_to(nrow[...], (bg, 8, w)),
               jax.lax.broadcasted_iota(jnp.int32, (bg, hq, 8), 2) < 1)
        pos = jax.lax.broadcasted_iota(jnp.int32, lens.shape, 2)

        def body(c, t):
            slot = jax.lax.rem(t, 2)
            more = c + 1 < nch_g
            start_chunk(jnp.where(more, g, g + 1),
                        jnp.where(more, c + 1, 0), 1 - slot)
            each_row(g, c, lambda *r: chunk_copy(*r, c, slot).wait())
            attend(buf[slot], c * cs + pos < lens)
            return t + 1

        t = jax.lax.fori_loop(0, nch_g, body, t)
        out = (acc_ref[...] / l_ref[...][:, :, None])[:, :, :wv].astype(
            out_ref.dtype)
        for j in range(bg):      # back in slot order
            p = g * bg + j

            @pl.when(p < n_act)
            def _():
                out_ref[pl.ds(slot_at(p), 1)] = out[j:j + 1]
        return t

    jax.lax.fori_loop(0, (n_act + bg - 1) // bg, group, 0)
    each_active(lambda p: win_copy(p, True).wait())   # before the kernel exits


def fused_mla_decode_step(q: jax.Array, latent: jax.Array, new_row: jax.Array,
                          layer, idx, *, value_width: int, scale: float,
                          active=None, interpret: Optional[bool] = None,
                          bg: Optional[int] = None, cs: Optional[int] = None):
    """One absorbed decode layer-step against the FULL stacked latent cache.

    q:        ``[B, H, W]``: a head's absorbed query ``q^_h`` (``latent``
              wide), its rotated ``q_rope_h`` behind it, zeros up to ``W``
    latent:   ``[L, B, S, W]`` the stacked cache (carry)
    new_row:  ``[B, W]`` the new token's row, not yet written
    layer:    scalar int32
    idx:      ``[B]`` int32 per-slot lengths: an active slot writes at and
              attends over its own prefix, and fetches that prefix only
    active:   which slots decode this step: a ``[B]`` mask, or the
              ``SlotWalk`` made of it once a step
              (ops/decode_step.slot_walk); ``None``: every slot
    value_width: the leading lanes of a row that are its value (``latent``)
    bg, cs:   the walk's plan, for the tests and the table; left out, the
              cache's geometry decides (:func:`_walk_plan`)

    Returns ``(u [B, H, value_width], latent)``, the cache updated in place
    (the returned cache aliases the input). An inactive slot's rows are
    neither read nor written and its ``u`` is zero."""
    b, hq, w = q.shape
    l, _, s_max, _ = latent.shape
    assert latent.shape == (l, b, s_max, w) and supports(s_max, w), \
        (q.shape, latent.shape)
    assert value_width % 128 == 0 and value_width <= w, value_width
    idx_a = jnp.asarray(idx, jnp.int32).reshape(-1)
    assert idx_a.shape[0] == b, (idx_a.shape, b)
    walk = active if isinstance(active, SlotWalk) \
        else slot_walk(idx_a, active)
    plan = _walk_plan(b, s_max, hq)
    bg, cs = (plan[0] if bg is None else bg), (plan[1] if cs is None else cs)
    assert cs % _SLOT_CHUNK == 0, cs
    count_walk(cs > _SLOT_CHUNK, "mla")
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1), idx_a, walk.order,
               walk.n_active]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_kernel, b=b, bg=bg, cs=cs, hq=hq, w=w,
                               wv=value_width, scale=float(scale))
    n_scalar = len(scalars)
    u, out = pl.pallas_call(
        kernel,
        name="dstpu_mla_decode_step",
        in_specs=[smem] * n_scalar + [vmem, vmem, hbm],
        out_specs=[vmem, hbm],
        out_shape=[jax.ShapeDtypeStruct((b, hq, value_width), q.dtype),
                   jax.ShapeDtypeStruct(latent.shape, latent.dtype)],
        scratch_shapes=[
            pltpu.VMEM((2, bg, cs, w), latent.dtype),     # chunk buffers
            pltpu.VMEM((b, 8, w), latent.dtype),          # write windows
            pltpu.VMEM((bg, hq, w), q.dtype),             # a group's queries
            pltpu.VMEM((bg, 1, w), latent.dtype),         # and new rows
            pltpu.VMEM((bg, hq), jnp.float32),            # running max
            pltpu.VMEM((bg, hq), jnp.float32),            # running sum
            pltpu.VMEM((bg, hq, w), jnp.float32),         # accumulator
            pltpu.SemaphoreType.DMA((b,)),
            pltpu.SemaphoreType.DMA((2, bg * (cs // _SLOT_CHUNK))),
        ],
        input_output_aliases={n_scalar + 2: 1},
        compiler_params=_compiler_params(),
        interpret=(jax.default_backend() != "tpu" if interpret is None
                   else interpret),
    )(*scalars, q, new_row.astype(latent.dtype)[:, None], latent)
    return u, out
