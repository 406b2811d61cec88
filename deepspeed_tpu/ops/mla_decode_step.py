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
in ``slot_walk`` order, a DMA a row a chunk of 128, nothing of an inactive
slot read or written, the new token's row written in place through an 8-row
window, the online softmax in VMEM started from the new token. Per chunk a
group of ``bg`` slots runs ``[heads, W] x [W, 128]`` for the scores and
``[heads, 128] x [128, W]`` for ``u`` on the MXU.

By bytes the step is memory-bound: a row of 1,152 live bytes (1,280 fetched)
meets 64 heads x 2 x (576 + 512) FLOPs, 121 FLOPs a byte against the v5e's
240. Serving-only: per-slot lengths, no VJP. ``generate()`` and a CPU take
the einsum route over the same leaf (models/sarvam_mla.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_step import (_NEG, _SLOT_CHUNK, SlotWalk,
                                           _compiler_params, slot_walk)


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
    j]``); a row's chunk DMA is started and waited on only while the row has
    cache rows left; a group walks to its first row's chunk count; the next
    group's first chunk is prefetched under the last chunk of this one. The
    chunk buffer is zeroed on entry: a row whose DMA was skipped is
    multiplied by a probability of zero, and ``0 * NaN`` is NaN."""
    layer = layer_ref[0]
    n_act = n_ref[0]

    def slot_at(p):
        return order_ref[jnp.minimum(p, b - 1)]

    def nch_at(p):
        return jnp.where(p < n_act, (idx_ref[slot_at(p)] + cs - 1) // cs, 0)

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
    def chunk_copy(p, j, c, slot):
        return pltpu.make_async_copy(
            cache_ref.at[layer, pl.ds(slot_at(p), 1), pl.ds(c * cs, cs), :],
            buf.at[slot, pl.ds(j, 1), :, :], rsem.at[slot, j])

    def each_row(g, c, fn):
        for j in range(bg):
            p = g * bg + j

            @pl.when(c < nch_at(p))
            def _():
                fn(p, j)

    def start_chunk(g, c, slot):
        each_row(g, c, lambda p, j: chunk_copy(p, j, c, slot).start())

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
            each_row(g, c, lambda p, j: chunk_copy(p, j, c, slot).wait())
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
                          bg: Optional[int] = None):
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
    cs = _SLOT_CHUNK
    if bg is None:
        bg = next(g for g in (4, 2, 1) if b % g == 0)
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
            pltpu.SemaphoreType.DMA((2, bg)),
        ],
        input_output_aliases={n_scalar + 2: 1},
        compiler_params=_compiler_params(),
        interpret=(jax.default_backend() != "tpu" if interpret is None
                   else interpret),
    )(*scalars, q, new_row.astype(latent.dtype)[:, None], latent)
    return u, out
