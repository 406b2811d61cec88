"""Fused single-token decode step: KV-cache write + attention, one Pallas
invocation per layer, manual double-buffered DMA over the full stacked cache.

Reference counterpart: ``softmax_context`` + the inference_context.h KV
workspace (csrc/transformer/inference/includes/inference_context.h:287 —
the reference's workspace exists precisely to CONTROL the KV layout that
its fused decode kernels stream). Here the same control is exercised
through Pallas: because every access to the decode loop's cache carry is
a Pallas op (this kernel owns both the write and the read), XLA's layout
assignment keeps the carry in the default row-major [L, B, H, S, Dh]
order — each (layer, batch, head) panel's [S, Dh] block contiguous in
HBM — instead of the einsum-oriented ``{4,2,1,3,0}`` layout it picks when
a ``dynamic_update_slice`` write anchors the carry (measured round 4:
that layout S-strides cache reads by 12 KB and capped batch-8 decode at
2.6x batch-1 vs a ~5x streaming roofline).

Why manual DMA instead of a gridded ``pallas_call``: the gridded decode
kernels measured ~2 us of per-grid-cell overhead, which at 125M shapes
(40 cells/layer) cost 5x more than the cache streaming itself. Here the
whole layer-step is ONE invocation: a dynamic ``fori_loop`` walks the
VALID prefix of the cache in token chunks, double-buffered so the VPU/MXU
math overlaps the next chunk's fetch, with the online-softmax state in
VMEM scratch. Two walks, chosen by the shape of ``idx``:

* a scalar ``idx`` (generate(): every row live and equally long) takes the
  uniform walk, ``_kernel``: one strided DMA a chunk covers all rows of a
  batch group;
* a per-slot ``[B]`` vector (continuous batching) takes ``_slot_kernel``,
  which fetches LIVE rows only. The decode program hands it the slots in
  walk order (``slot_walk``: the active ones by descending length); each
  row of a group of like length has its own DMA a chunk, started only
  while that row has cache rows left, and a slot that is not decoding is
  neither read nor written, whatever length it still carries. Until PR 33
  a group of eight NEIGHBOURING slots walked to its longest length, stale
  lengths of freed slots included, and three quarters of what the kernel
  fetched at the chip's peak was dead (PERF.md, PR 33). How many rows a
  loop step covers and how many slots share it comes from the cache's
  geometry (``_slot_plan``): a chunk of 128 rows at 1,024 or 2,048 rows a
  slot; where a slot holds thousands, a chunk of 512 fetched in four DMAs
  of 128 (a row's tail still rounds up to 128) and a slot a group, because
  a loop step costs what its bytes do not explain (PERF.md, PR 58).

Head-dim handling: Mosaic requires DMA slices of the minor dim to be
128-aligned, so for Dh < 128 the cache is VIEWED as token-pairs
``[L, B, Hkv, S/pair, Dh*pair]`` (a free bitcast of the row-major
buffer; ``pair = 128 // Dh``). Packed sub-tokens are never interleaved
back: each of the ``pair`` lane slices keeps its own position mask and
feeds the shared online-softmax state. The new token's write is a
read-modify-write of the 8-aligned pair-row window (HBM tiling forbids
single-row writes), a ~100 KB round-trip per layer step (per ACTIVE slot
in the per-slot walk).

Two widths and a sink (the per-slot walks; MiMo-V2's layers): keys and
values may differ in their last dimension. The two leaves then have chunk
buffers, write windows and new rows of their own width (``dh`` and ``dv``),
the scores contract over ``dh``, the accumulator and the result are ``dv``
wide, and nothing else of a walk changes: the DMAs, the masks and the order
are the one-width walk's. A key row must still be whole 128-lane tiles (the
HBM tiling pads a 192-wide row to 256 lanes whatever the leaf's shape says,
and Mosaic refuses a DMA slice of 192), so such a model caches its keys in
rows of ``ops/attention.key_row_width`` lanes with zeros behind the live
ones and hands queries over padded alike: the dot product over the padding
adds nothing, the fetch of it is the layout's cost (a quarter of a key row at
192). A ``sink`` (one learned logit a query head, in the softmax's
denominator, with no value) is the running softmax's FIRST STATE: maximum
``sink_h``, sum 1, accumulator 0, where a walk without one starts from
``-inf``, 0, 0. It costs no pass, no mask and no column. For one width and
no sink both walks trace to the kernels they traced to before
(tests/unit/ops/test_tpu_compile.py holds their digests).

MHA (rep == 1) scores/PV run as VPU broadcast-multiply + reduce;
GQA (rep > 1) runs batched MXU ``dot_general`` ([rep, Dh] x [Dh, CS]
slabs per kv head). Serving-only: no VJP (training uses
ops/flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = float("-inf")

# per-slot chunk budget: 4 chunk buffers live (2 slots x {K, V}) plus the
# compute temporaries of one chunk. The kernel raises Mosaic's scoped
# VMEM limit (vmem_limit_bytes below) past the 16 MB default, so the
# budget targets covering all of B in ONE batch group (one DMA warmup
# stall per layer instead of B/bg).
_CHUNK_BUDGET = 3_300_000
_VMEM_LIMIT = 40 * 1024 * 1024
# per-slot walk: all four chunk buffers of a group's rows together, and
# the tokens a row's last fetch rounds up to
_SLOT_BUFFERS = 12 * 1024 * 1024
_SLOT_CHUNK = 128
# the float32 scores of one loop step of the per-slot walk: half of the
# 64 vector registers of 4 KB
_SCORE_TILE = 128 * 1024


def _compiler_params(vmem_bytes: int = _VMEM_LIMIT):
    return pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes)


def supports(hq: int, hkv: int, s_max: int, dh: int,
             dv: Optional[int] = None) -> bool:
    """Shapes the fused kernel can stream: minor dim must tile to 128
    (dh a multiple of 128, or dh*pair == 128 with s_max % pair == 0).
    ``dv``: the value rows' width where it is not the keys' (the per-slot
    walks only): both rows as cached of whole 128-lane tiles (a key of 192
    live lanes is cached in a row of 256: ops/attention.key_row_width)."""
    if hq % hkv:
        return False
    if dv is not None and dv != dh:
        return dv % 128 == 0 and dh % 128 == 0 and s_max % 128 == 0
    if dh >= 128:
        return dh % 128 == 0 and s_max % 128 == 0
    # s_max % 128 == 0 implies s_max % (128 // dh) == 0 for any dh | 128
    return 128 % dh == 0 and s_max % 128 == 0


def _plan(b: int, hkv: int, s_max: int, dh: int, itemsize: int):
    """(bg, cs): batch-group and S-chunk (token) sizes. One DMA moves a
    [bg, hkv, (cs/pair), dh*pair] chunk — exactly bg*hkv*cs*dh elements
    (the packed view keeps the minor dim >= 128 lanes, so no VMEM lane
    padding). Prefer covering all of B per DMA (fewer loop iterations,
    one warmup stall) and the fattest cs that divides s_max."""

    def bytes_of(bg, cs):
        return bg * hkv * cs * dh * itemsize

    for bg in (b, b // 2, b // 4, b // 8, 1):
        if bg < 1 or b % max(bg, 1):
            continue
        for cs in (512, 256, 128):
            if s_max % cs == 0 and bytes_of(bg, cs) <= _CHUNK_BUDGET:
                return bg, cs
    return 1, 128


def _resolve_plan(b: int, hkv: int, s_max: int, dh: int, itemsize: int,
                  override=None):
    """(bg, cs, vmem_bytes, mha) for one fused_decode_step geometry:
    the measured artifact entry (ops/autotune.py) when one exists for
    this backend+shape and VALIDATES against the live shape, else the
    hand-picked :func:`_plan` constants. ``mha`` picks the rep==1
    score/PV engine — "mxu" (default: [1, Dh] x [Dh, CS] slabs like the
    GQA path, ISSUE 12's fused-decode shave) or "vpu" (the pre-ISSUE-12
    broadcast-multiply+reduce, kept plan-selectable so the autotuner
    can measure both). ``override`` is the micro-bench harness's
    candidate entry — same schema, same validation."""
    from deepspeed_tpu.ops import autotune

    ent = override
    if ent is None:
        ent = autotune.lookup(
            "decode_step", autotune.decode_key(b, hkv, s_max, dh, itemsize))
    bg, cs = _plan(b, hkv, s_max, dh, itemsize)
    vmem, mha = _VMEM_LIMIT, "mxu"
    if ent:
        try:
            bg2 = int(ent.get("bg", bg))
            cs2 = int(ent.get("cs", cs))
            # re-validate against the live shape: a stale artifact may
            # cost performance, never a mis-shaped DMA
            if (bg2 >= 1 and b % bg2 == 0 and cs2 >= 128
                    and cs2 % 128 == 0 and s_max % cs2 == 0):
                bg, cs = bg2, cs2
            vmem, mha = _entry_vmem_mha(ent, vmem, mha)
        except Exception:
            pass
    return bg, cs, vmem, mha


def _entry_vmem_mha(ent: dict, vmem: int, mha: str):
    """Shared artifact-entry parsing for the per-kernel tunables both
    decode resolvers honor: the clamped VMEM scope and the rep==1
    score/PV engine (one implementation, so the two kernels can never
    diverge in how they read the same schema).  The clamp bounds come
    from the per-generation table in ops/autotune.py — the same table
    the `vmem-budget` lint pass checks committed plans against."""
    from deepspeed_tpu.ops import autotune

    vmem = max(autotune.DEFAULT_VMEM_MB,
               min(int(ent.get("vmem_mb", vmem >> 20)),
                   autotune.SCOPED_VMEM_MAX_MB)) << 20
    if ent.get("mha") in ("mxu", "vpu"):
        mha = ent["mha"]
    return vmem, mha


def _resolve_block_plan(b: int, hkv: int, bs: int, dh: int, itemsize: int,
                        override=None):
    """(vmem_bytes, mha) for one fused_block_decode_step geometry (the
    block kernel's chunk size IS the pool's block size, so only the
    VMEM scope and the rep==1 engine are tunable)."""
    from deepspeed_tpu.ops import autotune

    ent = override
    if ent is None:
        ent = autotune.lookup(
            "block_decode_step",
            autotune.block_decode_key(b, hkv, bs, dh, itemsize))
    vmem, mha = _VMEM_LIMIT, "mxu"
    if ent:
        try:
            vmem, mha = _entry_vmem_mha(ent, vmem, mha)
        except Exception:
            pass
    return vmem, mha


def _attend_chunk(qv, kc, load_v, valid, m_ref, l_ref, acc_ref, *,
                  hq: int, hkv: int, dh: int, pair: int, scale: float,
                  mha: str, dv: Optional[int] = None):
    """One chunk of the online softmax, shared by the two slot-paged
    kernels. ``qv [bg, Hq, 1, Dh]`` (the unit dim comes pre-shaped from the
    wrapper: Mosaic cannot reshape bf16 vectors to add one before the minor
    dim); ``kc [bg, Hkv, CSP, Dh*pair]`` the loaded K chunk; ``load_v()``
    gives the V chunk and is called after the scores, so a kernel waits
    for V there; ``valid(h, shape)`` is the position mask of packed lane
    slice ``h`` (each slice keeps its own position stream). State in
    ``m_ref / l_ref [bg, Hq]``, ``acc_ref [bg, Hq, Dh]``. ``dv``: the
    width of a value row where it is not the keys' ``dh`` (unpacked rows
    only): scores contract over ``dh``, the accumulator is ``dv`` wide.

    bf16: products run in bf16 with f32 accumulation — the same precision
    contract as the einsum path's MXU (bf16 multiply, f32 accumulate); a
    full f32 materialization of both chunks measured ~2x the VPU time."""
    bg, _, csp, _ = kc.shape
    rep = hq // hkv
    dv = dh if dv is None else dv
    ss = []
    for h in range(pair):
        k = kc[..., h * dh:(h + 1) * dh]    # [bg, Hkv, CSP, Dh]
        if rep == 1 and mha == "vpu":
            s = jnp.sum(qv * k, -1,
                        dtype=jnp.float32)         # VPU [bg, H, CSP]
        else:
            # MXU [rep, Dh] x [Dh, CS] slabs per kv head (rep==1
            # degenerates to [1, Dh] matvecs — the ISSUE 12
            # default; the autotuned plan can select "vpu" back)
            qg = qv.reshape(bg * hkv, rep, dh)     # 1 batch dim
            kg = k.reshape(bg * hkv, csp, dh)      # (Mosaic limit)
            s = jax.lax.dot_general(               # MXU
                qg, kg, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            s = s.reshape(bg, hq, csp)
        s = s * scale
        ss.append(jnp.where(valid(h, s.shape), s, _NEG))

    m_prev = m_ref[...]                            # [bg, Hq]
    m_new = m_prev
    for s in ss:
        m_new = jnp.maximum(m_new, s.max(-1))
    corr = jnp.exp(m_prev - m_new)
    l_new = l_ref[...] * corr
    acc = acc_ref[...] * corr[:, :, None]
    ps = [jnp.exp(s - m_new[:, :, None]) for s in ss]
    for p in ps:
        l_new = l_new + p.sum(-1)

    vc = load_v()
    for h, p in enumerate(ps):
        v = vc[..., h * dv:(h + 1) * dv]
        if rep == 1 and mha == "vpu":
            pb = p[:, :, :, None].astype(v.dtype)  # None-insert in
            # f32 (bf16 unit-dim reshape is unsupported), cast after
            pv = jnp.sum(pb * v, 2,
                         dtype=jnp.float32)        # VPU [bg, H, Dh]
        else:
            pg = p.reshape(bg * hkv, rep, csp).astype(v.dtype)
            vg = v.reshape(bg * hkv, csp, dv)
            pv = jax.lax.dot_general(              # MXU
                pg, vg, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            pv = pv.reshape(bg, hq, dv)
        acc = acc + pv
    l_ref[...] = l_new
    acc_ref[...] = acc
    m_ref[...] = m_new


def _kernel(layer_ref, idx_ref, q_ref, kn_ref, vn_ref, _kin_ref, _vin_ref,
            attn_ref, k_ref, v_ref,
            kbuf, vbuf, kwin, vwin, m_ref, l_ref, acc_ref, wsem, rsem,
            *, b: int, bg: int, cs: int, hq: int, hkv: int, dh: int,
            pair: int, scale: float, mha: str = "mxu"):
    """The uniform walk (scalar ``idx``: generate(), every row live and
    equally long): one strided DMA a chunk covers all rows of a batch
    group. Per-slot lengths take :func:`_slot_kernel`."""
    layer = layer_ref[0]
    idx = idx_ref[0]
    csp = cs // pair          # pair-rows per chunk
    dhp = dh * pair           # packed minor dim (>= 128)

    # ---- write the new token's K/V into the cache (in place: k_ref/v_ref
    # alias the input cache buffers). HBM tiling forbids single-row
    # writes, so read-modify-write the 8-aligned pair-row window (fetch ->
    # vector-select insert -> write back). The write is for FUTURE steps
    # only and runs fully async: this step's attention walk splices the
    # new token into the loaded chunk IN-REGISTER (see `body`), so no
    # read waits on the write-back (a serialized RMW measured +0.13
    # ms/tok at B=1 — pure DMA latency, 12 layers x 4 chained waits).
    w0 = (idx // pair // 8) * 8
    fk = pltpu.make_async_copy(
        k_ref.at[layer, :, :, pl.ds(w0, 8), :], kwin, wsem.at[0, 0])
    fv = pltpu.make_async_copy(
        v_ref.at[layer, :, :, pl.ds(w0, 8), :], vwin, wsem.at[1, 0])
    fk.start()
    fv.start()

    def finish_write():
        """Insert the token into the fetched window and write it back —
        called after the first chunk DMAs are in flight."""
        fk.wait()
        fv.wait()
        row = idx // pair - w0
        half = idx - (idx // pair) * pair
        sel = (jax.lax.broadcasted_iota(
            jnp.int32, (b, hkv, 8, dhp), 2) == row)
        if pair > 1:
            sel &= (jax.lax.broadcasted_iota(
                jnp.int32, (b, hkv, 8, dhp), 3) // dh == half)
        kwin[...] = jnp.where(sel, kn_ref[...], kwin[...])
        vwin[...] = jnp.where(sel, vn_ref[...], vwin[...])
        pltpu.make_async_copy(
            kwin, k_ref.at[layer, :, :, pl.ds(w0, 8), :],
            wsem.at[0, 0]).start()
        pltpu.make_async_copy(
            vwin, v_ref.at[layer, :, :, pl.ds(w0, 8), :],
            wsem.at[1, 0]).start()

    nchunks = idx // cs + 1  # valid-prefix walk: dead chunks never fetched

    for g in range(b // bg):  # static unroll over batch groups
        b0 = g * bg

        def chunk_dma(slot, c, src, buf, t):
            return pltpu.make_async_copy(
                src.at[layer, pl.ds(b0, bg), :, pl.ds(c * csp, csp), :],
                buf.at[slot], rsem.at[slot, t])

        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        chunk_dma(0, 0, k_ref, kbuf, 0).start()
        chunk_dma(0, 0, v_ref, vbuf, 1).start()
        if g == 0:
            finish_write()  # overlaps with chunk 0's flight
        qv = q_ref[pl.ds(b0, bg)]                    # [bg, Hq, 1, Dh] bf16

        def body(c, _, splice=False):
            slot = jax.lax.rem(c, 2)
            nxt = 1 - slot

            @pl.when(c + 1 < nchunks)
            def _prefetch():
                chunk_dma(nxt, c + 1, k_ref, kbuf, 0).start()
                chunk_dma(nxt, c + 1, v_ref, vbuf, 1).start()

            # splice mask (shared by K now and V below): the new token
            # lands in the final chunk only — the prefix walk never pays
            # the vector work
            spl = None
            if splice:
                # in-register splice of the new token (its async cache
                # write may still be in flight; every other row is
                # unchanged, so a read/write race can only return
                # identical bytes)
                rowg = c * csp + jax.lax.broadcasted_iota(
                    jnp.int32, (bg, hkv, csp, dhp), 2)
                spl = rowg == idx // pair
                if pair > 1:
                    spl &= (jax.lax.broadcasted_iota(
                        jnp.int32, (bg, hkv, csp, dhp), 3) // dh
                            == idx - (idx // pair) * pair)

            # K first: the scores + running-max update run while the V
            # half of the chunk is still in flight (ISSUE 12 shave — the
            # old joint wait serialized ~half the chunk DMA behind the
            # VPU/MXU math it could hide under)
            chunk_dma(slot, c, k_ref, kbuf, 0).wait()
            kc = kbuf[slot]                         # [bg, Hkv, CSP, Dh*pair]
            if spl is not None:
                kc = jnp.where(spl, kn_ref[pl.ds(b0, bg)], kc)

            def valid(h, shape):
                pos = c * cs + pair * jax.lax.broadcasted_iota(
                    jnp.int32, shape, 2) + h
                return pos <= idx

            def load_v():
                chunk_dma(slot, c, v_ref, vbuf, 1).wait()
                vc = vbuf[slot]
                if spl is not None:
                    vc = jnp.where(spl, vn_ref[pl.ds(b0, bg)], vc)
                return vc

            _attend_chunk(qv, kc, load_v, valid, m_ref, l_ref, acc_ref,
                          hq=hq, hkv=hkv, dh=dh, pair=pair, scale=scale,
                          mha=mha)
            return 0

        jax.lax.fori_loop(0, nchunks - 1, body, 0)
        body(nchunks - 1, 0, splice=True)
        l_safe = jnp.maximum(l_ref[...], 1e-20)
        attn_ref[pl.ds(b0, bg)] = (acc_ref[...] / l_safe[:, :, None]) \
            .astype(attn_ref.dtype)

    # drain the async write-back before the kernel exits
    pltpu.make_async_copy(
        kwin, k_ref.at[layer, :, :, pl.ds(w0, 8), :], wsem.at[0, 0]).wait()
    pltpu.make_async_copy(
        vwin, v_ref.at[layer, :, :, pl.ds(w0, 8), :], wsem.at[1, 0]).wait()


def _slot_kernel(layer_ref, idx_ref, order_ref, n_ref, q_ref, kn_ref, vn_ref,
                 _kin_ref, _vin_ref, attn_ref, k_ref, v_ref,
                 kbuf, vbuf, kwin, vwin, qrow, knrow, vnrow,
                 m_ref, l_ref, acc_ref, wsem, rsem,
                 *, b: int, bg: int, cs: int, hq: int, hkv: int, dh: int,
                 pair: int, scale: float, mha: str = "mxu", wpos_ref=None,
                 dv: Optional[int] = None, sink_ref=None):
    """The per-slot walk (continuous batching): ``idx_ref [B]`` holds each
    slot's own length, ``order_ref [B]`` the slots with the active ones
    first by descending length (:func:`slot_walk`), ``n_ref [1]`` how many
    are active. Only what an active slot needs is fetched or written:

    * Everything goes by SORTED POSITION ``p``: row ``j`` of group ``g`` is
      slot ``order[g * bg + j]``. Sorted slots are not neighbours in HBM,
      so each row has its own DMA per chunk into row ``j`` of the group's
      buffer, started only while the row has cache rows left
      (``c < ceil(len / cs)``) and waited on under the same condition. A
      group walks to its FIRST row's chunk count (the longest); groups
      beyond ``ceil(n_active / bg)`` do not run. The chunk of group ``g+1``
      is prefetched under the last chunk of group ``g``: one DMA warm-up
      stall a layer, not one a group.
    * A chunk of more than ``_SLOT_CHUNK`` rows is fetched in parts of that
      many, each a DMA with a semaphore of its own into its rows of the
      buffer, started and waited on while the row has rows left for THAT
      part (``c * parts + u < ceil(len / 128)``): what a row fetches does
      not depend on the chunk.
    * A buffer row, or a part of one, whose DMA was skipped holds whatever
      an earlier chunk left there, and ``0 * NaN`` in the PV product is
      NaN, so ``vbuf`` is zeroed on entry: from then on it only ever holds
      cache rows of active slots (the K side is masked by select, which
      drops a NaN).
    * The new token is not spliced into a chunk: the online softmax starts
      from it (a one-position chunk made of ``k_new`` / ``v_new``), and the
      cache walk covers the positions strictly before it. So no chunk pays
      the splice's two full-chunk selects, and a row of length 0 fetches
      nothing.
    * An inactive slot's 8-row write window is neither read nor written
      (a slot midway through a chunked prefill is inactive here while its
      rows are live), and its output row is zero.
    * ``wpos_ref [B]`` (:func:`_ring_slot_kernel`; a sliding-window layer's
      cache is a ring of ``window`` rows): the token is written at row
      ``wpos`` and not at the length, and that row is left out of the walk:
      in a full ring it holds the one position that has just left the
      window. Without it the token lands at the length, which the walk's
      ``< length`` mask already leaves out.
    * ``dv``: value rows of another width than the keys' ``dh`` (unpacked
      rows): the two leaves' chunks, windows and new rows are ``dh`` and
      ``dv`` wide, the scores contract over ``dh``, the accumulator and the
      output are ``dv`` wide.
    * ``sink_ref [1, Hq]`` float32 (a learned logit a head that takes part
      in the softmax's denominator and has no value): the running softmax
      STARTS from it, maximum ``sink_h``, sum 1 (its own ``exp(0)``),
      accumulator 0, so it costs no pass and no mask."""
    write_at = idx_ref if wpos_ref is None else wpos_ref
    layer = layer_ref[0]
    n_act = n_ref[0]
    csp = cs // pair          # pair-rows per chunk
    dhp = dh * pair           # packed minor dim (>= 128)
    dvp = dhp if dv is None else dv
    dma = _SLOT_CHUNK         # rows a DMA: what a row's tail rounds up to
    parts = cs // dma         # DMAs a row a chunk
    dmp = dma // pair         # pair-rows a DMA

    def slot_at(p):
        # (clamped: a position past the last slot is never active)
        return order_ref[jnp.minimum(p, b - 1)]

    def nch_at(p, rows=cs):
        """Chunks of cache rows sorted position ``p`` walks (``rows=dma``:
        the DMAs it starts)."""
        return jnp.where(p < n_act,
                         (idx_ref[slot_at(p)] + rows - 1) // rows, 0)

    # ---- the active slots' new K/V into the cache: the same 8-row window
    # read-modify-write as the uniform kernel's, one window a slot (write
    # positions are unrelated), fully async under the walk
    def win_copy(p, t, back: bool):
        s = slot_at(p)
        w0 = (write_at[s] // pair // 8) * 8
        hbm = (k_ref, v_ref)[t].at[layer, pl.ds(s, 1), :, pl.ds(w0, 8), :]
        win = (kwin, vwin)[t].at[pl.ds(p, 1)]
        return pltpu.make_async_copy(win, hbm, wsem.at[t, p]) if back \
            else pltpu.make_async_copy(hbm, win, wsem.at[t, p])

    def each_active(fn):
        def step(p, _):
            fn(p)
            return 0
        jax.lax.fori_loop(0, n_act, step, 0)

    def fetch_window(p):
        win_copy(p, 0, False).start()
        win_copy(p, 1, False).start()

    def insert_token(p):
        win_copy(p, 0, False).wait()
        win_copy(p, 1, False).wait()
        s = slot_at(p)
        i = write_at[s]
        sel = (jax.lax.broadcasted_iota(jnp.int32, (1, hkv, 8, dhp), 2)
               == jax.lax.rem(i // pair, 8))
        if pair > 1:
            sel &= (jax.lax.broadcasted_iota(
                jnp.int32, (1, hkv, 8, dhp), 3) // dh
                    == jax.lax.rem(i, pair))
        kwin[pl.ds(p, 1)] = jnp.where(sel, kn_ref[pl.ds(s, 1)],
                                      kwin[pl.ds(p, 1)])
        if dvp != dhp:      # unpacked rows: the row alone selects
            sel = (jax.lax.broadcasted_iota(jnp.int32, (1, hkv, 8, dvp), 2)
                   == jax.lax.rem(i, 8))
        vwin[pl.ds(p, 1)] = jnp.where(sel, vn_ref[pl.ds(s, 1)],
                                      vwin[pl.ds(p, 1)])
        win_copy(p, 0, True).start()
        win_copy(p, 1, True).start()

    def drain_window(p):
        win_copy(p, 0, True).wait()
        win_copy(p, 1, True).wait()

    # ---- the walk
    def dma_at(c, u):
        """Part ``u`` of chunk ``c`` among a row's DMAs."""
        return c if parts == 1 else c * parts + u

    def chunk_copy(p, j, u, c, slot, t):
        s = slot_at(p)
        at, buf = dma_at(c, u) * dmp, (kbuf, vbuf)[t].at[slot, pl.ds(j, 1)]
        if parts > 1:         # into the part's own rows of the buffer
            buf = buf.at[:, :, pl.ds(u * dmp, dmp)]
        return pltpu.make_async_copy(
            (k_ref, v_ref)[t].at[layer, pl.ds(s, 1), :, pl.ds(at, dmp), :],
            buf, rsem.at[slot, t, j * parts + u])

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
        def go(p, j, u):
            chunk_copy(p, j, u, c, slot, 0).start()
            chunk_copy(p, j, u, c, slot, 1).start()
        each_row(g, c, go)

    attn_ref[...] = jnp.zeros_like(attn_ref)
    vbuf[...] = jnp.zeros_like(vbuf)
    each_active(fetch_window)
    start_chunk(0, 0, 0)
    each_active(insert_token)    # overlaps with chunk 0's flight

    def group(g, t):
        """Group ``g`` from linear step ``t`` (its parity names the buffer
        the step computes on) -> the next group's first step."""
        nch_g = nch_at(g * bg)
        lens = jnp.zeros((bg, hq, csp), jnp.int32)
        wrow = lens
        rows = jax.lax.broadcasted_iota(jnp.int32, lens.shape, 0)
        for j in range(bg):      # SMEM scalars cannot gather: bg selects
            p = g * bg + j
            s = slot_at(p)
            lens = jnp.where(rows == j,
                             jnp.where(p < n_act, idx_ref[s], 0), lens)
            if wpos_ref is not None:
                wrow = jnp.where(rows == j, wpos_ref[s], wrow)
            qrow[pl.ds(j, 1)] = q_ref[pl.ds(s, 1)]
            knrow[pl.ds(j, 1)] = kn_ref[pl.ds(s, 1)]
            vnrow[pl.ds(j, 1)] = vn_ref[pl.ds(s, 1)]
        qv = qrow[...]                               # [bg, Hq, 1, Dh] bf16
        attend = functools.partial(
            _attend_chunk, qv, m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref,
            hq=hq, hkv=hkv, dh=dh, pair=pair, scale=scale, mha=mha, dv=dv)

        if sink_ref is None:
            m_ref[...] = jnp.full_like(m_ref, _NEG)
            l_ref[...] = jnp.zeros_like(l_ref)
        else:
            m_ref[...] = jnp.broadcast_to(sink_ref[...], m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the new token first: one live position of an 8-row chunk
        attend(jnp.broadcast_to(knrow[...], (bg, hkv, 8, dhp)),
               lambda: jnp.broadcast_to(vnrow[...], (bg, hkv, 8, dvp)),
               lambda h, shape: jax.lax.broadcasted_iota(
                   jnp.int32, shape, 2) < (1 if h == 0 else 0))

        def body(c, t):
            slot = jax.lax.rem(t, 2)
            more = c + 1 < nch_g
            start_chunk(jnp.where(more, g, g + 1),
                        jnp.where(more, c + 1, 0), 1 - slot)
            # K first: the scores run while V is still in flight
            each_row(g, c, lambda *r: chunk_copy(*r, c, slot, 0).wait())

            def load_v():
                each_row(g, c, lambda *r: chunk_copy(*r, c, slot, 1).wait())
                return vbuf[slot]

            def valid(h, shape):
                tok = c * cs + pair * jax.lax.broadcasted_iota(
                    jnp.int32, shape, 2) + h
                if wpos_ref is None:
                    return tok < lens
                return (tok < lens) & (tok != wrow)

            attend(kbuf[slot], load_v, valid)
            return t + 1

        t = jax.lax.fori_loop(0, nch_g, body, t)
        out = (acc_ref[...] / l_ref[...][:, :, None]).astype(attn_ref.dtype)
        for j in range(bg):      # back in slot order
            p = g * bg + j

            @pl.when(p < n_act)
            def _():
                attn_ref[pl.ds(slot_at(p), 1)] = out[j:j + 1]
        return t

    jax.lax.fori_loop(0, (n_act + bg - 1) // bg, group, 0)
    each_active(drain_window)    # before the kernel exits


def _ring_slot_kernel(layer_ref, idx_ref, order_ref, n_ref, wpos_ref, *refs,
                      **geometry):
    """:func:`_slot_kernel` on a ring: ``idx_ref`` the live rows of each
    slot's ring, ``wpos_ref`` the row the new token takes."""
    _slot_kernel(layer_ref, idx_ref, order_ref, n_ref, *refs,
                 wpos_ref=wpos_ref, **geometry)


def _sink_kernel(kernel, n_scalar: int, *refs, **geometry):
    """A per-slot kernel whose operands carry the heads' sink behind the new
    value row: handed on by name."""
    at = n_scalar + 3
    kernel(*refs[:at], *refs[at + 1:], sink_ref=refs[at], **geometry)


def supports_block(hq: int, hkv: int, block_size: int, dh: int) -> bool:
    """Shapes the fused BLOCK-TABLE kernel can stream: minor dim must
    tile to 128 lanes (dh % 128 == 0, or dh*pair == 128), and each
    block's pair-row count must cover whole 8-row HBM tiles (the new
    token's write is an 8-aligned window RMW inside one block)."""
    if hq % hkv:
        return False
    if dh >= 128:
        return dh % 128 == 0 and block_size % 8 == 0
    return 128 % dh == 0 and block_size % (8 * (128 // dh)) == 0


def _quantize_token(x, kv_dtype: str, cdtype):
    """In-register quantization of one packed new-token row
    ``x [B, Hkv, 1, Dh*pair]``: a direct call into the einsum path's
    quantizer (serving/kv_quant.kv_quantize_keepdims — ONE shared
    implementation, so stored-byte bit-identity between the fused and
    einsum paths holds by construction). The pair lane slices are
    COPIES of the same Dh values, so the amax over the packed row
    equals the unpacked row's and one per-(row, head) scale covers
    every copy. Returns ``(payload [B, Hkv, 1, Dh*pair],
    scale [B, Hkv, 1, 1] bf16, deq [B, Hkv, 1, Dh*pair] cdtype)``
    where ``deq`` is the quantize->dequantize image — the value every
    LATER step will read, spliced into THIS step's chunks so kernel
    and einsum attend identically."""
    from deepspeed_tpu.serving.kv_quant import kv_quantize_keepdims

    payload, s = kv_quantize_keepdims(x, kv_dtype)
    deq = (payload.astype(jnp.float32)
           * s.astype(jnp.float32)).astype(cdtype)
    return payload, s, deq


def _block_kernel(*refs, b: int, mb: int, csp: int, hq: int, hkv: int,
                  dh: int, pair: int, scale: float, quant: bool,
                  kv_dtype: str, mha: str):
    """Block-paged decode layer-step (the block-table analog of
    :func:`_kernel`'s per_slot path): each batch row's KV lives in the
    pool blocks named by its ``tbl_ref[i]`` row, so both the new token's
    window RMW and the streaming walk indirect through the table —
    which is SMEM DATA, so remapping blocks between steps never
    recompiles. Rows are processed one at a time (serving batches are
    narrow; each row's block chain is its own DMA stream), with the
    same double-buffered fetch + in-register splice + online-softmax
    structure as the slot kernel. Sentinel table entries name the
    pool's garbage row (kv_blocks.BlockKVPool), so inactive slots'
    writes and reads are unconditionally safe — no predication.

    ``quant`` (ISSUE 12): the pools are int8/fp8 payload + pair-grouped
    bf16 scale arrays (serving/kv_quant.py). The chunk walk DMAs 1-byte
    payload blocks (half the streamed bytes of bf16) plus their tiny
    scale rows and dequantizes IN-REGISTER per lane slice; the write
    side quantizes the new token in-register and RMWs the WHOLE tail
    block + its scale row (whole-block windows sidestep int8's 32-row
    HBM tile quantum; a block is at most a few KB). Scores/PV run in
    the compute dtype either way — the quantization lives entirely in
    the DMA boundary."""
    if quant:
        (layer_ref, idx_ref, tbl_ref, q_ref, kn_ref, vn_ref,
         _kqi, _vqi, _ksi, _vsi,
         attn_ref, k_ref, v_ref, ks_ref, vs_ref,
         kbuf, vbuf, ksbuf, vsbuf, kwin, vwin, kswin, vswin,
         m_ref, l_ref, acc_ref, wsem, rsem) = refs
    else:
        (layer_ref, idx_ref, tbl_ref, q_ref, kn_ref, vn_ref,
         _kqi, _vqi,
         attn_ref, k_ref, v_ref,
         kbuf, vbuf, kwin, vwin,
         m_ref, l_ref, acc_ref, wsem, rsem) = refs
    layer = layer_ref[0]
    rep = hq // hkv
    bs = csp * pair           # tokens per block
    dhp = dh * pair
    cdtype = q_ref.dtype

    if quant:
        # quantize the new tokens once, up front (pure vector math —
        # nothing waits on it): payloads/scales for the write-back,
        # dequantized images for the in-register splices
        kq_new, ks_new, kn_spl = _quantize_token(
            kn_ref[...], kv_dtype, cdtype)
        vq_new, vs_new, vn_spl = _quantize_token(
            vn_ref[...], kv_dtype, cdtype)
    else:
        kn_spl, vn_spl = kn_ref[...], vn_ref[...]

    # ---- write each row's new token into its current tail block.
    # bf16: RMW the 8-aligned pair-row window (HBM tiling forbids
    # single-row writes). quant: RMW the WHOLE block + its scale row.
    pbs, w0s = [], []
    for i in range(b):
        pos = idx_ref[i]
        jb = jnp.minimum(pos // bs, mb - 1)
        pbs.append(tbl_ref[i, jb])
        w0s.append(0 if quant else (pos % bs // pair // 8) * 8)
    nwin = csp if quant else 8

    def kdma(i):
        return pltpu.make_async_copy(
            k_ref.at[layer, pl.ds(pbs[i], 1), :, pl.ds(w0s[i], nwin), :],
            kwin.at[pl.ds(i, 1)], wsem.at[0, i])

    def vdma(i):
        return pltpu.make_async_copy(
            v_ref.at[layer, pl.ds(pbs[i], 1), :, pl.ds(w0s[i], nwin), :],
            vwin.at[pl.ds(i, 1)], wsem.at[1, i])

    def ksdma(i):
        return pltpu.make_async_copy(
            ks_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
            kswin.at[pl.ds(i, 1)], wsem.at[2, i])

    def vsdma(i):
        return pltpu.make_async_copy(
            vs_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
            vswin.at[pl.ds(i, 1)], wsem.at[3, i])

    wdmas = [kdma, vdma] + ([ksdma, vsdma] if quant else [])
    for i in range(b):
        for mk in wdmas:
            mk(i).start()

    def finish_write():
        for i in range(b):
            for mk in wdmas:
                mk(i).wait()
        bi = jax.lax.broadcasted_iota(jnp.int32, (b, hkv, nwin, dhp), 0)
        ri = jax.lax.broadcasted_iota(jnp.int32, (b, hkv, nwin, dhp), 2)
        li = jax.lax.broadcasted_iota(jnp.int32, (b, hkv, nwin, dhp), 3)
        sel = bi < 0  # all-false
        for i in range(b):
            r = jax.lax.rem(idx_ref[i], bs)
            row = r // pair if quant else jax.lax.rem(r // pair, 8)
            sel_i = (bi == i) & (ri == row)
            if pair > 1:
                sel_i &= (li // dh == jax.lax.rem(r, pair))
            sel |= sel_i
        if quant:
            kwin[...] = jnp.where(sel, kq_new, kwin[...])
            vwin[...] = jnp.where(sel, vq_new, vwin[...])
            # scale row splice: pair-grouped [b, Hkv, pair, csp] —
            # token r sits at [.., r % pair, r // pair]
            sbi = jax.lax.broadcasted_iota(
                jnp.int32, (b, hkv, pair, csp), 0)
            spi = jax.lax.broadcasted_iota(
                jnp.int32, (b, hkv, pair, csp), 2)
            sri = jax.lax.broadcasted_iota(
                jnp.int32, (b, hkv, pair, csp), 3)
            sel_s = sbi < 0
            for i in range(b):
                r = jax.lax.rem(idx_ref[i], bs)
                sel_s |= ((sbi == i) & (spi == jax.lax.rem(r, pair))
                          & (sri == r // pair))
            kswin[...] = jnp.where(sel_s, ks_new, kswin[...])
            vswin[...] = jnp.where(sel_s, vs_new, vswin[...])
        else:
            kwin[...] = jnp.where(sel, kn_ref[...], kwin[...])
            vwin[...] = jnp.where(sel, vn_ref[...], vwin[...])
        for i in range(b):
            pltpu.make_async_copy(
                kwin.at[pl.ds(i, 1)],
                k_ref.at[layer, pl.ds(pbs[i], 1), :,
                         pl.ds(w0s[i], nwin), :],
                wsem.at[0, i]).start()
            pltpu.make_async_copy(
                vwin.at[pl.ds(i, 1)],
                v_ref.at[layer, pl.ds(pbs[i], 1), :,
                         pl.ds(w0s[i], nwin), :],
                wsem.at[1, i]).start()
            if quant:
                pltpu.make_async_copy(
                    kswin.at[pl.ds(i, 1)],
                    ks_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
                    wsem.at[2, i]).start()
                pltpu.make_async_copy(
                    vswin.at[pl.ds(i, 1)],
                    vs_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
                    wsem.at[3, i]).start()

    # ---- per-row valid-block walk (chunk == one pool block)
    for i in range(b):
        idx_i = idx_ref[i]
        nblk = idx_i // bs + 1

        def chunk_dma(slot, j, src, buf, t):
            pb = tbl_ref[i, jnp.minimum(j, mb - 1)]
            return pltpu.make_async_copy(
                src.at[layer, pl.ds(pb, 1), :, :, :],
                buf.at[slot], rsem.at[slot, t])

        def start_chunk(slot, j):
            chunk_dma(slot, j, k_ref, kbuf, 0).start()
            chunk_dma(slot, j, v_ref, vbuf, 1).start()
            if quant:
                chunk_dma(slot, j, ks_ref, ksbuf, 2).start()
                chunk_dma(slot, j, vs_ref, vsbuf, 3).start()

        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        start_chunk(0, 0)
        if i == 0:
            finish_write()  # overlaps with row 0 / chunk 0's flight
        qv = q_ref[pl.ds(i, 1)]                      # [1, Hq, 1, Dh]

        def half_slice(buf_val, sbuf_val, spl_val, c, h):
            """Lane slice ``h`` of a loaded chunk in the compute dtype:
            dequantized against its pair-grouped scale row (quant) or
            sliced directly (bf16), with the new token spliced in at
            its own (row, half)."""
            x = buf_val[..., h * dh:(h + 1) * dh]    # [1, Hkv, CSP, Dh]
            if quant:
                sc = sbuf_val[:, :, h, :]            # [1, Hkv, CSP]
                x = (x.astype(cdtype) * sc[..., None].astype(cdtype))
            rowg = c * csp + jax.lax.broadcasted_iota(
                jnp.int32, (1, hkv, csp, dh), 2)
            spl = rowg == idx_i // pair
            if pair > 1:
                spl &= jnp.full((1, hkv, csp, dh),
                                jax.lax.rem(idx_i, pair) == h)
            # spl_val is a traced VALUE (not a ref); i is a static
            # python index, so plain slicing selects the row
            return jnp.where(
                spl, spl_val[i:i + 1][..., h * dh:(h + 1) * dh], x)

        def body(c, _):
            slot = jax.lax.rem(c, 2)
            nxt = 1 - slot

            @pl.when(c + 1 < nblk)
            def _prefetch():
                start_chunk(nxt, c + 1)

            # K first: scores + running-max math run under the V half's
            # remaining flight time (ISSUE 12 fused-decode shave)
            chunk_dma(slot, c, k_ref, kbuf, 0).wait()
            if quant:
                chunk_dma(slot, c, ks_ref, ksbuf, 2).wait()
            kq = kbuf[slot]                          # [1, Hkv, CSP, Dh*pair]
            ksc = ksbuf[slot] if quant else None
            ss = []
            for h in range(pair):
                k = half_slice(kq, ksc, kn_spl, c, h)
                if rep == 1 and mha == "vpu":
                    s = jnp.sum(qv * k, -1, dtype=jnp.float32)
                else:
                    qg = qv.reshape(hkv, rep, dh)
                    kg = k.reshape(hkv, csp, dh)
                    s = jax.lax.dot_general(
                        qg, kg, (((2,), (2,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
                    s = s.reshape(1, hq, csp)
                s = s * scale
                pos = c * bs + pair * jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 2) + h
                ss.append(jnp.where(pos <= idx_i, s, _NEG))
            m_prev = m_ref[...]                      # [1, Hq]
            m_new = m_prev
            for s in ss:
                m_new = jnp.maximum(m_new, s.max(-1))
            corr = jnp.exp(m_prev - m_new)
            l_new = l_ref[...] * corr
            acc = acc_ref[...] * corr[:, :, None]
            ps = [jnp.exp(s - m_new[:, :, None]) for s in ss]
            for p in ps:
                l_new = l_new + p.sum(-1)

            chunk_dma(slot, c, v_ref, vbuf, 1).wait()
            if quant:
                chunk_dma(slot, c, vs_ref, vsbuf, 3).wait()
            vq = vbuf[slot]
            vsc = vsbuf[slot] if quant else None
            for h, p in enumerate(ps):
                v = half_slice(vq, vsc, vn_spl, c, h)
                if rep == 1 and mha == "vpu":
                    pb_ = p[:, :, :, None].astype(v.dtype)
                    pv = jnp.sum(pb_ * v, 2, dtype=jnp.float32)
                else:
                    pg = p.reshape(hkv, rep, csp).astype(v.dtype)
                    vg = v.reshape(hkv, csp, dh)
                    pv = jax.lax.dot_general(
                        pg, vg, (((2,), (1,)), ((0,), (0,))),
                        preferred_element_type=jnp.float32)
                    pv = pv.reshape(1, hq, dh)
                acc = acc + pv
            l_ref[...] = l_new
            acc_ref[...] = acc
            m_ref[...] = m_new
            return 0

        jax.lax.fori_loop(0, nblk, body, 0)
        l_safe = jnp.maximum(l_ref[...], 1e-20)
        attn_ref[pl.ds(i, 1)] = (acc_ref[...] / l_safe[:, :, None]) \
            .astype(attn_ref.dtype)

    # drain the async write-back before the kernel exits
    for i in range(b):
        pltpu.make_async_copy(
            kwin.at[pl.ds(i, 1)],
            k_ref.at[layer, pl.ds(pbs[i], 1), :, pl.ds(w0s[i], nwin), :],
            wsem.at[0, i]).wait()
        pltpu.make_async_copy(
            vwin.at[pl.ds(i, 1)],
            v_ref.at[layer, pl.ds(pbs[i], 1), :, pl.ds(w0s[i], nwin), :],
            wsem.at[1, i]).wait()
        if quant:
            pltpu.make_async_copy(
                kswin.at[pl.ds(i, 1)],
                ks_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
                wsem.at[2, i]).wait()
            pltpu.make_async_copy(
                vswin.at[pl.ds(i, 1)],
                vs_ref.at[layer, pl.ds(pbs[i], 1), :, :, :],
                wsem.at[3, i]).wait()


def fused_block_decode_step(q: jax.Array, k_pool, v_pool,
                            k_new: jax.Array, v_new: jax.Array,
                            layer, idx, block_table, *,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None,
                            plan: Optional[dict] = None):
    """One decode layer-step against the BLOCK-PAGED pool (ISSUE 6).

    q:             [B, 1, Hq, Dh]   — the new token's queries
    k_pool/v_pool: [L, N+1, Hkv, bs(/pair), Dh(*pair)] block pools
                   (serving/kv_blocks.BlockKVPool; last row = garbage),
                   or the quantized ``{"q": payload, "s": scales}``
                   pytrees (ISSUE 12, serving/kv_quant.py) — the kernel
                   then streams 1-byte payload chunks and dequantizes
                   in-register, and quantizes the new token on store.
    k_new/v_new:   [B, 1, Hkv, Dh]  — the new token's K/V (unwritten)
    layer:         scalar int32
    idx:           [B] int32 per-slot valid lengths
    block_table:   [B, MB] int32 — TRACED data, one compiled program
                   serves every block assignment.
    plan:          optional measured-plan override (the autotune
                   harness's candidate; ops/autotune.py entries are
                   consulted otherwise).

    Returns ``(attn [B, 1, Hq, Dh], k_pool, v_pool)`` with the pools
    updated in place (the returned pools alias the inputs).
    """
    b, t, hq, dh = q.shape
    assert t == 1, "fused_block_decode_step is the single-token path"
    quant = isinstance(k_pool, dict)
    kq_pool = k_pool["q"] if quant else k_pool
    vq_pool = v_pool["q"] if quant else v_pool
    l, n_phys, hkv, bsp, d_last = kq_pool.shape
    pair = d_last // dh
    bs = bsp * pair
    assert supports_block(hq, hkv, bs, dh), (hq, hkv, bs, dh)
    want_pair = 128 // dh if dh < 128 else 1
    assert pair == want_pair, (d_last, dh)  # router checks kv_pack_factor
    sc = float(scale) if scale is not None else dh ** -0.5
    store_dtype = kq_pool.dtype
    kv_dtype = ("int8" if store_dtype == jnp.int8 else "fp8") if quant \
        else "compute"
    vmem, mha = _resolve_block_plan(
        b, hkv, bs, dh, jnp.dtype(store_dtype).itemsize, override=plan)

    qf = q.transpose(0, 2, 1, 3)                   # [B, Hq, 1, Dh]
    kn = k_new.transpose(0, 2, 1, 3)               # [B, Hkv, 1, Dh]
    vn = v_new.transpose(0, 2, 1, 3)
    if pair > 1:
        kn = jnp.concatenate([kn] * pair, axis=-1)
        vn = jnp.concatenate([vn] * pair, axis=-1)
    layer_a = jnp.asarray(layer, jnp.int32).reshape(1)
    idx_a = jnp.asarray(idx, jnp.int32).reshape(-1)
    assert idx_a.shape[0] == b, (idx_a.shape, b)
    tbl = jnp.asarray(block_table, jnp.int32)
    mb = tbl.shape[1]

    kernel = functools.partial(
        _block_kernel, b=b, mb=mb, csp=bsp, hq=hq, hkv=hkv, dh=dh,
        pair=pair, scale=sc, quant=quant, kv_dtype=kv_dtype, mha=mha)
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),   # layer
        pl.BlockSpec(memory_space=pltpu.SMEM),   # idx
        pl.BlockSpec(memory_space=pltpu.SMEM),   # block table
        pl.BlockSpec(memory_space=pltpu.VMEM),   # q
        pl.BlockSpec(memory_space=pltpu.VMEM),   # k_new
        pl.BlockSpec(memory_space=pltpu.VMEM),   # v_new
        pl.BlockSpec(memory_space=pl.ANY),       # k payload (aliased)
        pl.BlockSpec(memory_space=pl.ANY),       # v payload (aliased)
    ]
    out_specs = [pl.BlockSpec(memory_space=pltpu.VMEM),
                 pl.BlockSpec(memory_space=pl.ANY),
                 pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [jax.ShapeDtypeStruct((b, hq, dh), q.dtype),
                 jax.ShapeDtypeStruct(kq_pool.shape, kq_pool.dtype),
                 jax.ShapeDtypeStruct(vq_pool.shape, vq_pool.dtype)]
    nwin = bsp if quant else 8
    scratch = [
        pltpu.VMEM((2, 1, hkv, bsp, dh * pair), kq_pool.dtype),
        pltpu.VMEM((2, 1, hkv, bsp, dh * pair), vq_pool.dtype),
    ]
    operands = [layer_a, idx_a, tbl, qf, kn, vn, kq_pool, vq_pool]
    if quant:
        ks_pool, vs_pool = k_pool["s"], v_pool["s"]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),   # k scales
                     pl.BlockSpec(memory_space=pl.ANY)]   # v scales
        out_specs += [pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)]
        out_shape += [jax.ShapeDtypeStruct(ks_pool.shape, ks_pool.dtype),
                      jax.ShapeDtypeStruct(vs_pool.shape, vs_pool.dtype)]
        operands += [ks_pool, vs_pool]
        scratch += [  # scale chunk double-buffers
            pltpu.VMEM((2, 1, hkv, pair, bsp), ks_pool.dtype),
            pltpu.VMEM((2, 1, hkv, pair, bsp), vs_pool.dtype),
        ]
        aliases = {6: 1, 7: 2, 8: 3, 9: 4}
    else:
        aliases = {6: 1, 7: 2}
    scratch += [
        pltpu.VMEM((b, hkv, nwin, dh * pair), kq_pool.dtype),  # write window
        pltpu.VMEM((b, hkv, nwin, dh * pair), vq_pool.dtype),
    ]
    if quant:
        scratch += [  # scale-row write windows
            pltpu.VMEM((b, hkv, pair, bsp), k_pool["s"].dtype),
            pltpu.VMEM((b, hkv, pair, bsp), v_pool["s"].dtype),
        ]
    scratch += [
        pltpu.VMEM((1, hq), jnp.float32),                  # running max
        pltpu.VMEM((1, hq), jnp.float32),                  # running sum
        pltpu.VMEM((1, hq, dh), jnp.float32),              # accumulator
        pltpu.SemaphoreType.DMA((4 if quant else 2, b)),   # write sems
        pltpu.SemaphoreType.DMA((2, 4 if quant else 2)),   # read sems
    ]
    out = pl.pallas_call(
        kernel,
        name="dstpu_block_decode_step",
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        input_output_aliases=aliases,
        compiler_params=_compiler_params(vmem),
        interpret=(jax.default_backend() != "tpu" if interpret is None
                   else interpret),
    )(*operands)
    if quant:
        attn, k_out, v_out, ks_out, vs_out = out
        return (attn[:, None], {"q": k_out, "s": ks_out},
                {"q": v_out, "s": vs_out})
    attn, k_out, v_out = out
    return attn[:, None], k_out, v_out


class SlotWalk(NamedTuple):
    """What the per-slot kernel walks by, made once a decode step
    (:func:`slot_walk`) and shared by every layer's call."""
    order: jax.Array       # [B] int32: active slots by descending length,
    #                        then the inactive ones
    n_active: jax.Array    # [1] int32


def slot_walk(lengths, active=None) -> SlotWalk:
    """The walk order of a decode step over per-slot ``lengths [B]``:
    ``active [B]`` (bool, or 0 / 1; ``None``: every slot) slots first, the
    longest first, so a group of neighbours in the order is of like length;
    ties and the inactive tail keep slot order. A freed slot's stale length
    does not matter: it sorts behind every active slot."""
    lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    act = jnp.ones(lengths.shape, bool) if active is None \
        else jnp.asarray(active).reshape(-1) != 0
    order = jnp.argsort(jnp.where(act, -lengths, 1), stable=True)
    return SlotWalk(order.astype(jnp.int32),
                    jnp.sum(act).astype(jnp.int32).reshape(1))


def _slot_plan(b: int, hkv: int, s_max: int, dh: int, itemsize: int,
               dv: Optional[int] = None, hq: Optional[int] = None):
    """(bg, cs) of the per-slot walk from the geometry it is handed, by
    measurement on the v5e. ``cs`` is the cache rows a loop step covers; the
    DMAs stay ``_SLOT_CHUNK`` rows, ``cs // _SLOT_CHUNK`` a row a step, so a
    row's tail rounds up to 128 under every plan
    (:func:`decode_rows_fetched`). A row has its own DMAs, so ``bg`` is free
    of the uniform plan's one-DMA-covers-the-group sizing: it is how many
    rows of like length share one loop step and one masked compute pass.

    A loop step has a cost that its bytes do not explain (a dependent chain
    DMA wait -> scores -> maximum -> exp -> weighted sum, not overlapped
    from one step to the next), and a masked row is computed whole. One
    layer's call alone, us, wrapped anew for each plan (PERF.md, PR 58;
    ``bg x cs``, all with DMAs of 128; in brackets one DMA a step):

    ==========================  =====  =====  =====  =====  =====  =====
    geometry, slots live        4x128  2x256  4x512  2x512  1x512  1x1024
    ==========================  =====  =====  =====  =====  =====  =====
    MiMo global, 3 at 4k-14k      359    161    159    136    127    126
      (16, 4, 16384, 256/128)           (154)  (143)  (128)  (127)
    the same, 6 at 4k-15k         534    270    279    248    251    247
    the same, 16 at 2k-15k        972                  555    566    552
    Solar, 2 at 7k and 15k        378    150    171    135    133    133
      (16, 8, 16384, 128)
    the same, 8 at 2k-15k         524                  358    353    354
    K-EXAONE, 10 at 0.1k-3.9k     177    124    128    118    118
      (32, 8, 4096, 128)
    the same, 32                  475    399    384    387    394
    hybrid, 24 at 0.1k-1.9k       214    149    119    120    164
      (64, 8, 2048, 64 packed)
    serve-chat, 6 at 0.1k-0.9k     45     41     52     43     42
      (32, 20, 1024, 64 packed)
    the same, 32 at 0.1k-1k       155    156    171    166    176
    ==========================  =====  =====  =====  =====  =====  =====

    So where a slot can hold thousands of rows (32 DMAs and more) a step
    covers 512, and a group is as many rows as keep that step's float32
    score tile ``[bg, hq, cs]`` inside ``_SCORE_TILE`` (one row at 64 query
    heads: nothing masked is computed, and a step of 512 rows needs no
    company to pay for itself). At 1,024 rows nothing gains and the plan is
    PR 33's, measured there at gpt2-large's geometry (36 layers: 2.38 /
    2.48 / 2.89 / 3.87 ms at ``bg`` 2 / 4 / 8 / 16 with 18 of 32 slots
    active, 4.15 / 4.01 / 4.28 ms at 2 / 4 / 8 with all 32). The hybrid's
    2,048 rows would gain and are left at ``(4, 128)`` by ISSUE 58: the
    next issue's (ROADMAP S1). A ring (``s_max`` the window) is one chunk.
    The four chunk buffers (2 slots x {K, V}) of a group stay inside
    ``_SLOT_BUFFERS``."""
    dv = dh if dv is None else dv
    long_rows = s_max >= 32 * _SLOT_CHUNK
    cs = 4 * _SLOT_CHUNK if long_rows else _SLOT_CHUNK
    bg = next(g for g in (4, 2, 1) if b % g == 0)
    while bg > 1 and (
            2 * bg * hkv * cs * (dh + dv) * itemsize > _SLOT_BUFFERS
            or (long_rows and (hq or 0) * bg * cs * 4 > _SCORE_TILE)):
        bg //= 2
    return bg, cs


def count_walk(long_step: bool, kernel: str = "decode") -> None:
    """Say in the program's registry which per-slot walk was traced:
    ``decode/traced_walk_long`` (a loop step of more than ``_SLOT_CHUNK``
    rows) or ``decode/traced_walk_128``; ``mla/...`` for ``kernel="mla"``
    (ops/mla_decode_step.py). Both exist from the first call on."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = [reg.counter(f"{kernel}/traced_walk_{n}")
                for n in ("128", "long")]
    counters[bool(long_step)].inc()


def decode_rows_fetched(active_lengths, cs: int = _SLOT_CHUNK) -> int:
    """Cache rows the per-slot walk fetches in one layer of a decode step,
    in plain integers for the host's bookkeeping: ``active_lengths`` are
    the ACTIVE slots' cache lengths (the rows before the token fed now),
    each rounded up to the walk's chunk; an inactive slot fetches nothing,
    whatever its stale length. The live rows among them are
    ``sum(active_lengths)``."""
    return sum(-(-int(n) // cs) * cs for n in active_lengths)


def fused_decode_step(q: jax.Array, k_full: jax.Array, v_full: jax.Array,
                      k_new: jax.Array, v_new: jax.Array,
                      layer, idx, *, active=None,
                      scale: Optional[float] = None,
                      interpret: Optional[bool] = None,
                      plan: Optional[dict] = None, ring: bool = False,
                      sink: Optional[jax.Array] = None):
    """One decode layer-step against the FULL stacked cache.

    q:            [B, 1, Hq, Dh]  — the new token's queries
    k_full/v_full:[L, B, Hkv, S, Dh] head-major stacked caches (carry)
    k_new/v_new:  [B, 1, Hkv, Dh]  — the new token's K/V (not yet written)
    layer:        scalar int32 — layer index
    idx:          scalar int32 first free cache position, or a PER-SLOT
                  [B] int32 vector of valid lengths (continuous batching,
                  serving/engine.py) — each active row then writes at and
                  attends over its own prefix, and fetches its own prefix
                  only (:func:`_slot_kernel`).
    active:       per-slot ``idx`` only: which slots decode this step — a
                  ``[B]`` mask (bool, or 0 / 1), or the :class:`SlotWalk`
                  made of it once a step (:func:`slot_walk`); ``None``:
                  every slot. An inactive slot's cache is neither read nor
                  written and its output row is zero.
    ring:         per-slot ``idx`` only: the cache's ``S`` rows are a ring
                  over positions (a sliding-window layer whose window is
                  ``S``: position ``p`` lives at row ``p % S``). A slot
                  at position ``idx`` writes at ``idx % S``, walks its
                  ``min(idx, S)`` live rows and leaves out the row it
                  writes, so it attends the last ``S`` positions with the
                  new one and nothing older.
    sink:         per-slot ``idx`` only: ``[Hq]`` learned logits, one a
                  query head, that take part in the softmax's denominator
                  and carry no value (``p_j = exp(s_j) / (exp(sink) + sum
                  exp(s))``); they are the running softmax's first state.
    plan:         optional measured-plan override (the autotune
                  harness's candidate; ops/autotune.py entries are
                  consulted otherwise — ``_resolve_plan``). The per-slot
                  walk takes ``bg`` / ``cs`` from :func:`_slot_plan`, by
                  the cache's geometry, unless the override names them;
                  its DMAs are ``_SLOT_CHUNK`` rows under every plan.

    Keys and values of two widths (``v_full [L, B, Hkv, S, Dv]``, ``v_new
    [B, 1, Hkv, Dv]``, unpacked rows, per-slot ``idx``): the scores are over
    ``Dh``, the result is ``[B, 1, Hq, Dv]``.

    Returns ``(attn [B, 1, Hq, Dv], k_full, v_full)`` with the caches
    updated in place (the returned caches alias the inputs).
    """
    b, t, hq, dh = q.shape
    assert t == 1, "fused_decode_step is the single-token path"
    l, _, hkv, s_rows, d_last = k_full.shape
    dv = v_new.shape[3]
    two = dv != dh               # keys and values of two widths
    pair = d_last // dh          # caller may pass an already-packed cache
    s_max = s_rows * pair
    assert supports(hq, hkv, s_max, dh, dv), (hq, hkv, s_max, dh, dv)
    assert not two or (pair == 1 and v_full.shape[4] == dv), \
        (k_full.shape, v_full.shape)
    assert pair in (1, 128 // dh if dh < 128 else 1), (d_last, dh)
    want_pair = 128 // dh if dh < 128 else 1
    sc = float(scale) if scale is not None else dh ** -0.5
    itemsize = jnp.dtype(k_full.dtype).itemsize
    bg, cs, vmem, mha = _resolve_plan(b, hkv, s_max, dh, itemsize,
                                      override=plan)

    qf = q.transpose(0, 2, 1, 3)                   # [B, Hq, 1, Dh]
    kn = k_new.transpose(0, 2, 1, 3)               # [B, Hkv, 1, Dh]
    vn = v_new.transpose(0, 2, 1, 3)
    if want_pair > 1:
        # pair-row window select needs the token's Dh values present in
        # every lane slice
        kn = jnp.concatenate([kn] * want_pair, axis=-1)
        vn = jnp.concatenate([vn] * want_pair, axis=-1)
    if pair == want_pair:
        kview, vview = k_full, v_full              # already packed (models
        # allocate the packed form so no repack copy rides the carry)
    else:
        kview = k_full.reshape(l, b, hkv, s_max // want_pair, dh * want_pair)
        vview = v_full.reshape(l, b, hkv, s_max // want_pair, dh * want_pair)
    pair = want_pair
    layer_a = jnp.asarray(layer, jnp.int32).reshape(1)
    idx_a = jnp.asarray(idx, jnp.int32).reshape(-1)
    assert idx_a.shape[0] in (1, b), (idx_a.shape, b)
    per_slot = idx_a.shape[0] > 1  # [1] degenerates to the uniform path

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem_spec = pl.BlockSpec(memory_space=pltpu.VMEM)
    scalars = [layer_a, idx_a]
    geometry = dict(b=b, hq=hq, hkv=hkv, dh=dh, pair=pair, scale=sc, mha=mha)
    assert per_slot or not (ring or two or sink is not None), \
        "a ring, two widths and a sink are the per-slot walks'"
    if two:
        geometry["dv"] = dv
    if per_slot:
        walk = active if isinstance(active, SlotWalk) \
            else slot_walk(idx_a, active)
        if not (plan and {"bg", "cs"} <= plan.keys()):
            bg, cs = _slot_plan(b, hkv, s_max, dh, itemsize, dv=dv, hq=hq)
        count_walk(cs > _SLOT_CHUNK)
        if ring:
            scalars = [layer_a, jnp.minimum(idx_a, s_max), walk.order,
                       walk.n_active, idx_a % s_max]
            kernel = functools.partial(_ring_slot_kernel, bg=bg, cs=cs,
                                       **geometry)
        else:
            scalars += [walk.order, walk.n_active]
            kernel = functools.partial(_slot_kernel, bg=bg, cs=cs,
                                       **geometry)
    else:
        kernel = functools.partial(_kernel, bg=bg, cs=cs, **geometry)
    n_scalar = len(scalars)
    operands = [qf, kn, vn]
    if sink is not None:
        kernel = functools.partial(_sink_kernel, kernel, n_scalar)
        operands.append(sink.astype(jnp.float32).reshape(1, hq))
    dvp = dv if two else dh * pair                 # a value row as cached
    chunk = (2, bg, hkv, cs // pair)
    scratch = [
        pltpu.VMEM(chunk + (dh * pair,), k_full.dtype),
        pltpu.VMEM(chunk + (dvp,), v_full.dtype),
        pltpu.VMEM((b, hkv, 8, dh * pair), k_full.dtype),  # write window
        pltpu.VMEM((b, hkv, 8, dvp), v_full.dtype),
    ]
    if per_slot:
        scratch += [  # a group's rows gathered by sorted position
            pltpu.VMEM((bg, hq, 1, dh), q.dtype),
            pltpu.VMEM((bg, hkv, 1, dh * pair), kn.dtype),
            pltpu.VMEM((bg, hkv, 1, dvp), vn.dtype),
        ]
    scratch += [
        pltpu.VMEM((bg, hq), jnp.float32),                 # running max
        pltpu.VMEM((bg, hq), jnp.float32),                 # running sum
        pltpu.VMEM((bg, hq, dv), jnp.float32),             # accumulator
        # write sems: per-row windows in the per-slot path; read sems:
        # per-row chunks there
        pltpu.SemaphoreType.DMA((2, b if per_slot else 1)),
        pltpu.SemaphoreType.DMA((2, 2, bg * (cs // _SLOT_CHUNK)) if per_slot
                                else (2, 2)),
    ]
    attn, k_out, v_out = pl.pallas_call(
        kernel,
        name="dstpu_decode_step",
        in_specs=[smem] * n_scalar + [
            vmem_spec                                # q, k_new, v_new, sink
        ] * len(operands) + [
            pl.BlockSpec(memory_space=pl.ANY),       # k_full (aliased)
            pl.BlockSpec(memory_space=pl.ANY),       # v_full (aliased)
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, dv), q.dtype),
            jax.ShapeDtypeStruct(kview.shape, k_full.dtype),
            jax.ShapeDtypeStruct(vview.shape, v_full.dtype),
        ],
        scratch_shapes=scratch,
        input_output_aliases={n_scalar + len(operands): 1,
                              n_scalar + len(operands) + 1: 2},
        compiler_params=_compiler_params(vmem),
        interpret=(jax.default_backend() != "tpu" if interpret is None
                   else interpret),
    )(*scalars, *operands, kview, vview)
    if k_out.shape != k_full.shape:
        k_out = k_out.reshape(k_full.shape)
        v_out = v_out.reshape(v_full.shape)
    return attn[:, None], k_out, v_out
