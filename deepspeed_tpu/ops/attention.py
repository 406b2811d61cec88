"""Attention ops.

Reference counterpart: the fused attention kernels in
``csrc/transformer/softmax_kernels.cu`` / ``csrc/transformer/inference/csrc/softmax.cu``
(training + inference softmax with causal/alibi masking). Here the canonical
implementation is jnp (XLA fuses QK^T→mask→softmax→PV well on the MXU);
a Pallas flash-attention fast path (``flash_attention.py``) overrides it via
the op registry on real TPU backends for long sequences.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

# B=1 fused-decode routing threshold: bytes of ONE layer's K cache at
# full allocated length (V doubles the actual stream; the threshold is
# calibrated in the same K-only unit). The kernel's fixed per-invocation
# cost (~28 us/call at 125M geometry, round-4 profile) only amortizes
# when the cache stream is fat enough: measured LOSS at 125M B=1 Dh=64
# (~1.0 MB K/layer: einsum 0.46 vs kernel 0.60 ms/tok) and WIN at 6.7B
# B=1 Dh=128 (~5.2 MB K/layer: 19.15 -> 18.25 ms/tok). 2 MB splits the
# two measured points (neither taken on the v5e: ROADMAP D5), and the env
# override lets a measurement force either path without a code change
# (ADVICE round 5: the fixed per-layer DMA overhead was never measured at
# B=1/Dh>=128).
_B1_FUSED_MIN_BYTES = int(os.environ.get(
    "DEEPSPEED_TPU_B1_FUSED_MIN_BYTES", 2 * 1024 * 1024))

# float32 scores one einsum of a prompt block may hold; a block with more is
# walked in query blocks (decode_attention). No cell's prefill up to PR 33
# comes near (32 heads x 1024 x 1024 are 134 MB).
_SCORE_BLOCK_BYTES = 256 * 1024 * 1024


def multihead_attention(
    q: jax.Array,  # [B, T, H, Dh]
    k: jax.Array,  # [B, S, H, Dh]
    v: jax.Array,  # [B, S, H, Dh]
    *,
    causal: bool = True,
    mask: Optional[jax.Array] = None,  # [B, 1, T, S] additive or bool
    bias: Optional[jax.Array] = None,  # e.g. alibi [H, T, S]
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Reference (jnp) attention; softmax in fp32 regardless of input dtype."""
    *_, t, h, dh = q.shape
    s = k.shape[1]
    scale = scale if scale is not None else dh ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k, precision=None).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        causal_mask = jnp.tril(jnp.ones((t, s), dtype=bool), k=s - t)
        logits = jnp.where(causal_mask[None, None], logits, jnp.finfo(jnp.float32).min)
    if mask is not None:
        if mask.dtype == bool:
            logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    probs = probs.astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def kv_pack_factor(head_dim: int) -> int:
    """Token-pair packing factor for the stacked KV cache. TPU HBM tiles
    bf16 buffers T(8, 128): a [.., S, Dh] cache with Dh < 128 is
    lane-PADDED to 128 in HBM (2x the footprint and stream traffic at
    Dh = 64). Packing ``pair = 128 / Dh`` adjacent tokens into one
    [.., S/pair, Dh*pair] row keeps the buffer dense and gives the fused
    decode kernel (ops/decode_step.py) 128-aligned DMA slices."""
    if head_dim >= 128 or 128 % head_dim:
        return 1
    return 128 // head_dim


def key_row_width(head_dim: int) -> int:
    """Lanes a cached key row of ``head_dim`` takes where it is wider than
    one 128-lane tile and no whole number of them (192 -> 256): the TPU's
    HBM tiling pads such a row to whole tiles whatever the leaf's shape says,
    and the kernels' DMA slices must be whole tiles, so the leaf states the
    padding and the model hands queries and keys over with zeros behind the
    live lanes (:func:`pad_lanes`): a dot product over them is the live
    one."""
    if head_dim <= 128 or head_dim % 128 == 0:
        return head_dim
    return -(-head_dim // 128) * 128


def pad_lanes(x: jax.Array, width: int) -> jax.Array:
    """``x [..., d]`` with zeros behind its last dimension up to ``width``."""
    d = x.shape[-1]
    if d == width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - d)])


def alloc_kv_cache(num_layers: int, batch: int, num_kv_heads: int,
                   max_len: int, head_dim: int, dtype, *,
                   packed: bool = True):
    """Zeros for one stacked cache tensor (call twice for K and V).
    Packed shape [L, B, H, S/pair, Dh*pair] unless ``packed=False``
    (models whose decode always needs the einsum path — ALiBi bias or
    per-layer windows — keep the plain [L, B, H, S, Dh] form). Batch-1
    caches with Dh < 128 also stay unpacked: there the fused kernel's
    fixed per-layer overhead loses to the einsum (measured 0.60 vs 0.46
    ms/tok at 125M B=1), and the allocation shape is what routes
    :func:`cached_attention`. A ``max_len`` the fused kernel can't
    stream (not 128-aligned) also stays unpacked — a packed cache the
    kernel rejects would pay the unpack view EVERY step."""
    pair = (kv_pack_factor(head_dim)
            if (packed and batch >= 2 and max_len % 128 == 0) else 1)
    assert max_len % max(pair, 1) == 0, (max_len, pair)
    # The barrier makes the zeros a real buffer. Without it the TPU compiler
    # (libtpu 0.0.34) turns an in-program cache that the layer scan updates
    # into an uninitialised AllocateBuffer, also where the prompt fills only
    # its head: generate()'s batch-1 prefill then attends over whatever the
    # tail of the buffer held, and 0 * NaN made every logit NaN on the v5e
    # (tests/unit/ops/test_tpu_compile.py pins the compiled text).
    return jax.lax.optimization_barrier(
        jnp.zeros((num_layers, batch, num_kv_heads, max_len // pair,
                   head_dim * pair), dtype))


def cache_seq_len(k_full, head_dim: int) -> int:
    """Max sequence length of a (possibly packed) stacked cache."""
    return k_full.shape[3] * (k_full.shape[4] // head_dim)


def cached_attention(q, k_full, v_full, k_new, v_new, layer, idx, *,
                     scale=None, bias=None, window=None, block_table=None,
                     active=None):
    """One cached-attention layer step: write the new block's K/V into the
    full stacked [L, B, Hkv, S, Dh] caches (possibly token-pair packed,
    see :func:`kv_pack_factor`), attend, return ``(attn, k_full, v_full)``.

    ``idx`` is the first free cache position: a scalar for the uniform
    batch-decode path, or a PER-SLOT ``[B]`` vector for the continuous-
    batching serving runtime (serving/engine.py) — each batch row then
    writes at and attends over ITS OWN valid prefix. ``active`` goes with
    the per-slot vector: which slots decode this step, as a ``[B]`` mask or
    as the ``SlotWalk`` the decode program made of it once for all layers
    (ops/decode_step.slot_walk). The fused step neither reads nor writes an
    inactive slot's rows and returns zeros for it; the einsum path ignores
    it (there an inactive slot's masked write lands behind its length).

    The geometry is each LEAF's: ``v_full`` and ``v_new`` may be of another
    last dimension than ``k_full`` and ``q`` (unpacked rows then), and the
    result is as wide as the values.

    Single-token decode on TPU routes to the fused Pallas step
    (ops/decode_step.py): the kernel owns BOTH the cache write and the
    streaming read, so XLA keeps the decode loop's cache carry in the
    default streaming-friendly layout instead of the einsum-oriented one
    a ``dynamic_update_slice`` write anchors (round-4 root cause of
    batch-8 decode at half its roofline). Everything
    else (prefill blocks, ALiBi bias, sliding windows, CPU) takes the
    einsum path, view-unpacking packed caches first.

    ``block_table`` switches to the BLOCK-PAGED addressing mode (ISSUE 6,
    serving/kv_blocks.py): ``k_full``/``v_full`` are then a global block
    POOL ``[L, N_blocks, Hkv, bs(/pair), Dh(*pair)]`` and each batch
    row's KV lives in the blocks named by its ``block_table[b]`` row —
    logical token position p maps to pool block ``table[b, p // bs]``,
    row ``p % bs``. ``idx`` must be the per-slot [B] length vector. The
    table is TRACED DATA (int32 [B, max_blocks]), never a shape: one
    compiled program serves every block assignment, which is what lets
    the radix prefix cache remap blocks between steps without a single
    recompile.

    A QUANTIZED pool (ISSUE 12, serving/kv_quant.py) arrives as a
    ``{"q": payload, "s": scales}`` pytree in place of each cache
    array — block-paged only (the write path quantizes on store, the
    read paths dequantize in-register; the models carry the tree
    opaquely, so one code path serves every kv_dtype)."""
    if block_table is not None:
        return _block_cached_attention(q, k_full, v_full, k_new, v_new,
                                       layer, idx, block_table,
                                       scale=scale, bias=bias,
                                       window=window)
    if isinstance(k_full, dict):
        raise ValueError(
            "quantized KV pools are block-paged only: cached_attention "
            "got a {'q','s'} cache without a block_table (serving must "
            "run with prefix_cache=True to use kv_dtype)")
    b, t = q.shape[0], q.shape[1]
    dh = q.shape[3]
    pair = k_full.shape[4] // dh
    if (t == 1 and bias is None and window is None
            and jax.default_backend() == "tpu"
            # the allocation shape routes: an unpacked Dh<128 cache means
            # alloc_kv_cache decided the einsum path wins (batch 1)
            and pair == kv_pack_factor(dh)
            # B=1 with a thin per-layer cache stream: the kernel's fixed
            # per-invocation cost loses to the einsum (see
            # _B1_FUSED_MIN_BYTES above; only Dh>=128 geometries reach
            # this — Dh<128 B=1 is already routed by allocation shape)
            and (b >= 2 or k_full.shape[2] * k_full.shape[3] * k_full.shape[4]
                 * jnp.dtype(k_full.dtype).itemsize >= _B1_FUSED_MIN_BYTES)):
        from deepspeed_tpu.ops.decode_step import fused_decode_step, supports

        if supports(q.shape[2], k_full.shape[2],
                    k_full.shape[3] * pair, dh, v_new.shape[3]):
            return fused_decode_step(q, k_full, v_full, k_new, v_new,
                                     layer, idx, scale=scale,
                                     active=active)
    if pair > 1:  # unpack for the einsum path (free on CPU; prefill-only
        # on TPU, where the repack copy is once per generate, not per step)
        l, b, hkv, sp, dhp = k_full.shape
        shape = (l, b, hkv, sp * pair, dh)
        ku, vu, kl, vl = write_kv_cache(
            k_full.reshape(shape), v_full.reshape(shape), k_new, v_new,
            layer, idx)
        attn = decode_attention(q, kl, vl, idx, scale=scale, bias=bias,
                                window=window)
        return attn, ku.reshape(k_full.shape), vu.reshape(v_full.shape)
    k_full, v_full, kl, vl = write_kv_cache(k_full, v_full, k_new, v_new,
                                            layer, idx)
    attn = decode_attention(q, kl, vl, idx, scale=scale, bias=bias,
                            window=window)
    return attn, k_full, v_full


def sink_softmax(logits, sink):
    """Softmax over the last axis of float32 ``logits`` with a SINK in the
    denominator: ``sink`` (broadcastable against ``logits``' leading axes,
    last axis 1) is one more column of the softmax, dropped behind it:
    ``p_j = exp(s_j) / (exp(sink) + sum_j' exp(s_j'))``."""
    column = jnp.broadcast_to(sink.astype(jnp.float32),
                              logits.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([logits, column], axis=-1),
                          axis=-1)[..., :-1]


def ring_positions(idx, rows: int):
    """The position each row of a ring of ``rows`` rows holds for a request
    whose next position is ``idx`` (``[B]``): the latest position before
    ``idx`` that is congruent to the row; negative where the row was never
    written. -> ``[B, rows]``."""
    last = idx[:, None] - 1
    return last - (last - jnp.arange(rows)[None, :]) % rows


def window_cached_attention(q, k_ring, v_ring, k_new, v_new, layer, idx, *,
                            scale=None, valid=None, active=None, sink=None):
    """One sliding-window layer step against a RING cache ``[L, B, Hkv, W,
    Dh]`` whose ``W`` rows are the window: position ``p`` lives at row ``p %
    W``, so a slot's cache does not grow with the request. A query at
    position ``i`` attends positions ``j <= i`` with ``i - j < W``; keys carry
    their own position (rotary applied before caching), so the order of the
    rows does not matter. Returns ``(attn, k_ring, v_ring)``.

    ``idx``: first position of the block, a scalar or a per-slot ``[B]``
    vector; ``valid`` (scalar or ``[B]``): how many of the block's ``T``
    positions are real (bucket padding behind a prompt; 0 for a slot that
    does not decode): the ring takes the last ``W`` REAL positions. ``v_ring``
    may be of another last dimension than ``k_ring``. ``sink [Hq]``: a learned
    logit a query head that takes part in the softmax's denominator and has
    no value, ``p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))``.

    One token on a TPU goes through the fused decode step with ``ring=True``
    (ops/decode_step.py: a slot fetches its ``min(idx, W)`` live rows and
    leaves out the row the new token takes, which in a full ring holds the
    position that has just left the window). Everything else is an einsum
    over the ring's rows and the block's own keys: a prompt block in query
    blocks of ``W``, each against the ``W`` keys before it and its own (a
    band, never ``T x T`` scores), the ring being the block before the first."""
    b, t, hq, dh = q.shape
    hkv, w, dv = k_ring.shape[2], k_ring.shape[3], v_ring.shape[4]
    assert k_ring.shape[4] == dh, "a ring cache is not token-pair packed"
    idx_v = jnp.broadcast_to(jnp.asarray(idx, jnp.int32), (b,))
    if t == 1 and b >= 2 and jax.default_backend() == "tpu":
        from deepspeed_tpu.ops.decode_step import fused_decode_step, supports

        if supports(hq, hkv, w, dh, dv) and dh % 128 == 0:
            if active is None and valid is not None:
                active = jnp.broadcast_to(valid, (b,)) > 0
            return fused_decode_step(q, k_ring, v_ring, k_new, v_new, layer,
                                     idx_v, scale=scale, active=active,
                                     ring=True, sink=sink)
    scale = scale if scale is not None else dh ** -0.5
    rep = hq // hkv
    kl = jax.lax.dynamic_index_in_dim(k_ring, layer, 0, keepdims=False)
    vl = jax.lax.dynamic_index_in_dim(v_ring, layer, 0, keepdims=False)
    kn = k_new.transpose(0, 2, 1, 3).astype(kl.dtype)       # [B, Hkv, T, Dh]
    vn = v_new.transpose(0, 2, 1, 3).astype(vl.dtype)
    k_all = jnp.concatenate([kl, kn], axis=2)               # [B, Hkv, W+T, Dh]
    v_all = jnp.concatenate([vl, vn], axis=2)
    q_pos = idx_v[:, None] + jnp.arange(t)[None, :]         # [B, T]
    pos = jnp.concatenate([ring_positions(idx_v, w), q_pos], axis=1)

    def masked_softmax(logits, kp, qp, lead, rep_axis):
        """Softmax over the keys at positions ``kp`` a query at ``qp`` may
        see; ``lead`` places the mask among the logits' head dimensions,
        ``rep_axis`` is the logits' axis of a key-value head's query heads
        (the key-value heads are axis 1)."""
        ok = (kp >= 0) & (kp <= qp) & (qp - kp < w)
        logits = jnp.where(ok[lead], logits, jnp.finfo(jnp.float32).min)
        if sink is None:
            return jax.nn.softmax(logits, axis=-1)
        shape = [1] * logits.ndim
        shape[1], shape[rep_axis] = hkv, rep
        return sink_softmax(logits, sink.reshape(shape))

    qg = q.reshape(b, t, hkv, rep, dh)
    if t > w and t % w == 0:
        n = t // w

        def band(a, lead):   # blocks i and i + 1 of W + T rows, side by side
            blocks = a.reshape(lead + (n + 1, w) + a.shape[len(lead) + 1:])
            ax = len(lead)
            return jnp.concatenate(
                [jax.lax.slice_in_dim(blocks, 0, n, axis=ax),
                 jax.lax.slice_in_dim(blocks, 1, n + 1, axis=ax)], axis=ax + 1)

        kb, vb = band(k_all, (b, hkv)), band(v_all, (b, hkv))  # [B,Hkv,n,2W,Dh]
        pb = band(pos, (b,))                                   # [B, n, 2W]
        qb = qg.reshape(b, n, w, hkv, rep, dh)
        logits = jnp.einsum("bnqkrd,bknsd->bknrqs", qb, kb
                            ).astype(jnp.float32) * scale
        probs = masked_softmax(
            logits, pb[:, :, None, :],                      # [B, n, 1, 2W]
            q_pos.reshape(b, n, w)[:, :, :, None],          # [B, n, W, 1]
            (slice(None), None, slice(None), None), 3).astype(vb.dtype)
        attn = jnp.einsum("bknrqs,bknsd->bnqkrd", probs, vb
                          ).reshape(b, t, hq, dv)
    else:
        logits = jnp.einsum("btkrd,bksd->bkrts", qg, k_all
                            ).astype(jnp.float32) * scale
        probs = masked_softmax(                             # [B, T, W+T]
            logits, pos[:, None, :], q_pos[:, :, None],
            (slice(None), None, None), 2).astype(v_all.dtype)
        attn = jnp.einsum("bkrts,bksd->btkrd", probs, v_all
                          ).reshape(b, t, hq, dv)
    # the ring after the block: row r takes the last real position congruent
    # to r, if the block holds one
    n_real = jnp.full((b,), t, jnp.int32) if valid is None \
        else jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
    after = ring_positions(idx_v + n_real, w)               # [B, W]
    take = (after >= idx_v[:, None])[:, None, :, None]
    j = jnp.clip(after - idx_v[:, None], 0, t - 1)[:, None, :, None]
    kl = jnp.where(take, jnp.take_along_axis(kn, j, axis=2), kl)
    vl = jnp.where(take, jnp.take_along_axis(vn, j, axis=2), vl)
    return (attn,
            jax.lax.dynamic_update_index_in_dim(k_ring, kl, layer, 0),
            jax.lax.dynamic_update_index_in_dim(v_ring, vl, layer, 0))


def write_kv_cache(k_full, v_full, k_new, v_new, layer, idx):
    """Write one block's new K/V ([B, T, Hkv, Dh]) into the full stacked
    head-major [L, B, Hkv, S, Dh] caches at (layer, idx) — the per-token
    slice write that XLA keeps in place on the layer-scan carry. Returns
    (k_full, v_full, k_layer, v_layer) with the per-layer [B, Hkv, S, Dh]
    views ready for :func:`decode_attention`.

    A per-slot ``[B]`` idx vector (continuous batching) scatters each
    row's block at its own position instead of one shared slice start:
    row b's token j lands at cache position ``idx[b] + j``. T > 1 is the
    speculative-decoding verify path (serving/speculative.py) — all
    ``k + 1`` candidate tokens' K/V are written in one pass, and entries
    past the accepted prefix stay dead behind the per-slot length vector
    (rollback-by-masking, no copies). ``mode="drop"`` makes any position
    past the allocation a silent no-op instead of undefined behavior
    (this path does not know which slots are active: an inactive slot
    carries a stale length, and its masked garbage write, which the fused
    decode step skips, must never land out of bounds)."""
    if jnp.ndim(idx) == 1:
        b, t = k_new.shape[0], k_new.shape[1]
        rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
        pos = idx[:, None] + jnp.arange(t)[None, :]              # [B, T]
        k_full = k_full.at[layer, rows, :, pos, :].set(
            k_new.astype(k_full.dtype), mode="drop")
        v_full = v_full.at[layer, rows, :, pos, :].set(
            v_new.astype(v_full.dtype), mode="drop")
    else:
        k_full = jax.lax.dynamic_update_slice(
            k_full, k_new.transpose(0, 2, 1, 3)[None].astype(k_full.dtype),
            (layer, 0, 0, idx, 0))
        v_full = jax.lax.dynamic_update_slice(
            v_full, v_new.transpose(0, 2, 1, 3)[None].astype(v_full.dtype),
            (layer, 0, 0, idx, 0))
    return (k_full, v_full,
            jax.lax.dynamic_index_in_dim(k_full, layer, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_full, layer, 0, keepdims=False))


def write_slot_rows(full, prefix, slot):
    """Insert ONE leaf of a prefilled single-sequence prefix cache into slot
    ``slot`` of the persistent slot-paged cache (serving/kv_slots.py),
    whatever the leaf's kind: ``prefix [L, 1, ...]`` from a batch-1 bucket
    prefill into ``full [L, B, ...]`` with ONE dynamic_update_slice at batch
    position ``slot``, at the origin of every axis behind the slot's.

    A leaf whose persistent form packs token pairs into its minor dimension
    (``k``, ``v`` at ``Dh < 128``: ``[L, B, Hkv, S / pair, Dh * pair]``;
    alloc_kv_cache never packs batch 1, so the prefix comes unpacked) gets
    the bucket rows viewed in the persistent pack factor (a free bitcast,
    requires ``T_bucket % pair == 0``); any other (a latent row ``[L, B, S,
    W]``) is written as it is. Rows past the request's true length hold
    pad-token garbage; the per-slot length vector masks them until the decode
    loop overwrites them one by one."""
    assert prefix.shape[1] == 1 and prefix.ndim == full.ndim, \
        (prefix.shape, full.shape)
    pair = full.shape[-1] // prefix.shape[-1]
    if pair > 1:
        t_b = prefix.shape[-2]
        assert t_b % pair == 0, (t_b, pair)
        prefix = prefix.reshape(prefix.shape[:-2]
                                + (t_b // pair, prefix.shape[-1] * pair))
    zero = jnp.zeros((), jnp.int32)
    starts = (zero, jnp.asarray(slot, jnp.int32)) + (zero,) * (full.ndim - 2)
    return jax.lax.dynamic_update_slice(full, prefix.astype(full.dtype),
                                        starts)


def extract_slot_row(full, slot):
    """Slot ``slot``'s row of one per-slot state leaf ``[L, B, ...]`` as a
    batch-1 leaf ``[L, 1, ...]`` (traced slot)."""
    return jax.lax.dynamic_slice_in_dim(
        full, jnp.asarray(slot, jnp.int32), 1, 1)


def insert_slot_row(full, row, slot):
    """Write a batch-1 row ``[L, 1, ...]`` (what :func:`extract_slot_row`
    gave, or a fresh batch-1 state) into slot ``slot`` of a per-slot state
    leaf: one ``dynamic_update_slice``, traced slot."""
    zero = jnp.zeros((), jnp.int32)
    starts = (zero, jnp.asarray(slot, jnp.int32)) + (zero,) * (full.ndim - 2)
    return jax.lax.dynamic_update_slice(full, row.astype(full.dtype), starts)


def extract_slot_kv(k_full, v_full, slot):
    """Slice slot ``slot``'s row pair out of the slot-paged caches as a
    batch-1 stacked cache ``[L, 1, Hkv, S(/pair), Dh(*pair)]`` in the
    persistent pack factor. Two callers (ISSUE 8):

      * the chunked-prefill program steps the sliced row as a batch-1
        cache (the chunk's queries attend over the slot's own
        already-prefilled prefix) and writes it back;
      * preemption swap-out hands the row to the host swap buffer.

    ``slot`` is a traced scalar — one compiled program serves every
    slot."""
    return extract_slot_row(k_full, slot), extract_slot_row(v_full, slot)


def insert_slot_kv(k_full, v_full, k_row, v_row, slot):
    """Write a batch-1 row pair (the persistent pack factor — exactly
    what :func:`extract_slot_kv` produced) back into slot ``slot`` of
    the slot-paged caches: the chunk-prefill write-back and the
    preemption swap-in (ISSUE 8). One ``dynamic_update_slice`` per
    cache, traced slot."""
    return (insert_slot_row(k_full, k_row, slot),
            insert_slot_row(v_full, v_row, slot))


def gather_pool_blocks(k_pool, v_pool, table):
    """Gather one slot's table-named block CONTENTS
    ``[L, MB, Hkv, bs(/pair), Dh(*pair)]`` out of the block pool — the
    device half of preemption swap-OUT (ISSUE 8): the engine
    device_gets the result into the host swap buffer before freeing the
    blocks. Sentinel table entries gather the pool's garbage row
    (finite junk the restore never uploads). ``table`` is traced int32
    ``[MB]`` — one compiled program serves every block assignment.
    Quantized ``{"q", "s"}`` pools gather payloads AND scales (both are
    block-major on axis 1), so the host copy round-trips the exact
    stored bytes — which is also why quantized swap halves the host
    transfer."""
    def g(leaf):
        return jnp.take(leaf, table, axis=1, mode="clip")

    return (jax.tree_util.tree_map(g, k_pool),
            jax.tree_util.tree_map(g, v_pool))


def scatter_pool_blocks(k_pool, v_pool, k_blocks, v_blocks, dst):
    """Scatter ``[L, MB, ...]`` block contents into the pool rows named
    by ``dst`` — preemption swap-IN (ISSUE 8). Entries the restore must
    SKIP (radix re-matched shared blocks, never-written tail blocks)
    point at the pool's garbage row: their writes land where nobody
    reads, so the program's shapes never vary with how much actually
    needs uploading (duplicate garbage-row writes race only against
    each other). Quantized pools scatter payloads and scales leaf-wise
    — host bytes land back bit-identically (no requantization on a
    swap round trip; pinned by tests)."""
    def s(pool_leaf, blk_leaf):
        return pool_leaf.at[:, dst].set(blk_leaf.astype(pool_leaf.dtype),
                                        mode="drop")

    return (jax.tree_util.tree_map(s, k_pool, k_blocks),
            jax.tree_util.tree_map(s, v_pool, v_blocks))


def pool_block_size(k_pool, head_dim: int) -> int:
    """Tokens per block of a (possibly token-pair packed, possibly
    quantized) KV block pool ``[L, N, Hkv, bs/pair, Dh*pair]``."""
    from deepspeed_tpu.serving.kv_quant import pool_payload

    p = pool_payload(k_pool)
    return p.shape[3] * (p.shape[4] // head_dim)


def write_kv_blocks(k_pool, v_pool, k_new, v_new, layer, idx, block_table):
    """Scatter one step's new K/V ([B, T, Hkv, Dh]) into the UNPACKED
    block pool ``[L, N+1, Hkv, bs, Dh]`` through the per-slot block
    table: row b's token j lands at logical position ``idx[b] + j``,
    i.e. pool block ``block_table[b, pos // bs]``, row ``pos % bs``.

    Sentinel semantics (serving/kv_blocks.py): the pool's LAST physical
    row is a permanent garbage block that is never allocated — the
    engine parks freed/unallocated table entries there, and logical
    overflow past the table width routes there too. Inactive slots
    carry stale lengths and sentinel tables, and their masked writes
    must never corrupt a live block — with prefix sharing a stale table
    entry may meanwhile be pinned by another request, so the garbage
    row is a correctness requirement, not a nicety (and it lets the
    fused Pallas block kernel skip per-row write predication
    entirely).

    Quantized pools (ISSUE 12): ``k_pool``/``v_pool`` may be the
    ``{"q", "s"}`` pytree with an UNPACKED payload view
    ``[L, N+1, Hkv, bs, Dh]`` — this is the quantize-on-store seam:
    each new token's symmetric per-head scale is computed HERE
    (serving/kv_quant.kv_quantize), its payload scatters exactly like
    the unquantized write, and the scale scatters into the pair-grouped
    scale array at ``[layer, block, :, pos % pair, (pos % bs) // pair]``."""
    if isinstance(k_pool, dict):
        from deepspeed_tpu.serving.kv_quant import kv_quantize

        kq_pool, ks_pool = k_pool["q"], k_pool["s"]
        vq_pool, vs_pool = v_pool["q"], v_pool["s"]
        kv_dtype = "int8" if kq_pool.dtype == jnp.int8 else "fp8"
        n_phys, bs = kq_pool.shape[1], kq_pool.shape[3]
        pair = ks_pool.shape[3]
        b, t = k_new.shape[0], k_new.shape[1]
        mb = block_table.shape[1]
        pos = idx[:, None] + jnp.arange(t)[None, :]              # [B, T]
        jb = pos // bs
        pb = jnp.take_along_axis(block_table, jnp.clip(jb, 0, mb - 1),
                                 axis=1)
        pb = jnp.where(jb < mb, pb, n_phys - 1)
        wi = pos % bs
        half, row = wi % pair, wi // pair        # pair-grouped scale idx
        kq, ks = kv_quantize(k_new, kv_dtype)    # [B,T,Hkv,Dh], [B,T,Hkv]
        vq, vs = kv_quantize(v_new, kv_dtype)
        k_pool = {"q": kq_pool.at[layer, pb, :, wi, :].set(kq, mode="drop"),
                  "s": ks_pool.at[layer, pb, :, half, row].set(
                      ks, mode="drop")}
        v_pool = {"q": vq_pool.at[layer, pb, :, wi, :].set(vq, mode="drop"),
                  "s": vs_pool.at[layer, pb, :, half, row].set(
                      vs, mode="drop")}
        return k_pool, v_pool
    n_phys, bs = k_pool.shape[1], k_pool.shape[3]
    b, t = k_new.shape[0], k_new.shape[1]
    mb = block_table.shape[1]
    pos = idx[:, None] + jnp.arange(t)[None, :]                  # [B, T]
    jb = pos // bs
    pb = jnp.take_along_axis(block_table, jnp.clip(jb, 0, mb - 1), axis=1)
    pb = jnp.where(jb < mb, pb, n_phys - 1)  # overflow -> garbage row
    wi = pos % bs
    k_pool = k_pool.at[layer, pb, :, wi, :].set(
        k_new.astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[layer, pb, :, wi, :].set(
        v_new.astype(v_pool.dtype), mode="drop")
    return k_pool, v_pool


def gather_block_kv(pool_layer, block_table, out_dtype=None):
    """Per-layer slot view of the block pool: gather each row's blocks
    ``[N+1, Hkv, bs, Dh] -> [B, Hkv, MB * bs, Dh]`` (the shape
    :func:`decode_attention` expects). Sentinel table entries read the
    garbage row — garbage, but FINITE (a fill-value NaN would poison
    the PV einsum through the masked positions' 0 * NaN), and always
    dead behind the per-slot length mask; ``mode="clip"`` keeps even a
    corrupt table in range.

    A quantized ``{"q", "s"}`` layer gathers payload AND scales, then
    dequantizes into ``out_dtype`` (required for quantized layers —
    callers pass the query dtype); garbage-row reads dequantize to
    finite junk exactly like the unquantized pool's (zero at
    allocation, arbitrary once inactive slots' masked writes land
    there — always dead behind the length mask either way)."""
    if isinstance(pool_layer, dict):
        from deepspeed_tpu.serving.kv_quant import (kv_dequantize,
                                                    scales_token_order)

        assert out_dtype is not None, \
            "gather_block_kv on a quantized layer needs out_dtype"
        ql, sl = pool_layer["q"], pool_layer["s"]    # [N,Hkv,bs,Dh] /
        n, hkv, bs, dh = ql.shape                    # [N,Hkv,pair,bs/pair]
        b, mb = block_table.shape
        kb = jnp.take(ql, block_table, axis=0, mode="clip")
        sb = scales_token_order(
            jnp.take(sl, block_table, axis=0, mode="clip"))  # [B,MB,Hkv,bs]
        kb = kb.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mb * bs, dh)
        sb = sb.transpose(0, 2, 1, 3).reshape(b, hkv, mb * bs)
        return kv_dequantize(kb, sb, out_dtype)
    n, hkv, bs, dh = pool_layer.shape
    b, mb = block_table.shape
    kb = jnp.take(pool_layer, block_table, axis=0, mode="clip")
    return kb.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mb * bs, dh)


def _block_cached_attention(q, k_pool, v_pool, k_new, v_new, layer, idx,
                            block_table, *, scale=None, bias=None,
                            window=None):
    """Block-paged cached attention (see :func:`cached_attention`): write
    the new tokens' K/V through the block table, then attend each row
    over its own gathered block chain. Single-token decode on TPU routes
    to the fused Pallas block-table step (ops/decode_step.py) — the
    kernel streams each slot's valid blocks straight from the pool, so
    paging costs no extra HBM copy; everything else (suffix prefill,
    speculative verify blocks, CPU) takes the gather + einsum path.

    Quantized pools (ISSUE 12): same two routes — the fused kernel
    streams int8/fp8 payload chunks and dequantizes in-register (half
    the HBM bytes per chunk), the einsum path writes through the
    quantizing :func:`write_kv_blocks` and reads through the
    dequantizing :func:`gather_block_kv`. Both attend over the
    quantize->dequantize image of the NEW token too (the value future
    steps will read), so kernel and einsum outputs agree across
    backends."""
    quant = isinstance(k_pool, dict)
    kq_arr = k_pool["q"] if quant else k_pool
    b, t = q.shape[0], q.shape[1]
    dh = q.shape[3]
    l, n, hkv, bsp, dhp = kq_arr.shape
    pair = dhp // dh
    bs = bsp * pair
    assert jnp.ndim(idx) == 1, \
        "block-paged attention needs the per-slot length vector"
    if (t == 1 and bias is None and window is None
            and jax.default_backend() == "tpu"
            and pair == kv_pack_factor(dh)):
        from deepspeed_tpu.ops.decode_step import (fused_block_decode_step,
                                                   supports_block)

        if supports_block(q.shape[2], hkv, bs, dh):
            return fused_block_decode_step(q, k_pool, v_pool, k_new, v_new,
                                           layer, idx, block_table,
                                           scale=scale)
    shape = (l, n, hkv, bs, dh)
    if quant:
        ku = {"q": k_pool["q"].reshape(shape) if pair > 1 else k_pool["q"],
              "s": k_pool["s"]}
        vu = {"q": v_pool["q"].reshape(shape) if pair > 1 else v_pool["q"],
              "s": v_pool["s"]}
    else:
        ku = k_pool.reshape(shape) if pair > 1 else k_pool
        vu = v_pool.reshape(shape) if pair > 1 else v_pool
    ku, vu = write_kv_blocks(ku, vu, k_new, v_new, layer, idx, block_table)

    def at_layer(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0,
                                                   keepdims=False), tree)

    kl, vl = at_layer(ku), at_layer(vu)
    attn = decode_attention(
        q, gather_block_kv(kl, block_table, q.dtype),
        gather_block_kv(vl, block_table, q.dtype), idx,
        scale=scale, bias=bias, window=window)
    if quant:
        return (attn,
                {"q": ku["q"].reshape(k_pool["q"].shape), "s": ku["s"]},
                {"q": vu["q"].reshape(v_pool["q"].shape), "s": vu["s"]})
    return attn, ku.reshape(k_pool.shape), vu.reshape(v_pool.shape)


def decode_attention(
    q: jax.Array,        # [B, T, Hq, Dh] current block's queries
    k_cache: jax.Array,  # [B, Hkv, S_max, Dh] — new keys ALREADY written
    v_cache: jax.Array,  # [B, Hkv, S_max, Dh]
    cache_index: jax.Array,  # scalar int — first position of q in the cache
    #                          (or per-slot [B] vector, continuous batching)
    *,
    scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,    # [H, S_max] additive (alibi)
    window: Optional[jax.Array] = None,  # scalar sliding-window size
) -> jax.Array:
    """Attention of q against a cache that already holds its keys/values.

    Reference counterpart: ``softmax_context`` (csrc/transformer/inference
    pt_binding.cpp) + the inference_context.h KV workspace. Static shapes
    keep the decode loop compiled once (the CUDA-graph analog — SURVEY
    §7.12). The write side (dynamic_update_slice of the new token's K/V at
    ``cache_index``) lives with the cache owner — models write into the full
    stacked [L, B, H, S, Dh] cache carried through the layer scan, which XLA
    updates in place; returning per-layer cache copies through scan ys
    rewrote the entire cache every decode step (round-2 weak #2, ~4x the
    weight-streaming roofline cost at batch 8).

    The cache is stored HEAD-MAJOR ([B, H, S, Dh]): each head's [S, Dh]
    K/V block is then contiguous in HBM, so the QK^T (contract Dh) and PV
    (contract S) reads stream sequentially. With the torch-style
    [B, S, Hkv, Dh] logical shape, XLA assigned the loop-carried cache a
    token-major layout (optimal for the one-token write, 128-byte-strided
    for every read): measured ~150 GB/s effective cache streaming vs
    1.6 TB/s on weights at batch 8.

    Single-token unbiased/unwindowed decode on TPU routes to the Pallas
    flash-decode kernel (ops/flash_decode.py): valid-prefix cache reads
    via scalar-prefetch block clamping + VMEM online softmax."""
    b, t, hq, dh = q.shape
    rep_ = hq // k_cache.shape[1]
    dv = v_cache.shape[3]
    per_slot = jnp.ndim(cache_index) == 1
    if (t == 1 and bias is None and window is None and not per_slot
            and k_cache.shape[2] % 128 == 0
            and rep_ >= 8 and dv == dh
            and jax.default_backend() == "tpu"):
        # Wide-GQA only (rep >= 8): each grid cell feeds the MXU a
        # [rep, Dh] x [Dh, BS] slab. For MHA both kernel variants MEASURED
        # SLOWER than this einsum (round 4, 125M B=8: einsum 1.42 ms/tok
        # vs 5.05 MXU-cell kernel / 1.94 head-batched VPU kernel): XLA
        # lays the decode loop's cache carry out for einsum lane
        # parallelism, and a pallas operand in that layout pays a
        # relayout copy per step. Cache length
        # must tile (the engine pads its KV allocation to 128).
        from deepspeed_tpu.ops.flash_decode import flash_decode

        return flash_decode(q, k_cache, v_cache, cache_index, scale=scale)
    s_max = k_cache.shape[2]
    qb = t
    while qb % 2 == 0 and b * hq * qb * s_max * 4 > _SCORE_BLOCK_BYTES:
        qb //= 2
    if qb < t:
        # a long prompt block (64 heads x 4096 x 4096 float32 scores are
        # 4.3 GB): the queries in blocks of ``qb``, one block's scores live
        def block(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 1)
            return _dense_decode_attention(
                qi, k_cache, v_cache, cache_index + i * qb, scale=scale,
                bias=bias, window=window)

        out = jax.lax.map(block, jnp.arange(t // qb))   # [n, B, qb, Hq, Dh]
        return out.transpose(1, 0, 2, 3, 4).reshape(b, t, hq, dv)
    return _dense_decode_attention(q, k_cache, v_cache, cache_index,
                                   scale=scale, bias=bias, window=window)


def _dense_decode_attention(q, k_cache, v_cache, cache_index, *, scale, bias,
                            window):
    """:func:`decode_attention`'s einsum: every score of the block at once."""
    b, t, hq, dh = q.shape
    per_slot = jnp.ndim(cache_index) == 1
    hkv = k_cache.shape[1]
    s_max = k_cache.shape[2]
    scale = scale if scale is not None else dh ** -0.5
    # GQA: q heads grouped over kv heads (hq == hkv * rep; rep == 1 for MHA)
    rep = hq // hkv
    qg = q.reshape(b, t, hkv, rep, dh)
    logits = jnp.einsum("btkrd,bksd->bkrts", qg, k_cache).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32).reshape(
            1, hkv, rep, 1, s_max)
    # positions <= cache_index + offset are valid (causal within the new block)
    if per_slot:
        # continuous batching: each slot's own valid-prefix mask
        pos = jnp.arange(s_max)[None, None, :]                   # [1, 1, S]
        q_pos = cache_index[:, None, None] + \
            jnp.arange(t)[None, :, None]                         # [B, T, 1]
        valid = pos <= q_pos                                     # [B, T, S]
        if window is not None:
            valid = valid & (q_pos - pos < window)
        logits = jnp.where(valid[:, None, None], logits,
                           jnp.finfo(jnp.float32).min)
    else:
        pos = jnp.arange(s_max)[None, :]  # [1, S]
        q_pos = cache_index + jnp.arange(t)[:, None]  # [T, 1]
        valid = pos <= q_pos  # [T, S]
        if window is not None:
            valid = valid & (q_pos - pos < window)
        logits = jnp.where(valid[None, None, None], logits,
                           jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bkrts,bksd->btkrd", probs, v_cache)
    return out.reshape(b, t, hq, v_cache.shape[3])


def blocked_prompt_attention(q, k_layer, v_layer, q_pos, *, scale=None,
                             key_block: int = 512):
    """A prompt block's grouped-query attention against a cache that already
    holds its rows, a block of ``key_block`` keys at a time up to the
    diagonal with a running softmax: no score matrix over the context exists
    (64 heads x 2,048 queries x 16,384 keys in float32 are 8.6 GB; the query
    blocks of :func:`decode_attention` compute every key of the allocation,
    the masked half too).

    ``q [B, T, Hq, Dh]`` at the consecutive positions ``q_pos [B, T]``;
    ``k_layer [B, Hkv, S, Dh]``, ``v_layer [B, Hkv, S, Dv]`` (unpacked rows,
    ``S`` a whole number of key blocks) -> ``[B, T, Hq, Dv]`` in ``q``'s
    dtype. Key blocks past the last query's are not visited; XLA's own
    matmuls."""
    b, t, hq, dh = q.shape
    hkv, s_max, dv = k_layer.shape[1], k_layer.shape[2], v_layer.shape[3]
    rep, bk = hq // hkv, key_block
    assert s_max % bk == 0, (s_max, bk)
    scale = scale if scale is not None else dh ** -0.5
    f32 = jnp.float32
    qg = q.reshape(b, t, hkv, rep, dh)
    blocks = jnp.minimum((jnp.max(q_pos) + bk) // bk, s_max // bk)

    def body(kb, carry):
        m, l, acc = carry
        keys = jax.lax.dynamic_slice_in_dim(k_layer, kb * bk, bk, 2)
        vals = jax.lax.dynamic_slice_in_dim(v_layer, kb * bk, bk, 2)
        s = jnp.einsum("btkrd,bksd->bkrts", qg, keys,
                       preferred_element_type=f32) * scale
        live = (kb * bk + jnp.arange(bk))[None, None, :] <= q_pos[:, :, None]
        s = jnp.where(live[:, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        pv = jnp.einsum("bkrts,bksd->bkrtd", p.astype(vals.dtype), vals,
                        preferred_element_type=f32)
        return m_new, l * corr + p.sum(-1), acc * corr[..., None] + pv

    # every query sees key 0, so the first block leaves a finite maximum
    init = (jnp.full((b, hkv, rep, t), -jnp.inf, f32),
            jnp.zeros((b, hkv, rep, t), f32),
            jnp.zeros((b, hkv, rep, t, dv), f32))
    _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
    out = (acc / l[..., None]).astype(q.dtype)           # [B, Hkv, rep, T, Dv]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, t, hq, dv)
