"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542, in the form of a byte-level decoder's release): a query
attends EXACTLY and causally to the keys of its own aligned window of ``W``
positions and to ONE pooled key and value for each ``c``-position chunk of
every window before its own, all under one softmax.

    chunk j = positions c j .. c j + c - 1
    a_jm   = softmax over m in chunk j of (s phi_h . k_m)
    ksum_j = sum_m a_jm k_m + mu_h;   vsum_j = sum_m a_jm v_m
    query t in window w = t // W:  exact keys m in [W w, t], logits s q_t . k_m;
                                   summaries j in [0, w W / c), logits
                                   s q_t . ksum_j
    p = softmax over the union (float32);  o_t = sum p_m v_m + sum p_j vsum_j

What a slot holds a layer (serving/kv_slots.py, the fifth kind of leaf): a
WINDOW THAT STARTS OVER, ``k_win``, ``v_win`` ``[L, B, H, W, D]`` with position
``p`` at row ``p mod W`` and the live rows ``0 .. p mod W`` (the rows behind
belong to the window before and are not read), and SUMMARY ROWS ``k_sum``,
``v_sum`` ``[L, B, H, S_max / c, D]``, chunk ``j`` at row ``j``, of which the
``(W / c) (p // W)`` of the closed windows are visible. Both are bounded
functions of the slot's one length (:func:`live_rows`).

Three pieces:

* :func:`pool_chunks`: a 16-row masked softmax and two weighted sums a head.
* the one-token step, :func:`eva_decode_step`: the token at ``p`` writes its
  key and value at window row ``p mod W``, attends the live window rows and
  the visible summary rows under one softmax, and recomputes the summary of
  chunk ``p // c`` from the window rows of that chunk, final when ``p mod c =
  c - 1`` and never visible before its window closes. No step branches on a
  window boundary and none re-reads a whole window. On a TPU it is ONE Pallas
  invocation a layer (``dstpu_eva_decode_step``), ops/decode_step.py's
  per-slot walk over TWO leaves: the visible summaries and the live window
  rows are one stream of rows (a window's ``W / c`` summaries are whole DMAs
  of 128, so the window's rows follow at a multiple of 128), a slot a loop
  step, the active slots in ``slot_walk`` order, nothing of an inactive slot
  read or written, the softmax started from the new token. The two rows go in
  place through a ``c``-row window of the window leaf (which is the chunk the
  summary is pooled from) and an 8-row window of the summary leaf.
* the prompt form, :func:`eva_prompt_block`: a block of at most ``W`` queries
  that starts a window attends the visible summary rows and then itself
  under the causal mask, one online softmax; on a TPU a gridded Pallas call
  (``dstpu_eva_prefill``) over the block's own ``[T, H D]`` operands and the
  summary leaf where it lies.

Serving-only: no VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.decode_step import (_NEG, _SLOT_CHUNK, SlotWalk,
                                           _attend_chunk, _compiler_params,
                                           slot_walk)
from deepspeed_tpu.ops.flash_attention import _NN, _NT, _dot_f32

LANES = 128
# rows of the row stream a loop step of the decode walk covers, in DMAs of
# ``_SLOT_CHUNK``. At 32 heads a DMA of 128 rows is 1 MB of keys and 1 MB of
# values, so a loop step's fixed cost is small beside its bytes, unlike the
# GQA walk's (PR 58) and the latent walk's (PR 60): one layer's call alone on
# the v5e (PERF.md, PR 61; 200 calls in one jitted loop over the donated
# leaves, 12 slots x 32,768) read 63.6 / 63.6 / 66.0 us at 128 / 256 / 512
# rows a step with one slot live at 12k, 267.4 / 267.8 / 269.4 with six at 4k
# to 24k and 505.5 / 507.1 / 509.0 with twelve at 2k to 30k (690 to 700 GB/s
# of the rows the tokens attend). 256: half the chunk buffers of 512
_STEP_ROWS = 256
# the prompt kernel: positions of a query tile and of a tile of the block's
# own keys (equal, so that a visited tile masks no query wholly), and the
# query rows one pair of matmuls takes (scores of 64 vector registers)
_PROMPT_TILE = 512
_PROMPT_ROWS = 128
_PROMPT_VMEM = 64 * 1024 * 1024
# a masked score in the prompt kernel: finite, so that a row that has seen
# nothing yet rescales by exp(0) and not by exp(-inf + inf)
_MASKED = -1e30

_TRACED = ("fused_step", "split_step", "prompt_block")


def _count(name: str) -> None:
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = {n: reg.counter("eva/traced_" + n) for n in _TRACED}
    counters[name].inc()


def record_traced(telemetry) -> None:
    """The ``eva/traced_*`` counters brought level in ``telemetry``
    (telemetry/registry.level_counters)."""
    from deepspeed_tpu.telemetry.registry import level_counters

    level_counters(telemetry, ["eva/traced_" + n for n in _TRACED])


def live_rows(position, window: int, chunk: int):
    """``(window rows, summary rows)`` the token at ``position`` (a slot's
    length: the tokens before it) attends: its window's rows up to its own,
    and the summaries of every chunk of the windows before. Integers or
    arrays."""
    return position % window + 1, (window // chunk) * (position // window)


def rows_fetched(position, window: int, chunk: int):
    """Rows the fused step brings for that token: the visible summaries and
    the window's rows before it in DMAs of ``_SLOT_CHUNK``, and the token's
    own row, which is handed in."""
    r = position % window
    return ((window // chunk) * (position // window)
            + (r + _SLOT_CHUNK - 1) // _SLOT_CHUNK * _SLOT_CHUNK + 1)


def pool_chunks(k, v, phi, mu, *, chunk: int, scale: float, live=None):
    """``k, v [..., H, T, D]`` (``T`` a multiple of ``chunk``) pooled a chunk:
    ``(ksum, vsum) [..., H, T / chunk, D]`` in float32. ``phi, mu [H, D]``;
    ``live [..., 1 or H, T]`` bool: rows that take part (a chunk the request
    has not filled yet); ``None``: all. Elementwise products and sums: a
    16-row softmax is no matmul's work, and float32 stays float32."""
    f32 = jnp.float32
    *lead, h, t, d = k.shape
    n = t // chunk
    kc = k.astype(f32).reshape(*lead, h, n, chunk, d)
    vc = v.astype(f32).reshape(*lead, h, n, chunk, d)
    logits = (kc * phi.astype(f32)[:, None, None, :]).sum(-1) * scale
    if live is not None:
        logits = jnp.where(live.reshape(*live.shape[:-1], n, chunk), logits,
                           _NEG)
    a = jax.nn.softmax(logits, axis=-1)[..., None]
    return ((a * kc).sum(-2) + mu.astype(f32)[:, None, :], (a * vc).sum(-2))


# ---------------------------------------------------------------- the step
def supports_step(heads: int, head_dim: int, window: int, chunk: int,
                  summary_rows: int) -> bool:
    """Shapes the fused step streams: rows of whole 128-lane tiles, a
    window's summaries and its rows in whole DMAs of ``_SLOT_CHUNK``, the
    chunk a whole packed sublane tile, summary rows in whole 8-row windows."""
    return (head_dim % LANES == 0 and chunk % 16 == 0
            and window % chunk == 0 and window % _SLOT_CHUNK == 0
            and (window // chunk) % _SLOT_CHUNK == 0
            and summary_rows % (window // chunk) == 0 and heads >= 1)


def _step_kernel(layer_ref, pos_ref, order_ref, n_ref, q_ref, kn_ref, vn_ref,
                 phi_ref, mu_ref, _kw_in, _vw_in, _ks_in, _vs_in,
                 attn_ref, kw_ref, vw_ref, ks_ref, vs_ref,
                 kbuf, vbuf, kwin, vwin, kswin, vswin, m_ref, l_ref, acc_ref,
                 wsem, rsem, *, b: int, cs: int, h: int, d: int, window: int,
                 chunk: int, scale: float, mha: str):
    """ops/decode_step._slot_kernel over two leaves, a slot a loop step:
    ``pos_ref [B]`` each slot's length (the new token's position),
    ``order_ref [B]`` the active slots first, ``n_ref [1]`` how many. Sorted
    position ``p`` walks the rows ``[0, ns + r)`` of ONE stream: the ``ns``
    visible summary rows, then the ``r = pos mod W`` window rows before the
    token's own; DMA ``u`` of the stream comes from the summary leaf while
    ``128 u < ns`` and from the window leaf behind. The next slot's first
    chunk is prefetched under this slot's last. ``vbuf`` is zeroed on entry
    (a part whose DMA was skipped is multiplied by a probability of zero)."""
    layer = layer_ref[0]
    n_act = n_ref[0]
    dma = _SLOT_CHUNK
    parts = cs // dma
    per_window = window // chunk
    wins = (kw_ref, vw_ref)
    sums = (ks_ref, vs_ref)

    def slot_at(p):
        return order_ref[jnp.minimum(p, b - 1)]

    def geometry(p):
        """(slot, row in the window, visible summary rows, DMAs) of ``p``; a
        position behind the active ones sees no summary and has no DMA."""
        s = slot_at(p)
        pos = pos_ref[s]
        r = jax.lax.rem(pos, window)
        ns = jnp.where(p < n_act, (pos // window) * per_window, 0)
        units = jnp.where(p < n_act, ns // dma + (r + dma - 1) // dma, 0)
        return s, r, ns, units

    # ---- the new token's two rows: the chunk's rows of the window leaf and
    # an 8-row window of the summary leaf, read, changed, written back
    def win_copy(p, t, back: bool):
        s, r, _, _ = geometry(p)
        hbm = wins[t].at[layer, pl.ds(s, 1), :,
                         pl.ds((r // chunk) * chunk, chunk), :]
        here = (kwin, vwin)[t].at[pl.ds(p, 1)]
        return pltpu.make_async_copy(here, hbm, wsem.at[t, p]) if back \
            else pltpu.make_async_copy(hbm, here, wsem.at[t, p])

    def sum_copy(p, t, back: bool):
        s = slot_at(p)
        j = pos_ref[s] // chunk
        hbm = sums[t].at[layer, pl.ds(s, 1), :, pl.ds((j // 8) * 8, 8), :]
        here = (kswin, vswin)[t].at[pl.ds(p, 1)]
        return pltpu.make_async_copy(here, hbm, wsem.at[2 + t, p]) if back \
            else pltpu.make_async_copy(hbm, here, wsem.at[2 + t, p])

    def each_active(fn):
        def step(p, _):
            fn(p)
            return 0
        jax.lax.fori_loop(0, n_act, step, 0)

    def fetch_windows(p):
        for t in (0, 1):
            win_copy(p, t, False).start()
            sum_copy(p, t, False).start()

    def insert_token(p):
        s, r, _, _ = geometry(p)
        at = jax.lax.rem(r, chunk)
        rows = jax.lax.broadcasted_iota(jnp.int32, (1, h, chunk, d), 2)
        for t, new in ((0, kn_ref), (1, vn_ref)):
            win_copy(p, t, False).wait()
            win = (kwin, vwin)[t]
            win[pl.ds(p, 1)] = jnp.where(rows == at, new[pl.ds(s, 1)],
                                         win[pl.ds(p, 1)])
            win_copy(p, t, True).start()
        # the chunk's summary from its rows so far, the token's own included
        f32 = jnp.float32
        kc = kwin[pl.ds(p, 1)].astype(f32)               # [1, H, c, D]
        vc = vwin[pl.ds(p, 1)].astype(f32)
        logits = jnp.sum(kc * phi_ref[...], -1) * scale  # [1, H, c]
        live = jax.lax.broadcasted_iota(jnp.int32, (1, h, chunk), 2) <= at
        logits = jnp.where(live, logits, _NEG)
        e = jnp.exp(logits - logits.max(-1, keepdims=True))
        a = (e / e.sum(-1, keepdims=True))[:, :, :, None]
        # (a dead row holds an earlier window's finite values: 0 * finite)
        pooled = (jnp.sum(a * kc, 2, keepdims=True) + mu_ref[...],
                  jnp.sum(a * vc, 2, keepdims=True))     # [1, H, 1, D]
        j = jax.lax.rem(pos_ref[s] // chunk, 8)
        rows8 = jax.lax.broadcasted_iota(jnp.int32, (1, h, 8, d), 2)
        for t in (0, 1):
            sum_copy(p, t, False).wait()
            win = (kswin, vswin)[t]
            win[pl.ds(p, 1)] = jnp.where(
                rows8 == j, pooled[t].astype(win.dtype), win[pl.ds(p, 1)])
            sum_copy(p, t, True).start()

    def drain_windows(p):
        for t in (0, 1):
            win_copy(p, t, True).wait()
            sum_copy(p, t, True).wait()

    # ---- the walk
    def unit_copies(p, c, u, slot, fn, which=(0, 1)):
        """``fn`` on the DMAs (``which``: 0 the key's, 1 the value's) of part
        ``u`` of chunk ``c`` of ``p``'s stream, where it has one: from the
        summary leaf or from the window leaf."""
        s, _, ns, units = geometry(p)
        ua = c * parts + u
        su = ns // dma

        def copies(leaves, row):
            # (clamped: a part known when traced is checked against the leaf
            # before its ``when`` is; under the ``when`` it is in bounds)
            row = jnp.minimum(row, leaves[0].shape[3] - dma)
            for t in which:
                fn(pltpu.make_async_copy(
                    leaves[t].at[layer, pl.ds(s, 1), :, pl.ds(row, dma), :],
                    (kbuf, vbuf)[t].at[slot, :, :, pl.ds(u * dma, dma), :],
                    rsem.at[slot, t, u]))

        pl.when(ua < su)(lambda: copies(sums, ua * dma))
        pl.when(jnp.logical_and(ua >= su, ua < units))(
            lambda: copies(wins, (ua - su) * dma))

    def start_chunk(p, c, slot):
        for u in range(parts):
            unit_copies(p, c, u, slot, lambda cp: cp.start())

    def wait_chunk(p, c, slot, which):
        for u in range(parts):
            unit_copies(p, c, u, slot, lambda cp: cp.wait(), (which,))

    attn_ref[...] = jnp.zeros_like(attn_ref)
    vbuf[...] = jnp.zeros_like(vbuf)
    each_active(fetch_windows)
    start_chunk(0, 0, 0)
    each_active(insert_token)        # overlaps with chunk 0's flight

    def one_slot(p, t):
        s, r, ns, units = geometry(p)
        nch = (units + parts - 1) // parts
        reach = ns + r
        attend = functools.partial(
            _attend_chunk, q_ref[pl.ds(s, 1)], m_ref=m_ref, l_ref=l_ref,
            acc_ref=acc_ref, hq=h, hkv=h, dh=d, pair=1, scale=scale, mha=mha)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the new token first: one live position of an 8-row chunk
        attend(jnp.broadcast_to(kn_ref[pl.ds(s, 1)], (1, h, 8, d)),
               lambda: jnp.broadcast_to(vn_ref[pl.ds(s, 1)], (1, h, 8, d)),
               lambda _, shape: jax.lax.broadcasted_iota(
                   jnp.int32, shape, 2) < 1)

        @pl.when(nch == 0)
        def _():     # nothing to walk (a slot's first position): the next
            # slot's first chunk has no last chunk of this one to start under
            start_chunk(p + 1, 0, jax.lax.rem(t, 2))

        def body(c, t):
            slot = jax.lax.rem(t, 2)
            more = c + 1 < nch
            start_chunk(jnp.where(more, p, p + 1),
                        jnp.where(more, c + 1, 0), 1 - slot)
            wait_chunk(p, c, slot, 0)    # K first: V is still in flight

            def load_v():
                wait_chunk(p, c, slot, 1)
                return vbuf[slot]

            attend(kbuf[slot], load_v,
                   lambda _, shape: c * cs + jax.lax.broadcasted_iota(
                       jnp.int32, shape, 2) < reach)
            return t + 1

        t = jax.lax.fori_loop(0, nch, body, t)
        attn_ref[pl.ds(s, 1)] = (
            acc_ref[...] / l_ref[...][:, :, None]).astype(attn_ref.dtype)
        return t

    jax.lax.fori_loop(0, n_act, one_slot, 0)
    each_active(drain_windows)       # before the kernel exits


def fused_eva_decode_step(q, k_win, v_win, k_sum, v_sum, k_new, v_new, phi,
                          mu, layer, pos, *, chunk: int, scale: float,
                          active=None, interpret: Optional[bool] = None,
                          cs: Optional[int] = None, mha: str = "mxu"):
    """One layer's step against the FULL stacked leaves, in place.

    q, k_new, v_new: ``[B, H, D]`` the new token's (rotated) query, key, value
    k_win, v_win:    ``[L, B, H, W, D]``; k_sum, v_sum: ``[L, B, H, S/c, D]``
    phi, mu:         ``[H, D]`` the pooling's direction and the pooled key's
                     offset
    pos:             ``[B]`` int32 per-slot lengths (the token's position)
    active:          which slots decode: a ``[B]`` mask or the step's
                     ``SlotWalk``; ``None``: every slot

    Returns ``(attn [B, H, D], k_win, v_win, k_sum, v_sum)``, the leaves
    aliasing the inputs. An inactive slot's rows are neither read nor written
    and its ``attn`` is zero."""
    b, h, d = q.shape
    l, _, _, window, _ = k_win.shape
    rows = k_sum.shape[3]
    assert k_win.shape == v_win.shape == (l, b, h, window, d) and \
        k_sum.shape == v_sum.shape == (l, b, h, rows, d) and \
        supports_step(h, d, window, chunk, rows), (q.shape, k_win.shape,
                                                   k_sum.shape, chunk)
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    walk = active if isinstance(active, SlotWalk) else slot_walk(pos, active)
    cs = _STEP_ROWS if cs is None else cs
    assert cs % _SLOT_CHUNK == 0, cs
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1), pos, walk.order,
               walk.n_active]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    dt = k_win.dtype
    f32 = jnp.float32
    n_scalar = len(scalars)
    leaves = (k_win, v_win, k_sum, v_sum)
    out = pl.pallas_call(
        functools.partial(_step_kernel, b=b, cs=cs, h=h, d=d, window=window,
                          chunk=chunk, scale=float(scale), mha=mha),
        name="dstpu_eva_decode_step",
        in_specs=[smem] * n_scalar + [vmem] * 5 + [hbm] * 4,
        out_specs=[vmem] + [hbm] * 4,
        out_shape=[jax.ShapeDtypeStruct((b, h, d), q.dtype)]
        + [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in leaves],
        scratch_shapes=[
            pltpu.VMEM((2, 1, h, cs, d), dt),         # key chunks
            pltpu.VMEM((2, 1, h, cs, d), dt),         # value chunks
            pltpu.VMEM((b, h, chunk, d), dt),         # the chunk's key rows
            pltpu.VMEM((b, h, chunk, d), dt),         # and value rows
            pltpu.VMEM((b, h, 8, d), dt),             # summary write windows
            pltpu.VMEM((b, h, 8, d), dt),
            pltpu.VMEM((1, h), f32),                  # running max
            pltpu.VMEM((1, h), f32),                  # running sum
            pltpu.VMEM((1, h, d), f32),               # accumulator
            pltpu.SemaphoreType.DMA((4, b)),
            pltpu.SemaphoreType.DMA((2, 2, cs // _SLOT_CHUNK)),
        ],
        input_output_aliases={n_scalar + 5 + i: 1 + i for i in range(4)},
        compiler_params=_compiler_params(),
        interpret=(jax.default_backend() != "tpu" if interpret is None
                   else interpret),
    )(*scalars, q[:, :, None, :], k_new.astype(dt)[:, :, None, :],
      v_new.astype(dt)[:, :, None, :],
      phi.astype(f32).reshape(1, h, 1, d), mu.astype(f32).reshape(1, h, 1, d),
      *leaves)
    return tuple(out)


def _drop_inactive(rows, active, bound: int):
    """Row indices with an inactive slot's sent out of bounds, where a
    scatter drops it."""
    return rows if active is None else jnp.where(active, rows, bound)


def split_eva_decode_step(q, k_win, v_win, k_sum, v_sum, k_new, v_new, phi,
                          mu, layer, pos, *, chunk: int, scale: float,
                          active=None):
    """:func:`fused_eva_decode_step` in XLA's own operations (a CPU, shapes
    the kernel does not take): the same three things in the same order,
    scatters that drop an inactive slot's rows, masks over whole leaves."""
    b, h, d = q.shape
    window, rows = k_win.shape[3], k_sum.shape[3]
    f32 = jnp.float32
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    if active is not None:
        active = jnp.asarray(active).reshape(-1) != 0
    slots = jnp.arange(b)
    r, ns = pos % window, (window // chunk) * (pos // window)
    at = _drop_inactive(r, active, window)
    k_win = k_win.at[layer, slots, :, at, :].set(
        k_new.astype(k_win.dtype), mode="drop")
    v_win = v_win.at[layer, slots, :, at, :].set(
        v_new.astype(v_win.dtype), mode="drop")
    kw = jax.lax.dynamic_index_in_dim(k_win, layer, 0, keepdims=False)
    vw = jax.lax.dynamic_index_in_dim(v_win, layer, 0, keepdims=False)
    ks = jax.lax.dynamic_index_in_dim(k_sum, layer, 0, keepdims=False)
    vs = jax.lax.dynamic_index_in_dim(v_sum, layer, 0, keepdims=False)
    logits = jnp.concatenate(
        [jnp.where((jnp.arange(window)[None] <= r[:, None])[:, None],
                   jnp.einsum("bhd,bhwd->bhw", q, kw).astype(f32) * scale,
                   _NEG),
         jnp.where((jnp.arange(rows)[None] < ns[:, None])[:, None],
                   jnp.einsum("bhd,bhjd->bhj", q, ks).astype(f32) * scale,
                   _NEG)], axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    attn = (jnp.einsum("bhw,bhwd->bhd", p[..., :window].astype(vw.dtype), vw)
            + jnp.einsum("bhj,bhjd->bhd", p[..., window:].astype(vs.dtype),
                         vs)).astype(q.dtype)
    # the chunk the token is in, pooled from its rows so far
    first = (r // chunk) * chunk
    cut = jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(a, s, chunk, 1))
    live = (first[:, None] + jnp.arange(chunk)[None] <= r[:, None])[:, None]
    ksum, vsum = pool_chunks(cut(kw, first), cut(vw, first), phi, mu,
                             chunk=chunk, scale=scale, live=live)
    j = _drop_inactive(pos // chunk, active, rows)
    k_sum = k_sum.at[layer, slots, :, j, :].set(
        ksum[:, :, 0].astype(k_sum.dtype), mode="drop")
    v_sum = v_sum.at[layer, slots, :, j, :].set(
        vsum[:, :, 0].astype(v_sum.dtype), mode="drop")
    if active is not None:
        attn = jnp.where(active[:, None, None], attn, 0)
    return attn, k_win, v_win, k_sum, v_sum


def eva_decode_step(q, k_win, v_win, k_sum, v_sum, k_new, v_new, phi, mu,
                    layer, pos, *, chunk: int, scale: float, active=None):
    """The one-token step, by the route the backend and the shapes allow
    (counted: ``eva/traced_fused_step``, ``eva/traced_split_step``)."""
    b, h, d = q.shape
    fused = (jax.default_backend() == "tpu"
             and supports_step(h, d, k_win.shape[3], chunk, k_sum.shape[3]))
    _count("fused_step" if fused else "split_step")
    if fused:
        return fused_eva_decode_step(
            q, k_win, v_win, k_sum, v_sum, k_new, v_new, phi, mu, layer, pos,
            chunk=chunk, scale=scale, active=active)
    if isinstance(active, SlotWalk):    # the walk's first n_active slots
        active = jnp.zeros((b,), bool).at[active.order].set(
            jnp.arange(b) < active.n_active[0])
    return split_eva_decode_step(
        q, k_win, v_win, k_sum, v_sum, k_new, v_new, phi, mu, layer, pos,
        chunk=chunk, scale=scale, active=active)


# --------------------------------------------------------- the prompt form
def supports_prompt(t: int, head_dim: int, summary_rows: int) -> bool:
    """Shapes the prompt kernel takes: whole query tiles of whole row
    chunks, rows of whole lane tiles, summary rows in whole tiles."""
    return (head_dim % LANES == 0 and t % _PROMPT_TILE == 0
            and summary_rows % LANES == 0)


def _summary_tile(rows: int) -> int:
    return next(n for n in (512, 256, 128) if rows % n == 0)


def _prompt_kernel(layer_ref, ns_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, n_st: int, scale: float):
    """A grid cell: batch row ``b``, head, query tile ``i``, column tile
    ``kv``: the first ``n_st`` are tiles of the summary leaf (visited while
    they hold a visible row, ``ns_ref [B]`` of them), the rest tiles of the
    block's own keys up to the diagonal."""
    del layer_ref                    # the index maps read it
    b, i, kv = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    tq, tk, ts = q_ref.shape[0], k_ref.shape[0], ks_ref.shape[0]
    n = _PROMPT_ROWS
    ns = ns_ref[b]

    @pl.when(kv == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(keys, values, visible):
        """``visible(rows, cols)``: the mask of a chunk of query rows."""
        cols = keys.shape[0]
        for c in range(tq // n):
            rs = pl.ds(c * n, n)
            s = _dot_f32(q_ref[rs, :], keys[...], _NT) * scale
            ok = visible(
                c * n + jax.lax.broadcasted_iota(jnp.int32, (n, cols), 0),
                jax.lax.broadcasted_iota(jnp.int32, (n, cols), 1))
            s = jnp.where(ok, s, _MASKED)
            m_prev = m_ref[rs]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_ref[rs] = l_ref[rs] * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[rs] = acc_ref[rs] * corr + _dot_f32(
                p.astype(values.dtype), values[...], _NN)
            m_ref[rs] = m_new

    @pl.when(jnp.logical_and(kv < n_st, kv * ts < ns))
    def _():
        tile(ks_ref, vs_ref, lambda rows, cols: cols + kv * ts < ns)

    j = kv - n_st

    @pl.when(jnp.logical_and(kv >= n_st, j * tk <= i * tq + tq - 1))
    def _():
        tile(k_ref, v_ref,
             lambda rows, cols: cols + j * tk <= rows + i * tq)

    @pl.when(kv == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def fused_eva_prompt_block(q, k, v, k_sum, v_sum, layer, visible, *,
                           scale: float, interpret: Optional[bool] = None):
    """One layer's attention of a block that starts a window.

    q, k, v:      ``[B, T, H, D]`` the block's own (rotated) queries, keys and
                  values
    k_sum, v_sum: ``[L, B, H, S/c, D]`` the summary leaf, where it lies
    visible:      ``[B]`` int32, the summary rows a query of the block sees

    Returns ``[B, T, H, D]`` in ``q``'s dtype."""
    b, t, h, d = q.shape
    l, _, _, rows, _ = k_sum.shape
    assert k.shape == v.shape == q.shape and supports_prompt(t, d, rows) \
        and k_sum.shape == v_sum.shape == (l, b, h, rows, d), \
        (q.shape, k.shape, k_sum.shape)
    tq = tk = _PROMPT_TILE
    ts = _summary_tile(rows)
    n_st = rows // ts
    i32 = jnp.int32
    scalars = [jnp.asarray(layer, i32).reshape(1),
               jnp.broadcast_to(jnp.asarray(visible, i32), (b,))]

    def q_at(bi, hi, i, kv, *_):
        return bi, i, hi

    def own_at(bi, hi, i, kv, *_):
        # parked on tile 0 under the summaries, and on the diagonal's behind
        return bi, jnp.clip(kv - n_st, 0, (i * tq + tq - 1) // tk), hi

    def sum_at(bi, hi, i, kv, layer_ref, ns_ref):
        last = jnp.maximum((ns_ref[bi] + ts - 1) // ts - 1, 0)
        return layer_ref[0], bi, hi, jnp.minimum(kv, last), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, h, t // tq, n_st + t // tk),
        in_specs=[pl.BlockSpec((None, tq, d), q_at),
                  pl.BlockSpec((None, tk, d), own_at),
                  pl.BlockSpec((None, tk, d), own_at),
                  pl.BlockSpec((None, None, None, ts, d), sum_at),
                  pl.BlockSpec((None, None, None, ts, d), sum_at)],
        out_specs=pl.BlockSpec((None, tq, d), q_at),
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),     # running max
                        pltpu.VMEM((tq, 1), jnp.float32),     # running sum
                        pltpu.VMEM((tq, d), jnp.float32)])    # accumulator
    interp = jax.default_backend() != "tpu" if interpret is None \
        else interpret
    kw = {} if interp else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_PROMPT_VMEM)}
    out = pl.pallas_call(
        functools.partial(_prompt_kernel, n_st=n_st, scale=float(scale)),
        name="dstpu_eva_prefill",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h * d), q.dtype),
        interpret=interp,
        **kw,
    )(*scalars, q.reshape(b, t, h * d), k.reshape(b, t, h * d),
      v.reshape(b, t, h * d), k_sum, v_sum)
    return out.reshape(b, t, h, d)


def plain_eva_prompt_block(q, k, v, k_sum, v_sum, layer, visible, *,
                           scale: float):
    """:func:`fused_eva_prompt_block` as one softmax over the concatenated
    scores (a CPU, a block that is no whole number of tiles)."""
    b, t, h, d = q.shape
    rows = k_sum.shape[3]
    f32 = jnp.float32
    ks = jax.lax.dynamic_index_in_dim(k_sum, layer, 0, keepdims=False)
    vs = jax.lax.dynamic_index_in_dim(v_sum, layer, 0, keepdims=False)
    visible = jnp.broadcast_to(jnp.asarray(visible, jnp.int32), (b,))
    i = jnp.arange(t)
    logits = jnp.concatenate(
        [jnp.where(i[:, None] >= i[None, :],
                   jnp.einsum("bthd,bshd->bhts", q, k).astype(f32) * scale,
                   _NEG),
         jnp.where((jnp.arange(rows)[None] < visible[:, None])[:, None, None],
                   jnp.einsum("bthd,bhjd->bhtj", q, ks).astype(f32) * scale,
                   _NEG)], axis=-1)
    p = jax.nn.softmax(logits, axis=-1)
    return (jnp.einsum("bhts,bshd->bthd", p[..., :t].astype(v.dtype), v)
            + jnp.einsum("bhtj,bhjd->bthd", p[..., t:].astype(vs.dtype), vs)
            ).astype(q.dtype)


def eva_prompt_block(q, k, v, k_sum, v_sum, layer, visible, *, scale: float):
    """A block's attention by the route the backend and the shapes allow
    (counted: ``eva/traced_prompt_block``)."""
    _count("prompt_block")
    if jax.default_backend() == "tpu" and supports_prompt(
            q.shape[1], q.shape[3], k_sum.shape[3]):
        return fused_eva_prompt_block(q, k, v, k_sum, v_sum, layer, visible,
                                      scale=scale)
    return plain_eva_prompt_block(q, k, v, k_sum, v_sum, layer, visible,
                                  scale=scale)
