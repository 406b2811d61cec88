"""Multi-head LATENT attention over one cached row a token, as three families
here have it (``sarvam_mla``, ``longcat_flash``, ``gigachat35``): keys and
values of all heads are up-projections of one compressed latent a token, so
the cache holds ONE row a token a layer, whatever the number of heads. What is
one family's (how
queries and the latent are projected, the rotation's frequencies, the stack
around the attention, a gate before ``Wo``) stays in its file; the cache leaf,
its two attention forms and the plain residual sum behind them are here,
once.

    [k_nope_h | v_h] = c~ Wkv_b[h];  k_r one rotated row for all heads
    score_h(i, j) = s (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i
    out = x + concat_h(softmax_j(score_h) v_h) Wo

**The cache** is one leaf ``latent [L, B, S, W]``: ``c~`` (``kv_lora_rank``),
the rotated ``k_r`` behind it, zero lanes up to ``W``, a whole number of 128
(512 + 64 -> 640): a token row that is no head's key or value. A model names
it in ``slot_state_keys`` and in ``row_state_keys`` (models/base.py), and the
serving layer handles it by that declaration (serving/kv_slots.py). ``L``
counts ATTENTION layers: a family with two of them a layer keeps ``2 L`` rows.

**Two attention forms over it, the same numbers.** A prompt block takes the
DECOMPRESSED form: a block of keys' ``k_nope`` and ``v`` are computed from
their latents and attended at head sizes ``nope + rope`` / ``v``, key blocks
walked up to the diagonal with a running softmax, so no score matrix over the
context exists (:meth:`_prompt_attention`, scope ``dstpu_mla_prefill``): on a
TPU ops/mla_prefill.py's one call a layer, which keeps a key block's scores in
VMEM, elsewhere XLA's own matmuls in a ``lax`` loop. One token takes the
ABSORBED form,

    q^_h = q_nope_h W_UK[h]^T;  score_h(j) = s (q^_h . c~(j) + q_rope_h . k_r(j))
    u_h = sum_j p_h(j) c~(j);   o_h = u_h W_UV[h]

where a cached row is key and value at once and is read once: on a TPU under
continuous batching ops/mla_decode_step.py's fused call, elsewhere an einsum
over the same leaf. Absorbed, a prompt would cost 1,088 FLOPs a (query, key,
head) for 320.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cache_positions, merge_heads, project_heads
from deepspeed_tpu.ops import mla_prefill
from deepspeed_tpu.ops.attention import multihead_attention
from deepspeed_tpu.models.stack import StackedDecoder
from deepspeed_tpu.ops.mla_decode_step import count_form, fused_mla_decode_step, supports


def latent_row_width(kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """Lanes of a cached row: latent and rotated key, padded to 128s."""
    return -(-(kv_lora_rank + qk_rope_head_dim) // 128) * 128


class LatentAttention(StackedDecoder):
    """What a model of this attention inherits, on top of the decoder's frame
    (models/stack.StackedDecoder). It reads ``self.config``
    (``num_heads``, ``kv_lora_rank``, ``qk_nope_head_dim``,
    ``qk_rope_head_dim``, ``v_head_dim``, ``row_width``, ``key_block``,
    ``score_scale``) and ``self.compute_dtype``, and calls the family's own
    ``self._projections(x, blk, pos) -> (q_nope [B,T,H,n], q_rope [B,T,H,rope]``
    rotated, ``c~ [B,T,r]``, ``k_r [B,T,rope]`` rotated) of the stream. A
    block's leaves here are ``wkv_b`` and ``wo``."""

    # per-slot state: one leaf of token rows that is no head's key or value
    slot_state_keys = ("latent",)
    row_state_keys = ("latent",)

    def _wkv_b(self, blk):
        """The layer's ``Wkv_b [r, H * (nope + v)]``. The family's walk hands the
        stack whole, so that the prompt kernel fetches a
        head's columns where they lie; every other consumer is a matmul that
        reads the layer's slice in place."""
        w = blk["wkv_b"]
        if isinstance(w, dict):
            w = jax.lax.dynamic_index_in_dim(w["__whole__"], w["__layer__"],
                                             0, keepdims=False)
        return w.astype(self.compute_dtype)

    def _up_projection(self, blk):
        """``Wkv_b`` as ``[r, H, nope + v]``: head ``h``'s ``W_UK`` are its
        first ``nope`` columns, ``W_UV`` the ``v`` behind them."""
        c = self.config
        return self._wkv_b(blk).reshape(
            c.kv_lora_rank, c.num_heads, c.qk_nope_head_dim + c.v_head_dim)

    def _prompt_attention(self, q_nope, q_rope, latent, layer, q_pos, blk,
                          valid=None):
        """The decompressed form over the cache's rows, a block of keys at a
        time up to the diagonal, running softmax: ``q_* [B, T, H, .]`` at
        the consecutive positions ``q_pos [B, T]``, of which the first
        ``valid [B]`` are real (``None``: all), against ``latent[layer]``'s
        rows, which already hold the block's own -> ``[B, T, H, v]``. On a
        TPU ops/mla_prefill.py's one call where the shapes fit (the rows of a
        query tile with no real position then come back as zeros: nothing
        real attends them), elsewhere XLA's own matmuls in a ``lax`` loop."""
        c = self.config
        b, t, h, n = q_nope.shape
        r, rope, vd = c.kv_lora_rank, c.qk_rope_head_dim, c.v_head_dim
        s_max, w = latent.shape[2], latent.shape[3]
        if jax.default_backend() == "tpu" and mla_prefill.supports(
                s_max, w, c.key_block, t):
            mla_prefill.count_traced()
            wkv_b, w_layer = blk["wkv_b"], None
            if isinstance(wkv_b, dict) and \
                    wkv_b["__whole__"].dtype == latent.dtype:
                wkv_b, w_layer = wkv_b["__whole__"], wkv_b["__layer__"]
            else:                     # a cast is a copy: of the layer alone
                wkv_b = self._wkv_b(blk).astype(latent.dtype)
            with jax.named_scope("dstpu_mla_prefill"):
                return mla_prefill.mla_prefill(
                    q_nope, q_rope, latent, wkv_b, layer, q_pos[:, 0], valid,
                    latent_width=r, scale=c.score_scale,
                    key_block=c.key_block, w_layer=w_layer)
        bk = c.key_block if s_max % c.key_block == 0 else s_max
        f32 = jnp.float32
        up = self._up_projection(blk)                     # [r, H, n + v]
        blocks = jnp.minimum((jnp.max(q_pos) + bk) // bk, s_max // bk)
        count_form(False)
        # heads lead, queries and keys whole (nope | rope): one batched dot a
        # block for the scores. Apart, the rotated key's product, which has no
        # head dimension, was lowered as a convolution inside the row maximum
        # and took longer than the scores themselves (PERF.md, PR 46)
        q_all = jnp.concatenate([q_nope, q_rope], -1).transpose(0, 2, 1, 3)

        def body(kb, carry):
            m, l, acc = carry
            rows = jax.lax.dynamic_slice(
                latent, (layer, 0, kb * bk, 0), (1, b, bk, w))[0]
            kv = jnp.einsum("bkc,che->bhke", rows[..., :r], up)
            keys = jnp.concatenate(
                [kv[..., :n], jnp.broadcast_to(
                    rows[:, None, :, r:r + rope], (b, h, bk, rope))], -1)
            s = jnp.einsum("bhtd,bhkd->bhtk", q_all, keys,
                           preferred_element_type=f32) * c.score_scale
            key_pos = kb * bk + jnp.arange(bk)
            live = key_pos[None, None, :] <= q_pos[:, :, None]   # [B, T, bk]
            s = jnp.where(live[:, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            pv = jnp.einsum("bhtk,bhkv->bhtv", p.astype(kv.dtype),
                            kv[..., n:], preferred_element_type=f32)
            return m_new, l * corr + p.sum(-1), acc * corr[..., None] + pv

        # every query sees key 0, so the first block leaves a finite maximum
        init = (jnp.full((b, h, t), -jnp.inf, f32), jnp.zeros((b, h, t), f32),
                jnp.zeros((b, h, t, vd), f32))
        with jax.named_scope("dstpu_mla_prefill"):
            _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
        return (acc / l[..., None]).astype(q_nope.dtype).transpose(0, 2, 1, 3)

    def _token_attention(self, q_nope, q_rope, row, latent, layer, idx, blk,
                         walk_):
        """The absorbed form for one token a row of the batch: ``q_nope [B,
        H, n]``, ``q_rope [B, H, rope]``, ``row [B, W]`` the token's cache
        row, not yet written -> ``(o [B, H, v], latent)``."""
        c = self.config
        b, h, n = q_nope.shape
        r, w = c.kv_lora_rank, c.row_width
        up = self._up_projection(blk)
        fence = jax.lax.optimization_barrier
        # batched over heads, the weight read where it lies: fenced from the
        # per-head work on both sides (models/base.project_heads)
        absorbed = fence(jnp.einsum("bhn,chn->bhc", q_nope, up[..., :n]))
        qcat = jnp.concatenate(
            [absorbed, q_rope,
             jnp.zeros((b, h, w - r - q_rope.shape[-1]), q_nope.dtype)], -1)
        per_slot = jnp.ndim(idx) == 1
        fused = (per_slot and jax.default_backend() == "tpu"
                 and self.fused_row_walk({"latent": latent}, b))
        with jax.named_scope("dstpu_mla_decode"):
            if fused:
                count_form(True)
                u, latent = fused_mla_decode_step(
                    qcat, latent, row, layer, idx, value_width=r,
                    scale=c.score_scale, active=walk_)
            else:
                latent = self._write_rows(latent, row[:, None], layer, idx)
                rows = jax.lax.dynamic_index_in_dim(latent, layer, 0,
                                                    keepdims=False)
                s = jnp.einsum("bhw,bsw->bhs", qcat, rows,
                               preferred_element_type=jnp.float32) \
                    * c.score_scale
                at = idx[:, None, None] if per_slot else idx
                live = jnp.arange(rows.shape[1])[None, None, :] <= at
                p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
                u = jnp.einsum("bhs,bsc->bhc", p.astype(rows.dtype),
                               rows[..., :r])
        return jnp.einsum("bhc,chv->bhv", fence(u), up[..., n:]), latent

    @staticmethod
    def _write_rows(latent, rows, layer, idx):
        """``rows [B, T, W]`` into ``latent[layer]`` from position ``idx`` on
        (a scalar, or ``[B]``: each row of the batch at its own)."""
        b, t, _ = rows.shape
        rows = rows.astype(latent.dtype)
        zero = jnp.zeros((), jnp.int32)
        if jnp.ndim(idx) == 1 and b > 1:
            slots = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
            pos = idx[:, None] + jnp.arange(t)[None, :]
            return latent.at[layer, slots, pos].set(rows, mode="drop")
        start = idx[0] if jnp.ndim(idx) == 1 else idx
        return jax.lax.dynamic_update_slice(
            latent, rows[None], (layer, zero, jnp.asarray(start, jnp.int32),
                                 zero))

    def _attention(self, x, blk, latent, at, idx, valid, walk_):
        """``x + Attn(x) Wo`` -> ``(x, latent)``: :meth:`_attend` and the
        residual sum behind ``Wo``, as two families have it. A family whose
        block does something else between the heads and the stream (a gate
        before ``Wo``, a norm behind it) takes :meth:`_attend` itself."""
        out, latent = self._attend(x, blk, latent, at, idx, valid, walk_)
        return x + merge_heads(out, blk["wo"]), latent

    def _attend(self, x, blk, latent, at, idx, valid, walk_):
        """What stands before ``Wo`` -> ``(out [B, T, H, v], latent)``: the
        family's projections of ``x``, then the plain causal form where
        there is no cache (``latent`` None), the absorbed step for one token
        a row, or the block's rows written at cache layer ``at`` and the
        decompressed form over them. ``valid [B]``: the block's real
        positions a row; ``walk_``: the decode program's ``slot_walk``."""
        c = self.config
        b, t, _ = x.shape
        pos = cache_positions(0 if idx is None else idx, t)
        q_nope, q_rope, lat, k_r = self._projections(x, blk, pos)
        if latent is None:
            kv = project_heads(lat, self._wkv_b(blk), c.num_heads,
                               c.qk_nope_head_dim + c.v_head_dim)
            keys = jnp.concatenate(
                [kv[..., :c.qk_nope_head_dim],
                 jnp.broadcast_to(k_r[:, :, None], q_rope.shape)], -1)
            out = multihead_attention(
                jnp.concatenate([q_nope, q_rope], -1), keys,
                kv[..., c.qk_nope_head_dim:], causal=True,
                scale=c.score_scale)
        else:
            pad = c.row_width - lat.shape[-1] - k_r.shape[-1]
            row = jnp.concatenate(
                [lat, k_r, jnp.zeros((b, t, pad), lat.dtype)], -1)
            if t == 1:
                out, latent = self._token_attention(
                    q_nope[:, 0], q_rope[:, 0], row[:, 0], latent, at, idx,
                    blk, walk_)
                out = out[:, None]
            else:
                latent = self._write_rows(latent, row, at, idx)
                out = self._prompt_attention(
                    q_nope, q_rope, latent, at,
                    jnp.broadcast_to(pos, (b, t)), blk, valid)
        return out, latent

    def _latent_cache(self, layers: int, batch_size: int, max_len: int,
                      dtype=None):
        """``latent [layers, B, max_len, W]`` and the index. The barrier makes
        the zeros a real buffer (ops/attention.alloc_kv_cache)."""
        return {"latent": jax.lax.optimization_barrier(jnp.zeros(
            (layers, batch_size, max_len, self.config.row_width),
            dtype or self.compute_dtype)), "index": jnp.zeros((), jnp.int32)}

    def fused_row_walk(self, state, num_slots: int) -> bool:
        """Whether a slot cache of these leaves routes a decode step to the
        fused absorbed call on a TPU (the shapes' part of
        :meth:`_token_attention`'s route): what serving/kv_slots.py asks of a
        model with row leaves of its own."""
        _, _, s_max, w = state["latent"].shape
        return num_slots >= 2 and supports(s_max, w)
