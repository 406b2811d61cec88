"""Multi-head LATENT attention decoder (HF ``sarvam_mla``; Sarvam-105B; the
attention of DeepSeek-V2 without query compression): keys and values of all
heads are up-projections of one compressed latent a token, so the cache holds
ONE row a token a layer, whatever the number of heads. Behind it a leading
dense SwiGLU layer and sparse layers of a shared expert plus the routed
experts HELD here (models/moe_ffn.py, shared with ``exaone_moe``).

    y = rms(x; g1);  q = y Wq -> [H, nope + rope] a token
    [c | k_r] = y Wkv_a;  c~ = rms(c; g_kv);  q_rope, k_r <- rotate(., pos)
    [k_nope_h | v_h] = c~ Wkv_b[h]
    score_h(i, j) = s (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i
    s = (nope + rope) ** -0.5 * m * m,  m = 0.1 ln(factor) + 1   (YaRN)
    h = x + concat_h(softmax_j(score_h) v_h) Wo;  out = h + FFN(rms(h; g2))

Rotation is rotate-half over the ``rope`` dimensions at YaRN's frequencies
(ops/rotary.yarn_inv_freq); ``k_r`` is one row for all heads.

**The cache** is one leaf ``latent [L, B, S, W]``: ``c~`` (``kv_lora_rank``),
the rotated ``k_r`` behind it, zero lanes up to ``W``, a whole number of 128
(512 + 64 -> 640): a token row that is no head's key or value. The model
names it in ``slot_state_keys`` and in ``row_state_keys`` (models/base.py),
and the serving layer handles it by that declaration (serving/kv_slots.py).

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

**A prompt longer than ``prompt_block``** passes the whole stack a block of
tokens at a time inside the one program call (write the block's rows, attend
rows ``[0, end of block)``, pass the FFN): the expert layer's sorted buffer
(moe/grouped.py) then holds ``prompt_block * min(k, held)`` rows whatever the
bucket. A prefill that is told the prompt's true length (``valid_len``)
computes its head at that position alone and returns ``[B, 1, V]`` logits.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import (cache_positions, cross_entropy_loss, gathered_top, merge_heads,
                                       project_heads, qdot, rms_norm, whole_leaves)
from deepspeed_tpu.models.moe_ffn import (DENSE, EXPERT_LEAVES, SPARSE, STEP_COUNTERS, ffn, gated_axes,
                                          gated_init, record_step_counters)
from deepspeed_tpu.models.stack import cached_walk, next_cache, prompt_walk, walk, wrapped_block
from deepspeed_tpu.ops.attention import multihead_attention
from deepspeed_tpu.ops import mla_prefill
from deepspeed_tpu.ops.mla_decode_step import count_form, fused_mla_decode_step, supports
from deepspeed_tpu.ops.rotary import apply_rotary_half_freqs, yarn_inv_freq, yarn_mscale


@dataclasses.dataclass
class SarvamMlaConfig:
    vocab_size: int = 262144
    max_seq_len: int = 131072
    hidden_size: int = 4096
    num_heads: int = 64
    kv_lora_rank: int = 512                  # the cached latent
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 16384           # the dense layers' FFN
    moe_intermediate_size: int = 2048        # an expert's, and the shared one's
    num_layers: int = 32
    first_k_dense: int = 1                   # leading dense layers
    num_experts: int = 128                   # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 40.0                # deepseek_yarn
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    eps: float = 1e-6
    prompt_block: int = 2048     # tokens of a prompt that pass the stack at once
    key_block: int = 512         # cached rows decompressed at once
    has_position_table = False   # rotation is computed, nothing is indexed

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
        if not 0 <= self.first_k_dense <= self.num_layers or \
                self.num_layers < 1:
            raise ValueError(f"first_k_dense={self.first_k_dense} of "
                             f"{self.num_layers} layers")
        if self.num_shared_experts != 1:
            raise ValueError(f"num_shared_experts={self.num_shared_experts}: "
                             "one shared expert is computed")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotation turns pairs")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError("mscale / mscale_all_dim other than 1 would "
                             "scale cos and sin: not computed")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Lanes of a cached row: latent and rotated key, padded to 128s."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def score_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.q_head_dim ** -0.5 * m * m

    def count(self, kind: str) -> int:
        return self.first_k_dense if kind == DENSE \
            else self.num_layers - self.first_k_dense

    def runs(self):
        """``(ffn kind, first layer of the cache, count)``, in stack order:
        the stacked weights are indexed by FFN kind, the cache by layer."""
        return tuple((kind, first, self.count(kind)) for kind, first in
                     ((DENSE, 0), (SPARSE, self.first_k_dense))
                     if self.count(kind))

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 3)
        kw.setdefault("num_experts", 16)
        kw.setdefault("num_experts_per_tok", 4)
        kw.setdefault("rope_original_max", 16)
        kw.setdefault("prompt_block", 16)
        kw.setdefault("key_block", 8)
        return cls(hidden_size=64, num_heads=4, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   intermediate_size=128, moe_intermediate_size=32, **kw)


class SarvamMlaModel:
    """Causal-LM ModelSpec: batch = {"input_ids": [B,T], "labels": [B,T]}."""

    supports_weight_quant = False
    # per-slot state: one leaf of token rows that is no head's key or value
    slot_state_keys = ("latent",)
    row_state_keys = ("latent",)
    step_counters = STEP_COUNTERS
    record_step_counters = staticmethod(record_step_counters)

    def __init__(self, config: SarvamMlaConfig, compute_dtype=jnp.bfloat16,
                 param_dtype=jnp.float32, remat: bool = False,
                 remat_policy: Optional[str] = None):
        self.config = config
        self.compute_dtype = compute_dtype
        self.param_dtype = param_dtype
        self.remat = remat
        self.remat_policy = remat_policy

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v, h = c.hidden_size, c.vocab_size, c.num_heads
        r, rope = c.kv_lora_rank, c.qk_rope_head_dim
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # as ExaoneMoeModel draws them: output projections scaled down by
        # depth, the embedding's rows at the stream's own scale
        out_scale = (2 * c.num_layers) ** -0.5
        embed_init = jax.nn.initializers.normal(1.0)

        def attention(keys, l):
            return {"attn_norm": jnp.ones((l, d)),
                    "wq": init(keys[0], (l, d, h * c.q_head_dim), pd),
                    "wkv_a": init(keys[1], (l, d, r + rope), pd),
                    "kv_norm": jnp.ones((l, r)),
                    "wkv_b": init(keys[2], (l, r, h * (c.qk_nope_head_dim
                                                       + c.v_head_dim)), pd),
                    "wo": init(keys[3], (l, h * c.v_head_dim, d), pd)
                    * out_scale,
                    "mlp_norm": jnp.ones((l, d))}

        def gated(keys, lead, width, prefix):
            return gated_init(init, keys, lead, d, width, prefix, pd,
                              out_scale)

        k = jax.random.split(rng, 8)
        ld, ls = c.count(DENSE), c.count(SPARSE)
        dense = {**attention(jax.random.split(k[1], 4), ld),
                 **gated(jax.random.split(k[2], 3), (ld,),
                         c.intermediate_size, "w_")}
        sparse = {**attention(jax.random.split(k[3], 4), ls),
                  "router": init(k[4], (ls, d, c.num_experts), pd),
                  "select_bias": jnp.zeros((ls, c.num_experts)),
                  **gated(jax.random.split(k[5], 3), (ls,),
                          c.moe_intermediate_size, "shared_"),
                  **gated(jax.random.split(k[6], 3), (ls, c.held[1]),
                          c.moe_intermediate_size, "expert_")}
        return {"embed": embed_init(k[0], (v, d), pd), DENSE: dense,
                SPARSE: sparse, "final_norm": jnp.ones((d,)),
                "lm_head": init(k[7], (d, v), pd)}

    def logical_axes(self):
        attention = {"attn_norm": ("layer", "hidden"),
                     "wq": ("layer", "hidden", "heads"),
                     "wkv_a": ("layer", "hidden", None),
                     "kv_norm": ("layer", None),
                     "wkv_b": ("layer", None, "heads"),
                     "wo": ("layer", "heads", "hidden"),
                     "mlp_norm": ("layer", "hidden")}
        return {"embed": ("vocab_in", "hidden"),
                DENSE: {**attention, **gated_axes("w_")},
                SPARSE: {**attention, "router": ("layer", "hidden", None),
                         "select_bias": ("layer", None),
                         **gated_axes("shared_"),
                         **gated_axes("expert_", "expert")},
                "final_norm": ("hidden",), "lm_head": ("hidden", "vocab")}

    # ------------------------------------------------------------ attention
    def _projections(self, x, blk, pos):
        """-> ``(q_nope [B,T,H,n], q_rope [B,T,H,rope]`` rotated, ``c~
        [B,T,r]``, ``k_r [B,T,rope]`` rotated) of the normed input."""
        c = self.config
        r, n = c.kv_lora_rank, c.qk_nope_head_dim
        inv = yarn_inv_freq(c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                            c.rope_original_max, c.rope_beta_fast,
                            c.rope_beta_slow)
        y = rms_norm(x, blk["attn_norm"], c.eps)
        q = project_heads(y, blk["wq"], c.num_heads, c.q_head_dim)
        ckr = qdot("btd,de->bte", y, blk["wkv_a"])
        lat = rms_norm(ckr[..., :r], blk["kv_norm"], c.eps)
        k_r = apply_rotary_half_freqs(ckr[..., None, r:], pos, inv)[:, :, 0]
        return (q[..., :n], apply_rotary_half_freqs(q[..., n:], pos, inv),
                lat, k_r)

    def _wkv_b(self, blk):
        """The layer's ``Wkv_b [r, H * (nope + v)]``. The walk hands the
        stack whole (:meth:`_stack`), so that the prompt kernel fetches a
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

    # --------------------------------------------------------------- layers
    def _block(self, x, blk, state, layer, idx, valid, walk_, *, kind: str,
               shift: int = 0):
        """One layer -> ``(x, state)``. ``state``: ``None`` (no cache), or
        ``(latent, counts)``: the cache leaf, read and written at ``layer +
        shift``, and the step's counters. ``valid [B]``: the block's real
        positions a row; ``walk_``: the decode program's ``slot_walk``."""
        c = self.config
        b, t, _ = x.shape
        pos = cache_positions(0 if idx is None else idx, t)
        q_nope, q_rope, lat, k_r = self._projections(x, blk, pos)
        if state is None:
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
            latent, counts = state
            at = layer + shift
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
        x = x + merge_heads(out, blk["wo"])
        z = rms_norm(x, blk["mlp_norm"], c.eps)
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        y, n = ffn(z, blk, kind, tokens, c)
        return x + y, (None if state is None else (latent, counts + n))

    @staticmethod
    def _stack(params, kind: str):
        """The stacked layers of one FFN kind as the walk takes them: the
        expert stacks whole, for the grouped matmul to address by group, and
        ``wkv_b`` whole, for the prompt kernel to address by layer and head
        (a layer's slice as a kernel's operand is written out: 16.8 MB a
        layer a token block)."""
        return whole_leaves(params[kind], *EXPERT_LEAVES, "wkv_b")

    # -------------------------------------------------------------- forward
    def forward_hidden(self, params, input_ids, *, rngs=None,
                       train: bool = False):
        c = self.config
        top = gathered_top(params, DENSE, SPARSE)
        x = top["embed"].astype(self.compute_dtype)[input_ids]
        for kind, _, count in c.runs():
            block_fn = wrapped_block(
                lambda x, blk, kind=kind: self._block(
                    x, blk, None, None, None, None, None, kind=kind)[0],
                kind, self.remat, self.remat_policy)
            x = walk(block_fn, x, self._stack(params, kind), run=(0, count))
        return rms_norm(x, top["final_norm"], c.eps)

    def logits(self, params, hidden):
        return jnp.einsum("btd,dv->btv", hidden,
                          params["lm_head"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs,
                                     train=train)
        head = gathered_top(params, DENSE, SPARSE)
        loss, n = cross_entropy_loss(self.logits(head, hidden),
                                     batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``latent [L, B, max_len, W]`` and the index. The barrier makes
        the zeros a real buffer (ops/attention.alloc_kv_cache)."""
        c = self.config
        return {"latent": jax.lax.optimization_barrier(jnp.zeros(
            (c.num_layers, batch_size, max_len, c.row_width),
            dtype or self.compute_dtype)), "index": jnp.zeros((), jnp.int32)}

    def fused_row_walk(self, state, num_slots: int) -> bool:
        """Whether a slot cache of these leaves routes a decode step to the
        fused absorbed call on a TPU (the shapes' part of
        :meth:`_token_attention`'s route): what serving/kv_slots.py asks of a
        model with row leaves of its own."""
        _, _, s_max, w = state["latent"].shape
        return num_slots >= 2 and supports(s_max, w)

    def _layers(self, params, x, leaves, counts, idx, valid, walk_):
        (latent,) = leaves
        for kind, first, count in self.config.runs():
            block = functools.partial(self._block, kind=kind, shift=first)
            x, (latent, counts) = cached_walk(
                block, x, self._stack(params, kind), (latent, counts), idx,
                valid, walk_, count=count)
        return x, (latent,), counts

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T > 1) or decode (T == 1) against the cache tree.
        ``cache["index"]`` is a scalar or a per-slot ``[B]`` vector;
        ``cache["valid_len"]`` (scalar or ``[B]``) how many of the block's
        positions are real for each row (padding is routed to no expert;
        rows it writes lie behind the length and are dead);
        ``cache["slot_walk"]`` the decode program's walk order for the fused
        step. With ``valid_len`` a prompt block's logits are those of each
        row's last real position alone, ``[B, 1, V]``. The returned cache
        carries ``step_counters`` (models/moe_ffn.STEP_COUNTERS)."""
        c = self.config
        x, (latent,), counts = prompt_walk(
            functools.partial(self._layers, params),
            params["embed"].astype(self.compute_dtype), input_ids,
            (cache["latent"],), jnp.zeros((len(STEP_COUNTERS),), jnp.int32),
            cache, c.prompt_block)
        hidden = rms_norm(x, params["final_norm"], c.eps)
        out = next_cache(cache, input_ids.shape[1], latent=latent)
        out["step_counters"] = counts
        return self.logits(params, hidden), out

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a sparse layer."""
        c = self.config
        d, h = c.hidden_size, c.num_heads
        attn = (d * h * c.q_head_dim + d * (c.kv_lora_rank
                                            + c.qk_rope_head_dim)
                + c.kv_lora_rank
                + c.kv_lora_rank * h * (c.qk_nope_head_dim + c.v_head_dim)
                + h * c.v_head_dim * d + 2 * d)
        dense = attn + 3 * d * c.intermediate_size
        sparse = (attn + d * c.num_experts + c.num_experts
                  + 3 * d * c.moe_intermediate_size * (1 + c.held[1]))
        return (2 * c.vocab_size * d + d + c.count(DENSE) * dense
                + c.count(SPARSE) * sparse)

    def flops_per_token(self) -> float:
        c = self.config
        expert = 3 * c.hidden_size * c.moe_intermediate_size
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        return 6.0 * (self.num_params()
                      - c.count(SPARSE) * expert * (c.held[1] - routed))
