"""LLaMA model family, TPU-first.

The reference serves LLaMA through the AutoTP path (no dedicated container in
the v0.9.2 snapshot — SURVEY §2.5); here it is a first-class model: RMSNorm,
RoPE, SwiGLU, grouped-query attention, scan-stacked blocks, logical axes for
TP/EP, optional remat.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import ATTN_IMPLS, cross_entropy_loss, gathered_top, qdot, rms_norm, sp_attention
from deepspeed_tpu.models.stack import cached_walk, kv_cache, next_cache, walk, wrapped_block
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention
from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb, rope_frequencies


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 32
    hidden_size: int = 4096
    num_heads: int = 32
    num_kv_heads: Optional[int] = None  # GQA; None => MHA
    intermediate_size: Optional[int] = None
    rope_theta: float = 10000.0
    eps: float = 1e-5

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.intermediate_size is None:
            # LLaMA: 2/3 * 4h rounded to multiple of 256
            inter = int(2 * (4 * self.hidden_size) / 3)
            self.intermediate_size = 256 * ((inter + 255) // 256)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def llama_7b(cls, **kw):
        return cls(num_layers=32, hidden_size=4096, num_heads=32, **kw)

    @classmethod
    def llama_13b(cls, **kw):
        return cls(num_layers=40, hidden_size=5120, num_heads=40, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_kv_heads", 2)
        return cls(num_layers=2, hidden_size=64, num_heads=4,
                   intermediate_size=128, **kw)


class LlamaModel:
    """Causal-LM ModelSpec: batch = {"input_ids": [B,T], "labels": [B,T]}."""

    supports_weight_quant = True   # weight matmuls go through base.qdot

    def __init__(self, config: LlamaConfig, compute_dtype=jnp.bfloat16,
                 remat: bool = False, remat_policy: Optional[str] = None,
                 attn_impl: str = "dense"):
        self.config = config
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.remat_policy = remat_policy
        assert attn_impl in ATTN_IMPLS, attn_impl
        self.attn_impl = attn_impl

    def init(self, rng):
        c = self.config
        k = jax.random.split(rng, 8)
        d, l, m, v = c.hidden_size, c.num_layers, c.intermediate_size, c.vocab_size
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        init = jax.nn.initializers.normal(0.02)
        out_scale = (2 * l) ** -0.5
        return {
            "embed": init(k[0], (v, d), jnp.float32),
            "blocks": {
                "attn_norm": jnp.ones((l, d)),
                "wq": init(k[1], (l, d, hq * dh), jnp.float32),
                "wk": init(k[2], (l, d, hkv * dh), jnp.float32),
                "wv": init(k[3], (l, d, hkv * dh), jnp.float32),
                "wo": init(k[4], (l, hq * dh, d), jnp.float32) * out_scale,
                "mlp_norm": jnp.ones((l, d)),
                "w_gate": init(k[5], (l, d, m), jnp.float32),
                "w_up": init(k[6], (l, d, m), jnp.float32),
                "w_down": init(k[7], (l, m, d), jnp.float32) * out_scale,
            },
            "final_norm": jnp.ones((d,)),
            "lm_head": init(jax.random.fold_in(k[0], 1), (d, v), jnp.float32),
        }

    def logical_axes(self):
        return {
            "embed": ("vocab_in", "hidden"),
            "blocks": {
                "attn_norm": ("layer", "hidden"),
                "wq": ("layer", "hidden", "heads"),
                "wk": ("layer", "hidden", "kv_heads"),
                "wv": ("layer", "hidden", "kv_heads"),
                "wo": ("layer", "heads", "hidden"),
                "mlp_norm": ("layer", "hidden"),
                "w_gate": ("layer", "hidden", "mlp"),
                "w_up": ("layer", "hidden", "mlp"),
                "w_down": ("layer", "mlp", "hidden"),
            },
            "final_norm": ("hidden",),
            "lm_head": ("hidden", "vocab"),
        }

    def _block(self, x, blk, kv, layer, idx, bt, cos, sin, active=None):
        """One LLaMA block -> ``(x, kv)``; with ``kv=(k_full, v_full)``
        attention runs against the GQA KV cache at ``layer`` and ``idx``
        (shared implementation for train + serving; training passes None and
        position 0). Only the new token's slice of the full stacked
        head-major [L, B, Hkv, S, Dh] cache is written — see
        ops/attention.decode_attention."""
        c = self.config
        b, t, d = x.shape
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        y = rms_norm(x, blk["attn_norm"], c.eps)
        # qdot streams int8 weights straight into the matmul (scale folded
        # into the output) — no dequantized bf16 tiles in HBM
        q = qdot("btd,de->bte", y, blk["wq"]).reshape(b, t, hq, dh)
        k_ = qdot("btd,de->bte", y, blk["wk"]).reshape(b, t, hkv, dh)
        v_ = qdot("btd,de->bte", y, blk["wv"]).reshape(b, t, hkv, dh)
        q = apply_rotary_pos_emb(q, cos, sin, position_offset=idx)
        k_ = apply_rotary_pos_emb(k_, cos, sin, position_offset=idx)
        if kv is None:
            if hkv != hq:  # GQA: repeat kv heads
                rep = hq // hkv
                k_ = jnp.repeat(k_, rep, axis=2)
                v_ = jnp.repeat(v_, rep, axis=2)
            if self.attn_impl != "dense":
                attn = sp_attention(self.attn_impl, q, k_, v_)
            else:
                attn = multihead_attention(q, k_, v_, causal=True)
        else:
            attn, kc, vc = cached_attention(q, *kv, k_, v_, layer, idx,
                                            block_table=bt, active=active)
            kv = (kc, vc)
        x = x + qdot("bte,ed->btd", attn.reshape(b, t, hq * dh), blk["wo"])
        y = rms_norm(x, blk["mlp_norm"], c.eps)
        gate = jax.nn.silu(qdot("btd,dm->btm", y, blk["w_gate"]))
        up = qdot("btd,dm->btm", y, blk["w_up"])
        x = x + qdot("btm,md->btd", gate * up, blk["w_down"])
        return x, kv

    def forward_hidden(self, params, input_ids, *, rngs=None, train: bool = False):
        c = self.config
        top = gathered_top(params, "blocks")   # ZeRO-3: the embedding, whole
        x = top["embed"].astype(self.compute_dtype)[input_ids]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)
        block_fn = wrapped_block(     # no cache: every row at position 0
            lambda x, blk, cos, sin: self._block(x, blk, None, None, 0, None,
                                                 cos, sin)[0],
            "blocks", self.remat, self.remat_policy)
        x = walk(block_fn, x, params["blocks"], cos, sin)
        return rms_norm(x, top["final_norm"], c.eps)

    def logits(self, params, hidden):
        return jnp.einsum("btd,dv->btv", hidden, params["lm_head"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs, train=train)
        logits = self.logits(gathered_top(params, "blocks"), hidden)
        loss, n = cross_entropy_loss(logits, batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # --------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Static-shape GQA KV cache (models/stack.kv_cache): stores
        num_kv_heads only, the grouped query repeat happens inside
        decode_attention."""
        c = self.config
        return kv_cache(c.num_layers, batch_size, c.num_kv_heads, max_len,
                        c.head_dim, dtype or self.compute_dtype)

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T>1) or decode (T=1) against the KV cache. Stacked caches
        ride the scan carry (models/stack.cached_walk).
        ``cache["index"]`` may be a scalar or a per-slot [B] vector
        (continuous batching): RoPE then rotates each row at its own
        position (ops/rotary vector offset) and cached_attention masks
        each row's own prefix."""
        c = self.config
        b, t = input_ids.shape
        idx = cache["index"]
        x = params["embed"].astype(self.compute_dtype)[input_ids]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)
        x, (k_new, v_new) = cached_walk(
            self._block, x, params["blocks"], (cache["k"], cache["v"]), idx,
            cache.get("block_table"), cos, sin, cache.get("slot_walk"),
            count=c.num_layers)
        hidden = rms_norm(x, params["final_norm"], c.eps)
        return self.logits(params, hidden), next_cache(cache, t, k=k_new,
                                                       v=v_new)

    def flops_per_token(self) -> float:
        c = self.config
        n_params = (c.vocab_size * c.hidden_size * 2 + c.num_layers * (
            c.hidden_size * c.head_dim * (c.num_heads + 2 * c.num_kv_heads) +
            c.num_heads * c.head_dim * c.hidden_size +
            3 * c.hidden_size * c.intermediate_size))
        attn = 12 * c.num_layers * c.hidden_size * c.max_seq_len
        return 6.0 * n_params + attn
