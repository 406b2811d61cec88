"""LLaMA model family, TPU-first.

The reference serves LLaMA through the AutoTP path (no dedicated container in
the v0.9.2 snapshot — SURVEY §2.5); here it is a first-class model: RMSNorm,
RoPE, SwiGLU, grouped-query attention, scan-stacked blocks, logical axes for
TP/EP, optional remat. Flagship config for the BASELINE ladder is llama_7b.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import ATTN_IMPLS, cross_entropy_loss, gathered, gathered_top, layer_view, qdot, rms_norm, sp_attention  # noqa: E501
from deepspeed_tpu.ops.attention import alloc_kv_cache, cached_attention, multihead_attention
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
                 attn_impl: str = "dense", decode_unroll: int = 1):
        self.config = config
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.remat_policy = remat_policy
        assert attn_impl in ATTN_IMPLS, attn_impl
        self.attn_impl = attn_impl
        # see GPT2Model: layer-scan unroll for single-token decode steps
        self.decode_unroll = decode_unroll

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

    def _block_impl(self, x, blk, cos, sin, train: bool, cache):
        """One LLaMA block; with ``cache=(k_full, v_full, layer, idx)``
        attention runs against the GQA KV cache (shared implementation for
        train + serving). Only the new token's slice of the full stacked
        head-major [L, B, Hkv, S, Dh] cache is written — see
        ops/attention.decode_attention."""
        c = self.config
        b, t, d = x.shape
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        idx = cache[3] if cache is not None else 0
        y = rms_norm(x, blk["attn_norm"], c.eps)
        # qdot streams int8 weights straight into the matmul (scale folded
        # into the output) — no dequantized bf16 tiles in HBM
        q = qdot("btd,de->bte", y, blk["wq"]).reshape(b, t, hq, dh)
        k_ = qdot("btd,de->bte", y, blk["wk"]).reshape(b, t, hkv, dh)
        v_ = qdot("btd,de->bte", y, blk["wv"]).reshape(b, t, hkv, dh)
        q = apply_rotary_pos_emb(q, cos, sin, position_offset=idx)
        k_ = apply_rotary_pos_emb(k_, cos, sin, position_offset=idx)
        if cache is None:
            if hkv != hq:  # GQA: repeat kv heads
                rep = hq // hkv
                k_ = jnp.repeat(k_, rep, axis=2)
                v_ = jnp.repeat(v_, rep, axis=2)
            if self.attn_impl != "dense":
                attn = sp_attention(self.attn_impl, q, k_, v_)
            else:
                attn = multihead_attention(q, k_, v_, causal=True)
            kc = vc = None
        else:
            kc, vc, layer, idx, *rest = cache
            attn, kc, vc = cached_attention(
                q, kc, vc, k_, v_, layer, idx,
                block_table=rest[0] if rest else None)
        x = x + qdot("bte,ed->btd", attn.reshape(b, t, hq * dh), blk["wo"])
        y = rms_norm(x, blk["mlp_norm"], c.eps)
        gate = jax.nn.silu(qdot("btd,dm->btm", y, blk["w_gate"]))
        up = qdot("btd,dm->btm", y, blk["w_up"])
        x = x + qdot("btm,md->btd", gate * up, blk["w_down"])
        return x, kc, vc

    def _block(self, x, blk, cos, sin, train: bool):
        return self._block_impl(x, blk, cos, sin, train, None)[0]

    def forward_hidden(self, params, input_ids, *, rngs=None, train: bool = False):
        c = self.config
        b, t = input_ids.shape
        top = gathered_top(params)     # ZeRO-3: the embedding, whole
        x = top["embed"].astype(self.compute_dtype)[input_ids]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)

        def block_fn(x, blk, cos, sin, train):
            # ZeRO-3 gathers inside what remat wraps; a closure of this
            # call, because jax keeps a traced block by its function
            blk = gathered(blk, "blocks", stacked=True)
            return self._block(x, blk, cos, sin, train)

        if self.remat:
            from deepspeed_tpu.runtime.activation_checkpointing import checkpoint_policy

            block_fn = jax.checkpoint(block_fn, policy=checkpoint_policy(self.remat_policy),
                                      static_argnums=(4,))

        def scan_body(x, layer_params):
            return block_fn(x, layer_params, cos, sin, train), None

        x, _ = jax.lax.scan(scan_body, x, params["blocks"])
        return rms_norm(x, top["final_norm"], c.eps)

    def logits(self, params, hidden):
        return jnp.einsum("btd,dv->btv", hidden, params["lm_head"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs, train=train)
        logits = self.logits(gathered_top(params), hidden)
        loss, n = cross_entropy_loss(logits, batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # --------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Static-shape GQA KV cache — stores num_kv_heads only (the grouped
        query repeat happens inside decode_attention). Head-major,
        token-pair packed for Dh < 128 — see ops/attention.kv_pack_factor."""
        c = self.config
        dtype = dtype or self.compute_dtype
        return {"k": alloc_kv_cache(c.num_layers, batch_size,
                                    c.num_kv_heads, max_len, c.head_dim,
                                    dtype),
                "v": alloc_kv_cache(c.num_layers, batch_size,
                                    c.num_kv_heads, max_len, c.head_dim,
                                    dtype),
                "index": jnp.zeros((), jnp.int32)}

    def _block_cached(self, x, blk, kc, vc, layer, idx, cos, sin, bt):
        return self._block_impl(x, blk, cos, sin, False,
                                (kc, vc, layer, idx, bt))

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T>1) or decode (T=1) against the KV cache. Stacked caches
        ride the scan carry with per-layer slice writes (see GPT2Model).
        ``cache["index"]`` may be a scalar or a per-slot [B] vector
        (continuous batching): RoPE then rotates each row at its own
        position (ops/rotary vector offset) and cached_attention masks
        each row's own prefix."""
        c = self.config
        b, t = input_ids.shape
        idx = cache["index"]
        bt = cache.get("block_table")
        x = params["embed"].astype(self.compute_dtype)[input_ids]
        cos, sin = rope_frequencies(c.head_dim, c.max_seq_len, c.rope_theta)

        def scan_body(carry, _):
            x, kc, vc, layer = carry
            # blocks are indexed by the carried counter (not scan xs):
            # layer_view keeps int8 weight dicts WHOLE so qdot's kernel
            # DMA-slices the layer in-kernel instead of paying a full
            # per-step operand copy (models/base.layer_view)
            blk = layer_view(params["blocks"], layer)
            x, kc, vc = self._block_cached(x, blk, kc, vc, layer, idx,
                                           cos, sin, bt)
            return (x, kc, vc, layer + 1), None

        (x, k_new, v_new, _), _ = jax.lax.scan(
            scan_body,
            (x, cache["k"], cache["v"], jnp.zeros((), jnp.int32)),
            None, length=c.num_layers,
            unroll=self.decode_unroll if t == 1 else 1)
        hidden = rms_norm(x, params["final_norm"], c.eps)
        logits = self.logits(params, hidden)
        out = {"k": k_new, "v": v_new, "index": idx + t}
        if bt is not None:
            out["block_table"] = bt
        return logits, out

    def flops_per_token(self) -> float:
        c = self.config
        n_params = (c.vocab_size * c.hidden_size * 2 + c.num_layers * (
            c.hidden_size * c.head_dim * (c.num_heads + 2 * c.num_kv_heads) +
            c.num_heads * c.head_dim * c.hidden_size +
            3 * c.hidden_size * c.intermediate_size))
        attn = 12 * c.num_layers * c.hidden_size * c.max_seq_len
        return 6.0 * n_params + attn
