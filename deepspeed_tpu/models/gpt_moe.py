"""GPT with MoE FFN layers (reference analog: Megatron-DeepSpeed MoE models
driven through ``deepspeed.moe.layer.MoE``; test fixture analog
SimpleMoEModel, reference tests/unit/simple_model.py:70).

Interleaves dense and MoE transformer blocks (every other layer MoE, the
standard GShard/DeepSpeed-MoE pattern). Blocks are a list looped over in
Python (not a scanned stack) because MoE and dense layers alternate
structurally; the cache tree and its way out are models/stack's.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import (cache_positions, cross_entropy_loss,
                                       gathered, gathered_top, gelu,
                                       layer_norm)
from deepspeed_tpu.models.stack import kv_cache, next_cache
from deepspeed_tpu.moe.layer import MoE
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention


@dataclasses.dataclass
class GPTMoEConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    hidden_size: int = 768
    num_heads: int = 12
    num_experts: int = 8
    moe_every: int = 2          # every Nth layer is MoE
    # explicit MoE layer indices (overrides moe_every) — checkpoints decide
    # their own dense/MoE interleave (ref containers/megatron_gpt_moe.py
    # converts whatever pattern the Megatron run used)
    moe_layers: Optional[tuple] = None
    top_k: int = 1
    capacity_factor: float = 1.25
    eval_capacity_factor: float = 2.0
    aux_loss_weight: float = 0.01
    use_residual: bool = False  # PR-MoE
    eps: float = 1e-5

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_experts", 4)
        return cls(num_layers=2, hidden_size=64, num_heads=4, **kw)


class GPTMoEModel:
    def __init__(self, config: GPTMoEConfig, compute_dtype=jnp.bfloat16):
        self.config = config
        self.compute_dtype = compute_dtype
        c = config
        if c.moe_layers is not None:
            self.moe_layers = sorted(int(i) for i in c.moe_layers)
        else:
            self.moe_layers = [i for i in range(c.num_layers)
                               if (i + 1) % c.moe_every == 0]
        self.moe = MoE(c.hidden_size, c.num_experts, k=c.top_k,
                       capacity_factor=c.capacity_factor,
                       eval_capacity_factor=c.eval_capacity_factor,
                       use_residual=c.use_residual)

    def init(self, rng):
        c = self.config
        d = c.hidden_size
        keys = jax.random.split(rng, 2 * c.num_layers + 3)
        init = jax.nn.initializers.normal(0.02)
        blocks = []
        for i in range(c.num_layers):
            k1, k2 = keys[2 * i], keys[2 * i + 1]
            blk = {
                "ln1_scale": jnp.ones((d,)), "ln1_bias": jnp.zeros((d,)),
                "qkv_w": init(k1, (d, 3 * d), jnp.float32),
                "qkv_b": jnp.zeros((3 * d,)),
                "out_w": init(k2, (d, d), jnp.float32) / (2 * c.num_layers) ** 0.5,
                "out_b": jnp.zeros((d,)),
                "ln2_scale": jnp.ones((d,)), "ln2_bias": jnp.zeros((d,)),
            }
            if i in self.moe_layers:
                blk["moe"] = self.moe.init(jax.random.fold_in(k2, 7))
            else:
                k3 = jax.random.fold_in(k1, 13)
                blk["mlp_fc_w"] = init(k3, (d, 4 * d), jnp.float32)
                blk["mlp_fc_b"] = jnp.zeros((4 * d,))
                blk["mlp_out_w"] = init(jax.random.fold_in(k3, 1), (4 * d, d),
                                        jnp.float32) / (2 * c.num_layers) ** 0.5
                blk["mlp_out_b"] = jnp.zeros((d,))
            blocks.append(blk)
        return {
            "wte": init(keys[-3], (c.vocab_size, d), jnp.float32),
            "wpe": init(keys[-2], (c.max_seq_len, d), jnp.float32),
            "blocks": blocks,
            "ln_f_scale": jnp.ones((d,)), "ln_f_bias": jnp.zeros((d,)),
        }

    def logical_axes(self):
        c = self.config
        d_axes = {
            "ln1_scale": ("hidden",), "ln1_bias": ("hidden",),
            "qkv_w": ("hidden", "heads"), "qkv_b": ("heads",),
            "out_w": ("heads", "hidden"), "out_b": ("hidden",),
            "ln2_scale": ("hidden",), "ln2_bias": ("hidden",),
        }
        blocks = []
        for i in range(c.num_layers):
            blk = dict(d_axes)
            if i in self.moe_layers:
                blk["moe"] = self.moe.logical_axes()
            else:
                blk.update({"mlp_fc_w": ("hidden", "mlp"), "mlp_fc_b": ("mlp",),
                            "mlp_out_w": ("mlp", "hidden"), "mlp_out_b": ("hidden",)})
            blocks.append(blk)
        return {"wte": ("vocab_in", "hidden"), "wpe": ("seq", "hidden"),
                "blocks": blocks, "ln_f_scale": ("hidden",), "ln_f_bias": ("hidden",)}

    def _attn(self, x, blk, cache=None):
        """Attention sub-block; ``cache=(k_full, v_full, layer, idx,
        block_table, slot_walk)`` runs against the stacked head-major
        [L, B, H, S, Dh] KV cache (same write/read ops as the dense
        families — ops/attention.py)."""
        c = self.config
        b, t, d = x.shape
        y = layer_norm(x, blk["ln1_scale"], blk["ln1_bias"], c.eps)
        qkv = y @ blk["qkv_w"].astype(y.dtype) + blk["qkv_b"].astype(y.dtype)
        q, k_, v_ = jnp.split(qkv, 3, axis=-1)
        shape = (b, t, c.num_heads, c.head_dim)
        q, k_, v_ = q.reshape(shape), k_.reshape(shape), v_.reshape(shape)
        if cache is None:
            attn = multihead_attention(q, k_, v_, causal=True)
            kc = vc = None
        else:
            kc, vc, layer, idx, bt, active = cache
            attn, kc, vc = cached_attention(
                q, kc, vc, k_, v_, layer, idx, block_table=bt, active=active)
        x = x + attn.reshape(b, t, d) @ blk["out_w"].astype(x.dtype) + \
            blk["out_b"].astype(x.dtype)
        return x, kc, vc

    def _ffn(self, x, blk, i, *, train: bool, rng):
        """Dense MLP or MoE FFN for layer ``i`` → (x, aux_loss)."""
        c = self.config
        y = layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], c.eps)
        if i in self.moe_layers:
            sub = jax.random.fold_in(rng, i) if rng is not None else None
            moe_out, l_aux, _ = self.moe.apply(blk["moe"], y, train=train, rng=sub)
            return x + moe_out, l_aux
        h = gelu(y @ blk["mlp_fc_w"].astype(y.dtype) +
                 blk["mlp_fc_b"].astype(y.dtype))
        x = x + h @ blk["mlp_out_w"].astype(x.dtype) + \
            blk["mlp_out_b"].astype(x.dtype)
        return x, jnp.zeros((), jnp.float32)

    def _embed(self, params, input_ids, start_pos=0):
        x = params["wte"].astype(self.compute_dtype)[input_ids]
        # start_pos may be a per-slot [B] vector (continuous batching)
        pos = cache_positions(start_pos, input_ids.shape[1])
        pe = params["wpe"].astype(self.compute_dtype)[pos]
        return x + (pe if pos.ndim == 2 else pe[None])

    def _forward_blocks(self, params, x, *, rng=None, train: bool = False):
        total_aux = jnp.zeros((), jnp.float32)
        for i, blk in enumerate(params["blocks"]):
            # ZeRO-3 gathers a block's weights at the block (expert
            # weights keep their 'expert' axis)
            blk = gathered(blk, "blocks", i)
            x, _, _ = self._attn(x, blk)
            x, l_aux = self._ffn(x, blk, i, train=train, rng=rng)
            total_aux = total_aux + l_aux
        c = self.config
        top = gathered_top(params, "blocks")
        return layer_norm(x, top["ln_f_scale"], top["ln_f_bias"],
                          c.eps), total_aux

    def forward_hidden(self, params, input_ids, *, rngs=None,
                       train: bool = False):
        rng = rngs.get("dropout") if isinstance(rngs, dict) else rngs
        x = self._embed(params, input_ids)
        hidden, _ = self._forward_blocks(params, x, rng=rng, train=train)
        return hidden

    def logits(self, params, hidden):
        return jnp.einsum("btd,vd->btv", hidden,
                          params["wte"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        c = self.config
        rng = rngs.get("dropout") if isinstance(rngs, dict) else rngs
        x = self._embed(gathered_top(params, "blocks"), batch["input_ids"])
        hidden, total_aux = self._forward_blocks(params, x, rng=rng, train=train)
        logits = self.logits(gathered_top(params, "blocks"), hidden)
        ce, n = cross_entropy_loss(logits, batch["labels"])
        loss = ce + c.aux_loss_weight * total_aux / max(len(self.moe_layers), 1)
        return loss, {"loss": loss, "ce_loss": ce, "aux_loss": total_aux, "ntokens": n}

    # --------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Static-shape stacked KV cache, the dense families' layout
        (models/stack.kv_cache)."""
        c = self.config
        return kv_cache(c.num_layers, batch_size, c.num_heads, max_len,
                        c.head_dim, dtype or self.compute_dtype)

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T>1) or decode (T=1) step against the KV cache →
        (logits [B,T,V], new_cache). MoE layers gate in eval mode
        (eval_capacity_factor, no gate noise) so decode is deterministic;
        with experts sharded over the 'expert' mesh axis the dispatch and
        combine einsums lower to the same all-to-alls as training (ref
        inference/engine.py:274 expert groups at serve time)."""
        c = self.config
        idx = cache["index"]
        bt = cache.get("block_table")
        x = self._embed(params, input_ids, start_pos=idx)
        kc, vc = cache["k"], cache["v"]
        for i, blk in enumerate(params["blocks"]):
            x, kc, vc = self._attn(
                x, blk, cache=(kc, vc, i, idx, bt, cache.get("slot_walk")))
            x, _ = self._ffn(x, blk, i, train=False, rng=None)
        hidden = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], c.eps)
        return self.logits(params, hidden), next_cache(
            cache, input_ids.shape[1], k=kc, v=vc)
