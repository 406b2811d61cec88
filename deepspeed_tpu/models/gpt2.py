"""GPT-2 model family (125M default), TPU-first.

Design notes (vs. the reference's per-module torch GPT-2 used in its tests
and the fused ``csrc/transformer`` training kernel, SURVEY §2.4):
  * all transformer blocks are *stacked* on a leading 'layer' dimension and
    executed as a scan (models/stack.py) — one compiled block, L iterations;
    the XLA-idiomatic form that keeps compile time flat in depth and lets
    ZeRO-3 shard the layer dimension.
  * activations/matmuls run in the engine's compute dtype (bf16); softmax,
    layernorm statistics and the CE loss run in fp32.
  * logical axis names per param dim feed the PartitionPlan (TP over 'heads'/
    'mlp'/'vocab', ZeRO over 'layer' or the largest free dim).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import ATTN_IMPLS, cache_positions, cross_entropy_loss, embed_tokens, gathered_top, gelu, layer_norm, qdot, sp_attention, tied_logits
from deepspeed_tpu.models.stack import cached_walk, kv_cache, next_cache, walk, wrapped_block
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention


@dataclasses.dataclass
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    hidden_size: int = 768
    num_heads: int = 12
    mlp_ratio: int = 4
    dropout: float = 0.0
    tie_embeddings: bool = True
    eps: float = 1e-5
    # >0: compute the LM loss in sequence chunks of this size without ever
    # materializing [B, T, V] logits (runtime/zero/tiling.py — the memory
    # win matters from ~50k vocab; requires tie_embeddings)
    loss_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mlp_dim(self) -> int:
        return self.hidden_size * self.mlp_ratio

    @classmethod
    def gpt2_125m(cls, **kw):
        return cls(num_layers=12, hidden_size=768, num_heads=12, **kw)

    @classmethod
    def gpt2_350m(cls, **kw):
        return cls(num_layers=24, hidden_size=1024, num_heads=16, **kw)

    @classmethod
    def gpt2_774m(cls, **kw):
        return cls(num_layers=36, hidden_size=1280, num_heads=20, **kw)

    @classmethod
    def gpt2_1b3(cls, **kw):
        return cls(num_layers=24, hidden_size=2048, num_heads=16, **kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        return cls(num_layers=2, hidden_size=64, num_heads=4, **kw)


class GPT2Model:
    """Causal-LM ModelSpec. batch = {"input_ids": [B,T] int32, "labels": [B,T]}."""

    supports_weight_quant = True   # weight matmuls go through base.qdot
    # the tied embedding/lm-head may ALSO quantize (per-vocab-row scales,
    # quant.quantize_embedding): embed gathers + tied logits route
    # through base.embed_tokens / base.tied_logits
    supports_embedding_quant = True

    def __init__(self, config: GPT2Config, compute_dtype=jnp.bfloat16,
                 remat: bool = False, remat_policy: Optional[str] = None,
                 attn_impl: str = "dense"):
        self.config = config
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.remat_policy = remat_policy
        assert attn_impl in ATTN_IMPLS, attn_impl
        if attn_impl != "dense" and config.dropout > 0.0:
            raise ValueError(
                f"attn_impl={attn_impl!r} does not implement attention dropout; "
                f"set dropout=0.0 or use attn_impl='dense'")
        if config.loss_chunk and not config.tie_embeddings:
            raise ValueError("loss_chunk requires tie_embeddings (the "
                             "chunked LM loss projects through wte)")
        self.attn_impl = attn_impl

    # ------------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        k = jax.random.split(rng, 8)
        d, l, m, v = c.hidden_size, c.num_layers, c.mlp_dim, c.vocab_size
        std = 0.02
        init = jax.nn.initializers.normal(std)
        params = {
            "wte": init(k[0], (v, d), jnp.float32),
            "wpe": init(k[1], (c.max_seq_len, d), jnp.float32),
            "blocks": {
                "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
                "qkv_w": init(k[2], (l, d, 3 * d), jnp.float32),
                "qkv_b": jnp.zeros((l, 3 * d)),
                "attn_out_w": init(k[3], (l, d, d), jnp.float32) / (2 * l) ** 0.5,
                "attn_out_b": jnp.zeros((l, d)),
                "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
                "mlp_fc_w": init(k[4], (l, d, m), jnp.float32),
                "mlp_fc_b": jnp.zeros((l, m)),
                "mlp_out_w": init(k[5], (l, m, d), jnp.float32) / (2 * l) ** 0.5,
                "mlp_out_b": jnp.zeros((l, d)),
            },
            "ln_f_scale": jnp.ones((d,)), "ln_f_bias": jnp.zeros((d,)),
        }
        if not c.tie_embeddings:
            params["lm_head"] = init(k[6], (d, v), jnp.float32)
        return params

    def logical_axes(self):
        c = self.config
        axes = {
            "wte": ("vocab_in", "hidden"),
            "wpe": ("seq", "hidden"),
            "blocks": {
                "ln1_scale": ("layer", "hidden"), "ln1_bias": ("layer", "hidden"),
                "qkv_w": ("layer", "hidden", "heads"),
                "qkv_b": ("layer", "heads"),
                "attn_out_w": ("layer", "heads", "hidden"),
                "attn_out_b": ("layer", "hidden"),
                "ln2_scale": ("layer", "hidden"), "ln2_bias": ("layer", "hidden"),
                "mlp_fc_w": ("layer", "hidden", "mlp"),
                "mlp_fc_b": ("layer", "mlp"),
                "mlp_out_w": ("layer", "mlp", "hidden"),
                "mlp_out_b": ("layer", "hidden"),
            },
            "ln_f_scale": ("hidden",), "ln_f_bias": ("hidden",),
        }
        if not c.tie_embeddings:
            axes["lm_head"] = ("hidden", "vocab")
        return axes

    # ------------------------------------------------------------------ layers
    def _block(self, x, blk, kv=None, layer=None, idx=None, bt=None,
               active=None, *, rng=None, train: bool = False):
        """One transformer block -> ``(x, kv)``; with ``kv=(k_full, v_full)``
        the attention runs against the KV cache at ``layer`` and ``idx`` (one
        shared implementation so training and serving can never diverge
        numerically). ``k_full`` / ``v_full`` are the FULL stacked head-major
        [L, B, H, S, Dh] caches: only the new token's slice is written (in
        place, as a loop-carry dynamic update) — never the whole cache (see
        ops/attention.decode_attention)."""
        c = self.config
        b, t, d = x.shape
        h, dh = c.num_heads, c.head_dim
        y = layer_norm(x, blk["ln1_scale"], blk["ln1_bias"], c.eps)
        # qdot streams int8 weights straight into the matmul (scale folded
        # into the output) — no dequantized bf16 tiles in HBM
        qkv = qdot("btd,de->bte", y, blk["qkv_w"]) + \
            blk["qkv_b"].astype(y.dtype)
        q, k_, v_ = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, h, dh)
        k_ = k_.reshape(b, t, h, dh)
        v_ = v_.reshape(b, t, h, dh)
        if kv is None:
            if self.attn_impl != "dense":
                attn = sp_attention(self.attn_impl, q, k_, v_)
            else:
                drop_rng = None
                if train and c.dropout > 0.0 and rng is not None:
                    rng, drop_rng = jax.random.split(rng)
                attn = multihead_attention(q, k_, v_, causal=True,
                                           dropout_rate=c.dropout if train else 0.0,
                                           dropout_rng=drop_rng)
        else:
            attn, kc, vc = cached_attention(q, *kv, k_, v_, layer, idx,
                                            block_table=bt, active=active)
            kv = (kc, vc)
        attn = attn.reshape(b, t, d)
        x = x + qdot("btd,de->bte", attn, blk["attn_out_w"]) + \
            blk["attn_out_b"].astype(x.dtype)
        y = layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], c.eps)
        hmid = gelu(qdot("btd,dm->btm", y, blk["mlp_fc_w"]) +
                    blk["mlp_fc_b"].astype(y.dtype))
        x = x + qdot("btm,md->btd", hmid, blk["mlp_out_w"]) + \
            blk["mlp_out_b"].astype(x.dtype)
        return x, kv

    def forward_hidden(self, params, input_ids, *, rngs=None, train: bool = False,
                       pld_theta=None, ltd_keep=None):
        c = self.config
        b, t = input_ids.shape
        top = gathered_top(params, "blocks")   # ZeRO-3: the embeddings, whole
        x = embed_tokens(top["wte"], input_ids, self.compute_dtype)
        x = x + top["wpe"].astype(self.compute_dtype)[:t][None]

        def block(x, blk, rng):
            return self._block(x, blk, rng=rng, train=train)[0]

        rng0 = rngs.get("dropout") if isinstance(rngs, dict) else rngs
        if (ltd_keep is not None and train and ltd_keep < t
                and c.num_layers >= 3):
            # random-LTD token routing (reference data_routing/
            # basic_layer.py RandomLayerTokenDrop): every layer except the
            # first and last runs on a per-layer random SORTED subset of
            # ``ltd_keep`` tokens — gather -> block -> scatter, with the
            # dropped tokens' hidden states passing through unchanged.
            # Sorted indices keep the reduced sequence causal w.r.t. the
            # original token order, so the block's causal mask is exact.
            assert rng0 is not None, "random-LTD needs a dropout rng"
            assert pld_theta is None, \
                "random-LTD and progressive_layer_drop are exclusive"
            block_fn = wrapped_block(block, "blocks", self.remat,
                                     self.remat_policy)
            from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
                gather_tokens, sample_token_indices, scatter_tokens)

            first = jax.tree_util.tree_map(lambda p: p[0], params["blocks"])
            last = jax.tree_util.tree_map(lambda p: p[-1], params["blocks"])
            mid = jax.tree_util.tree_map(lambda p: p[1:-1], params["blocks"])
            rng0, sub = jax.random.split(rng0)
            x = block_fn(x, first, sub)

            def ltd_body(carry, blk):
                x, rng = carry
                rng, r_idx, r_blk = jax.random.split(rng, 3)
                idx = sample_token_indices(r_idx, b, t, ltd_keep)
                kept = block_fn(gather_tokens(x, idx), blk, r_blk)
                return (scatter_tokens(x, kept, idx), rng), None

            (x, rng0), _ = jax.lax.scan(ltd_body, (x, rng0), mid)
            rng0, sub = jax.random.split(rng0)
            x = block_fn(x, last, sub)
            return layer_norm(x, top["ln_f_scale"], top["ln_f_bias"], c.eps)

        use_pld = pld_theta is not None and train
        # a block reads qkv_w first: ZeRO-3 fetches it a layer ahead
        block_fn = wrapped_block(block, "blocks", self.remat,
                                 self.remat_policy, first="qkv_w")

        def layer(carry, layer_params, i, ahead=None):
            x, rng = carry
            if rng is not None:
                rng, sub = jax.random.split(rng)
            else:
                sub = None
            x_new = block_fn(x, layer_params, sub, ahead=ahead)
            if use_pld:
                # stochastic depth (progressive layer drop): keep prob anneals
                # linearly in depth from 1 to theta; expectation-preserving
                # residual scaling keeps activations calibrated
                assert rng is not None, "pld needs a dropout rng"
                rng, pld_rng = jax.random.split(rng)
                frac = i / max(c.num_layers - 1, 1)
                p_keep = 1.0 - frac * (1.0 - pld_theta)
                keep = jax.random.bernoulli(pld_rng, p_keep)
                gate = jnp.where(keep, 1.0 / p_keep, 0.0).astype(x.dtype)
                x = x + gate * (x_new - x)
            else:
                x = x_new
            return x, rng

        x, _ = walk(layer, (x, rng0), params["blocks"],
                    xs=(jnp.arange(c.num_layers),),
                    first_leaf=block_fn.first_leaf)
        return layer_norm(x, top["ln_f_scale"], top["ln_f_bias"], c.eps)

    def logits(self, params, hidden):
        if self.config.tie_embeddings:
            return tied_logits(hidden, params["wte"])
        return jnp.einsum("btd,dv->btv", hidden, params["lm_head"].astype(hidden.dtype))

    def apply(self, params, batch, *, rngs=None, train: bool = False,
              pld_theta=None, ltd_keep=None):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs,
                                     train=train, pld_theta=pld_theta,
                                     ltd_keep=ltd_keep)
        c = self.config
        head = gathered_top(params, "blocks")  # gathered again, for the head
        if c.loss_chunk:
            from deepspeed_tpu.runtime.zero.tiling import (
                chunked_cross_entropy)

            loss, n = chunked_cross_entropy(hidden, head["wte"],
                                            batch["labels"],
                                            chunk=c.loss_chunk)
        else:
            loss, n = cross_entropy_loss(self.logits(head, hidden),
                                         batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # --------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Static-shape KV cache (the inference_context.h workspace analog —
        reference csrc/transformer/inference/includes/inference_context.h):
        models/stack.kv_cache."""
        c = self.config
        return kv_cache(c.num_layers, batch_size, c.num_heads, max_len,
                        c.head_dim, dtype or self.compute_dtype)

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T>1) or decode (T=1) step against the KV cache.
        Returns (logits [B,T,V], new_cache).

        ``cache["index"]`` may be a scalar (uniform batch) or a per-slot
        [B] vector (continuous batching — models/base.cache_positions).
        ``cache["block_table"]`` (optional, int32 [B, max_blocks])
        switches the cache arrays to the block-paged pool addressing of
        ops/attention.write_kv_blocks (prefix-sharing serving, ISSUE 6).
        ``cache["slot_walk"]`` (optional; the slot decode program's) goes to
        ops/attention.cached_attention as ``active``: the fused decode step
        skips the rows of a slot that is not decoding.

        The stacked caches ride the layer scan's carry
        (models/stack.cached_walk)."""
        c = self.config
        b, t = input_ids.shape
        idx = cache["index"]
        x = embed_tokens(params["wte"], input_ids, self.compute_dtype)
        pos = cache_positions(idx, t)
        pe = params["wpe"].astype(self.compute_dtype)[pos]
        x = x + (pe if pos.ndim == 2 else pe[None])
        x, (k_new, v_new) = cached_walk(
            self._block, x, params["blocks"], (cache["k"], cache["v"]), idx,
            cache.get("block_table"), cache.get("slot_walk"),
            count=c.num_layers)
        hidden = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"], c.eps)
        return self.logits(params, hidden), next_cache(cache, t, k=k_new,
                                                       v=v_new)

    # ------------------------------------------------------------------- cost
    def flops_per_token(self) -> float:
        """6*N approximation + attention quadratic term (training fwd+bwd)."""
        c = self.config
        n_params = (c.vocab_size * c.hidden_size + c.max_seq_len * c.hidden_size +
                    c.num_layers * (4 * c.hidden_size ** 2 + 2 * c.hidden_size * c.mlp_dim))
        attn = 12 * c.num_layers * c.hidden_size * c.max_seq_len
        return 6.0 * n_params + attn
