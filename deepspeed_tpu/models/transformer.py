"""Generalized causal decoder family — OPT / BLOOM / GPT-NeoX / GPT-J.

Reference analog: the per-architecture inference containers
(``deepspeed/module_inject/containers/{opt,bloom,gptneox,gptj}.py``) and
``model_implementations/``.  The reference keeps one fused CUDA transformer
and injects per-arch weight layouts into it; here the same economy comes
from ONE scanned decoder block parameterized by the architectural axes these
families actually differ on:

  * position encoding: learned table (OPT, with its +2 offset), ALiBi
    (BLOOM), rotary (GPT-NeoX partial / GPT-J partial-interleaved), or none
  * residual topology: sequential (GPT-2/OPT/BLOOM) vs parallel
    attention+MLP (GPT-NeoX dual-LN, GPT-J single-LN)
  * activation: gelu / relu
  * embedding LayerNorm (BLOOM)

Rotary always uses the interleaved convention of ``ops/rotary.py``; policies
that load rotate-half checkpoints (NeoX) permute projection columns at load
time (see inference/policies.py), so the compute path stays single-form.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.base import (cache_positions, cross_entropy_loss,
                                       gathered_top, gelu, layer_norm, qdot)
from deepspeed_tpu.models.stack import (cached_walk, kv_cache, next_cache,
                                        walk, wrapped_block)
from deepspeed_tpu.ops.attention import (cache_seq_len, cached_attention,
                                         multihead_attention,
                                         pool_block_size)
from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb, rope_frequencies


def alibi_slopes(num_heads: int) -> np.ndarray:
    """Standard ALiBi slope schedule (power-of-two geometric; BLOOM paper)."""

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(np.log2(n) - 3)))
        return start * (start ** np.arange(n))

    if np.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** int(np.floor(np.log2(num_heads)))
    extra = pow2_slopes(2 * closest)[0::2][:num_heads - closest]
    return np.concatenate([pow2_slopes(closest), extra])


@dataclasses.dataclass
class DecoderConfig:
    vocab_size: int = 50272
    max_seq_len: int = 2048
    num_layers: int = 12
    hidden_size: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    eps: float = 1e-5
    # positional scheme
    pos_emb: str = "learned"          # "learned" | "none"
    pos_offset: int = 0               # OPT stores positions at index+2
    alibi: bool = False               # BLOOM
    rotary_dim: int = 0               # 0 = no rotary; NeoX/GPT-J partial
    rope_theta: float = 10000.0       # NeoX rotary_emb_base
    # block topology
    parallel_residual: bool = False   # NeoX / GPT-J
    dual_ln: bool = True              # NeoX two LNs; GPT-J single
    post_ln: bool = False             # OPT do_layer_norm_before=False
    final_ln: bool = True             # opt-350m has no final LayerNorm
    activation: str = "gelu"          # "gelu" (tanh) | "gelu_exact" | "relu"
    embedding_ln: bool = False        # BLOOM word_embeddings_layernorm
    tie_embeddings: bool = False
    # OPT word_embed_proj_dim != hidden (opt-350m): embeddings live in a
    # smaller space with project_in/project_out linears around the stack
    word_embed_dim: int = 0           # 0 = same as hidden_size
    # attention-score scale override (GPT-Neo scales by 1.0, not dh^-0.5)
    qk_scale: Optional[float] = None
    # GPT-Neo local (sliding-window causal) attention on marked layers
    local_attn_window: int = 0
    attn_layer_pattern: tuple = ()    # per-layer: "global" | "local"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def has_position_table(self) -> bool:
        """False only for pure-ALiBi models (BLOOM): they extrapolate to any
        length.  Learned tables AND rotary cos/sin tables are sized to
        max_seq_len, so those keep the inference engine's guard."""
        return self.pos_emb == "learned" or self.rotary_dim > 0

    # ---- family presets (HF config names in parens)
    @classmethod
    def opt(cls, **kw):
        kw.setdefault("activation", "relu")
        kw.setdefault("pos_offset", 2)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def gpt_neo(cls, **kw):
        kw.setdefault("qk_scale", 1.0)        # HF GPTNeo never scales QK^T
        kw.setdefault("local_attn_window", 256)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def bloom(cls, **kw):
        kw.setdefault("pos_emb", "none")
        kw.setdefault("alibi", True)
        kw.setdefault("embedding_ln", True)
        kw.setdefault("tie_embeddings", True)
        return cls(**kw)

    @classmethod
    def gpt_neox(cls, **kw):
        kw.setdefault("pos_emb", "none")
        kw.setdefault("parallel_residual", True)
        kw.setdefault("dual_ln", True)
        return cls(**kw)

    @classmethod
    def gptj(cls, **kw):
        kw.setdefault("pos_emb", "none")
        kw.setdefault("parallel_residual", True)
        kw.setdefault("dual_ln", False)
        return cls(**kw)


class DecoderModel:
    """Causal-LM ModelSpec. batch = {"input_ids": [B,T], "labels": [B,T]}."""

    supports_weight_quant = True   # weight matmuls go through base.qdot

    def __init__(self, config: DecoderConfig, compute_dtype=jnp.bfloat16,
                 remat: bool = False, remat_policy: Optional[str] = None):
        self.config = config
        self.compute_dtype = compute_dtype
        self.remat = remat
        self.remat_policy = remat_policy
        c = config
        assert c.activation in ("gelu", "gelu_exact", "relu"), c.activation
        assert c.pos_emb in ("learned", "none"), c.pos_emb
        assert not (c.post_ln and c.parallel_residual), \
            "post_ln is a sequential-residual (OPT) topology"
        if c.alibi:
            self._alibi = jnp.asarray(alibi_slopes(c.num_heads), jnp.float32)
        if c.rotary_dim > 0:
            self._rope_cos, self._rope_sin = rope_frequencies(
                c.rotary_dim, c.max_seq_len, theta=c.rope_theta)
        self._local_flags = None
        if c.attn_layer_pattern:
            assert c.local_attn_window > 0, \
                "attn_layer_pattern needs local_attn_window"
            assert len(c.attn_layer_pattern) == c.num_layers
            self._local_flags = jnp.asarray(
                [p == "local" for p in c.attn_layer_pattern], bool)

    def _act(self, x):
        if self.config.activation == "gelu":
            return gelu(x)                       # tanh approximation
        if self.config.activation == "gelu_exact":
            return jax.nn.gelu(x, approximate=False)
        return jax.nn.relu(x)

    # ------------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        k = jax.random.split(rng, 9)
        d, l, m, v = c.hidden_size, c.num_layers, c.mlp_dim, c.vocab_size
        init = jax.nn.initializers.normal(0.02)
        blocks = {
            "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
            "qkv_w": init(k[2], (l, d, 3 * d), jnp.float32),
            "qkv_b": jnp.zeros((l, 3 * d)),
            "attn_out_w": init(k[3], (l, d, d), jnp.float32) / (2 * l) ** 0.5,
            "attn_out_b": jnp.zeros((l, d)),
            "mlp_fc_w": init(k[4], (l, d, m), jnp.float32),
            "mlp_fc_b": jnp.zeros((l, m)),
            "mlp_out_w": init(k[5], (l, m, d), jnp.float32) / (2 * l) ** 0.5,
            "mlp_out_b": jnp.zeros((l, d)),
        }
        if c.dual_ln or not c.parallel_residual:
            blocks["ln2_scale"] = jnp.ones((l, d))
            blocks["ln2_bias"] = jnp.zeros((l, d))
        we = c.word_embed_dim or d
        params = {
            "wte": init(k[0], (v, we), jnp.float32),
            "blocks": blocks,
        }
        if c.final_ln:
            params["ln_f_scale"] = jnp.ones((d,))
            params["ln_f_bias"] = jnp.zeros((d,))
        if we != d:
            params["project_in"] = init(k[7], (we, d), jnp.float32)
            params["project_out"] = init(k[8], (d, we), jnp.float32)
        if c.pos_emb == "learned":
            params["wpe"] = init(k[1], (c.max_seq_len + c.pos_offset, d),
                                 jnp.float32)
        if c.embedding_ln:
            params["emb_ln_scale"] = jnp.ones((d,))
            params["emb_ln_bias"] = jnp.zeros((d,))
        if not c.tie_embeddings:
            params["lm_head"] = init(k[6], (d, v), jnp.float32)
        return params

    def logical_axes(self):
        c = self.config
        blocks = {
            "ln1_scale": ("layer", "hidden"), "ln1_bias": ("layer", "hidden"),
            "qkv_w": ("layer", "hidden", "heads"),
            "qkv_b": ("layer", "heads"),
            "attn_out_w": ("layer", "heads", "hidden"),
            "attn_out_b": ("layer", "hidden"),
            "mlp_fc_w": ("layer", "hidden", "mlp"),
            "mlp_fc_b": ("layer", "mlp"),
            "mlp_out_w": ("layer", "mlp", "hidden"),
            "mlp_out_b": ("layer", "hidden"),
        }
        if c.dual_ln or not c.parallel_residual:
            blocks["ln2_scale"] = ("layer", "hidden")
            blocks["ln2_bias"] = ("layer", "hidden")
        axes = {"wte": ("vocab_in", "hidden"), "blocks": blocks}
        if c.final_ln:
            axes["ln_f_scale"] = ("hidden",)
            axes["ln_f_bias"] = ("hidden",)
        if (c.word_embed_dim or c.hidden_size) != c.hidden_size:
            axes["project_in"] = (None, "hidden")
            axes["project_out"] = ("hidden", None)
        if c.pos_emb == "learned":
            axes["wpe"] = ("seq", "hidden")
        if c.embedding_ln:
            axes["emb_ln_scale"] = ("hidden",)
            axes["emb_ln_bias"] = ("hidden",)
        if not c.tie_embeddings:
            axes["lm_head"] = ("hidden", "vocab")
        return axes

    # ------------------------------------------------------------------ block
    def _attn_bias(self, t, s):
        if not self.config.alibi:
            return None
        # slopes * key position; shift-invariant per softmax row
        return (self._alibi[:, None, None] *
                jnp.arange(s, dtype=jnp.float32)[None, None, :]) * \
            jnp.ones((1, t, 1), jnp.float32)

    def _qkv(self, x, blk, pos_offset):
        c = self.config
        b, t, d = x.shape
        h, dh = c.num_heads, c.head_dim
        qkv = qdot("btd,de->bte", x, blk["qkv_w"]) + \
            blk["qkv_b"].astype(x.dtype)
        q, k_, v_ = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, h, dh)
        k_ = k_.reshape(b, t, h, dh)
        v_ = v_.reshape(b, t, h, dh)
        if c.rotary_dim > 0:
            rq, pq = q[..., :c.rotary_dim], q[..., c.rotary_dim:]
            rk, pk = k_[..., :c.rotary_dim], k_[..., c.rotary_dim:]
            rq = apply_rotary_pos_emb(rq, self._rope_cos, self._rope_sin,
                                      position_offset=pos_offset)
            rk = apply_rotary_pos_emb(rk, self._rope_cos, self._rope_sin,
                                      position_offset=pos_offset)
            q = jnp.concatenate([rq, pq], axis=-1)
            k_ = jnp.concatenate([rk, pk], axis=-1)
        return q, k_, v_

    def _block(self, x, blk, kv=None, layer=None, idx=0, bt=None,
               local_flag=None, active=None):
        # -> (x, kv). kv = (k_full, v_full): full stacked head-major
        # [L,B,H,S,Dh] caches, updated at ``layer`` and ``idx`` with
        # per-token slice writes only (see ops/attention.decode_attention
        # docstring); None in training. Weight matmuls go through qdot: int8
        # weights stream into the matmul, scale on the output.
        c = self.config
        b, t, d = x.shape

        y1 = x if c.post_ln else layer_norm(x, blk["ln1_scale"],
                                            blk["ln1_bias"], c.eps)
        q, k_, v_ = self._qkv(y1, blk, idx)
        if kv is None:
            mask = None
            if local_flag is not None:
                # sliding-window causal: key allowed iff q_pos-k_pos < window
                # (on layers whose pattern says "local"; others stay global)
                delta = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
                mask = (jnp.logical_not(local_flag) |
                        (delta < c.local_attn_window))[None, None]
            attn = multihead_attention(q, k_, v_, causal=True, mask=mask,
                                       bias=self._attn_bias(t, t),
                                       scale=c.qk_scale)
        else:
            kc, vc = kv
            if bt is not None:
                # block-paged pool (ISSUE 6): the attended view is the
                # gathered block chain [B, MB * bs, ...], not the pool's
                # physical row count
                s_max = bt.shape[1] * pool_block_size(kc, c.head_dim)
            else:
                s_max = cache_seq_len(kc, c.head_dim)
            dec_bias = None
            if c.alibi:
                dec_bias = self._alibi[:, None] * jnp.arange(
                    s_max, dtype=jnp.float32)[None, :]
            window = None
            if local_flag is not None:
                window = jnp.where(local_flag, c.local_attn_window, s_max + 1)
            attn, kc, vc = cached_attention(q, kc, vc, k_, v_, layer, idx,
                                            bias=dec_bias, scale=c.qk_scale,
                                            window=window, block_table=bt,
                                            active=active)
            kv = (kc, vc)
        attn = attn.reshape(b, t, d)
        attn_out = qdot("btd,de->bte", attn, blk["attn_out_w"]) + \
            blk["attn_out_b"].astype(x.dtype)

        if c.parallel_residual:
            y2 = layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], c.eps) \
                if c.dual_ln else y1
            mid = self._act(qdot("btd,dm->btm", y2, blk["mlp_fc_w"]) +
                            blk["mlp_fc_b"].astype(x.dtype))
            mlp_out = qdot("btm,md->btd", mid, blk["mlp_out_w"]) + \
                blk["mlp_out_b"].astype(x.dtype)
            x = x + attn_out + mlp_out
        else:
            x = x + attn_out
            if c.post_ln:      # OPT do_layer_norm_before=False: LN after add
                x = layer_norm(x, blk["ln1_scale"], blk["ln1_bias"], c.eps)
            y2 = x if c.post_ln else layer_norm(x, blk["ln2_scale"],
                                                blk["ln2_bias"], c.eps)
            mid = self._act(qdot("btd,dm->btm", y2, blk["mlp_fc_w"]) +
                            blk["mlp_fc_b"].astype(x.dtype))
            x = x + qdot("btm,md->btd", mid, blk["mlp_out_w"]) + \
                blk["mlp_out_b"].astype(x.dtype)
            if c.post_ln:
                x = layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], c.eps)
        return x, kv

    # ---------------------------------------------------------------- forward
    def _embed(self, params, input_ids, idx):
        c = self.config
        b, t = input_ids.shape
        x = params["wte"].astype(self.compute_dtype)[input_ids]
        if "project_in" in params:
            x = x @ params["project_in"].astype(x.dtype)
        if c.pos_emb == "learned":
            # idx may be a per-slot [B] vector (continuous batching)
            pos = cache_positions(idx, t) + c.pos_offset
            pe = params["wpe"].astype(self.compute_dtype)[pos]
            x = x + (pe if pos.ndim == 2 else pe[None])
        if c.embedding_ln:
            x = layer_norm(x, params["emb_ln_scale"], params["emb_ln_bias"],
                           c.eps)
        return x

    def forward_hidden(self, params, input_ids, *, rngs=None, train=False):
        c = self.config
        # ZeRO-3 gathers what is used, where it is used: the embedding's
        # leaves here, a layer's weights inside the (rematerialised) block
        top = gathered_top(params, "blocks")
        x = self._embed(top, input_ids, jnp.zeros((), jnp.int32))
        block_fn = wrapped_block(
            lambda x, blk, flag=None: self._block(x, blk, local_flag=flag)[0],
            "blocks", self.remat, self.remat_policy)
        flags = self._local_flags
        x = walk(block_fn, x, params["blocks"],
                 xs=() if flags is None else (flags,))
        if c.final_ln:
            x = layer_norm(x, top["ln_f_scale"], top["ln_f_bias"], c.eps)
        return x

    def logits(self, params, hidden):
        if "project_out" in params:
            hidden = hidden @ params["project_out"].astype(hidden.dtype)
        if self.config.tie_embeddings:
            out = jnp.einsum("btd,vd->btv", hidden,
                             params["wte"].astype(hidden.dtype))
        else:
            out = jnp.einsum("btd,dv->btv", hidden,
                             params["lm_head"].astype(hidden.dtype))
        if "lm_head_bias" in params:   # GPT-J ships a biased lm head
            out = out + params["lm_head_bias"].astype(out.dtype)
        return out

    def apply(self, params, batch, *, rngs=None, train=False):
        hidden = self.forward_hidden(params, batch["input_ids"], rngs=rngs,
                                     train=train)
        logits = self.logits(gathered_top(params, "blocks"), hidden)
        loss, n = cross_entropy_loss(logits, batch["labels"])
        return loss, {"loss": loss, "ntokens": n}

    # --------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        # head-major, token-pair packed for Dh < 128 — except for models
        # whose decode always needs the einsum path (ALiBi bias, per-layer
        # local windows), which keep the plain [L, B, H, S, Dh] form so
        # every step isn't paying an unpack view (ops/attention.kv_pack_factor)
        c = self.config
        return kv_cache(c.num_layers, batch_size, c.num_heads, max_len,
                        c.head_dim, dtype or self.compute_dtype,
                        packed=not (c.alibi or c.attn_layer_pattern))

    def forward_with_cache(self, params, input_ids, cache):
        c = self.config
        idx = cache["index"]
        x = self._embed(params, input_ids, idx)
        flags = self._local_flags

        def block(x, blk, kv, layer, idx, bt, active):
            return self._block(x, blk, kv, layer, idx, bt,
                               None if flags is None else flags[layer],
                               active)

        x, (k_new, v_new) = cached_walk(
            block, x, params["blocks"], (cache["k"], cache["v"]), idx,
            cache.get("block_table"), cache.get("slot_walk"),
            count=c.num_layers)
        if c.final_ln:
            x = layer_norm(x, params["ln_f_scale"], params["ln_f_bias"],
                           c.eps)
        return self.logits(params, x), next_cache(
            cache, input_ids.shape[1], k=k_new, v=v_new)

    def flops_per_token(self) -> float:
        c = self.config
        n_params = (c.vocab_size * c.hidden_size +
                    c.num_layers * (4 * c.hidden_size ** 2 +
                                    2 * c.hidden_size * c.mlp_dim))
        return 6.0 * n_params + 12 * c.num_layers * c.hidden_size * c.max_seq_len
