"""BERT encoder family, TPU-first.

Reference analog: the BERT training/inference pillar — fused
``DeepSpeedTransformerLayer`` trained in the fastest-BERT-training claim
(csrc/transformer, docs/_posts/2020-05-28-fastest-bert-training.md) and the
inference containers (module_inject/containers/{bert,distil_bert}.py).
Same scanned-stack design as the decoders: one compiled post-LN encoder
block, L scan iterations; bidirectional attention with an additive padding
mask; MLM and sequence-classification heads.

batch = {"input_ids" [B,T], "attention_mask" [B,T] (1=real, 0=pad),
         "token_type_ids" [B,T] (optional), "labels"}.
For MLM, label -100 marks unscored positions (HF convention).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cross_entropy_loss, gathered_top, layer_norm, qdot
from deepspeed_tpu.models.stack import walk, wrapped_block
from deepspeed_tpu.ops.attention import multihead_attention

_ACTS = {
    # HF BERT's default is the EXACT (erf) gelu — the repo-wide tanh
    # approximation would drift per layer across deep post-LN stacks
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
    "relu": jax.nn.relu,
}


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    max_seq_len: int = 512
    type_vocab_size: int = 2
    num_layers: int = 12
    hidden_size: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    eps: float = 1e-12
    num_labels: int = 2          # sequence classification head width
    hidden_act: str = "gelu"     # exact erf gelu (HF BERT default)
    tie_mlm_decoder: bool = True
    # DistilBERT: no token-type embeddings (type_vocab_size=0) and a
    # relu pre-classifier instead of BERT's tanh pooler
    pooler_act: str = "tanh"     # "tanh" | "relu"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def bert_base(cls, **kw):
        return cls(**kw)

    @classmethod
    def bert_large(cls, **kw):
        kw.setdefault("num_layers", 24)
        kw.setdefault("hidden_size", 1024)
        kw.setdefault("num_heads", 16)
        kw.setdefault("mlp_dim", 4096)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        return cls(num_layers=2, hidden_size=64, num_heads=4, mlp_dim=128,
                   **kw)


class BertModel:
    """Encoder ModelSpec with MLM ("mlm") or classification ("cls") head."""

    supports_weight_quant = True   # weight matmuls go through base.qdot

    def __init__(self, config: BertConfig, compute_dtype=jnp.bfloat16,
                 head: str = "mlm", remat: bool = False):
        assert head in ("mlm", "cls", "none"), head
        self.config = config
        self.compute_dtype = compute_dtype
        self.head = head
        self.remat = remat
        assert config.hidden_act in _ACTS, config.hidden_act
        self._act = _ACTS[config.hidden_act]

    # ------------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        k = jax.random.split(rng, 12)
        d, l, m, v = c.hidden_size, c.num_layers, c.mlp_dim, c.vocab_size
        init = jax.nn.initializers.normal(0.02)
        params = {
            "wte": init(k[0], (v, d), jnp.float32),
            "wpe": init(k[1], (c.max_seq_len, d), jnp.float32),
            "emb_ln_scale": jnp.ones((d,)), "emb_ln_bias": jnp.zeros((d,)),
            "blocks": {
                "qkv_w": init(k[3], (l, d, 3 * d), jnp.float32),
                "qkv_b": jnp.zeros((l, 3 * d)),
                "attn_out_w": init(k[4], (l, d, d), jnp.float32),
                "attn_out_b": jnp.zeros((l, d)),
                "attn_ln_scale": jnp.ones((l, d)),
                "attn_ln_bias": jnp.zeros((l, d)),
                "mlp_fc_w": init(k[5], (l, d, m), jnp.float32),
                "mlp_fc_b": jnp.zeros((l, m)),
                "mlp_out_w": init(k[6], (l, m, d), jnp.float32),
                "mlp_out_b": jnp.zeros((l, d)),
                "mlp_ln_scale": jnp.ones((l, d)),
                "mlp_ln_bias": jnp.zeros((l, d)),
            },
            "pooler_w": init(k[7], (d, d), jnp.float32),
            "pooler_b": jnp.zeros((d,)),
        }
        if c.type_vocab_size > 0:
            params["wtt"] = init(k[2], (c.type_vocab_size, d), jnp.float32)
        if self.head == "mlm":
            params["mlm"] = {
                "transform_w": init(k[8], (d, d), jnp.float32),
                "transform_b": jnp.zeros((d,)),
                "ln_scale": jnp.ones((d,)), "ln_bias": jnp.zeros((d,)),
                "decoder_bias": jnp.zeros((v,)),   # decoder weight ties wte
            }
        elif self.head == "cls":
            params["cls"] = {
                "w": init(k[9], (d, c.num_labels), jnp.float32),
                "b": jnp.zeros((c.num_labels,)),
            }
        return params

    def logical_axes(self):
        c = self.config
        axes = {
            "wte": ("vocab_in", "hidden"), "wpe": ("seq", "hidden"),
            "emb_ln_scale": ("hidden",), "emb_ln_bias": ("hidden",),
            "blocks": {
                "qkv_w": ("layer", "hidden", "heads"),
                "qkv_b": ("layer", "heads"),
                "attn_out_w": ("layer", "heads", "hidden"),
                "attn_out_b": ("layer", "hidden"),
                "attn_ln_scale": ("layer", "hidden"),
                "attn_ln_bias": ("layer", "hidden"),
                "mlp_fc_w": ("layer", "hidden", "mlp"),
                "mlp_fc_b": ("layer", "mlp"),
                "mlp_out_w": ("layer", "mlp", "hidden"),
                "mlp_out_b": ("layer", "hidden"),
                "mlp_ln_scale": ("layer", "hidden"),
                "mlp_ln_bias": ("layer", "hidden"),
            },
            "pooler_w": ("hidden", "hidden"), "pooler_b": ("hidden",),
        }
        if c.type_vocab_size > 0:
            axes["wtt"] = (None, "hidden")
        if self.head == "mlm":
            axes["mlm"] = {"transform_w": ("hidden", "hidden"),
                           "transform_b": ("hidden",),
                           "ln_scale": ("hidden",), "ln_bias": ("hidden",),
                           "decoder_bias": ("vocab",)}
        elif self.head == "cls":
            axes["cls"] = {"w": ("hidden", None), "b": (None,)}
        return axes

    # ------------------------------------------------------------------ block
    def _block(self, x, blk, mask_bias):
        c = self.config
        b, t, d = x.shape
        h, dh = c.num_heads, c.head_dim
        # qdot: int8 weights stream into the matmul, scale on the output
        qkv = qdot("btd,de->bte", x, blk["qkv_w"]) + \
            blk["qkv_b"].astype(x.dtype)
        q, k_, v_ = (z.reshape(b, t, h, dh) for z in jnp.split(qkv, 3, -1))
        attn = multihead_attention(q, k_, v_, causal=False, mask=mask_bias)
        attn = attn.reshape(b, t, d)
        a_out = qdot("btd,de->bte", attn, blk["attn_out_w"]) + \
            blk["attn_out_b"].astype(x.dtype)
        x = layer_norm(x + a_out, blk["attn_ln_scale"], blk["attn_ln_bias"],
                       c.eps)                                  # post-LN
        mid = self._act(qdot("btd,dm->btm", x, blk["mlp_fc_w"]) +
                        blk["mlp_fc_b"].astype(x.dtype))
        m_out = qdot("btm,md->btd", mid, blk["mlp_out_w"]) + \
            blk["mlp_out_b"].astype(x.dtype)
        return layer_norm(x + m_out, blk["mlp_ln_scale"], blk["mlp_ln_bias"],
                          c.eps)

    # ---------------------------------------------------------------- forward
    def forward_hidden(self, params, input_ids, attention_mask=None,
                       token_type_ids=None, *, rngs=None, train=False):
        c = self.config
        b, t = input_ids.shape
        emb = gathered_top(params, "blocks")
        x = emb["wte"].astype(self.compute_dtype)[input_ids]
        x = x + emb["wpe"].astype(self.compute_dtype)[:t][None]
        if c.type_vocab_size > 0:
            if token_type_ids is None:
                token_type_ids = jnp.zeros_like(input_ids)
            x = x + emb["wtt"].astype(self.compute_dtype)[token_type_ids]
        x = layer_norm(x, emb["emb_ln_scale"], emb["emb_ln_bias"], c.eps)

        mask_bias = None
        if attention_mask is not None:
            # [B, 1, 1, T] boolean: key positions that may be attended
            mask_bias = attention_mask[:, None, None, :].astype(bool)

        # "nothing": save nothing, whatever policy the engine configured
        block_fn = wrapped_block(self._block, "blocks", self.remat, "nothing")
        return walk(block_fn, x, params["blocks"], mask_bias)

    def pooled(self, params, hidden):
        """act(dense(CLS)) — tanh (reference BertPooler) or relu
        (DistilBERT pre_classifier)."""
        cls = hidden[:, 0]
        act = jnp.tanh if self.config.pooler_act == "tanh" else jax.nn.relu
        return act(cls @ params["pooler_w"].astype(cls.dtype) +
                   params["pooler_b"].astype(cls.dtype))

    def logits(self, params, hidden):
        c = self.config
        if self.head == "mlm":
            m = params["mlm"]
            h = self._act(hidden @ m["transform_w"].astype(hidden.dtype) +
                          m["transform_b"].astype(hidden.dtype))
            h = layer_norm(h, m["ln_scale"], m["ln_bias"], c.eps)
            dec = m["decoder_w"] if "decoder_w" in m else params["wte"]
            return jnp.einsum("btd,vd->btv", h, dec.astype(h.dtype)) + \
                m["decoder_bias"].astype(h.dtype)
        if self.head == "cls":
            p = self.pooled(params, hidden)
            return p @ params["cls"]["w"].astype(p.dtype) + \
                params["cls"]["b"].astype(p.dtype)
        return hidden

    def apply(self, params, batch, *, rngs=None, train=False):
        assert self.head in ("mlm", "cls"), \
            "head='none' is a feature extractor — use forward_hidden()"
        hidden = self.forward_hidden(
            params, batch["input_ids"], batch.get("attention_mask"),
            batch.get("token_type_ids"), rngs=rngs, train=train)
        logits = self.logits(gathered_top(params, "blocks"), hidden)
        labels = batch["labels"]
        if self.head == "mlm":
            loss, n = cross_entropy_loss(logits, labels)
        else:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            loss = -jnp.take_along_axis(logp, labels[:, None], -1).mean()
            n = labels.shape[0]
        return loss, {"loss": loss, "ntokens": n}

    def flops_per_token(self) -> float:
        c = self.config
        n = c.num_layers * (4 * c.hidden_size ** 2 + 2 * c.hidden_size * c.mlp_dim)
        return 6.0 * n
