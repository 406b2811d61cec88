"""CLIP text encoder, TPU-first.

Reference analog: the CLIP serving path of the diffusers pillar —
``module_inject/containers/clip.py`` (HFCLIPLayerPolicy routes
CLIPEncoderLayer into the fused GPT inference kernels) and the text-encoder
half of DeepSpeed-Diffusers. Same scanned-stack design as the other model
families: one compiled pre-LN encoder block, causal text mask (CLIP text
towers are autoregressive), quick-gelu activation, final LN, pooled output
at the EOS position.

batch = {"input_ids" [B, T]}; ``forward_hidden`` returns [B, T, D] and
``pooled`` the EOS-token embedding (HF convention: position of the largest
token id, which is EOS for CLIP vocabularies).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import gathered_top, layer_norm
from deepspeed_tpu.models.stack import walk, wrapped_block
from deepspeed_tpu.ops.attention import multihead_attention

_ACTS = {
    "quick_gelu": lambda x: x * jax.nn.sigmoid(1.702 * x),
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_new": lambda x: jax.nn.gelu(x, approximate=True),
}


@dataclasses.dataclass
class CLIPTextConfig:
    vocab_size: int = 49408
    max_seq_len: int = 77
    num_layers: int = 12
    hidden_size: int = 512
    num_heads: int = 8
    mlp_dim: int = 2048
    eps: float = 1e-5
    hidden_act: str = "quick_gelu"
    projection_dim: int = 0        # 0 = no text projection head

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class CLIPTextModel:
    """Text-tower ModelSpec (feature extractor; no loss head)."""

    def __init__(self, config: CLIPTextConfig, compute_dtype=jnp.float32):
        assert config.hidden_act in _ACTS, config.hidden_act
        self.config = config
        self.compute_dtype = compute_dtype
        self._act = _ACTS[config.hidden_act]

    def init(self, rng):
        c = self.config
        k = jax.random.split(rng, 8)
        d, l, m = c.hidden_size, c.num_layers, c.mlp_dim
        init = jax.nn.initializers.normal(0.02)
        params = {
            "wte": init(k[0], (c.vocab_size, d), jnp.float32),
            "wpe": init(k[1], (c.max_seq_len, d), jnp.float32),
            "blocks": {
                "ln1_scale": jnp.ones((l, d)), "ln1_bias": jnp.zeros((l, d)),
                "qkv_w": init(k[2], (l, d, 3 * d), jnp.float32),
                "qkv_b": jnp.zeros((l, 3 * d)),
                "attn_out_w": init(k[3], (l, d, d), jnp.float32),
                "attn_out_b": jnp.zeros((l, d)),
                "ln2_scale": jnp.ones((l, d)), "ln2_bias": jnp.zeros((l, d)),
                "mlp_fc_w": init(k[4], (l, d, m), jnp.float32),
                "mlp_fc_b": jnp.zeros((l, m)),
                "mlp_out_w": init(k[5], (l, m, d), jnp.float32),
                "mlp_out_b": jnp.zeros((l, d)),
            },
            "ln_f_scale": jnp.ones((d,)), "ln_f_bias": jnp.zeros((d,)),
        }
        if c.projection_dim:
            params["text_projection"] = init(k[6], (d, c.projection_dim),
                                             jnp.float32)
        return params

    def logical_axes(self):
        c = self.config
        axes = {
            "wte": ("vocab_in", "hidden"), "wpe": ("seq", "hidden"),
            "blocks": {
                "ln1_scale": ("layer", "hidden"),
                "ln1_bias": ("layer", "hidden"),
                "qkv_w": ("layer", "hidden", "heads"),
                "qkv_b": ("layer", "heads"),
                "attn_out_w": ("layer", "heads", "hidden"),
                "attn_out_b": ("layer", "hidden"),
                "ln2_scale": ("layer", "hidden"),
                "ln2_bias": ("layer", "hidden"),
                "mlp_fc_w": ("layer", "hidden", "mlp"),
                "mlp_fc_b": ("layer", "mlp"),
                "mlp_out_w": ("layer", "mlp", "hidden"),
                "mlp_out_b": ("layer", "hidden"),
            },
            "ln_f_scale": ("hidden",), "ln_f_bias": ("hidden",),
        }
        if c.projection_dim:
            axes["text_projection"] = ("hidden", None)
        return axes

    def _block(self, x, blk):
        c = self.config
        b, t, d = x.shape
        h, dh = c.num_heads, c.head_dim
        y = layer_norm(x, blk["ln1_scale"], blk["ln1_bias"], c.eps)
        qkv = jnp.einsum("btd,de->bte", y, blk["qkv_w"].astype(y.dtype)) + \
            blk["qkv_b"].astype(y.dtype)
        q, k_, v_ = (z.reshape(b, t, h, dh) for z in jnp.split(qkv, 3, -1))
        attn = multihead_attention(q, k_, v_, causal=True).reshape(b, t, d)
        x = x + jnp.einsum("btd,de->bte", attn,
                           blk["attn_out_w"].astype(x.dtype)) + \
            blk["attn_out_b"].astype(x.dtype)
        y = layer_norm(x, blk["ln2_scale"], blk["ln2_bias"], c.eps)
        mid = self._act(jnp.einsum("btd,dm->btm", y,
                                   blk["mlp_fc_w"].astype(y.dtype)) +
                        blk["mlp_fc_b"].astype(y.dtype))
        return x + jnp.einsum("btm,md->btd", mid,
                              blk["mlp_out_w"].astype(x.dtype)) + \
            blk["mlp_out_b"].astype(x.dtype)

    def forward_hidden(self, params, input_ids, *, rngs=None, train=False):
        c = self.config
        t = input_ids.shape[1]
        top = gathered_top(params, "blocks")
        x = top["wte"].astype(self.compute_dtype)[input_ids]
        x = x + top["wpe"].astype(self.compute_dtype)[:t][None]
        x = walk(wrapped_block(self._block, "blocks"), x, params["blocks"])
        return layer_norm(x, top["ln_f_scale"], top["ln_f_bias"], c.eps)

    def pooled(self, params, hidden, input_ids):
        """EOS-position embedding (HF: argmax of token ids), optionally
        projected."""
        eos = jnp.argmax(input_ids, axis=-1)
        p = jnp.take_along_axis(hidden, eos[:, None, None].repeat(
            hidden.shape[-1], axis=-1), axis=1)[:, 0]
        if "text_projection" in params:
            p = p @ params["text_projection"].astype(p.dtype)
        return p

    def apply(self, params, batch, *, rngs=None, train=False):
        hidden = self.forward_hidden(params, batch["input_ids"])
        return hidden, {"pooled": self.pooled(params, hidden,
                                              batch["input_ids"])}
