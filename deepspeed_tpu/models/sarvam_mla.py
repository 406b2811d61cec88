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

**The cache leaf** ``latent [L, B, S, W]`` and the **two attention forms**
over it (a prompt block decompressed, one token absorbed) are
models/mla.py's, shared with ``longcat_flash``.

**A prompt longer than ``prompt_block``** passes the whole stack a block of
tokens at a time inside the one program call (write the block's rows, attend
rows ``[0, end of block)``, pass the FFN): the expert layer's sorted buffer
(moe/grouped.py) then follows a block's pairs and not the bucket's: twice the
``prompt_block * k * held / num_experts`` pairs expected here, ``prompt_block
* min(k, held)`` rows only when more are routed to this chip, none for a
block of padding. A prefill that is told the prompt's true length (``valid_len``)
computes its head at that position alone and returns ``[B, 1, V]`` logits.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import project_heads, qdot, rms_norm
from deepspeed_tpu.models.mla import LatentAttention, latent_row_width
from deepspeed_tpu.models.moe_ffn import DENSE, EXPERT_LEAVES, SPARSE, ffn, gated_axes, gated_init
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
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def score_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.q_head_dim ** -0.5 * m * m

    def count(self, kind: str) -> int:
        return self.first_k_dense if kind == DENSE \
            else self.num_layers - self.first_k_dense

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


class SarvamMlaModel(LatentAttention):
    """The leading dense layers, then the sparse ones: a stack a kind of
    FFN, the cache leaf indexed by layer (models/stack.StackedDecoder)."""

    stacks = (DENSE, SPARSE)
    kinds = {DENSE: (DENSE, ("latent",)), SPARSE: (SPARSE, ("latent",))}
    # the expert stacks, for the grouped matmul to address by group, and
    # ``wkv_b``, for the prompt kernel to address by layer and head (a layer's
    # slice as a kernel's operand is written out: 16.8 MB a layer a token
    # block)
    whole = (*EXPERT_LEAVES, "wkv_b")

    def layer_kinds(self):
        c = self.config
        return (DENSE,) * c.count(DENSE) + (SPARSE,) * c.count(SPARSE)

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._block, walk_=walk_, kind=kind,
                                 shift=shift)

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

    # --------------------------------------------------------------- layers
    def _block(self, x, blk, state, layer, idx, valid, walk_, *, kind: str,
               shift: int = 0):
        """One layer -> ``(x, state)``. ``state``: ``None`` (no cache), or
        ``(latent, counts)``: the cache leaf, read and written at ``layer +
        shift``, and the step's counters. ``valid [B]``: the block's real
        positions a row; ``walk_``: the decode program's ``slot_walk``."""
        c = self.config
        t = x.shape[1]
        latent, counts = (None, None) if state is None else state
        x, latent = self._attention(
            x, blk, latent, None if state is None else layer + shift, idx,
            valid, walk_)
        z = rms_norm(x, blk["mlp_norm"], c.eps)
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        y, n = ffn(z, blk, kind, tokens, c)
        return x + y, (None if state is None else (latent, counts + n))

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        return self._latent_cache(self.config.num_layers, batch_size, max_len,
                                  dtype)

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
