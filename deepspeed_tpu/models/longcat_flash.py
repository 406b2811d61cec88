"""The shortcut-connected double-layer decoder (LongCat-Flash-Chat): every
layer holds TWO multi-head latent attention sublayers, TWO dense SwiGLU FFNs
and ONE expert layer whose input is the first sublayer's normed stream and
whose output joins the stream only at the END of the layer (the shortcut: in a
deployment the experts' exchange runs beside the second attention and both
dense FFNs). The router has ``n_routed_experts + zero_experts`` outputs,
softmax, ``k`` a token, no shared expert; the last ``zero_experts`` outputs
are ZERO-COMPUTE identity experts, so a token costs between 0 and ``k`` expert
MLPs (models/moe_ffn.py, moe/grouped.softmax_topk_route).

    a1  = x  + MLA_0(rms(x;  g_in0))
    z   = rms(a1; g_post0)
    m   = MoE(z)                                   joins at the end
    b1  = a1 + Dense_0(z)
    a2  = b1 + MLA_1(rms(b1; g_in1))
    out = a2 + Dense_1(rms(a2; g_post1)) + m

    MLA(y): q = s_q (rms(y Wq_a; g_q) Wq_b) -> [H, nope + rope] a token
            [c | k_r] = y Wkv_a;  c~ = s_kv rms(c; g_kv)
            s_q = (hidden / q_lora_rank) ** 0.5, s_kv = (hidden / kv_lora_rank) ** 0.5
            q_rope, k_r <- rotate(., pos), rotate-half at plain frequencies
            the rest, and the cache of c~ and k_r, as models/mla.py has it

The two scale factors are folded into the gains of the norms they follow
(``rms(.; s g) = s rms(.; g)``), so the cached row holds the SCALED latent and
both attention forms of models/mla.py, the absorbed step included, see what
they see of ``sarvam_mla``: no kernel knows of a scale.

**Two stacks**: ``sub``, the ``2 L`` sublayers' leaves (attention and dense
FFN, sublayer ``i`` of double layer ``l`` at ``2 l + i``), and ``pair``, what
a double layer holds once, ``[L, ...]``: the router, the held experts, and
each sublayer's ``Wkv_b`` as a stack of its own (``wkv_b0``, ``wkv_b1``).
**The cache** is still one leaf, ``latent [2 L, B, S, W]``: layer ``l`` reads
and writes rows ``2 l`` and ``2 l + 1``. models/stack.py's walks hand a block
its layer number and the whole state and serve as they are; a sublayer's
weights are addressed in the whole ``sub`` stack at ``2 l + i``. (Compiled for
the described chip: stacked ``[L, 2, ...]``, a layer's slice of a slice was
written out, 420 MB a decode step; and one ``wkv_b [2 L, ...]`` that a loop
body reads at two indices was transposed WHOLE in every step's entry, 134 MB,
where a stack read once a body is sliced a layer at a time as
``sarvam_mla``'s is.)

On ONE chip the shortcut is a dataflow and not an overlap: there is no
exchange to hide, and a decode step is bound by the bytes of the weights it
streams whichever order XLA gives the branches. The scopes
``dstpu_scmoe_experts`` and ``dstpu_scmoe_dense`` name the two in the
program's metadata.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import project_heads, qdot, rms_norm, whole_leaves
from deepspeed_tpu.models.mla import LatentAttention, latent_row_width
from deepspeed_tpu.models.moe_ffn import DENSE, EXPERT_LEAVES, SPARSE, ffn, gated_axes, gated_init
from deepspeed_tpu.ops.rotary import apply_rotary_half

SUB, PAIR = "sub", "pair"
# a sublayer's leaves, stacked ``[2 L, ...]``
SUB_LEAVES = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
              "wo", "mlp_norm", "w_gate", "w_up", "w_down")
# the two sublayers' ``Wkv_b``, each stacked ``[L, ...]`` beside the expert
# layer's leaves
WKV_B = ("wkv_b0", "wkv_b1")


@dataclasses.dataclass
class LongcatFlashConfig:
    vocab_size: int = 131072
    max_seq_len: int = 131072
    hidden_size: int = 6144
    num_heads: int = 64
    q_lora_rank: int = 1536                  # the query's low-rank path
    kv_lora_rank: int = 512                  # the cached latent
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    ffn_hidden_size: int = 12288             # each of a layer's two dense FFNs
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28                     # DOUBLE layers
    n_routed_experts: int = 512              # real experts
    zero_experts: int = 256                  # identity experts behind them
    num_experts_per_tok: int = 12
    held: Optional[Tuple[int, int]] = None   # (first, count) of the real; None: all
    routed_scaling_factor: float = 6.0
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    rope_theta: float = 1e7
    eps: float = 1e-5
    prompt_block: int = 2048     # tokens of a prompt that pass the stack at once
    key_block: int = 512         # cached rows decompressed at once
    has_position_table = False   # rotation is computed, nothing is indexed
    # what models/moe_ffn.ffn reads of a router that is not the sigmoid one
    scoring_func = "softmax"
    norm_topk_prob = False

    def __post_init__(self):
        if self.held is None:
            self.held = (0, self.n_routed_experts)
        self.held = tuple(self.held)
        if self.num_layers < 1:
            raise ValueError(f"num_layers={self.num_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotation turns pairs")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.n_routed_experts} real experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than router outputs")

    @property
    def num_experts(self) -> int:
        """The router's width: real experts, then the identity ones."""
        return self.n_routed_experts + self.zero_experts

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        return latent_row_width(self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def score_scale(self) -> float:
        return self.q_head_dim ** -0.5

    @property
    def q_scale(self) -> float:
        return (self.hidden_size / self.q_lora_rank) ** 0.5 \
            if self.mla_scale_q_lora else 1.0

    @property
    def kv_scale(self) -> float:
        return (self.hidden_size / self.kv_lora_rank) ** 0.5 \
            if self.mla_scale_kv_lora else 1.0

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 2)
        kw.setdefault("n_routed_experts", 16)
        kw.setdefault("zero_experts", 8)
        kw.setdefault("num_experts_per_tok", 4)
        kw.setdefault("prompt_block", 16)
        kw.setdefault("key_block", 8)
        return cls(hidden_size=64, num_heads=4, q_lora_rank=16,
                   kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, ffn_hidden_size=128,
                   expert_ffn_hidden_size=32, **kw)


class LongcatFlashModel(LatentAttention):
    """Double layers of one kind: a walk takes BOTH stacks, under the first
    one's name (models/stack.StackedDecoder)."""

    stacks = (SUB, PAIR)
    kinds = {PAIR: (SUB, ("latent",))}

    def layer_kinds(self):
        return (PAIR,) * self.config.num_layers

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._block, walk_=walk_)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v, h, l = c.hidden_size, c.vocab_size, c.num_heads, c.num_layers
        ql, r, rope = c.q_lora_rank, c.kv_lora_rank, c.qk_rope_head_dim
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # as ExaoneMoeModel draws them: output projections scaled down by
        # depth (a sublayer counts as a layer), the embedding's rows at the
        # stream's own scale
        out_scale = (2 * 2 * l) ** -0.5
        embed_init = jax.nn.initializers.normal(1.0)
        k = jax.random.split(rng, 11)
        l2 = 2 * l
        sub = {
            "attn_norm": jnp.ones((l2, d)),
            "wq_a": init(k[1], (l2, d, ql), pd),
            "q_norm": jnp.ones((l2, ql)),
            "wq_b": init(k[2], (l2, ql, h * c.q_head_dim), pd),
            "wkv_a": init(k[3], (l2, d, r + rope), pd),
            "kv_norm": jnp.ones((l2, r)),
            "wo": init(k[5], (l2, h * c.v_head_dim, d), pd) * out_scale,
            "mlp_norm": jnp.ones((l2, d)),
            **gated_init(init, jax.random.split(k[6], 3), (l2,), d,
                         c.ffn_hidden_size, "w_", pd, out_scale)}
        kv = jax.random.split(k[4])
        moe = {
            **{name: init(kv[i], (l, r, h * (c.qk_nope_head_dim
                                             + c.v_head_dim)), pd)
               for i, name in enumerate(WKV_B)},
            "router": init(k[7], (l, d, c.num_experts), pd),
            "select_bias": jnp.zeros((l, c.num_experts)),
            **gated_init(init, jax.random.split(k[8], 3), (l, c.held[1]), d,
                         c.expert_ffn_hidden_size, "expert_", pd, out_scale)}
        return {"embed": embed_init(k[0], (v, d), pd), SUB: sub, PAIR: moe,
                "final_norm": jnp.ones((d,)),
                "lm_head": init(k[9], (d, v), pd)}

    def logical_axes(self):
        return {"embed": ("vocab_in", "hidden"),
                SUB: {"attn_norm": ("layer", "hidden"),
                      "wq_a": ("layer", "hidden", None),
                      "q_norm": ("layer", None),
                      "wq_b": ("layer", None, "heads"),
                      "wkv_a": ("layer", "hidden", None),
                      "kv_norm": ("layer", None),
                      "wo": ("layer", "heads", "hidden"),
                      "mlp_norm": ("layer", "hidden"),
                      **gated_axes("w_")},
                PAIR: {**{name: ("layer", None, "heads") for name in WKV_B},
                      "router": ("layer", "hidden", None),
                      "select_bias": ("layer", None),
                      **gated_axes("expert_", "expert")},
                "final_norm": ("hidden",), "lm_head": ("hidden", "vocab")}

    # --------------------------------------------------------------- layers
    def _projections(self, x, blk, pos):
        """What models/mla.LatentAttention asks of a family: ``(q_nope, q_rope``
        rotated, ``c~``, ``k_r`` rotated) of the stream, both scale factors in
        the gains of the norms they follow."""
        c = self.config
        r, n = c.kv_lora_rank, c.qk_nope_head_dim
        y = rms_norm(x, blk["attn_norm"], c.eps)
        qa = rms_norm(qdot("btd,de->bte", y, blk["wq_a"]),
                      blk["q_norm"] * c.q_scale, c.eps)
        q = project_heads(qa, blk["wq_b"], c.num_heads, c.q_head_dim)
        ckr = qdot("btd,de->bte", y, blk["wkv_a"])
        lat = rms_norm(ckr[..., :r], blk["kv_norm"] * c.kv_scale, c.eps)
        k_r = apply_rotary_half(ckr[..., None, r:], pos, c.rope_theta)[:, :, 0]
        return (q[..., :n], apply_rotary_half(q[..., n:], pos, c.rope_theta),
                lat, k_r)

    @staticmethod
    def _sub(blk, i: int):
        """Sublayer ``i``'s leaves of a double layer's view: each leaf of the
        ``sub`` stack addressed in the whole stack at ``2 l + i``, and the
        sublayer's own ``wkv_b`` stack, whole for the prompt kernel, as
        ``sarvam_mla`` hands it."""
        out = {"wkv_b": blk[PAIR][WKV_B[i]]}
        for name, node in blk[SUB].items():
            out[name] = jax.lax.dynamic_index_in_dim(
                node["__whole__"], 2 * node["__layer__"] + i, 0,
                keepdims=False)
        return out

    def _block(self, x, blk, state, layer, idx, valid, walk_):
        """One DOUBLE layer -> ``(x, state)``. ``state``: ``None`` (no cache),
        or ``(latent, counts)``: the cache leaf, read and written at rows ``2
        layer`` and ``2 layer + 1``, and the step's counters. ``valid [B]``:
        the block's real positions a row; ``walk_``: the decode program's
        ``slot_walk``."""
        c = self.config
        t = x.shape[1]
        latent, counts = (None, None) if state is None else state
        first, second = self._sub(blk, 0), self._sub(blk, 1)
        at0, at1 = (None, None) if state is None else (2 * layer,
                                                       2 * layer + 1)
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        a1, latent = self._attention(x, first, latent, at0, idx, valid, walk_)
        z = rms_norm(a1, first["mlp_norm"], c.eps)
        with jax.named_scope("dstpu_scmoe_experts"):
            m, n = ffn(z, blk[PAIR], SPARSE, tokens, c)
        with jax.named_scope("dstpu_scmoe_dense"):
            b1 = a1 + ffn(z, first, DENSE, None, c)[0]
        a2, latent = self._attention(b1, second, latent, at1, idx, valid,
                                     walk_)
        with jax.named_scope("dstpu_scmoe_dense"):
            out = a2 + ffn(rms_norm(a2, second["mlp_norm"], c.eps), second,
                           DENSE, None, c)[0] + m
        return out, (None if state is None else (latent, counts + n))

    def _stack(self, params, kind):
        """Both stacks as the walk takes them: the expert stacks whole, for
        the grouped matmul to address by group, and every sublayer's leaf
        whole, for :meth:`_sub` to address by sublayer."""
        return {SUB: whole_leaves(params[SUB], *SUB_LEAVES),
                PAIR: whole_leaves(params[PAIR], *EXPERT_LEAVES, *WKV_B)}

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """Two rows of the leaf a double layer."""
        return self._latent_cache(2 * self.config.num_layers, batch_size,
                                  max_len, dtype)

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the real experts a layer; the
        identity experts hold none."""
        c = self.config
        d, h = c.hidden_size, c.num_heads
        attn = (d * c.q_lora_rank + c.q_lora_rank
                + c.q_lora_rank * h * c.q_head_dim
                + d * (c.kv_lora_rank + c.qk_rope_head_dim) + c.kv_lora_rank
                + c.kv_lora_rank * h * (c.qk_nope_head_dim + c.v_head_dim)
                + h * c.v_head_dim * d + 2 * d)
        layer = (2 * (attn + 3 * d * c.ffn_hidden_size)
                 + d * c.num_experts + c.num_experts
                 + 3 * d * c.expert_ffn_hidden_size * c.held[1])
        return 2 * c.vocab_size * d + d + c.num_layers * layer

    def flops_per_token(self) -> float:
        c = self.config
        expert = 3 * c.hidden_size * c.expert_ffn_hidden_size
        # of a token's k choices, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        return 6.0 * (self.num_params()
                      - c.num_layers * expert * (c.held[1] - routed))
