"""EXAONE-MoE style decoder (HF ``exaone_moe``; K-EXAONE-236B-A23B): grouped
queries with an RMSNorm on queries and keys, sliding-window layers that
rotate (RoPE, rotate-half) beside global layers that do not, a leading dense
SwiGLU layer and then sparse layers: a shared expert plus the ``k`` routed
experts a sigmoid router picks, of which this model HOLDS ``held = (first,
count)`` (moe/grouped.py: one chip's share of an expert-parallel layer).

    h = x + Attn(rms(x; g1));  y = h + FFN(rms(h; g2))
    Attn: q, k <- rms over the head dimension; sliding layers rotate q, k and
          attend i - j < window; global layers attend every j <= i, unrotated
    FFN (sparse) = Shared(z) + s * sum over the held of the chosen w_e Expert_e(z)

What it brings that no other model here has: two sizes of key-value state in
one cache tree. ``k``, ``v`` ``[Lg, B, Hkv, max_len, Dh]`` over the global
layers grow with the request; ``k_win``, ``v_win`` ``[Ls, B, Hkv, window,
Dh]`` over the sliding layers are rings of the last ``window`` positions and
do not (ops/attention.window_cached_attention). The layers are two stacked
trees by the kind of their FFN, walked in runs of equal (FFN, attention)
kind as the hybrid model's are (models/stack.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cache_positions, merge_heads, project_heads, rms_norm
from deepspeed_tpu.models.moe_ffn import (DENSE, EXPERT_LEAVES, SPARSE, carried_counts, gated_axes, gated_init,
                                          zero_counts)
from deepspeed_tpu.models.moe_ffn import ffn as ffn_layer
from deepspeed_tpu.models.stack import StackedDecoder, kv_cache, next_cache
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention, window_cached_attention
from deepspeed_tpu.ops.rotary import apply_rotary_half

SLIDING, GLOBAL = "sliding_attention", "full_attention"
# a kind of layer is its (FFN kind, attention kind): the FFN kind's stack, and
# the attention kind's cache leaves (models/stack.runs_of)
KINDS = {(ffn, attn): (ffn, leaves) for ffn in (DENSE, SPARSE)
         for attn, leaves in ((GLOBAL, ("k", "v")),
                              (SLIDING, ("k_win", "v_win")))}


@dataclasses.dataclass
class ExaoneMoeConfig:
    vocab_size: int = 153600
    max_seq_len: int = 262144
    hidden_size: int = 6144
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432           # the dense layers' FFN
    moe_intermediate_size: int = 2048        # an expert's, and the shared one's
    layer_types: Sequence[str] = (SLIDING, SLIDING, SLIDING, GLOBAL)
    mlp_layer_types: Sequence[str] = (DENSE, SPARSE, SPARSE, SPARSE)
    sliding_window: int = 128
    num_experts: int = 128                   # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 1e6
    eps: float = 1e-5
    tie_word_embeddings: bool = False
    has_position_table = False    # rotation is computed, nothing is indexed

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        self.mlp_layer_types = tuple(self.mlp_layer_types)
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
        if len(self.layer_types) != len(self.mlp_layer_types) or \
                not self.layer_types:
            raise ValueError("layer_types and mlp_layer_types must name the "
                             "same, non-zero number of layers")
        for names, known in ((self.layer_types, {SLIDING, GLOBAL}),
                             (self.mlp_layer_types, {DENSE, SPARSE})):
            if set(names) - known:
                raise ValueError(f"unknown layer kinds "
                                 f"{sorted(set(names) - known)}")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func={self.scoring_func!r}: this "
                             "router scores by sigmoid only")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(f"n_group={self.n_group}, topk_group="
                             f"{self.topk_group}: this router has no group "
                             "limit")
        if self.tie_word_embeddings:
            raise ValueError("this model's head is untied")
        if self.num_shared_experts != 1:
            raise ValueError(f"num_shared_experts={self.num_shared_experts}: "
                             "one shared expert is computed")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("key-value heads must divide the heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types + self.mlp_layer_types)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("layer_types", (SLIDING, SLIDING, GLOBAL, SLIDING))
        kw.setdefault("mlp_layer_types", (DENSE, SPARSE, SPARSE, SPARSE))
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("sliding_window", 8)
        kw.setdefault("num_experts", 16)
        kw.setdefault("num_experts_per_tok", 4)
        return cls(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=32,
                   intermediate_size=128, moe_intermediate_size=32, **kw)


class ExaoneMoeModel(StackedDecoder):
    """Layers of four kinds in runs of equal pairs: the stacked weights are
    indexed by FFN kind, the cache by attention kind
    (models/stack.StackedDecoder)."""

    stacks = (DENSE, SPARSE)
    kinds = KINDS
    # the expert stacks, for the grouped matmul to address by group
    whole = EXPERT_LEAVES
    # per-slot state, in operand order: rows that grow with the request on
    # the global layers, rings of the window on the sliding layers. Leaves
    # other than k, v are not addressed by token rows: the serving engine
    # refuses prefix reuse, speculation, swap and kv_dtype by this list
    slot_state_keys = ("k", "v", "k_win", "v_win")
    # ring leaves and the window they hold: SlotKVCache counts their rows
    window_state_keys = ("k_win", "v_win")

    def layer_kinds(self):
        c = self.config
        return tuple(zip(c.mlp_layer_types, c.layer_types))

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._block, walk_=walk_, ffn=kind[0],
                                 attn=kind[1], shift=shift)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v, dh = c.hidden_size, c.vocab_size, c.head_dim
        hq, hkv = c.num_heads, c.num_kv_heads
        m, e = c.moe_intermediate_size, c.num_experts
        held = c.held[1]
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # output projections scaled down by depth, as LlamaModel's. The head
        # is untied, so a random model does not read its input token back
        # (what the hybrid family's initial values had to avoid)
        out_scale = (2 * c.num_layers) ** -0.5
        # the embedding's rows at the stream's own scale: drawn at 0.02 a
        # token's row is an eightieth of what attention adds (a context's
        # average, 0.16 to 0.9 an element), every token of a request then
        # brings the router the same input and picks the same experts, and a
        # layer's load is one draw a request (PERF.md, PR 35)
        embed_init = jax.nn.initializers.normal(1.0)

        def attention(keys, l):
            return {"attn_norm": jnp.ones((l, d)),
                    "wq": init(keys[0], (l, d, hq * dh), pd),
                    "wk": init(keys[1], (l, d, hkv * dh), pd),
                    "wv": init(keys[2], (l, d, hkv * dh), pd),
                    "q_norm": jnp.ones((l, dh)), "k_norm": jnp.ones((l, dh)),
                    "wo": init(keys[3], (l, hq * dh, d), pd) * out_scale,
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
                  "router": init(k[4], (ls, d, e), pd),
                  "select_bias": jnp.zeros((ls, e)),
                  **gated(jax.random.split(k[5], 3), (ls,), m, "shared_"),
                  **gated(jax.random.split(k[6], 3), (ls, held), m,
                          "expert_")}
        return {"embed": embed_init(k[0], (v, d), pd), DENSE: dense,
                SPARSE: sparse,
                "final_norm": jnp.ones((d,)),
                "lm_head": init(k[7], (d, v), pd)}

    def logical_axes(self):
        attention = {"attn_norm": ("layer", "hidden"),
                     "wq": ("layer", "hidden", "heads"),
                     "wk": ("layer", "hidden", "kv_heads"),
                     "wv": ("layer", "hidden", "kv_heads"),
                     "q_norm": ("layer", None), "k_norm": ("layer", None),
                     "wo": ("layer", "heads", "hidden"),
                     "mlp_norm": ("layer", "hidden")}

        return {"embed": ("vocab_in", "hidden"),
                DENSE: {**attention, **gated_axes("w_")},
                SPARSE: {**attention, "router": ("layer", "hidden", None),
                         "select_bias": ("layer", None),
                         **gated_axes("shared_"),
                         **gated_axes("expert_", "expert")},
                "final_norm": ("hidden",), "lm_head": ("hidden", "vocab")}

    # --------------------------------------------------------------- layers
    def _block(self, x, blk, state, layer, idx, valid, walk_, *, ffn: str,
               attn: str, shift: int = 0):
        """One layer -> ``(x, state)``. ``state``: ``None`` (no cache), or
        ``(k, v, counts)`` with the cache leaves of this layer's attention
        kind, read at ``layer + shift`` (the stacked weights are indexed by
        FFN kind, the cache by attention kind), and the step's counters.
        ``valid [B]``: the block's real positions a row; ``walk_``: the
        decode program's ``cache["slot_walk"]``."""
        c = self.config
        b, t, _ = x.shape
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        y = rms_norm(x, blk["attn_norm"], c.eps)
        q = rms_norm(project_heads(y, blk["wq"], hq, dh), blk["q_norm"], c.eps)
        k_ = rms_norm(project_heads(y, blk["wk"], hkv, dh), blk["k_norm"],
                      c.eps)
        v_ = project_heads(y, blk["wv"], hkv, dh)
        if attn == SLIDING:
            pos = cache_positions(0 if idx is None else idx, t)
            q = apply_rotary_half(q, pos, c.rope_theta)
            k_ = apply_rotary_half(k_, pos, c.rope_theta)
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        if state is None:
            rep = hq // hkv
            band = None
            if attn == SLIDING:
                i = jnp.arange(t)
                band = (i[:, None] - i[None, :] < c.sliding_window)[None, None]
            out = multihead_attention(
                q, jnp.repeat(k_, rep, axis=2), jnp.repeat(v_, rep, axis=2),
                causal=True, mask=band)
        else:
            kc, vc, counts = state
            at = layer + shift
            if attn == SLIDING:
                out, kc, vc = window_cached_attention(
                    q, kc, vc, k_, v_, at, idx, valid=valid, active=walk_)
            else:
                out, kc, vc = cached_attention(q, kc, vc, k_, v_, at, idx,
                                               active=walk_)
        x = x + merge_heads(out, blk["wo"])
        z = rms_norm(x, blk["mlp_norm"], c.eps)
        y, n = ffn_layer(z, blk, ffn, tokens, c)
        return x + y, (None if state is None else (kc, vc, counts + n))

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``k``, ``v`` over the global layers at ``max_len`` rows; ``k_win``,
        ``v_win`` over the sliding layers at ``sliding_window`` rows, whatever
        ``max_len`` is; the index."""
        c = self.config
        dtype = dtype or self.compute_dtype
        ring = kv_cache(c.count(SLIDING), batch_size, c.num_kv_heads,
                        c.sliding_window, c.head_dim, dtype, packed=False)
        return dict(kv_cache(c.count(GLOBAL), batch_size, c.num_kv_heads,
                             max_len, c.head_dim, dtype),
                    k_win=ring["k"], v_win=ring["v"])

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T > 1) or decode (T == 1) against the cache tree.
        ``cache["index"]`` is a scalar or a per-slot ``[B]`` vector;
        ``cache["valid_len"]`` (scalar or ``[B]``) how many of the block's
        positions are real for each row: a ring keeps the last ``window``
        REAL positions, and a position that is not real is routed to no
        expert. ``cache["slot_walk"]`` is the decode program's walk order
        for the fused decode step of both kinds of layer. The returned cache
        carries ``step_counters`` (models/moe_ffn.STEP_COUNTERS), summed over
        the sparse layers. Not the shared frame's step: a prompt passes whole,
        whatever its length, and every position's logits come back (ROADMAP.md,
        D16)."""
        b, t = input_ids.shape
        valid = cache.get("valid_len")
        if valid is not None:
            valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
        x, leaves, counts = self._layers(
            params, self._embed(params, input_ids),
            tuple(cache[k] for k in self.slot_state_keys), zero_counts(t),
            cache["index"], valid, cache.get("slot_walk"))
        hidden = self._norm(x, params["final_norm"])
        out = next_cache(cache, t, **dict(zip(self.slot_state_keys, leaves)))
        out.update(carried_counts(cache, counts))
        return self.logits(params, hidden), out

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a sparse layer."""
        c = self.config
        d, dh = c.hidden_size, c.head_dim
        attn = (d * dh * (2 * c.num_heads + 2 * c.num_kv_heads) + 2 * dh
                + 2 * d)
        dense = attn + 3 * d * c.intermediate_size
        sparse = (attn + d * c.num_experts + c.num_experts
                  + 3 * d * c.moe_intermediate_size * (1 + c.held[1]))
        return (2 * c.vocab_size * d + d + c.count(DENSE) * dense
                + c.count(SPARSE) * sparse)

    def flops_per_token(self) -> float:
        c = self.config
        d, dh = c.hidden_size, c.head_dim
        attn = d * dh * (2 * c.num_heads + 2 * c.num_kv_heads)
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        active = (2 * c.vocab_size * d + c.count(DENSE) * (
            attn + 3 * d * c.intermediate_size) + c.count(SPARSE) * (
            attn + d * c.num_experts
            + 3 * d * c.moe_intermediate_size * (1 + routed)))
        return 6.0 * active
