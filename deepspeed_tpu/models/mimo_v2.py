"""MiMo-V2 style decoder (HF ``mimo_v2``; MiMo-V2.5, the MiMo-V2-Flash
family): sliding-window layers with a learned attention SINK beside global
layers of another key-value head count, keys wider than values, rotation on a
part of the key, a leading dense SwiGLU layer and then sparse layers of
routed experts with no shared one, of which this model HOLDS ``held = (first,
count)`` (moe/grouped.py: one chip's share of an expert-parallel layer).

    h = x + Attn(rms(x; g1)) Wo;  y = h + FFN(rms(h; g2))
    [q | k | v] = rms(x) Wqkv       q: Hq x Dk; k: Hkv x Dk; v: Hkv x Dv
                                    Hkv by the layer's kind
    q, k: rotate-half on the FIRST int(partial_rotary_factor Dk) lanes, at
          ``rope_theta`` (global) or ``swa_rope_theta`` (sliding)
    v <- attention_value_scale v
    s_ij = q_i . k_j / sqrt(Dk);  j <= i (global);  i - j < window too (sliding)
    global:  p = softmax_j(s);  sliding: p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))
    FFN (sparse) = sum over the held of the chosen w_e Expert_e(z), sigmoid scores

What it brings that no other model here has: one attention geometry a LEAF
and not a model. ``k [Lg, B, Hkv_g, max_len, Dk']``, ``v [Lg, B, Hkv_g,
max_len, Dv]`` over the global layers and the rings ``k_win [Ls, B, Hkv_s,
window, Dk']``, ``v_win [Ls, B, Hkv_s, window, Dv]`` over the sliding ones
differ in heads AND in last dimension; ``Dk'`` is the key row as cached
(ops/attention.key_row_width: 192 live lanes in a row of 256, which is what
the TPU's HBM tiling makes of a 192-wide row anyway). The attention weights
differ by attention kind (the fused projection's columns), so the layers are
stacked by (FFN kind, attention kind) and walked in runs of equal pairs
(models/stack.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cache_positions, merge_heads, project_heads, rms_norm
from deepspeed_tpu.models.moe_ffn import DENSE, EXPERT_LEAVES, SPARSE, gated_axes, gated_init
from deepspeed_tpu.models.moe_ffn import ffn as ffn_layer
from deepspeed_tpu.models.stack import StackedDecoder, kv_cache
from deepspeed_tpu.ops import gqa_prefill
from deepspeed_tpu.ops.attention import (blocked_prompt_attention, cached_attention, key_row_width, pad_lanes,
                                         sink_softmax, window_cached_attention, write_kv_cache)
from deepspeed_tpu.ops.rotary import apply_rotary_half

SLIDING, GLOBAL = "sliding", "global"


@dataclasses.dataclass
class MimoV2Config:
    vocab_size: int = 152576
    max_seq_len: int = 1048576
    hidden_size: int = 4096
    num_heads: int = 64
    num_kv_heads: int = 4                    # the global layers'
    swa_num_kv_heads: int = 8                # the sliding layers'
    head_dim: int = 192                      # queries and keys
    v_head_dim: int = 128
    intermediate_size: int = 16384           # the dense layers' FFN
    moe_intermediate_size: int = 2048        # an expert's
    hybrid_layer_pattern: Sequence[int] = (0, 1, 1, 1, 1, 0)   # 1: sliding
    moe_layer_freq: Sequence[int] = (0, 1, 1, 1, 1, 1)         # 1: sparse
    sliding_window: int = 128
    num_experts: int = 256                   # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: Optional[int] = None
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: Optional[float] = None    # None: 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    eps: float = 1e-5
    tie_word_embeddings: bool = False
    prompt_block: int = 2048     # tokens of a prompt that pass the stack at once
    key_block: int = 512         # cached rows a global layer attends at once
    has_position_table = False    # rotation is computed, nothing is indexed

    def __post_init__(self):
        self.hybrid_layer_pattern = tuple(int(p) for p in
                                          self.hybrid_layer_pattern)
        self.moe_layer_freq = tuple(int(p) for p in self.moe_layer_freq)
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
        if self.routed_scaling_factor is None:
            self.routed_scaling_factor = 1.0
        if len(self.hybrid_layer_pattern) != len(self.moe_layer_freq) or \
                not self.hybrid_layer_pattern:
            raise ValueError("hybrid_layer_pattern and moe_layer_freq must "
                             "name the same, non-zero number of layers")
        if set(self.hybrid_layer_pattern + self.moe_layer_freq) - {0, 1}:
            raise ValueError("hybrid_layer_pattern and moe_layer_freq hold "
                             "0 and 1 alone")
        if self.scoring_func != "sigmoid":
            raise ValueError(f"scoring_func={self.scoring_func!r}: this "
                             "router scores by sigmoid only")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(f"n_group={self.n_group}, topk_group="
                             f"{self.topk_group}: this router has no group "
                             "limit")
        if self.n_shared_experts:
            raise ValueError(f"n_shared_experts={self.n_shared_experts}: "
                             "this layer has no shared expert")
        if self.add_full_attention_sink_bias:
            raise ValueError("a sink on the global layers is not computed")
        if self.tie_word_embeddings:
            raise ValueError("this model's head is untied")
        for hkv in (self.num_kv_heads, self.swa_num_kv_heads):
            if self.num_heads % hkv:
                raise ValueError("key-value heads must divide the heads")
        if not 0 < self.rotary_dim <= self.head_dim or self.rotary_dim % 2:
            raise ValueError(f"partial_rotary_factor="
                             f"{self.partial_rotary_factor} rotates "
                             f"{self.rotary_dim} of {self.head_dim} lanes")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def rotary_dim(self) -> int:
        return int(self.partial_rotary_factor * self.head_dim)

    @property
    def layer_kinds(self):
        """``(FFN kind, attention kind)`` a layer, in stack order."""
        return tuple((SPARSE if m else DENSE, SLIDING if s else GLOBAL)
                     for m, s in zip(self.moe_layer_freq,
                                     self.hybrid_layer_pattern))

    def count(self, *kinds: str) -> int:
        """Layers whose pair of kinds holds every one of ``kinds``."""
        return sum(set(kinds) <= set(pair) for pair in self.layer_kinds)

    def kv_heads(self, attn: str) -> int:
        return self.swa_num_kv_heads if attn == SLIDING else self.num_kv_heads

    def qkv_columns(self, attn: str) -> int:
        """Columns of a layer's fused ``[q | k | v]`` projection."""
        return (self.num_heads * self.head_dim
                + self.kv_heads(attn) * (self.head_dim + self.v_head_dim))

    @classmethod
    def tiny(cls, **kw):
        sizes = dict(hybrid_layer_pattern=(0, 1, 1, 0, 1),
                     moe_layer_freq=(0, 1, 1, 1, 1), vocab_size=512,
                     max_seq_len=128, sliding_window=8, num_experts=16,
                     num_experts_per_tok=4, prompt_block=16, key_block=8,
                     hidden_size=64, num_heads=4, num_kv_heads=1,
                     swa_num_kv_heads=2, head_dim=24, v_head_dim=16,
                     intermediate_size=128, moe_intermediate_size=32)
        return cls(**{**sizes, **kw})


def stack_name(ffn: str, attn: str) -> str:
    """The params tree's key of the layers of one (FFN, attention) pair."""
    return f"{ffn}_{attn}"


# a kind of layer is its pair: the pair's stack, and the attention kind's
# cache leaves (models/stack.runs_of)
KINDS = {(ffn, attn): (stack_name(ffn, attn), leaves)
         for ffn in (DENSE, SPARSE) for attn, leaves in
         ((GLOBAL, ("k", "v")), (SLIDING, ("k_win", "v_win")))}


def count_window_traced(ring_step: bool) -> None:
    """Say in the program's registry how a sliding layer with a cache was
    traced: ``swa/traced_ring_step`` (one token a row: the ring's step) or
    ``swa/traced_band_block`` (a prompt block: the band). Both exist from the
    first call on."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = [reg.counter("swa/traced_" + n)
                for n in ("band_block", "ring_step")]
    counters[bool(ring_step)].inc()


class MimoV2Model(StackedDecoder):
    """Layers of four kinds in runs of equal pairs: the stacked weights are
    indexed by the pair, the cache by attention kind
    (models/stack.StackedDecoder)."""

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

    @property
    def stacks(self) -> Tuple[str, ...]:
        """The params tree's layer stacks, in order of first appearance."""
        return tuple(dict.fromkeys(stack_name(f, a)
                                   for f, a in self.config.layer_kinds))

    def layer_kinds(self):
        return self.config.layer_kinds

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._block, walk_=walk_, ffn=kind[0],
                                 attn=kind[1], shift=shift)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v = c.hidden_size, c.vocab_size
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # as ExaoneMoeModel's: output projections scaled down by depth, the
        # embedding's rows at the stream's own scale so that a request's
        # tokens do not all pick the same experts (PERF.md, PR 35)
        out_scale = (2 * c.num_layers) ** -0.5
        embed_init = jax.nn.initializers.normal(1.0)
        rng, k_embed, k_head = jax.random.split(rng, 3)
        params = {"embed": embed_init(k_embed, (v, d), pd)}
        for ffn, attn in dict.fromkeys(c.layer_kinds):
            n = c.count(ffn, attn)
            rng, *k = jax.random.split(rng, 9)
            blk = {"attn_norm": jnp.ones((n, d)),
                   "wqkv": init(k[0], (n, d, c.qkv_columns(attn)), pd),
                   "wo": init(k[1], (n, c.num_heads * c.v_head_dim, d), pd)
                   * out_scale,
                   "mlp_norm": jnp.ones((n, d))}
            if attn == SLIDING and c.add_swa_attention_sink_bias:
                # at the scores' own scale, so that a softmax without the
                # sink is a different function and not a rounding
                blk["sink"] = jax.random.normal(k[2], (n, c.num_heads), pd)
            if ffn == DENSE:
                blk.update(gated_init(init, k[3:6], (n,), d,
                                      c.intermediate_size, "w_", pd,
                                      out_scale))
            else:
                blk.update(router=init(k[3], (n, d, c.num_experts), pd),
                           select_bias=jnp.zeros((n, c.num_experts)),
                           **gated_init(init, k[4:7], (n, c.held[1]), d,
                                        c.moe_intermediate_size, "expert_",
                                        pd, out_scale))
            params[stack_name(ffn, attn)] = blk
        params["final_norm"] = jnp.ones((d,))
        params["lm_head"] = init(k_head, (d, v), pd)
        return params

    def logical_axes(self):
        c = self.config
        axes = {"embed": ("vocab_in", "hidden")}
        for ffn, attn in dict.fromkeys(c.layer_kinds):
            blk = {"attn_norm": ("layer", "hidden"),
                   "wqkv": ("layer", "hidden", "heads"),
                   "wo": ("layer", "heads", "hidden"),
                   "mlp_norm": ("layer", "hidden")}
            if attn == SLIDING and c.add_swa_attention_sink_bias:
                blk["sink"] = ("layer", None)
            if ffn == DENSE:
                blk.update(gated_axes("w_"))
            else:
                blk.update(router=("layer", "hidden", None),
                           select_bias=("layer", None),
                           **gated_axes("expert_", "expert"))
            axes[stack_name(ffn, attn)] = blk
        axes.update(final_norm=("hidden",), lm_head=("hidden", "vocab"))
        return axes

    # --------------------------------------------------------------- layers
    def _qkv(self, y, blk, attn: str, pos):
        """The fused projection split into heads, rotated and scaled:
        ``q [B, T, Hq, Dk]``, ``k [B, T, Hkv, Dk]``, ``v [B, T, Hkv, Dv]``."""
        c = self.config
        b, t, _ = y.shape
        hq, hkv, dk, dv = c.num_heads, c.kv_heads(attn), c.head_dim, \
            c.v_head_dim
        qkv = project_heads(y, blk["wqkv"], 1, c.qkv_columns(attn))[:, :, 0]
        q = qkv[..., :hq * dk].reshape(b, t, hq, dk)
        k_ = qkv[..., hq * dk:(hq + hkv) * dk].reshape(b, t, hkv, dk)
        v_ = qkv[..., (hq + hkv) * dk:].reshape(b, t, hkv, dv)
        theta = c.swa_rope_theta if attn == SLIDING else c.rope_theta
        rot = c.rotary_dim

        def rotate(x):
            return jnp.concatenate(
                [apply_rotary_half(x[..., :rot], pos, theta), x[..., rot:]],
                axis=-1)

        v_ = (v_.astype(jnp.float32) * c.attention_value_scale
              ).astype(v_.dtype)
        return rotate(q), rotate(k_), v_

    def _plain_attention(self, q, k_, v_, attn: str, sink):
        """A whole sequence with no cache (training, a full forward)."""
        c = self.config
        b, t, hq, dk = q.shape
        hkv = k_.shape[2]
        qg = q.reshape(b, t, hkv, hq // hkv, dk)
        logits = jnp.einsum("btkrd,bskd->bkrts", qg, k_
                            ).astype(jnp.float32) * dk ** -0.5
        i = jnp.arange(t)
        ok = i[:, None] >= i[None, :]
        if attn == SLIDING:
            ok &= i[:, None] - i[None, :] < c.sliding_window
        logits = jnp.where(ok, logits, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(logits, axis=-1) if sink is None else \
            sink_softmax(logits, sink.reshape(1, hkv, hq // hkv, 1, 1))
        out = jnp.einsum("bkrts,bskd->btkrd", probs.astype(v_.dtype), v_)
        return out.reshape(b, t, hq, v_.shape[3])

    def _cached_attention(self, q, k_, v_, state, at, idx, valid, walk_,
                          attn: str, sink):
        """One layer against its cache leaves ``(kc, vc)`` at ``at`` ->
        ``(out, kc, vc)``. The key row as cached is whole lane tiles: queries
        and keys go over with zeros behind their live lanes, and the scale is
        the live width's."""
        c = self.config
        b, t, hq, dk = q.shape
        kc, vc = state
        scale = dk ** -0.5
        q, k_ = pad_lanes(q, kc.shape[4]), pad_lanes(k_, kc.shape[4])
        if attn == SLIDING:
            count_window_traced(t == 1)
            if t == 1:
                return window_cached_attention(
                    q, kc, vc, k_, v_, at, idx, scale=scale, valid=valid,
                    active=walk_, sink=sink)
            with jax.named_scope("dstpu_swa_band"):
                return window_cached_attention(
                    q, kc, vc, k_, v_, at, idx, scale=scale, valid=valid,
                    sink=sink)
        s_max, hkv = kc.shape[3], kc.shape[2]
        if t > 1 and s_max > c.key_block and s_max % c.key_block == 0:
            kc, vc, kl, vl = write_kv_cache(kc, vc, k_, v_, at, idx)
            # serving only (the kernel has no VJP): a TPU and shapes that fit
            # take the one call over the leaves where they lie, whose dead
            # query tiles come back as zeros; the rest the loop
            kernel = jax.default_backend() == "tpu" and gqa_prefill.supports(
                s_max, kc.shape[4], q.shape[3], c.key_block, t, hq, hkv,
                vc.shape[4])
            gqa_prefill.count_traced(kernel)
            with jax.named_scope("dstpu_gqa_prefill"):
                out = gqa_prefill.gqa_prefill(
                    q, kc, vc, at, idx, valid, key_block=c.key_block,
                    scale=scale
                ) if kernel else blocked_prompt_attention(
                    q, kl, vl, jnp.broadcast_to(cache_positions(idx, t),
                                                (b, t)),
                    scale=scale, key_block=c.key_block)
            return out, kc, vc
        return cached_attention(q, kc, vc, k_, v_, at, idx, scale=scale,
                                active=walk_)

    def _block(self, x, blk, state, layer, idx, valid, walk_, *, ffn: str,
               attn: str, shift: int = 0):
        """One layer -> ``(x, state)``. ``state``: ``None`` (no cache), or
        ``(k, v, counts)`` with the cache leaves of this layer's attention
        kind, read at ``layer + shift`` (the stacked weights are indexed by
        the pair of kinds, the cache by attention kind), and the step's
        counters. ``valid [B]``: the block's real positions a row;
        ``walk_``: the decode program's ``cache["slot_walk"]``."""
        c = self.config
        t = x.shape[1]
        y = rms_norm(x, blk["attn_norm"], c.eps)
        q, k_, v_ = self._qkv(y, blk, attn,
                              cache_positions(0 if idx is None else idx, t))
        sink = blk.get("sink")
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        if state is None:
            out = self._plain_attention(q, k_, v_, attn, sink)
        else:
            *leaves, counts = state
            out, kc, vc = self._cached_attention(
                q, k_, v_, leaves, layer + shift, idx, valid, walk_, attn,
                sink)
        x = x + merge_heads(out.astype(x.dtype), blk["wo"])
        z = rms_norm(x, blk["mlp_norm"], c.eps)
        y, n = ffn_layer(z, blk, ffn, tokens, c)
        return x + y, (None if state is None else (kc, vc, counts + n))

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``k``, ``v`` over the global layers at ``max_len`` rows and the
        global layers' heads; ``k_win``, ``v_win`` over the sliding layers at
        ``sliding_window`` rows and THEIR heads, whatever ``max_len`` is; the
        index. A key row is ``key_row_width(head_dim)`` lanes, a value row
        ``v_head_dim``."""
        c = self.config
        dtype = dtype or self.compute_dtype
        dk, dv = key_row_width(c.head_dim), c.v_head_dim
        ring = kv_cache(c.count(SLIDING), batch_size, c.swa_num_kv_heads,
                        c.sliding_window, dk, dtype, packed=False,
                        v_head_dim=dv)
        return dict(kv_cache(c.count(GLOBAL), batch_size, c.num_kv_heads,
                             max_len, dk, dtype, packed=False, v_head_dim=dv),
                    k_win=ring["k"], v_win=ring["v"])

    def _layer_params(self, ffn: str, attn: str, held: int) -> int:
        c = self.config
        d = c.hidden_size
        n = (d * c.qkv_columns(attn) + c.num_heads * c.v_head_dim * d + 2 * d)
        if attn == SLIDING and c.add_swa_attention_sink_bias:
            n += c.num_heads
        if ffn == DENSE:
            return n + 3 * d * c.intermediate_size
        return (n + d * c.num_experts + c.num_experts
                + 3 * d * c.moe_intermediate_size * held)

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a sparse layer."""
        c = self.config
        return (2 * c.vocab_size * c.hidden_size + c.hidden_size
                + sum(self._layer_params(f, a, c.held[1])
                      for f, a in c.layer_kinds))

    def flops_per_token(self) -> float:
        c = self.config
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        return 6.0 * (2 * c.vocab_size * c.hidden_size + sum(
            self._layer_params(f, a, routed) for f, a in c.layer_kinds))
