"""Granite 4.0-H style hybrid decoder (HF ``granitemoehybrid`` without
experts): a stack whose layers are of two kinds, Mamba-2 state-space mixers
and grouped-query attention without positions, every layer followed by a
SwiGLU MLP, with four muP-style multipliers and a tied head.

What it brings that no other model here has: a selective state-space
recurrence (``ops/ssm.py``), a causal depthwise convolution with carried
state, a gated RMSNorm, attention at ``attention_multiplier`` and not
``head_dim ** -0.5``, and per-request state of two kinds in one cache tree:
key-value rows that grow with the request on the attention layers, and a
fixed-size recurrent state (``ssm``, ``conv``) on the Mamba layers.

The stack follows ``layer_types``: the Mamba layers are one stacked tree, the
attention layers another, and the forward walks runs of equal layers
(models/stack.py), so 40 layers compile as a handful of loops.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba
from deepspeed_tpu.models.base import merge_heads, project_heads, qdot, rms_norm, tied_logits
from deepspeed_tpu.models.stack import StackedDecoder, kv_cache, next_cache
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass
class GraniteHybridConfig:
    vocab_size: int = 100352
    max_seq_len: int = 131072
    hidden_size: int = 2048
    layer_types: Sequence[str] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 8192
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    eps: float = 1e-5
    num_local_experts: int = 0
    position_embedding_type: str = "nope"
    has_position_table = False    # nothing is indexed by position

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - {MAMBA, ATTENTION}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types must name {MAMBA!r} or "
                             f"{ATTENTION!r} layers, got {sorted(unknown)}")
        if self.num_local_experts:
            raise ValueError("this model computes no experts: "
                             f"num_local_experts={self.num_local_experts}")
        if self.position_embedding_type != "nope":
            raise ValueError("this model computes no position term: "
                             f"position_embedding_type="
                             f"{self.position_embedding_type!r}")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"mamba_n_groups {self.mamba_n_groups} does not "
                             f"divide mamba_n_heads {self.mamba_n_heads}")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head} is not "
                f"mamba_expand x hidden_size = {self.d_inner}")
        if self.hidden_size % self.num_heads or \
                self.num_heads % self.num_kv_heads:
            raise ValueError("heads must divide hidden_size, and key-value "
                             "heads the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("layer_types", (MAMBA, MAMBA, ATTENTION, MAMBA))
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        return cls(hidden_size=64, num_heads=4, num_kv_heads=2,
                   intermediate_size=128, mamba_n_heads=4, mamba_d_head=32,
                   mamba_d_state=16, mamba_chunk_size=8, **kw)


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class GraniteHybridModel(StackedDecoder):
    """Layers of two kinds, a stack and a pair of cache leaves each
    (models/stack.StackedDecoder); the embedding is scaled, the head is the
    embedding's, scaled, and no step counts anything."""

    stacks = (MAMBA, ATTENTION)
    kinds = {MAMBA: (MAMBA, ("ssm", "conv")), ATTENTION: (ATTENTION, ("k", "v"))}
    # the per-slot state the serving cache holds, in operand order: key-value
    # rows on the attention layers, recurrent state on the Mamba layers
    # (``state_dtype``: 75.5 MB a slot at the published sizes; the
    # convolution's tail is in the compute dtype)
    slot_state_keys = ("k", "v", "ssm", "conv")
    step_counters = prompt_counters = ()

    def layer_kinds(self):
        return self.config.layer_types

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._mamba_layer, step=step) \
            if kind == MAMBA else functools.partial(self._attn_layer,
                                                    walk_=walk_)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, m, v = c.hidden_size, c.intermediate_size, c.vocab_size
        lm, la = c.count(MAMBA), c.count(ATTENTION)
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        h, d_in = c.mamba_n_heads, c.d_inner
        k = jax.random.split(rng, 16)
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # The multipliers do what other models leave to the initial values:
        # the embedding is drawn at 0.02 / embedding_multiplier, so the
        # stream starts at the usual 0.02, and the output projections are not
        # scaled down by depth, residual_multiplier does that. As
        # LlamaModel.init draws them (0.02 and (2L)**-0.5), the tied head
        # would read back the input token from a stream that 12 x embedding
        # dominates: every random model would repeat its last prompt token,
        # and no logit comparison could tell two precisions apart.
        embed_init = jax.nn.initializers.normal(0.02 / c.embedding_multiplier)

        def mlp(keys, l):
            return {"mlp_norm": jnp.ones((l, d)),
                    "w_gate": init(keys[0], (l, d, m), pd),
                    "w_up": init(keys[1], (l, d, m), pd),
                    "w_down": init(keys[2], (l, m, d), pd)}

        # Mamba-2 convention: A in 1..16, dt log-uniform in 0.001..0.1
        # through the inverse softplus, D = 1: neither dead nor saturated
        dt = jnp.exp(jax.random.uniform(k[8], (lm, h)) *
                     (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        mamba = {
            "norm": jnp.ones((lm, d)),
            "in_proj": init(k[3], (lm, d, 2 * d_in + 2 * c.mamba_n_groups
                                   * c.mamba_d_state + h), pd),
            "conv_w": jax.random.uniform(
                k[9], (lm, c.mamba_d_conv, c.conv_dim), jnp.float32, -1.0,
                1.0) * c.mamba_d_conv ** -0.5,
            "conv_b": jnp.zeros((lm, c.conv_dim)),
            "dt_bias": _inv_softplus(dt),
            "A_log": jnp.log(jax.random.uniform(k[10], (lm, h), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((lm, h)),
            "gate_norm": jnp.ones((lm, d_in)),
            "out_proj": init(k[4], (lm, d_in, d), pd),
            **mlp(k[5:8], lm),
        }
        attn = {
            "norm": jnp.ones((la, d)),
            "wq": init(k[11], (la, d, hq * dh), pd),
            "wk": init(k[12], (la, d, hkv * dh), pd),
            "wv": init(k[13], (la, d, hkv * dh), pd),
            "wo": init(k[14], (la, hq * dh, d), pd),
            **mlp(jax.random.split(k[15], 3), la),
        }
        return {"embed": embed_init(k[0], (v, d), pd), MAMBA: mamba,
                ATTENTION: attn, "final_norm": jnp.ones((d,))}

    def logical_axes(self):
        mlp = {"mlp_norm": ("layer", "hidden"),
               "w_gate": ("layer", "hidden", "mlp"),
               "w_up": ("layer", "hidden", "mlp"),
               "w_down": ("layer", "mlp", "hidden")}
        return {
            "embed": ("vocab_in", "hidden"),
            MAMBA: {"norm": ("layer", "hidden"),
                    "in_proj": ("layer", "hidden", None),
                    "conv_w": ("layer", None, None),
                    "conv_b": ("layer", None),
                    "dt_bias": ("layer", None), "A_log": ("layer", None),
                    "D": ("layer", None), "gate_norm": ("layer", None),
                    "out_proj": ("layer", None, "hidden"), **mlp},
            ATTENTION: {"norm": ("layer", "hidden"),
                        "wq": ("layer", "hidden", "heads"),
                        "wk": ("layer", "hidden", "kv_heads"),
                        "wv": ("layer", "hidden", "kv_heads"),
                        "wo": ("layer", "heads", "hidden"), **mlp},
            "final_norm": ("hidden",),
        }

    # --------------------------------------------------------------- layers
    def _mlp(self, x, blk):
        c = self.config
        y = rms_norm(x, blk["mlp_norm"], c.eps)
        gate = jax.nn.silu(qdot("btd,dm->btm", y, blk["w_gate"]))
        up = qdot("btd,dm->btm", y, blk["w_up"])
        return x + c.residual_multiplier * qdot("btm,md->btd", gate * up,
                                                blk["w_down"])

    def _mamba_layer(self, x, blk, state=None, layer=None, idx=None, valid=None,
                     step=None):
        """-> ``(x, state)``: the Mamba mixer (models/mamba.py; one gated
        norm over all of ``d_inner``) at the residual multiplier, then the
        MLP. ``state``: ``None`` or ``(ssm_full, conv_full, counts)``
        (``counts``: the walk's, handed on as it came); ``step``:
        :meth:`_decode_step`'s."""
        leaves, counts = (None, None) if state is None else \
            (state[:-1], state[-1])
        y, leaves = mamba.mixer(x, blk, self.config, leaves, layer, idx,
                                valid, step)
        x = self._mlp(x + self.config.residual_multiplier * y, blk)
        return x, (None if state is None else (*leaves, counts))

    def _decode_step(self, params, valid, b):
        return mamba.decode_step(params[MAMBA], self.config, valid, b)

    def _attn_layer(self, x, blk, state=None, layer=None, idx=None,
                    valid=None, walk_=None):
        """No rotary and no position term; softmax of
        ``q k^T * attention_multiplier``. ``state``: ``None`` or
        ``(k_full, v_full, counts)`` at ``layer`` and ``idx``; ``walk_``: the
        decode program's ``cache["slot_walk"]``. Key-value rows need no
        ``valid``: padding is causally invisible and masked by the lengths.
        -> ``(x, state)``."""
        c = self.config
        b, t, _ = x.shape
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        y = rms_norm(x, blk["norm"], c.eps)
        q = project_heads(y, blk["wq"], hq, dh)
        k_ = project_heads(y, blk["wk"], hkv, dh)
        v_ = project_heads(y, blk["wv"], hkv, dh)
        if state is None:
            rep = hq // hkv
            attn = multihead_attention(
                q, jnp.repeat(k_, rep, axis=2), jnp.repeat(v_, rep, axis=2),
                causal=True, scale=c.attention_multiplier)
        else:
            kc, vc, counts = state
            attn, kc, vc = cached_attention(q, kc, vc, k_, v_, layer, idx,
                                            scale=c.attention_multiplier,
                                            active=walk_)
            state = (kc, vc, counts)
        x = x + c.residual_multiplier * merge_heads(attn, blk["wo"])
        return self._mlp(x, blk), state

    # -------------------------------------------------------------- forward
    def _embed(self, params, input_ids):
        return (params["embed"].astype(self.compute_dtype)[input_ids]
                * jnp.asarray(self.config.embedding_multiplier,
                              self.compute_dtype))

    def logits(self, params, hidden):
        out = tied_logits(hidden, params["embed"])
        return out / jnp.asarray(self.config.logits_scaling, out.dtype)

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """One tree for both kinds of per-request state: ``k``, ``v`` over
        the attention layers only, ``ssm`` ``[Lm, B, H, P, N]`` and ``conv``
        (the convolution's tail, ``[Lm, B, K-1, C]`` or as rows of lanes:
        ``ops/ssm.conv_tail_shape``) over the Mamba layers, and the index."""
        c = self.config
        dtype = dtype or self.compute_dtype
        lm = c.count(MAMBA)
        # no barrier as alloc_kv_cache has: a block at position 0 starts from
        # zeros whatever the buffer held (_mamba_layer), every layer's row is
        # written before it is read again, and called eagerly at 64 slots a
        # barrier would copy 4.8 GB
        state = jnp.zeros((lm, batch_size, c.mamba_n_heads, c.mamba_d_head,
                           c.mamba_d_state), self.state_dtype)
        conv = jnp.zeros((lm, batch_size) + ssm.conv_tail_shape(
            c.mamba_d_conv, c.d_inner, c.conv_dim - c.d_inner), dtype)
        return dict(kv_cache(c.count(ATTENTION), batch_size, c.num_kv_heads,
                             max_len, c.head_dim, dtype), ssm=state, conv=conv)

    def forward_with_cache(self, params, input_ids, cache):
        """Prefill (T > 1) or decode (T == 1) against the cache tree.
        ``cache["index"]`` is a scalar or a per-slot ``[B]`` vector. The
        optional ``cache["valid_len"]`` (scalar or ``[B]``) is how many of
        this block's positions are real for each row: bucket padding behind
        a prompt, or 0 for a slot that is not decoding. Key-value rows need
        no such thing (padding is causally invisible and masked by the
        lengths); recurrent state would fold the padding in, so it stops at
        ``valid_len``: a row with 0 valid positions keeps its state. The
        decode program's ``cache["slot_walk"]`` says the same to the fused
        decode step of the attention layers (ops/attention.cached_attention,
        ``active``): it skips the rows of a slot that is not decoding. Not
        the shared frame's step: a prompt passes whole, whatever its length,
        and every position's logits come back (ROADMAP.md, D16)."""
        b, t = input_ids.shape
        valid = cache.get("valid_len")
        if valid is not None:
            valid = jnp.broadcast_to(jnp.asarray(valid, jnp.int32), (b,))
        x, leaves, _ = self._layers(
            params, self._embed(params, input_ids),
            tuple(cache[k] for k in self.slot_state_keys), None,
            cache["index"], valid, cache.get("slot_walk"))
        hidden = self._norm(x, params["final_norm"])
        return self.logits(params, hidden), next_cache(
            cache, t, **dict(zip(self.slot_state_keys, leaves)))

    def num_params(self) -> int:
        c = self.config
        d, m = c.hidden_size, c.intermediate_size
        mlp = 3 * d * m + d
        mamba = (d + d * (2 * c.d_inner + 2 * c.mamba_n_groups
                          * c.mamba_d_state + c.mamba_n_heads)
                 + (c.mamba_d_conv + 1) * c.conv_dim + 3 * c.mamba_n_heads
                 + c.d_inner + c.d_inner * d + mlp)
        attn = (d + d * c.head_dim * (2 * c.num_heads + 2 * c.num_kv_heads)
                + mlp)
        return (c.vocab_size * d + d + c.count(MAMBA) * mamba
                + c.count(ATTENTION) * attn)

    def flops_per_token(self) -> float:
        c = self.config
        attn = 12 * c.count(ATTENTION) * c.hidden_size * c.max_seq_len
        return 6.0 * self.num_params() + attn
