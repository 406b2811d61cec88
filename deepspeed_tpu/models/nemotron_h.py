"""Nemotron-H style hybrid decoder (HF ``nemotron_h``; Nemotron 3 Super
120B-A12B): a stack whose every layer is ONE sublayer, ``x + Mixer(rms(x))``,
of one of three kinds that ``hybrid_override_pattern`` names a layer at a
time:

    M  Mamba-2 (models/mamba.py), ``n_groups`` groups of heads: a head reads
       its group's ``B`` and ``C``, the gated norm runs a group
    *  grouped-query attention with no rotation and no position term
    E  LatentMoE (models/moe_ffn.py): a sigmoid router over all experts and
       one shared expert on the stream, the routed experts two-matrix
       ``relu(l W1)^2 W2`` in a latent ``l = u W_dn`` narrower than the
       stream, their weighted sum back through ``W_up``; of the experts this
       model HOLDS ``held = (first, count)`` (moe/grouped.py)

What it brings that no other model here has: layers that are a mixer OR a
feed-forward part and never both, so an expert layer owns no cache leaf and
only carries the step's counters; Mamba-2 with several groups inside the
folded decode step (ops/ssm.mamba_step); and experts without a gate matrix
in a latent. No bias but the convolution's, no multipliers, an untied head.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import mamba, moe_ffn
from deepspeed_tpu.models.base import merge_heads, project_heads, rms_norm
from deepspeed_tpu.models.stack import StackedDecoder, kv_cache
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.attention import cached_attention, multihead_attention
from deepspeed_tpu.telemetry.registry import level_counters

MAMBA, ATTENTION, MOE = "mamba", "attention", "moe"
PATTERN = {"M": MAMBA, "*": ATTENTION, "E": MOE}
# one published period: five Mamba-2 layers, five expert layers, one attention
PERIOD = "MEMEMEMEM*E"


@dataclasses.dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    max_seq_len: int = 262144
    hidden_size: int = 4096
    hybrid_override_pattern: str = PERIOD
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 8
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    moe_intermediate_size: int = 2688        # a routed expert's, in the latent
    moe_latent_size: int = 1024
    shared_intermediate_size: int = 5376     # the shared expert's, on the stream
    num_experts: int = 512                   # the router's width
    num_experts_per_tok: int = 22
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    eps: float = 1e-5
    prompt_block: int = 512      # tokens of a prompt that pass the stack at once
    has_position_table = False   # nothing is indexed or rotated by position

    def __post_init__(self):
        unknown = set(self.hybrid_override_pattern) - set(PATTERN)
        if unknown or not self.hybrid_override_pattern:
            raise ValueError("hybrid_override_pattern names layers by "
                             f"{sorted(PATTERN)}, got {sorted(unknown)}")
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(f"mamba_n_groups {self.mamba_n_groups} does not "
                             f"divide mamba_n_heads {self.mamba_n_heads}")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(f"n_group={self.n_group}, topk_group="
                             f"{self.topk_group}: this router has no group "
                             "limit")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("key-value heads must divide the heads")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(f"held={self.held} is not a range of the "
                             f"{self.num_experts} experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("more experts a token than experts")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(PATTERN[c] for c in self.hybrid_override_pattern)

    @property
    def num_layers(self) -> int:
        return len(self.hybrid_override_pattern)

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @classmethod
    def tiny(cls, **kw):
        """Sandbox sizes that keep what the published ones exercise: four
        groups of heads, 3 of 16 experts a token of which 4 are held (so
        empty experts and ``count > k`` both occur), a latent narrower than
        the stream, two key-value heads."""
        sizes = dict(
            hybrid_override_pattern="MEM*EME", vocab_size=512, max_seq_len=128,
            hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
            mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
            mamba_n_groups=4, mamba_chunk_size=8, moe_intermediate_size=48,
            moe_latent_size=32, shared_intermediate_size=96, num_experts=16,
            num_experts_per_tok=3, held=(4, 4), prompt_block=16)
        return cls(**{**sizes, **kw})


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class NemotronHModel(StackedDecoder):
    """Layers of three kinds, a stack each; the Mamba layers' recurrent state
    and the attention layers' rows are the cache, an expert layer holds none
    and hands the step's counters on (models/stack.StackedDecoder)."""

    stacks = ("mamba", "attn", "moe")
    kinds = {MAMBA: ("mamba", ("ssm", "conv")), ATTENTION: ("attn", ("k", "v")),
             MOE: ("moe", ())}
    # the expert stacks, for the grouped matmul to address by group
    whole = moe_ffn.EXPERT_LEAVES
    # per-slot state, in operand order: key-value rows on the attention
    # layers, recurrent state (``state_dtype``) and the convolution's tail
    # (compute dtype) on the Mamba layers
    slot_state_keys = ("k", "v", "ssm", "conv")
    # a decode step also counts the rows its sorted buffers ran and the
    # experts it holds, behind the shared five
    step_counters = moe_ffn.STEP_COUNTERS + moe_ffn.BUFFER_COUNTERS

    def layer_kinds(self):
        return self.config.layer_types

    def _block_of(self, kind, shift, walk_, step):
        extra = {MAMBA: step, ATTENTION: walk_, MOE: None}[kind]
        return functools.partial(self._block, kind=kind, shift=shift,
                                 extra=extra)

    @staticmethod
    def record_step_counters(telemetry, counts) -> None:
        """The expert layer's step vector, the two counters of this family
        behind it, and with them which way the Mamba layers of the serving
        programs were traced (``ssm/traced_*``), into the serving engine's
        registry."""
        n = len(moe_ffn.STEP_COUNTERS)
        moe_ffn.record_step_counters(telemetry, counts[:n])
        for name, value in zip(moe_ffn.BUFFER_COUNTERS, counts[n:]):
            telemetry.counter("serving/" + name).inc(int(value))
        level_counters(telemetry, ssm.TRACED + ("moe/traced_latent",))

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v, dh = c.hidden_size, c.vocab_size, c.head_dim
        hq, hkv = c.num_heads, c.num_kv_heads
        h, d_in, g, n = (c.mamba_n_heads, c.d_inner, c.mamba_n_groups,
                         c.mamba_d_state)
        lat, m, sm = (c.moe_latent_size, c.moe_intermediate_size,
                      c.shared_intermediate_size)
        lm, la, le = c.count(MAMBA), c.count(ATTENTION), c.count(MOE)
        e, held = c.num_experts, c.held[1]
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # every mixer's output projection scaled down by depth
        # (``rescale_prenorm_residual``); the head is untied and the
        # embedding's rows are at the stream's own scale, so a random model
        # does not read its input token back and each token brings the
        # router its own input (PERF.md, PR 35)
        out_scale = c.num_layers ** -0.5
        k = jax.random.split(rng, 20)
        # Mamba-2 convention: A in 1..16, dt log-uniform in 0.001..0.1
        # through the inverse softplus, D = 1: neither dead nor saturated.
        # The gated norm's weight is drawn wide a channel, so that one norm
        # over all groups for one a group fails a comparison
        dt = jnp.exp(jax.random.uniform(k[1], (lm, h)) *
                     (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        mamba_ = {
            "norm": jnp.ones((lm, d)),
            "in_proj": init(k[2], (lm, d, 2 * d_in + 2 * g * n + h), pd),
            "conv_w": jax.random.uniform(
                k[3], (lm, c.mamba_d_conv, c.conv_dim), jnp.float32, -1.0,
                1.0) * c.mamba_d_conv ** -0.5,
            "conv_b": jnp.zeros((lm, c.conv_dim)),
            "dt_bias": _inv_softplus(dt),
            "A_log": jnp.log(jax.random.uniform(k[4], (lm, h), jnp.float32,
                                                1.0, 16.0)),
            "D": jnp.ones((lm, h)),
            "gate_norm": 1.0 + 0.5 * jax.random.normal(k[5], (lm, d_in)),
            "out_proj": init(k[6], (lm, d_in, d), pd) * out_scale,
        }
        attn = {
            "norm": jnp.ones((la, d)),
            "wq": init(k[7], (la, d, hq * dh), pd),
            "wk": init(k[8], (la, d, hkv * dh), pd),
            "wv": init(k[9], (la, d, hkv * dh), pd),
            "wo": init(k[10], (la, hq * dh, d), pd) * out_scale,
        }
        def centred(w):
            """A ``relu2`` MLP's second matrix with zero column sums: the
            activation is positive, its mean over the hidden units is the
            same for every token, and through a matrix drawn freely that
            mean is one constant vector added to the stream a layer, which
            the router and the head then read for every token alike (the
            served tokens collapse to a few and every slot picks the same
            experts: PERF.md, PR 65). A gated MLP has no such mean."""
            return w - w.mean(axis=-2, keepdims=True)

        # the router's logits on a normed input spread with a standard
        # deviation near 1.6, so that a token's choices are distinct experts
        # and not the bias's
        moe = {
            "norm": jnp.ones((le, d)),
            "router": jax.nn.initializers.normal(1.6 * d ** -0.5)(
                k[11], (le, d, e), pd),
            "select_bias": jnp.zeros((le, e)),
            "latent_down": init(k[12], (le, d, lat), pd),
            "latent_up": init(k[13], (le, lat, d), pd) * out_scale,
            "shared_up": init(k[14], (le, d, sm), pd),
            "shared_down": centred(init(k[15], (le, sm, d), pd)) * out_scale,
            "expert_up": init(k[16], (le, held, lat, m), pd),
            "expert_down": centred(init(k[17], (le, held, m, lat), pd)),
        }
        return {"embed": jax.nn.initializers.normal(1.0)(k[0], (v, d), pd),
                "mamba": mamba_, "attn": attn, "moe": moe,
                "final_norm": jnp.ones((d,)),
                "lm_head": init(k[18], (d, v), pd)}

    def logical_axes(self):
        return {
            "embed": ("vocab_in", "hidden"),
            "mamba": {"norm": ("layer", "hidden"),
                      "in_proj": ("layer", "hidden", None),
                      "conv_w": ("layer", None, None),
                      "conv_b": ("layer", None),
                      "dt_bias": ("layer", None), "A_log": ("layer", None),
                      "D": ("layer", None), "gate_norm": ("layer", None),
                      "out_proj": ("layer", None, "hidden")},
            "attn": {"norm": ("layer", "hidden"),
                     "wq": ("layer", "hidden", "heads"),
                     "wk": ("layer", "hidden", "kv_heads"),
                     "wv": ("layer", "hidden", "kv_heads"),
                     "wo": ("layer", "heads", "hidden")},
            "moe": {"norm": ("layer", "hidden"),
                    "router": ("layer", "hidden", None),
                    "select_bias": ("layer", None),
                    "latent_down": ("layer", "hidden", None),
                    "latent_up": ("layer", None, "hidden"),
                    "shared_up": ("layer", "hidden", "mlp"),
                    "shared_down": ("layer", "mlp", "hidden"),
                    "expert_up": ("layer", "expert", None, "mlp"),
                    "expert_down": ("layer", "expert", "mlp", None)},
            "final_norm": ("hidden",), "lm_head": ("hidden", "vocab"),
        }

    # --------------------------------------------------------------- layers
    def _block(self, x, blk, state, layer, idx, valid, *, kind: str,
               shift: int = 0, extra=None):
        """One layer, ``x + Mixer(rms(x))`` -> ``(x, state)``. ``state``:
        ``None`` (no cache) or the kind's cache leaves, read and written at
        ``layer + shift``, and the step's counters: ``(ssm, conv, counts)``,
        ``(k, v, counts)`` or, an expert layer, ``(counts,)``. ``valid [B]``:
        the block's real positions a row; ``extra``: the step's shared Mamba
        operands (:meth:`_decode_step`) or the decode program's
        ``slot_walk``."""
        c = self.config
        leaves, counts = (None, None) if state is None else \
            (state[:-1], state[-1])
        at = None if state is None else layer + shift
        if kind == MAMBA:
            y, leaves = mamba.mixer(x, blk, c, leaves, at, idx, valid, extra,
                                    norm_groups=c.mamba_n_groups, apart=True)
        elif kind == ATTENTION:
            y, leaves = self._attention(x, blk, leaves, at, idx, extra)
        else:
            t = x.shape[1]
            tokens = None if valid is None else \
                jnp.arange(t)[None, :] < valid[:, None]
            y, n = moe_ffn.ffn(rms_norm(x, blk["norm"], c.eps), blk,
                               moe_ffn.SPARSE, tokens, c,
                               buffer_counters=True)
            if state is not None:
                counts = counts + n
        return x + y, (None if state is None else (*leaves, counts))

    def _attention(self, x, blk, leaves, layer, idx, walk_):
        """No rotation and no position term; softmax of ``q k^T
        head_dim ** -0.5``. Key-value rows need no ``valid``: padding is
        causally invisible and masked by the lengths."""
        c = self.config
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        u = rms_norm(x, blk["norm"], c.eps)
        q = project_heads(u, blk["wq"], hq, dh)
        k_ = project_heads(u, blk["wk"], hkv, dh)
        v_ = project_heads(u, blk["wv"], hkv, dh)
        if leaves is None:
            rep = hq // hkv
            out = multihead_attention(
                q, jnp.repeat(k_, rep, axis=2), jnp.repeat(v_, rep, axis=2),
                causal=True)
        else:
            out, *leaves = cached_attention(q, *leaves, k_, v_, layer, idx,
                                            active=walk_)
        return merge_heads(out, blk["wo"]), leaves

    def _decode_step(self, params, valid, b):
        c = self.config
        return mamba.decode_step(params["mamba"], c, valid, b,
                                 norm_groups=c.mamba_n_groups)

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """``k``, ``v`` over the attention layers only, ``ssm`` ``[Lm, B, H,
        P, N]`` and ``conv`` (the convolution's tail as rows of lanes:
        ``ops/ssm.conv_tail_shape``) over the Mamba layers, and the index;
        an expert layer holds nothing."""
        c = self.config
        dtype = dtype or self.compute_dtype
        lm = c.count(MAMBA)
        # no barrier as alloc_kv_cache has: a block at position 0 starts from
        # zeros whatever the buffer held (models/mamba.mixer)
        state = jnp.zeros((lm, batch_size, c.mamba_n_heads, c.mamba_d_head,
                           c.mamba_d_state), self.state_dtype)
        conv = jnp.zeros((lm, batch_size) + ssm.conv_tail_shape(
            c.mamba_d_conv, c.d_inner, c.conv_dim - c.d_inner), dtype)
        return dict(kv_cache(c.count(ATTENTION), batch_size, c.num_kv_heads,
                             max_len, c.head_dim, dtype), ssm=state, conv=conv)

    def _layer_params(self, experts: float):
        """Parameters of a layer of each kind, an expert layer with
        ``experts`` routed experts."""
        c = self.config
        d, d_in = c.hidden_size, c.d_inner
        mamba_ = (d + d * (2 * d_in + 2 * c.mamba_n_groups * c.mamba_d_state
                           + c.mamba_n_heads)
                  + (c.mamba_d_conv + 1) * c.conv_dim + 3 * c.mamba_n_heads
                  + d_in + d_in * d)
        attn = d + d * c.head_dim * (2 * c.num_heads + 2 * c.num_kv_heads)
        moe = (d + d * c.num_experts + c.num_experts
               + 2 * d * c.moe_latent_size
               + 2 * d * c.shared_intermediate_size
               + 2 * c.moe_latent_size * c.moe_intermediate_size * experts)
        return (c.count(MAMBA) * mamba_ + c.count(ATTENTION) * attn
                + c.count(MOE) * moe)

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a layer."""
        c = self.config
        return int(2 * c.vocab_size * c.hidden_size + c.hidden_size
                   + self._layer_params(c.held[1]))

    def flops_per_token(self) -> float:
        c = self.config
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        attn = 12 * c.count(ATTENTION) * c.num_heads * c.head_dim \
            * c.max_seq_len
        return 6.0 * (2 * c.vocab_size * c.hidden_size
                      + self._layer_params(routed)) + attn
