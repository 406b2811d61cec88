"""Hybrid decoder of scalar-decay gated delta-rule linear attention and gated
multi-head latent attention (HF ``gigachat3_5``; GigaChat3.5-432B-A28B): every
layer named in ``full_attention_layers`` is latent attention with a low-rank
query path whose output passes an elementwise sigmoid gate before ``Wo``
(models/mla.py's cache leaf and its two attention forms), every other layer
GatedDeltaNet (ops/gdn.py): a delta rule with ONE decay a head over a ``[K,
V]`` float32 state a VALUE head, ``Hk`` key heads for ``Hv`` value heads,
behind a four-tap convolution. The leading ``first_k_dense`` layers' FFN is a
dense SwiGLU, the others' a shared expert plus the routed experts HELD here
(models/moe_ffn.py), every gated MLP clamped by ``swiglu_limit``. Norms are
SANDWICHED and zero-centred:

    N(x; w) = x / rms(x) * (2 sigmoid(w))         w = 0 is scale one
    h = x + N(Mixer(N(x; w1)); w2);   y = h + N(FFN(N(h; w3)); w4)

    GDN(u):  [q | k | v] = silu(conv4(u W_qkv));  z = u W_z;  [b | a] = u W_ba
             q, k L2-normalised a head (Hk heads), q also * K ** -0.5; key
             head j serves value heads j r .. j r + r - 1, r = Hv / Hk
             beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)  a head
             the recurrence of ops/gdn.py
             o_h <- o_h / rms(o_h) * (1 + w_o) * (gate_scale sigmoid(z_h))
             Mixer = merge(o) W_out
    MLA(u):  q = rms(u Wq_a; g_q) Wq_b -> [H, nope + rope] a token
             [c | k_r] = u Wkv_a;  c~ = rms(c; g_kv)
             q_rope, k_r <- rotate(., pos): neighbours are pairs
             (rope_interleave), YaRN's frequencies; scores times
             (nope + rope) ** -0.5 * (0.1 ln factor + 1) ** 2
             Mixer = (merge(Attn) * sigmoid(u W_g)) Wo

**The cache** is one tree of two kinds of state: ``latent`` rows over the
latent layers only (models/mla.py), and over the delta-rule layers ``gdn``
``[Lg, B, Hv, K, V]`` float32 with ``gdn_conv`` (the convolution's last
inputs, ``[Lg, B, taps - 1, 2 Hk + Hv, K]``: ops/gdn.tail_shape). The model
names all three in ``slot_state_keys`` and the first in ``row_state_keys``;
the serving layer handles the last two as recurrent state by that declaration
alone (serving/kv_slots.py).

**Four stacks**, by mixer and FFN (``gdn_dense``, ``gdn_sparse``,
``mla_dense``, ``mla_sparse``; one with no layer has no leaf), walked in runs
of equal kind; a stack is indexed by the layer's place in it, a cache leaf by
the layer's place among its mixer's layers (the dense layers lead, so the
delta-rule layers of ``gdn_dense`` come before those of ``gdn_sparse``).

**One token** with a cache runs the recurrence in place on the stacked state;
where the shapes fold (ops/gdn.supports) everything between the projections
and the output matmul is the one call ``dstpu_gdn_update``, and the latent
layer's absorbed step is ``dstpu_mla_decode_step``. **A prompt block** runs
the chunked form from the layer's state (scope ``dstpu_gdn_prefill``: XLA's
own matmuls) and writes the state at the true length back, and the latent
layer attends its rows decompressed; a prompt longer than ``prompt_block``
passes the whole stack a block of tokens at a time inside the one program
call. A prefill that is told the prompt's true length (``valid_len``) computes
its head there alone: ``[B, 1, V]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import merge_heads, project_heads, qdot, rms_norm
from deepspeed_tpu.models.mla import LatentAttention, latent_row_width
from deepspeed_tpu.models.moe_ffn import (DENSE, EXPERT_LEAVES, SPARSE, ffn, gated_axes, gated_init,
                                          record_step_counters)
from deepspeed_tpu.models.stack import runs_of
from deepspeed_tpu.ops import gdn
from deepspeed_tpu.ops.rotary import apply_rotary_pairs_freqs, yarn_inv_freq, yarn_mscale
from deepspeed_tpu.ops.ssm import causal_conv, slot_order

GDN, MLA = "gdn", "mla"
# the stacks: a layer's mixer and its FFN
KINDS = {f"{mixer}_{kind}": (mixer, kind)
         for mixer in (GDN, MLA) for kind in (DENSE, SPARSE)}
# a kind of layer is its stack, counted in its mixer's cache leaves
# (models/stack.runs_of)
LAYERS = {name: (name, ("latent",) if mixer == MLA else ("gdn", "gdn_conv"))
          for name, (mixer, _) in KINDS.items()}


@dataclasses.dataclass
class GigaChat35Config:
    vocab_size: int = 128256
    max_seq_len: int = 262144
    hidden_size: int = 7168
    num_layers: int = 40
    full_attention_layers: Sequence[int] = tuple(range(3, 40, 4))
    first_k_dense: int = 3                   # leading dense layers
    # the latent layers
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512                  # the cached latent
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 100000.0
    rope_factor: float = 8.0                 # yarn
    rope_original_max: int = 32768
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # the delta-rule layers
    gdn_key_heads: int = 32
    gdn_value_heads: int = 64
    gdn_key_dim: int = 128
    gdn_value_dim: int = 128
    gdn_conv: int = 4                        # taps
    gdn_gate_scale: float = 2.0              # linear_sigmoid_gate_scale
    gdn_norm_eps: float = 1e-6               # linear_attn_o_norm_eps
    # the FFNs
    intermediate_size: int = 18432           # the dense layers'
    moe_intermediate_size: int = 2048        # an expert's, and the shared one's
    num_experts: int = 256                   # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    swiglu_limit: Optional[float] = 10.0     # models/moe_ffn.ffn reads it
    norm_gate: float = 2.0                   # layernorm_gating_weight
    eps: float = 1e-6
    gdn_chunk: int = 64          # positions of the chunked form's chunk
    prompt_block: int = 2048     # tokens of a prompt that pass the stack at once
    key_block: int = 512         # cached rows decompressed at once
    has_position_table = False   # rotation is computed, nothing is indexed

    def __post_init__(self):
        self.full_attention_layers = tuple(
            i for i in self.full_attention_layers if i < self.num_layers)
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
        if not 0 <= self.first_k_dense <= self.num_layers or \
                self.num_layers < 1:
            raise ValueError(f"first_k_dense={self.first_k_dense} of "
                             f"{self.num_layers} layers")
        if min(self.full_attention_layers, default=0) < 0:
            raise ValueError("full_attention_layers names layers from 0")
        if self.num_shared_experts != 1:
            raise ValueError(f"num_shared_experts={self.num_shared_experts}: "
                             "one shared expert is computed")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotation turns pairs")
        if self.rope_mscale != self.rope_mscale_all_dim:
            raise ValueError("mscale / mscale_all_dim other than 1 would "
                             "scale cos and sin: not computed")
        if self.gdn_value_heads % self.gdn_key_heads:
            raise ValueError("key heads must divide the value heads")
        if self.gdn_conv < 2:
            raise ValueError("the convolution carries at least one input")
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

    @property
    def gdn_rows(self) -> int:
        """Rows of lanes of a token's ``q | k | v``: a head a row."""
        return gdn.conv_rows(self.gdn_key_heads, self.gdn_value_heads)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's stack, in stack order."""
        return tuple(
            f"{MLA if i in self.full_attention_layers else GDN}_"
            f"{DENSE if i < self.first_k_dense else SPARSE}"
            for i in range(self.num_layers))

    def count(self, name: str) -> int:
        """Layers of a stack (``gdn_sparse``) or of a mixer (``gdn``)."""
        return sum(k == name or k.startswith(name + "_")
                   for k in self.layer_kinds())

    def runs(self) -> Tuple[Tuple[str, int, int, int], ...]:
        """Runs of equal layers as ``(stack, first index in that stack, first
        index in the mixer's cache leaves, count)``, in stack order."""
        return runs_of(self.layer_kinds(), LAYERS)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_layers", 5)
        kw.setdefault("full_attention_layers", (1,))
        kw.setdefault("first_k_dense", 1)
        kw.setdefault("num_experts", 16)
        kw.setdefault("num_experts_per_tok", 4)
        kw.setdefault("rope_original_max", 16)
        kw.setdefault("gdn_chunk", 8)
        kw.setdefault("prompt_block", 16)
        kw.setdefault("key_block", 8)
        return cls(hidden_size=64, num_heads=4, q_lora_rank=16,
                   kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, gdn_key_heads=2, gdn_value_heads=4,
                   gdn_key_dim=16, gdn_value_dim=16, intermediate_size=128,
                   moe_intermediate_size=32, **kw)


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class GigaChat35Model(LatentAttention):
    """Layers of four kinds, a stack each; a mixer's layers share its cache
    leaves (models/stack.StackedDecoder)."""

    stacks = tuple(KINDS)
    kinds = LAYERS
    # the expert stacks, for the grouped matmul to address by group, and
    # ``wkv_b``, for the prompt kernel to address by layer and head
    # (models/sarvam_mla.py)
    whole = (*EXPERT_LEAVES, "wkv_b")
    # per-slot state, in operand order: the latent layers' token rows, the
    # delta rule's state (``state_dtype``: 4.19 MB a layer a slot at the
    # published sizes) and the convolution's tails (in the compute dtype) on
    # the others
    slot_state_keys = ("latent", "gdn", "gdn_conv")

    def layer_kinds(self):
        return self.config.layer_kinds()

    def _block_of(self, name, shift, walk_, step):
        mixer, kind = KINDS[name]
        return functools.partial(
            self._block, extra=step if mixer == GDN else walk_, mixer=mixer,
            kind=kind, shift=shift)

    @staticmethod
    def record_step_counters(telemetry, counts) -> None:
        """The expert layer's step vector, and with it which way the
        delta-rule layers of the serving programs were traced
        (``gdn/traced_*``), into the serving engine's registry."""
        record_step_counters(telemetry, counts)
        gdn.record_traced(telemetry)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v, h = c.hidden_size, c.vocab_size, c.num_heads
        ql, r, rope = c.q_lora_rank, c.kv_lora_rank, c.qk_rope_head_dim
        hv, rows = c.gdn_value_heads, c.gdn_rows
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # a stream norm's weight sits under ``2 sigmoid``: drawn wide, so that
        # a program that reads it as a plain or a ``1 + w`` weight is far off
        gate = jax.nn.initializers.normal(0.5)
        # as ExaoneMoeModel draws them: output projections scaled down by
        # depth, the embedding's rows at the stream's own scale
        out_scale = (2 * c.num_layers) ** -0.5
        embed_init = jax.nn.initializers.normal(1.0)

        def norms(key, l):
            k = jax.random.split(key, 4)
            return {name: gate(k[i], (l, d), jnp.float32) for i, name in
                    enumerate(("attn_norm", "attn_post_norm", "mlp_norm",
                               "mlp_post_norm"))}

        def latent(key, l):
            k = jax.random.split(key, 6)
            return {"wq_a": init(k[0], (l, d, ql), pd),
                    "q_norm": jnp.ones((l, ql)),
                    "wq_b": init(k[1], (l, ql, h * c.q_head_dim), pd),
                    "wkv_a": init(k[2], (l, d, r + rope), pd),
                    "kv_norm": jnp.ones((l, r)),
                    "wkv_b": init(k[3], (l, r, h * (c.qk_nope_head_dim
                                                    + c.v_head_dim)), pd),
                    "attn_gate": init(k[4], (l, d, h * c.v_head_dim), pd),
                    "wo": init(k[5], (l, h * c.v_head_dim, d), pd)
                    * out_scale}

        def delta(key, l):
            # the Mamba-2 convention for a decay that is neither dead nor
            # saturated: A in 1..16, the step log-uniform in 0.001..0.1
            # through the inverse softplus; taps uniform +-taps ** -0.5
            k = jax.random.split(key, 8)
            dt = jnp.exp(jax.random.uniform(k[4], (l, hv)) *
                         (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
            return {"w_qkv": init(k[0], (l, d, rows * c.gdn_key_dim), pd),
                    "w_z": init(k[1], (l, d, hv * c.gdn_value_dim), pd),
                    "w_ba": init(k[2], (l, d, 2 * hv), pd),
                    "conv_w": jax.random.uniform(
                        k[3], (l, c.gdn_conv, rows * c.gdn_key_dim),
                        jnp.float32, -1.0, 1.0) * c.gdn_conv ** -0.5,
                    "A_log": jnp.log(jax.random.uniform(
                        k[5], (l, hv), jnp.float32, 1.0, 16.0)),
                    "dt_bias": _inv_softplus(dt),
                    "o_norm": 0.2 * jax.random.normal(
                        k[6], (l, c.gdn_value_dim), jnp.float32),
                    "wo": init(k[7], (l, hv * c.gdn_value_dim, d), pd)
                    * out_scale}

        def feed_forward(key, l, kind):
            k = jax.random.split(key, 4)
            if kind == DENSE:
                return gated_init(init, jax.random.split(k[0], 3), (l,), d,
                                  c.intermediate_size, "w_", pd, out_scale)
            return {"router": init(k[0], (l, d, c.num_experts), pd),
                    "select_bias": jnp.zeros((l, c.num_experts)),
                    **gated_init(init, jax.random.split(k[1], 3), (l,), d,
                                 c.moe_intermediate_size, "shared_", pd,
                                 out_scale),
                    **gated_init(init, jax.random.split(k[2], 3),
                                 (l, c.held[1]), d, c.moe_intermediate_size,
                                 "expert_", pd, out_scale)}

        keys = jax.random.split(rng, 3 + len(KINDS))
        params = {"embed": embed_init(keys[0], (v, d), pd),
                  "final_norm": gate(keys[1], (d,), jnp.float32),
                  "lm_head": init(keys[2], (d, v), pd)}
        for key, (name, (mixer, kind)) in zip(keys[3:], KINDS.items()):
            l = c.count(name)
            if l:
                k = jax.random.split(key, 3)
                params[name] = {
                    **norms(k[0], l),
                    **(latent if mixer == MLA else delta)(k[1], l),
                    **feed_forward(k[2], l, kind)}
        return params

    def logical_axes(self):
        c = self.config
        norms = {name: ("layer", "hidden") for name in
                 ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")}
        mixers = {
            MLA: {"wq_a": ("layer", "hidden", None), "q_norm": ("layer", None),
                  "wq_b": ("layer", None, "heads"),
                  "wkv_a": ("layer", "hidden", None),
                  "kv_norm": ("layer", None),
                  "wkv_b": ("layer", None, "heads"),
                  "attn_gate": ("layer", "hidden", "heads"),
                  "wo": ("layer", "heads", "hidden")},
            GDN: {"w_qkv": ("layer", "hidden", None),
                  "w_z": ("layer", "hidden", None),
                  "w_ba": ("layer", "hidden", None),
                  "conv_w": ("layer", None, None), "A_log": ("layer", None),
                  "dt_bias": ("layer", None), "o_norm": ("layer", None),
                  "wo": ("layer", None, "hidden")}}
        ffns = {DENSE: gated_axes("w_"),
                SPARSE: {"router": ("layer", "hidden", None),
                         "select_bias": ("layer", None),
                         **gated_axes("shared_"),
                         **gated_axes("expert_", "expert")}}
        return {"embed": ("vocab_in", "hidden"),
                **{name: {**norms, **mixers[mixer], **ffns[kind]}
                   for name, (mixer, kind) in KINDS.items() if c.count(name)},
                "final_norm": ("hidden",), "lm_head": ("hidden", "vocab")}

    # --------------------------------------------------------------- layers
    def _norm(self, x, w):
        """``x / rms(x) * (norm_gate sigmoid(w))``: the zero-centred gated
        norm, wherever a norm stands on the stream."""
        c = self.config
        return rms_norm(x, c.norm_gate * jax.nn.sigmoid(
            w.astype(jnp.float32)), c.eps)

    def _projections(self, u, blk, pos):
        """What models/mla.LatentAttention asks of a family, of the NORMED
        input ``u``: ``(q_nope, q_rope`` rotated, ``c~``, ``k_r`` rotated)."""
        c = self.config
        r, n = c.kv_lora_rank, c.qk_nope_head_dim
        inv = yarn_inv_freq(c.qk_rope_head_dim, c.rope_theta, c.rope_factor,
                            c.rope_original_max, c.rope_beta_fast,
                            c.rope_beta_slow)
        qa = rms_norm(qdot("btd,de->bte", u, blk["wq_a"]), blk["q_norm"],
                      c.eps)
        q = project_heads(qa, blk["wq_b"], c.num_heads, c.q_head_dim)
        ckr = qdot("btd,de->bte", u, blk["wkv_a"])
        lat = rms_norm(ckr[..., :r], blk["kv_norm"], c.eps)
        k_r = apply_rotary_pairs_freqs(ckr[..., None, r:], pos, inv)[:, :, 0]
        return (q[..., :n], apply_rotary_pairs_freqs(q[..., n:], pos, inv),
                lat, k_r)

    def _latent_mixer(self, u, blk, latent, at, idx, valid, walk_):
        """Latent attention over the normed input, its heads times an
        elementwise sigmoid gate of that input, through ``Wo`` -> ``(m,
        latent)``."""
        c = self.config
        out, latent = self._attend(u, blk, latent, at, idx, valid, walk_)
        gate = jax.nn.sigmoid(project_heads(
            u, blk["attn_gate"], c.num_heads, c.v_head_dim).astype(jnp.float32))
        out = (out.astype(jnp.float32) * gate).astype(u.dtype)
        return merge_heads(out, blk["wo"]), latent

    def _gdn_mixer(self, u, blk, state, tail, at, idx, valid, step):
        """GatedDeltaNet over the normed input -> ``(m, state, tail)``.
        ``state [Lg, B, Hv, K, V]`` and ``tail`` (ops/gdn.tail_shape) at
        cache layer ``at``, or both ``None`` (no cache: zeros in, nothing
        out); ``step`` (:meth:`_decode_step`): what a one-token step's layers
        share."""
        c = self.config
        b, t, _ = u.shape
        hv, dv = c.gdn_value_heads, c.gdn_value_dim
        fence = jax.lax.optimization_barrier
        # fenced from the per-head work behind them, so that a layer's slice
        # of a stacked weight is read where it lies (base.project_heads)
        qkv = fence(qdot("btd,de->bte", u, blk["w_qkv"]))
        z = fence(qdot("btd,de->bte", u, blk["w_z"]))
        ba = qdot("btd,dh->bth", u, blk["w_ba"])             # b | a
        folded = step is not None and step["weights"] is not None
        if step is not None:
            gdn.count_step(folded)
        with jax.named_scope("dstpu_gdn_decode" if step is not None
                             else "dstpu_gdn_prefill"):
            if folded:
                o, state, tail = gdn.gdn_step(
                    qkv[:, 0], jnp.stack([ba[:, 0, hv:], ba[:, 0, :hv]], 1),
                    z[:, 0], state, tail, at, step["weights"], step["walk"],
                    step["active"], eps=c.gdn_norm_eps,
                    gate_scale=c.gdn_gate_scale)
                o = o.reshape(b, 1, hv, dv)
            else:
                o, state, tail = self._gdn_split(
                    qkv, z, ba, blk, state, tail, at, idx, valid, step)
        return merge_heads(o, blk["wo"]), state, tail

    def _gdn_split(self, qkv, z, ba, blk, state, tail, at, idx, valid, step):
        """The mixer out of XLA's own operations: the carried convolution,
        the norms, the one-token update or the chunked prompt form, the head
        norm and the gate -> ``(o [B, T, Hv, V], state, tail)``."""
        c = self.config
        b, t, w = qkv.shape
        hk, hv = c.gdn_key_heads, c.gdn_value_heads
        dk, dv = c.gdn_key_dim, c.gdn_value_dim
        s0 = None
        if state is None:
            tail0 = jnp.zeros((b, c.gdn_conv - 1, w), qkv.dtype)
        else:
            tail0 = jax.lax.dynamic_index_in_dim(
                tail, at, 0, False).reshape(b, c.gdn_conv - 1, w)
            if t > 1:
                # a row at position 0 has no history, whatever its slot held
                s0 = jax.lax.dynamic_index_in_dim(state, at, 0, False)
                fresh = jnp.reshape(idx == 0, (-1, 1, 1))
                tail0 = jnp.where(fresh, 0, tail0)
                s0 = jnp.where(fresh[..., None], 0, s0)
        act, tail1 = causal_conv(qkv, tail0, blk["conv_w"],
                                 jnp.zeros((w,), jnp.float32), valid)
        q = act[..., :hk * dk].reshape(b, t, hk, dk)
        k_ = act[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
        v_ = act[..., 2 * hk * dk:].reshape(b, t, hv, dv)
        q = gdn.l2_normalize(q) * dk ** -0.5
        k_ = gdn.l2_normalize(k_)
        beta = jax.nn.sigmoid(ba[..., :hv].astype(jnp.float32))
        g = gdn.log_decay(ba[..., hv:], blk["A_log"], blk["dt_bias"])
        if step is not None:
            o, state = gdn.gdn_update(state, at, q[:, 0], k_[:, 0], v_[:, 0],
                                      g[:, 0], beta[:, 0], step["active"])
            o = o[:, None]
        else:
            gdn.count_chunked_block()
            o, s1 = gdn.gdn_chunked(q, k_, v_, g, beta, chunk=c.gdn_chunk,
                                    init_state=s0, length=valid)
            if state is not None:
                state = jax.lax.dynamic_update_index_in_dim(
                    state, s1.astype(state.dtype), at, 0)
        if tail is not None:
            tail = jax.lax.dynamic_update_index_in_dim(
                tail, tail1.reshape((b,) + tail.shape[2:]).astype(tail.dtype),
                at, 0)
        o = rms_norm(o, 1.0 + blk["o_norm"].astype(jnp.float32),
                     c.gdn_norm_eps) * (c.gdn_gate_scale * jax.nn.sigmoid(
                         z.reshape(b, t, hv, dv).astype(jnp.float32)))
        return o.astype(qkv.dtype), state, tail

    def _decode_step(self, params, valid, b):
        """What the delta-rule layers of one decode step (one token a slot, a
        cache) share, made once a step: which slots decode, their order for
        the kernel's grid and, where the kernel route is taken and the shapes
        fold, the small weights of ALL delta-rule layers in the cache's layer
        order as the folded call reads them; ``weights`` ``None`` says the
        layers run split."""
        c = self.config
        active = jnp.ones((b,), bool) if valid is None else valid > 0
        folds = gdn.default_route() == "pallas" and gdn.supports(
            c.gdn_key_heads, c.gdn_value_heads, c.gdn_key_dim,
            c.gdn_value_dim, c.gdn_conv)
        weights = None
        if folds:
            # the dense layers lead: ``gdn_dense``'s before ``gdn_sparse``'s
            stacks = [gdn.fold_weights(params[name], c.gdn_key_heads,
                                       c.gdn_value_heads)
                      for name in (f"{GDN}_{DENSE}", f"{GDN}_{SPARSE}")
                      if name in params]
            weights = jax.tree_util.tree_map(
                lambda *leaves: jnp.concatenate(leaves, 0), *stacks)
        return {"active": active, "walk": slot_order(active),
                "weights": weights}

    def _block(self, x, blk, state, layer, idx, valid, extra, *, mixer: str,
               kind: str, shift: int = 0):
        """One layer -> ``(x, state)``. ``state``: ``None`` (no cache), or
        the mixer's cache leaves and the step's counters: ``(latent,
        counts)`` or ``(gdn, gdn_conv, counts)``, read and written at ``layer
        + shift``. ``valid [B]``: the block's real positions a row;
        ``extra``: the decode program's ``slot_walk`` for a latent layer,
        :meth:`_decode_step` for a delta-rule one."""
        c = self.config
        t = x.shape[1]
        at = None if state is None else layer + shift
        u = self._norm(x, blk["attn_norm"])
        if mixer == MLA:
            latent, counts = (None, None) if state is None else state
            m, latent = self._latent_mixer(u, blk, latent, at, idx, valid,
                                           extra)
            leaves = (latent,)
        else:
            s, tail, counts = (None, None, None) if state is None else state
            m, s, tail = self._gdn_mixer(u, blk, s, tail, at, idx, valid,
                                         extra)
            leaves = (s, tail)
        h = x + self._norm(m, blk["attn_post_norm"])
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        y, n = ffn(self._norm(h, blk["mlp_norm"]), blk, kind, tokens, c)
        out = h + self._norm(y, blk["mlp_post_norm"])
        return out, (None if state is None else (*leaves, counts + n))

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """One tree for both kinds of per-request state: ``latent`` rows over
        the latent layers only, ``gdn`` ``[Lg, B, Hv, K, V]`` float32 and
        ``gdn_conv`` (ops/gdn.tail_shape) over the delta-rule layers, and the
        index."""
        c = self.config
        dtype = dtype or self.compute_dtype
        lg = c.count(GDN)
        # no barrier as the latent leaf has: a block at position 0 starts
        # from zeros whatever the buffer held (_gdn_split) and every layer's
        # row is written before it is read again
        state = jnp.zeros((lg, batch_size, c.gdn_value_heads, c.gdn_key_dim,
                           c.gdn_value_dim), self.state_dtype)
        tail = jnp.zeros((lg, batch_size) + gdn.tail_shape(
            c.gdn_conv, c.gdn_key_heads, c.gdn_value_heads, c.gdn_key_dim),
            dtype)
        return dict(self._latent_cache(c.count(MLA), batch_size, max_len,
                                       dtype), gdn=state, gdn_conv=tail)

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a sparse layer."""
        c = self.config
        d, h, hv = c.hidden_size, c.num_heads, c.gdn_value_heads
        width = c.gdn_rows * c.gdn_key_dim
        mixer = {
            MLA: (d * c.q_lora_rank + c.q_lora_rank
                  + c.q_lora_rank * h * c.q_head_dim
                  + d * (c.kv_lora_rank + c.qk_rope_head_dim) + c.kv_lora_rank
                  + c.kv_lora_rank * h * (c.qk_nope_head_dim + c.v_head_dim)
                  + 2 * d * h * c.v_head_dim),
            GDN: (d * width + d * hv * c.gdn_value_dim + 2 * d * hv
                  + c.gdn_conv * width + 2 * hv + c.gdn_value_dim
                  + hv * c.gdn_value_dim * d)}
        feed = {DENSE: 3 * d * c.intermediate_size,
                SPARSE: (d * c.num_experts + c.num_experts
                         + 3 * d * c.moe_intermediate_size * (1 + c.held[1]))}
        return 2 * c.vocab_size * d + d + sum(
            c.count(name) * (4 * d + mixer[m] + feed[kind])
            for name, (m, kind) in KINDS.items())

    def flops_per_token(self) -> float:
        c = self.config
        expert = 3 * c.hidden_size * c.moe_intermediate_size
        sparse = sum(c.count(name) for name, (_, kind) in KINDS.items()
                     if kind == SPARSE)
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        return 6.0 * (self.num_params() - sparse * expert
                      * (c.held[1] - routed))
