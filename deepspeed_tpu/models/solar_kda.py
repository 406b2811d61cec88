"""Hybrid decoder of gated delta-rule linear attention and gated softmax
attention (HF ``solar_open2``; Solar-Open2-250B): in periods of ``gqa_interval
+ 1`` layers, one grouped-query softmax layer WITHOUT positions whose output
passes an elementwise sigmoid gate, then ``gqa_interval`` layers of Kimi Delta
Attention (ops/kda.py): a per-channel-gated delta rule over a ``[K, V]``
float32 state a head behind three four-tap convolutions. Every layer's FFN is
sparse: a shared expert plus the routed experts HELD here (models/moe_ffn.py,
shared with ``exaone_moe`` and ``sarvam_mla``).

    y = rms(x; g1)
    KDA: q, k, v = silu(conv4(y Wq | y Wk | y Wv));  q, k <- L2 norm a head,
         q also * K ** -0.5;  g = -exp(A_log) softplus((y F_a) F_b + dt_bias);
         beta = 2 sigmoid(y W_beta);  the recurrence of ops/kda.py;
         o_h <- rms(o_h; g_o) * sigmoid((y G_a) G_b)_h;  h = x + o Wo
    GQA: q = y Wq, k = y Wk, v = y Wv, no rotation, no q/k norm;
         o = softmax(q k^T / sqrt(Dh)) v * sigmoid(y W_g);  h = x + o Wo
    out = h + Shared(z) + s * sum over the held of the chosen w_e Expert_e(z),
         z = rms(h; g2)

**The cache** is one tree of two kinds of state: ``k``, ``v`` rows over the
softmax layers only, and over the delta-rule layers ``kda`` ``[Lk, B, H, K,
V]`` float32 with ``kda_conv`` (the convolutions' last inputs, ``[Lk, B, taps
- 1, 3, H, K]``: ops/kda.tail_shape). The model names all four in
``slot_state_keys``; the serving layer handles the last two as recurrent
state by that declaration alone (serving/kv_slots.py).

**One token** with a cache runs the recurrence in place on the stacked state;
where the shapes fold (ops/kda.supports) everything between the projections
and the output matmul is the one call ``dstpu_kda_update``. **A prompt block**
runs the chunked form from the layer's state and writes the state at the true
length back: with a cache, on a TPU and where the shapes fit
(ops/kda.supports_prefill) everything between the convolutions and the output
matmul is the one call ``dstpu_kda_prefill``, which reads the convolution's
result and the gates where they lie; elsewhere (training, a CPU, a tiny
configuration) XLA's own norms, decay, ``kda_chunked``, head norm and gate. A
prompt longer than ``prompt_block`` passes the whole stack a block of tokens
at a time inside the one program call, the state and tails carried from block
to block, and the softmax layer attends rows ``[0, end of block)`` in key
blocks with a running softmax (scope ``dstpu_gqa_prefill``:
on a TPU ops/gqa_prefill.py's one call, which keeps a key block's scores in
VMEM, elsewhere ops/attention.blocked_prompt_attention). A prefill that is
told the prompt's true length (``valid_len``) computes its head there alone:
``[B, 1, V]``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import cache_positions, merge_heads, project_heads, qdot, rms_norm
from deepspeed_tpu.models.moe_ffn import EXPERT_LEAVES, SPARSE, ffn, gated_axes, gated_init
from deepspeed_tpu.models.stack import StackedDecoder, kv_cache, runs_of
from deepspeed_tpu.ops import gqa_prefill, kda
from deepspeed_tpu.ops.attention import (blocked_prompt_attention, cached_attention, multihead_attention,
                                         write_kv_cache)
from deepspeed_tpu.ops.ssm import causal_conv, slot_order

GQA, KDA = "gqa", "kda"
# a kind of layer: its stack, and its cache leaves (models/stack.runs_of)
KINDS = {GQA: (GQA, ("k", "v")), KDA: (KDA, ("kda", "kda_conv"))}


@dataclasses.dataclass
class SolarKdaConfig:
    vocab_size: int = 196608
    max_seq_len: int = 1048576
    hidden_size: int = 4096
    layer_types: Sequence[str] = (GQA, KDA, KDA, KDA)
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128                  # keys and values alike
    kda_conv: int = 4                        # taps
    kda_gate_rank: int = 128                 # both low-rank gates
    moe_intermediate_size: int = 1280        # an expert's, and the shared one's
    num_experts: int = 320                   # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    held: Optional[Tuple[int, int]] = None   # (first, count); None: all
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    eps: float = 1e-5
    kda_chunk: int = 64          # positions of the chunked form's chunk
    prompt_block: int = 2048     # tokens of a prompt that pass the stack at once
    key_block: int = 512         # cached rows a softmax layer attends at once
    has_position_table = False   # nothing is indexed by position

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        unknown = set(self.layer_types) - {GQA, KDA}
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types must name {GQA!r} or {KDA!r} "
                             f"layers, got {sorted(unknown)}")
        if self.held is None:
            self.held = (0, self.num_experts)
        self.held = tuple(self.held)
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
        if self.kda_conv < 2:
            raise ValueError("the convolution carries at least one input")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """Runs of equal layers as ``(kind, first index in that kind's
        stacked tree and cache leaves, count)``, in stack order."""
        return tuple((k, i, n) for k, i, _, n in runs_of(self.layer_types, KINDS))

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("vocab_size", 512)
        kw.setdefault("max_seq_len", 128)
        kw.setdefault("num_experts", 16)
        kw.setdefault("num_experts_per_tok", 4)
        kw.setdefault("kda_chunk", 8)
        kw.setdefault("prompt_block", 16)
        kw.setdefault("key_block", 8)
        return cls(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
                   kda_heads=4, kda_head_dim=16, kda_gate_rank=8,
                   moe_intermediate_size=32, **kw)


def _inv_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class SolarKdaModel(StackedDecoder):
    """Layers of two kinds, a stack and a pair of cache leaves each
    (models/stack.StackedDecoder)."""

    stacks = (GQA, KDA)
    kinds = KINDS
    # the expert stacks, for the grouped matmul to address by group
    whole = EXPERT_LEAVES
    # per-slot state, in operand order: key-value rows on the softmax layers,
    # the delta rule's state (``state_dtype``: 4.19 MB a layer a slot at the
    # published sizes) and the convolutions' tails (in the compute dtype) on
    # the others
    slot_state_keys = ("k", "v", "kda", "kda_conv")

    def layer_kinds(self):
        return self.config.layer_types

    def _block_of(self, kind, shift, walk_, step):
        return functools.partial(self._kda_layer, step=step) if kind == KDA \
            else functools.partial(self._gqa_layer, walk_=walk_)

    # ----------------------------------------------------------------- init
    def init(self, rng):
        c = self.config
        d, v = c.hidden_size, c.vocab_size
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        hk, w, r = c.kda_heads, c.kda_width, c.kda_gate_rank
        pd = self.param_dtype
        init = jax.nn.initializers.normal(0.02)
        # as ExaoneMoeModel draws them: output projections scaled down by
        # depth, the embedding's rows at the stream's own scale
        out_scale = (2 * c.num_layers) ** -0.5
        embed_init = jax.nn.initializers.normal(1.0)

        def sparse(keys, l):
            return {"mlp_norm": jnp.ones((l, d)),
                    "router": init(keys[0], (l, d, c.num_experts), pd),
                    "select_bias": jnp.zeros((l, c.num_experts)),
                    **gated_init(init, jax.random.split(keys[1], 3), (l,), d,
                                 c.moe_intermediate_size, "shared_", pd,
                                 out_scale),
                    **gated_init(init, jax.random.split(keys[2], 3),
                                 (l, c.held[1]), d, c.moe_intermediate_size,
                                 "expert_", pd, out_scale)}

        k = jax.random.split(rng, 24)
        lg, lk = c.count(GQA), c.count(KDA)
        gqa = {"attn_norm": jnp.ones((lg, d)),
               "wq": init(k[1], (lg, d, hq * dh), pd),
               "wk": init(k[2], (lg, d, hkv * dh), pd),
               "wv": init(k[3], (lg, d, hkv * dh), pd),
               "w_gate": init(k[4], (lg, d, hq * dh), pd),
               "wo": init(k[5], (lg, hq * dh, d), pd) * out_scale,
               **sparse(k[6:9], lg)}
        # the Mamba-2 convention for a decay that is neither dead nor
        # saturated: A in 1..16, the step log-uniform in 0.001..0.1 through
        # the inverse softplus; taps uniform +-taps ** -0.5
        dt = jnp.exp(jax.random.uniform(k[9], (lk, w)) *
                     (jnp.log(0.1) - jnp.log(0.001)) + jnp.log(0.001))
        kda_ = {"attn_norm": jnp.ones((lk, d)),
                "w_qkv": init(k[10], (lk, d, 3 * w), pd),
                "conv_w": jax.random.uniform(
                    k[11], (lk, c.kda_conv, 3 * w), jnp.float32, -1.0, 1.0)
                * c.kda_conv ** -0.5,
                "f_a": init(k[12], (lk, d, r), pd),
                "f_b": init(k[13], (lk, r, w), pd),
                "w_beta": init(k[14], (lk, d, hk), pd),
                "A_log": jnp.log(jax.random.uniform(
                    k[15], (lk, hk), jnp.float32, 1.0, 16.0)),
                "dt_bias": _inv_softplus(dt),
                "g_a": init(k[16], (lk, d, r), pd),
                "g_b": init(k[17], (lk, r, w), pd),
                "o_norm": jnp.ones((lk, c.kda_head_dim)),
                "wo": init(k[18], (lk, w, d), pd) * out_scale,
                **sparse(k[19:22], lk)}
        return {"embed": embed_init(k[0], (v, d), pd), GQA: gqa, KDA: kda_,
                "final_norm": jnp.ones((d,)),
                "lm_head": init(k[22], (d, v), pd)}

    def logical_axes(self):
        sparse = {"mlp_norm": ("layer", "hidden"),
                  "router": ("layer", "hidden", None),
                  "select_bias": ("layer", None),
                  **gated_axes("shared_"), **gated_axes("expert_", "expert")}
        return {
            "embed": ("vocab_in", "hidden"),
            GQA: {"attn_norm": ("layer", "hidden"),
                  "wq": ("layer", "hidden", "heads"),
                  "wk": ("layer", "hidden", "kv_heads"),
                  "wv": ("layer", "hidden", "kv_heads"),
                  "w_gate": ("layer", "hidden", "heads"),
                  "wo": ("layer", "heads", "hidden"), **sparse},
            KDA: {"attn_norm": ("layer", "hidden"),
                  "w_qkv": ("layer", "hidden", None),
                  "conv_w": ("layer", None, None),
                  "f_a": ("layer", "hidden", None),
                  "f_b": ("layer", None, None),
                  "w_beta": ("layer", "hidden", None),
                  "A_log": ("layer", None), "dt_bias": ("layer", None),
                  "g_a": ("layer", "hidden", None),
                  "g_b": ("layer", None, None), "o_norm": ("layer", None),
                  "wo": ("layer", None, "hidden"), **sparse},
            "final_norm": ("hidden",), "lm_head": ("hidden", "vocab"),
        }

    # --------------------------------------------------------------- layers
    def _ffn(self, x, blk, valid, counts):
        c = self.config
        t = x.shape[1]
        tokens = None if valid is None else \
            jnp.arange(t)[None, :] < valid[:, None]
        y, n = ffn(rms_norm(x, blk["mlp_norm"], c.eps), blk, SPARSE, tokens, c)
        return x + y, (None if counts is None else counts + n)

    def _kda_layer(self, x, blk, state=None, layer=None, idx=None, valid=None,
                   step=None):
        """-> ``(x, state)``. ``state``: ``None`` (no cache: zeros in, nothing
        out) or ``(kda [Lk,B,H,K,V], tail [Lk,B,taps-1,3,H,K], counts)`` at
        ``layer``, ``idx`` and the rows' ``valid`` lengths. ``step``
        (:meth:`_decode_step`): what a one-token step's layers share."""
        c = self.config
        b = x.shape[0]
        h, dk = c.kda_heads, c.kda_head_dim
        fence = jax.lax.optimization_barrier
        y = rms_norm(x, blk["attn_norm"], c.eps)
        # fenced from the per-head work behind them, so that a layer's slice
        # of a stacked weight is read where it lies (base.project_heads)
        qkv = fence(qdot("btd,de->bte", y, blk["w_qkv"]))
        g_pre = fence(qdot("btr,re->bte", qdot("btd,dr->btr", y, blk["f_a"]),
                           blk["f_b"]))
        gate_pre = fence(qdot("btr,re->bte",
                              qdot("btd,dr->btr", y, blk["g_a"]), blk["g_b"]))
        beta = 2.0 * jax.nn.sigmoid(
            qdot("btd,dh->bth", y, blk["w_beta"]).astype(jnp.float32))
        kda_full = tail_full = counts = None
        if state is not None:
            kda_full, tail_full, counts = state
        folded = step is not None and step["weights"] is not None
        if step is not None:
            kda.count_step(folded)
        with jax.named_scope("dstpu_kda_decode" if step is not None
                             else "dstpu_kda_prefill"):
            if folded:
                o, kda_full, tail_full = kda.kda_step(
                    qkv[:, 0], g_pre[:, 0], beta[:, 0], gate_pre[:, 0],
                    kda_full, tail_full, layer, step["weights"], step["walk"],
                    step["active"], eps=c.eps)
                o = o.reshape(b, 1, h, dk)
            else:
                o, kda_full, tail_full = self._kda_mixer(
                    qkv, g_pre, beta, gate_pre, blk, kda_full, tail_full,
                    layer, idx, valid, step)
        x = x + merge_heads(o, blk["wo"])
        x, counts = self._ffn(x, blk, valid, counts)
        return x, (None if state is None else (kda_full, tail_full, counts))

    def _kda_mixer(self, qkv, g_pre, beta, gate_pre, blk, kda_full, tail_full,
                   layer, idx, valid, step):
        """The mixer between the projections and the output matmul -> ``(o [B,
        T, H, V], kda_full, tail_full)``. The carried convolutions are XLA's
        own. Behind them a cached prompt block on a TPU whose shapes fit is
        the one call :func:`kda.kda_prefill`, which takes the convolution's
        result and the gates as they are; everything else (no cache,
        training, a CPU, shapes that fit no tile, the split one-token step)
        is XLA's too: the norms, the decay, the one-token update or the
        chunked prompt form, the head norm and the gate."""
        c = self.config
        b, t, _ = qkv.shape
        h, dk, w = c.kda_heads, c.kda_head_dim, c.kda_width
        s0 = None
        if kda_full is None:
            tail0 = jnp.zeros((b, c.kda_conv - 1, 3 * w), qkv.dtype)
        else:
            tail0 = jax.lax.dynamic_index_in_dim(
                tail_full, layer, 0, False).reshape(b, c.kda_conv - 1, 3 * w)
            if t > 1:
                # a row at position 0 has no history, whatever its slot held
                s0 = jax.lax.dynamic_index_in_dim(kda_full, layer, 0, False)
                fresh = jnp.reshape(idx == 0, (-1, 1, 1))
                tail0 = jnp.where(fresh, 0, tail0)
                s0 = jnp.where(fresh[..., None], 0, s0)
        act, tail1 = causal_conv(qkv, tail0, blk["conv_w"],
                                 jnp.zeros((3 * w,), jnp.float32), valid)
        if tail_full is not None:
            tail_full = jax.lax.dynamic_update_index_in_dim(
                tail_full, tail1.reshape((b,) + tail_full.shape[2:]).astype(
                    tail_full.dtype), layer, 0)
        # serving only (the kernel has no VJP): a cache, a TPU, shapes that
        # fit; training and everything else take the chunked form
        if step is None and s0 is not None \
                and kda.default_route() == "pallas" \
                and kda.supports_prefill(t, h, dk, dk, c.kda_chunk):
            kda.count_prefill_kernel()
            o, s1 = kda.kda_prefill(
                act, g_pre, beta, gate_pre, kda.fold_layer(blk), s0,
                chunk=c.kda_chunk, eps=c.eps, length=valid)
            return o.reshape(b, t, h, dk), \
                jax.lax.dynamic_update_index_in_dim(
                    kda_full, s1.astype(kda_full.dtype), layer, 0), tail_full
        q, k_, v_ = (a.reshape(b, t, h, dk) for a in jnp.split(act, 3, -1))
        q = kda.l2_normalize(q) * dk ** -0.5
        k_ = kda.l2_normalize(k_)
        g = kda.log_decay(g_pre.reshape(b, t, h, dk), blk["A_log"],
                          blk["dt_bias"].reshape(h, dk))
        if step is not None:
            o, kda_full = kda.kda_update(kda_full, layer, q[:, 0], k_[:, 0],
                                         v_[:, 0], g[:, 0], beta[:, 0],
                                         step["active"])
            o = o[:, None]
        else:
            kda.count_chunked_block()
            o, s1 = kda.kda_chunked(q, k_, v_, g, beta, chunk=c.kda_chunk,
                                    init_state=s0, length=valid)
            if kda_full is not None:
                kda_full = jax.lax.dynamic_update_index_in_dim(
                    kda_full, s1.astype(kda_full.dtype), layer, 0)
        o = rms_norm(o, blk["o_norm"], c.eps) * jax.nn.sigmoid(
            gate_pre.reshape(b, t, h, dk).astype(jnp.float32))
        return o.astype(qkv.dtype), kda_full, tail_full

    def _decode_step(self, params, valid, b):
        """What the delta-rule layers of one decode step (one token a slot, a
        cache) share, made once a step: which slots decode, their order for
        the kernel's grid and, where the kernel route is taken and the shapes
        fold, the stack's small weights as the folded call reads them;
        ``weights`` ``None`` says the layers run split."""
        c = self.config
        active = jnp.ones((b,), bool) if valid is None else valid > 0
        folds = kda.default_route() == "pallas" and kda.supports(
            c.kda_heads, c.kda_head_dim, c.kda_head_dim, c.kda_conv)
        return {"active": active, "walk": slot_order(active),
                "weights": kda.fold_weights(params[KDA], c.kda_heads)
                if folds else None}

    def _gqa_layer(self, x, blk, state=None, layer=None, idx=None, valid=None,
                   walk_=None):
        """No rotation, no q/k norm; the attention's output times an
        elementwise sigmoid gate of the normed input. ``state``: ``None`` or
        ``(k_full, v_full, counts)`` at ``layer`` and ``idx``; ``walk_``: the
        decode program's ``cache["slot_walk"]``. -> ``(x, state)``."""
        c = self.config
        b, t, _ = x.shape
        hq, hkv, dh = c.num_heads, c.num_kv_heads, c.head_dim
        y = rms_norm(x, blk["attn_norm"], c.eps)
        q = project_heads(y, blk["wq"], hq, dh)
        k_ = project_heads(y, blk["wk"], hkv, dh)
        v_ = project_heads(y, blk["wv"], hkv, dh)
        gate = jax.nn.sigmoid(
            project_heads(y, blk["w_gate"], hq, dh).astype(jnp.float32))
        counts = None
        if state is None:
            rep = hq // hkv
            attn = multihead_attention(
                q, jnp.repeat(k_, rep, axis=2), jnp.repeat(v_, rep, axis=2),
                causal=True)
        else:
            kc, vc, counts = state
            s_max = kc.shape[3]
            if t > 1 and kc.shape[4] == dh and s_max > c.key_block \
                    and s_max % c.key_block == 0:
                kc, vc, kl, vl = write_kv_cache(kc, vc, k_, v_, layer, idx)
                # serving only (the kernel has no VJP): a TPU and shapes that
                # fit take the one call over the leaves where they lie, whose
                # dead query tiles come back as zeros; the rest the loop
                kernel = jax.default_backend() == "tpu" and \
                    gqa_prefill.supports(s_max, kc.shape[4], dh, c.key_block,
                                         t, hq, hkv)
                gqa_prefill.count_traced(kernel)
                with jax.named_scope("dstpu_gqa_prefill"):
                    attn = gqa_prefill.gqa_prefill(
                        q, kc, vc, layer, idx, valid, key_block=c.key_block
                    ) if kernel else blocked_prompt_attention(
                        q, kl, vl, jnp.broadcast_to(cache_positions(idx, t),
                                                    (b, t)),
                        key_block=c.key_block)
            else:
                attn, kc, vc = cached_attention(q, kc, vc, k_, v_, layer, idx,
                                                active=walk_)
        attn = (attn.astype(jnp.float32) * gate).astype(x.dtype)
        x = x + merge_heads(attn, blk["wo"])
        x, counts = self._ffn(x, blk, valid, counts)
        return x, (None if state is None else (kc, vc, counts))

    # ------------------------------------------------------- inference path
    def init_cache(self, batch_size: int, max_len: int, dtype=None):
        """One tree for both kinds of per-request state: ``k``, ``v`` over
        the softmax layers only, ``kda`` ``[Lk, B, H, K, V]`` float32 and
        ``kda_conv`` (ops/kda.tail_shape) over the delta-rule layers, and the
        index."""
        c = self.config
        dtype = dtype or self.compute_dtype
        lk = c.count(KDA)
        # no barrier as alloc_kv_cache has: a block at position 0 starts from
        # zeros whatever the buffer held (_kda_mixer) and every layer's row is
        # written before it is read again
        state = jnp.zeros((lk, batch_size, c.kda_heads, c.kda_head_dim,
                           c.kda_head_dim), self.state_dtype)
        tail = jnp.zeros((lk, batch_size) + kda.tail_shape(
            c.kda_conv, c.kda_heads, c.kda_head_dim), dtype)
        return dict(kv_cache(c.count(GQA), batch_size, c.num_kv_heads,
                             max_len, c.head_dim, dtype), kda=state,
                    kda_conv=tail)

    def num_params(self) -> int:
        """Parameters held here: ``held[1]`` of the experts a layer."""
        c = self.config
        d, w, r = c.hidden_size, c.kda_width, c.kda_gate_rank
        expert = 3 * d * c.moe_intermediate_size
        sparse = d + d * c.num_experts + c.num_experts \
            + expert * (1 + c.held[1])
        gqa = (d + d * c.head_dim * (3 * c.num_heads + 2 * c.num_kv_heads)
               + sparse)
        kda_ = (d + 3 * d * w + 3 * w * c.kda_conv + 2 * (d * r + r * w)
                + d * c.kda_heads + c.kda_heads + w + c.kda_head_dim + w * d
                + sparse)
        return (2 * c.vocab_size * d + d + c.count(GQA) * gqa
                + c.count(KDA) * kda_)

    def flops_per_token(self) -> float:
        c = self.config
        expert = 3 * c.hidden_size * c.moe_intermediate_size
        # of a token's k experts, the share held here on average
        routed = c.num_experts_per_tok * c.held[1] / c.num_experts
        return 6.0 * (self.num_params()
                      - c.num_layers * expert * (c.held[1] - routed))
