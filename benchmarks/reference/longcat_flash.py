"""Plain reference of the shortcut-connected double-layer decoder
(LongCat-Flash-Chat): float32 ``jax.numpy`` at "highest" matmul precision; no
kernel, no cache, no absorption, no sorting. It reads the configuration's dict
under its published keys and the program's parameter tree (``embed``;
``sub.*``, the sublayers' leaves stacked ``[2 L, ...]``, sublayer ``i`` of
double layer ``l`` at ``2 l + i``; ``pair.*``, what a double layer holds once,
``[L, ...]``: the router, the held experts, and sublayer ``i``'s ``Wkv_b`` as
``wkv_b<i>``; ``final_norm``; ``lm_head``) and shares no code with ``deepspeed_tpu``.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    double layer, sublayers i = 0, 1:
        a1  = x  + MLA_0(rms(x;  g_in0))
        z   = rms(a1; g_post0)
        m   = MoE(z)                                 the shortcut: joins at the end
        b1  = a1 + Dense_0(z)
        a2  = b1 + MLA_1(rms(b1; g_in1))
        out = a2 + Dense_1(rms(a2; g_post1)) + m
    MLA(y), head h of H, the DECOMPRESSED form at every position:
        q_h = s_q (rms(y Wq_a; g_q) Wq_b[h]) = [q_nope (nope) | q_rope (rope)]
        [c | k_r] = y Wkv_a  (kv_lora_rank + rope);  c~ = s_kv rms(c; g_kv)
        s_q = (hidden / q_lora_rank) ** 0.5 if mla_scale_q_lora,
        s_kv = (hidden / kv_lora_rank) ** 0.5 if mla_scale_kv_lora; k_r is not scaled
        [k_nope_h | v_h] = c~ Wkv_b[h]  (nope + v)
        q_rope, k_r rotated (rotate-half over rope, f_i = theta ** (-2 i / rope));
        k_r is one row for all heads
        score_h(i, j) = (nope + rope) ** -0.5 (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i
        o_h = softmax_j(score_h) v_h;  MLA(y) = concat_h(o_h) Wo
    Dense(z) = (silu(z Wg) * (z Wu)) Wd
    MoE(z):  p = softmax(z Wr) over ALL router outputs (real experts, then
             zero_expert_num identity experts); the moe_topk largest of p + b
             chosen; w_e = routed_scaling_factor p_e, not normalised
             MoE(z) = sum over chosen real e of w_e Expert_e(z)
                      + (sum over chosen identity e of w_e) z

That the program's absorbed decode step over cached latent rows, its scales
folded into two norms' gains, agrees with this is what the comparison shows.

**The share.** The configuration holds ``n_routed_experts`` real experts of
``n_routed_experts_published``, those from ``experts_held_first`` on. The
router and the choice run over all outputs; the first sum is a plain loop over
the held experts, each applied to EVERY token and masked by the token's weight
for it (zero where it was not chosen); the identity term is whole, as it is
computed where the token is; the rest is left out, here as in the program.
Without the two share keys the file gives the uncut layer.

Nothing here is clever but its footprint (the engine's 10.35 GB stay resident
while it runs): the double layers are one loop, attention runs one head at a
time and a head's queries in blocks, a gated MLP a block of tokens at a time,
experts one at a time, and every matrix is read in the stack where it lies and
cast to float32 a slice of its columns at a time where it is multiplied (a
layer's slice taken outside the loops is a copy of it: 5.5 GB of temporaries
for four double layers, compiled for the described chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048

def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _cols(w, at, start, width):
    """``float32(w[at][:, start:start + width])`` of a stacked ``w`` read
    where it lies (``at``: its leading indices, numbers or traced): no copy of
    a layer's slice of the stack is made."""
    lead = tuple(jnp.asarray(i, jnp.int32) for i in at)
    rows = w.shape[-2]
    return _f32(jax.lax.dynamic_slice(
        w, lead + (jnp.zeros((), jnp.int32), jnp.asarray(start, jnp.int32)),
        (1,) * len(lead) + (rows, width))).reshape(rows, width)


def _mm(x, w, at=(), limit=1 << 25):
    """``x @ float32(w[at])``, a slice of the matrix's columns at a time
    where it is large: the float32 copy of one slice is live, not of the
    matrix."""
    rows, cols = w.shape[-2:]
    pieces = 1
    while rows * cols // pieces > limit and cols % (2 * pieces) == 0:
        pieces *= 2
    width = cols // pieces
    if pieces == 1:
        return x @ _cols(w, at, 0, cols)

    def piece(out, i):
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ _cols(w, at, i * width, width), i * width,
            x.ndim - 1), None

    out, _ = jax.lax.scan(piece, jnp.zeros(x.shape[:-1] + (cols,),
                                           jnp.float32),
                          jnp.arange(pieces, dtype=jnp.int32))
    return out


def _rotate(x, theta):
    """``x [B, T, rope]`` at positions 0..T-1, rotate-half, plain frequencies."""
    t, rope = x.shape[1], x.shape[-1]
    half = rope // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / rope)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _attention(x, sub, wkv_b, at, layer, cfg):
    """``MLA(rms(x; g_in))`` of sublayer ``at`` of the ``sub`` stack, its
    ``Wkv_b`` the stack ``wkv_b`` at ``layer``."""
    b, t, d = x.shape
    heads, r, ql = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                    cfg["q_lora_rank"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    s_q = (d / ql) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    s_kv = (d / r) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    scale = (nope + rope) ** -0.5
    y = _rms(x, _f32(sub["attn_norm"][at]), eps)
    qa = _rms(_mm(y, sub["wq_a"], (at,)), _f32(sub["q_norm"][at]), eps)
    ckr = _mm(y, sub["wkv_a"], (at,))
    c = s_kv * _rms(ckr[..., :r], _f32(sub["kv_norm"][at]), eps)
    k_r = _rotate(ckr[..., r:], theta)                     # [b, t, rope]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    def head(h):
        q = s_q * (qa @ _cols(sub["wq_b"], (at,), h * (nope + rope),
                              nope + rope))
        kv = c @ _cols(wkv_b, (layer,), h * (nope + vd), nope + vd)
        q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], theta)],
                            -1)
        k = jnp.concatenate([kv[..., :nope], k_r], -1)     # [b, t, nope+rope]
        v = kv[..., nope:]

        def block(i):
            # queries [i qb, (i + 1) qb) against the keys up to their end
            end = (i + 1) * qb
            s = q[:, i * qb:end] @ k[:, :end].swapaxes(-1, -2) * scale
            mask = pos[None, :end] <= pos[i * qb:end, None]
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf),
                                  axis=-1) @ v[:, :end]

        return jnp.concatenate([block(i) for i in range(t // qb)], 1)

    out = jax.lax.map(head, jnp.arange(heads))             # [H, b, t, vd]
    out = out.transpose(1, 2, 0, 3).reshape(b, t, heads * vd)
    return _mm(out, sub["wo"], (at,))


def _gated(z, tree, prefix, at):
    """The gated MLP ``tree[prefix + gate / up / down]`` at the leading
    indices ``at``, ``QUERY_BLOCK`` tokens at a time."""
    def some(z):
        return _mm(jax.nn.silu(_mm(z, tree[prefix + "gate"], at))
                   * _mm(z, tree[prefix + "up"], at),
                   tree[prefix + "down"], at)

    b, t, d = z.shape
    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return some(z)
    blocks = z.reshape(b, t // QUERY_BLOCK, QUERY_BLOCK, d).swapaxes(0, 1)
    return jax.lax.map(some, blocks).swapaxes(0, 1).reshape(b, t, d)


def _moe(z, pair, layer, cfg):
    """The expert layer ``layer`` of the ``pair`` stack; its expert stacks
    ``[layers, held, ...]`` are read at ``[layer, e]`` one expert at a time."""
    held = cfg["n_routed_experts"]
    real = cfg.get("n_routed_experts_published", held)
    first = cfg.get("experts_held_first", 0)
    p = jax.nn.softmax(_mm(z, pair["router"], (layer,)), axis=-1)
    if p.shape[-1] != real + cfg["zero_expert_num"]:
        raise ValueError("the router is as wide as the real and the identity "
                         "experts together")
    _, chosen = jax.lax.top_k(p + _f32(pair["select_bias"][layer]),
                              cfg["moe_topk"])
    w = cfg["routed_scaling_factor"] * jnp.take_along_axis(p, chosen, -1)
    # [.., E] weight of each output for each token, zero where not chosen
    dense_w = (jax.nn.one_hot(chosen, p.shape[-1]) * w[..., None]).sum(-2)

    def one(acc, e):
        mine = jax.lax.dynamic_index_in_dim(dense_w, first + e, -1)
        return acc + mine * _gated(z, pair, "expert_", (layer, e)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z),
                             jnp.arange(held, dtype=jnp.int32))
    return routed + dense_w[..., real:].sum(-1, keepdims=True) * z


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg.get("attention_bias") or cfg["zero_expert_type"] != "identity" \
            or cfg.get("rope_scaling"):
        raise ValueError("this reference has no attention bias, identity "
                         "zero experts and plain rotary frequencies")
    eps = cfg["rms_norm_eps"]
    sub, pair = params["sub"], params["pair"]

    def double_layer(layer, x):
        first, second = 2 * layer, 2 * layer + 1
        a1 = x + _attention(x, sub, pair["wkv_b0"], first, layer, cfg)
        z = _rms(a1, _f32(sub["mlp_norm"][first]), eps)
        m = _moe(z, pair, layer, cfg)
        b1 = a1 + _gated(z, sub, "w_", (first,))
        a2 = b1 + _attention(b1, sub, pair["wkv_b1"], second, layer, cfg)
        return a2 + _gated(_rms(a2, _f32(sub["mlp_norm"][second]), eps),
                           sub, "w_", (second,)) + m

    with jax.default_matmul_precision("highest"):
        x = jax.lax.fori_loop(0, cfg["num_layers"], double_layer,
                              _f32(params["embed"][input_ids]))
        x = _rms(x, _f32(params["final_norm"]), eps)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
