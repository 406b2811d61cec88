"""Plain reference of the GigaChat 3.5 decoder (HF ``gigachat3_5``;
GigaChat3.5-432B-A28B): float32 ``jax.numpy`` at "highest" matmul precision;
no kernel, no cache, no chunked form, no absorption, no sorting. It reads the
configuration's dict under its published keys and the program's parameter tree
(``embed``; ``gdn_dense.*``, ``gdn_sparse.*``, ``mla_dense.*``,
``mla_sparse.*`` stacked on a leading axis over the layers of that mixer and
FFN, in stack order, a kind without a layer absent; ``final_norm``;
``lm_head``) and shares no code with ``deepspeed_tpu``.

    N(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * (layernorm_gating_weight sigmoid(w))
    rms(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g
    block (layernorm_type pre_post): h = x + N(Mixer(N(x; w1)); w2)
                                     y = h + N(FFN(N(h; w3)); w4)
    GatedDeltaNet (a layer not in full_attention_layers), on u = N(x; w1),
    Hk key heads and Hv value heads of K = V lanes:
        [q' | k' | v'] = conv(u W_qkv)   depthwise, causal, `taps` taps, no
                         bias: out_t = sum_j w_j in_{t - taps + 1 + j}, zeros
                         before position 0
        q, k, v = silu(q'), silu(k'), silu(v')
        q_j <- q_j / sqrt(|q_j|^2 + 1e-6) * K ** -0.5;  k_j likewise, unscaled
        z = u W_z;  [b | a] = u W_ba;  beta = sigmoid(b)
        g = -exp(A_log) softplus(a + dt_bias)          ONE number a value head
        POSITION BY POSITION, value head h with key head h // (Hv / Hk),
        S_h [K, V] = 0 before position 0:
            S~ = exp(g_t) S;  S = S~ + beta_t k_t (v_t - S~^T k_t)^T
            o_t = S^T q_t
        o_h <- o_h / sqrt(mean(o_h^2) + linear_attn_o_norm_eps) * (1 + w_o)
               * (linear_sigmoid_gate_scale sigmoid(z_h))
        Mixer = concat_h(o_h) W_out
    Latent attention (a layer in full_attention_layers), on u, head h of H,
    the DECOMPRESSED form at every position:
        q_h = rms(u Wq_a; g_q) Wq_b[h] = [q_nope (nope) | q_rope (rope)]
        [c | k_r] = u Wkv_a (kv_lora_rank + rope);  c~ = rms(c; g_kv)
        [k_nope_h | v_h] = c~ Wkv_b[h]
        q_rope, k_r rotated with NEIGHBOURS as pairs (rope_interleave):
            (x_2i, x_2i+1) <- (x_2i cos - x_2i+1 sin, x_2i+1 cos + x_2i sin)
            at angle pos * inv_freq_i, YaRN's frequencies; k_r is one row for
            all heads
        score_h(i, j) = s (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i
        s = (nope + rope) ** -0.5 * m * m,  m = 0.1 mscale_all_dim ln(factor) + 1
        o_h = softmax_j(score_h) v_h
        Mixer = (concat_h(o_h) * sigmoid(u W_g)) Wo
    YaRN (rope_scaling.type yarn), i < rope / 2:
        f_i = theta ** (-2 i / rope)
        dim(n) = rope ln(original_max / (2 pi n)) / (2 ln theta)
        low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), in [0, rope/2 - 1]
        r_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i (1 - r_i) + f_i / factor r_i;  cos, sin unscaled
        (mscale / mscale_all_dim = 1)
    every gated MLP: (silu(min(z Wg, L)) * clip(z Wu, -L, L)) Wd, L = swiglu_limit
    dense FFN (a layer before first_k_dense_replace): one gated MLP
    sparse FFN: Shared(z) + scale * sum over e in top_k(sigma + b) of
                w_e Expert_e(z),  sigma = sigmoid(z Wr),
                w = sigma_chosen / (sum of the chosen + 1e-20)

**The share.** The configuration holds ``n_routed_experts`` experts of
``n_routed_experts_published`` (the router's width), those from
``experts_held_first`` on. The router, the choice and the normalisation run
over all of them; the sum is a plain loop over the held experts, each applied
to EVERY token and masked by the token's weight for it (zero where it was not
chosen); the rest is left out, here as in the program.

**In blocks, and nothing else clever**: so that 4,096 positions fit beside
the served model, the delta-rule layer runs ``HEAD_BLOCK`` value heads at a
time (their columns of the projections, their convolutions, their recurrence
as one ``lax.scan`` over the positions), the latent layer one head at a time
with a plain masked softmax over all positions, a gated MLP a block of tokens
at a time, the experts one at a time, and a large matrix is cast to float32 a
slice of its columns at a time where it is multiplied.

Departures from the published code: none known; the builder could not read it
(no network). What the catalog's keys do not settle is listed in the
configuration file's ``assumed``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HEAD_BLOCK = 64
TOKEN_BLOCK = 2048


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _unit_rms(x, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps)


def _stream_norm(x, w, cfg):
    """``N(x; w)``: zero-centred, gated."""
    return _unit_rms(x, cfg["rms_norm_eps"]) * (
        cfg["layernorm_gating_weight"] * jax.nn.sigmoid(w))


def _mm(x, w, limit=1 << 25):
    """``x @ float32(w)``, a slice of ``w``'s columns at a time where ``w`` is
    large: the float32 copy of one slice is live, not of the matrix."""
    rows, cols = w.shape
    pieces = 1
    while rows * cols // pieces > limit and cols % (2 * pieces) == 0:
        pieces *= 2
    if pieces == 1:
        return x @ _f32(w)
    width = cols // pieces

    def piece(out, i):
        part = jax.lax.dynamic_slice_in_dim(w, i * width, width, 1)
        return jax.lax.dynamic_update_slice_in_dim(
            out, x @ _f32(part), i * width, x.ndim - 1), None

    out, _ = jax.lax.scan(piece, jnp.zeros(x.shape[:-1] + (cols,),
                                           jnp.float32), jnp.arange(pieces))
    return out


def _columns(w, start, width):
    return _f32(jax.lax.dynamic_slice_in_dim(w, start, width, w.ndim - 1))


# ----------------------------------------------------------- GatedDeltaNet
def _delta_rule(q, k, v, decay, beta):
    """The recurrence, one position a step, the heads one to one. ``q, k [B,
    T, H, K]``, ``v [B, T, H, V]``, ``decay``, ``beta [B, T, H]`` -> ``o [B,
    T, H, V]``."""
    b, _, h, dk = k.shape

    def step(s, xs):
        q_t, k_t, v_t, a_t, beta_t = xs
        s = a_t[..., None, None] * s                         # [B, H, K, V]
        pred = (s * k_t[..., None]).sum(-2)
        s = s + beta_t[..., None, None] * k_t[..., None] \
            * (v_t - pred)[..., None, :]
        return s, (s * q_t[..., None]).sum(-2)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, decay, beta)))
    return jnp.moveaxis(o, 0, 1)


def _gated_delta_net(u, p, cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    b, t, _ = u.shape
    group = hv // hk
    vb = min(HEAD_BLOCK, hv)                 # value heads a block
    kb = vb // group                         # their key heads
    ba = u @ _f32(p["w_ba"])
    beta = jax.nn.sigmoid(ba[..., :hv])                      # [B, T, Hv]
    decay = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))

    def unit(a):
        return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    def conv_silu(start, heads, width):
        """The projection's columns from ``start`` on through their own
        convolution and SiLU -> ``[B, T, heads, width]``."""
        n = heads * width
        raw = u @ _columns(p["w_qkv"], start, n)
        w = _columns(p["conv_w"], start, n)                  # [taps, n]
        padded = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0)))
        out = sum(padded[:, j:j + t] * w[j] for j in range(taps))
        return jax.nn.silu(out).reshape(b, t, heads, width)

    def block(i):
        q = unit(conv_silu(i * kb * dk, kb, dk)) * dk ** -0.5
        k = unit(conv_silu(hk * dk + i * kb * dk, kb, dk))
        v = conv_silu(2 * hk * dk + i * vb * dv, vb, dv)
        # key head j serves value heads j group .. j group + group - 1
        q, k = jnp.repeat(q, group, axis=2), jnp.repeat(k, group, axis=2)
        o = _delta_rule(q, k, v,
                        jax.lax.dynamic_slice_in_dim(decay, i * vb, vb, 2),
                        jax.lax.dynamic_slice_in_dim(beta, i * vb, vb, 2))
        gate = cfg["linear_sigmoid_gate_scale"] * jax.nn.sigmoid(
            u @ _columns(p["w_z"], i * vb * dv, vb * dv))
        o = _unit_rms(o, cfg["linear_attn_o_norm_eps"]) * (1.0 + p["o_norm"])
        return (o * gate.reshape(b, t, vb, dv)).reshape(b, t, vb * dv)

    o = jax.lax.map(block, jnp.arange(hv // vb))             # [n, B, T, vb V]
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, hv * dv)
    return _mm(o, p["wo"])


# -------------------------------------------------------- latent attention
def yarn_inv_freq(cfg):
    rope, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    if sc["type"] != "yarn" or sc["mscale"] != sc["mscale_all_dim"]:
        raise ValueError("this reference rotates by yarn with mscale == "
                         "mscale_all_dim (cos and sin unscaled)")
    half = rope // 2

    def dim(n):
        return rope * math.log(sc["original_max_position_embeddings"]
                               / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(dim(sc["beta_fast"])), 0)
    high = min(math.ceil(dim(sc["beta_slow"])), half - 1)
    i = jnp.arange(half, dtype=jnp.float32)
    f = theta ** (-2.0 * i / rope)
    r = jnp.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return f * (1.0 - r) + f / sc["factor"] * r


def score_scale(cfg):
    sc = cfg["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 \
        if sc["factor"] > 1 else 1.0
    if not cfg["use_mla_scaling_factor"]:
        m = 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate_pairs(x, inv):
    """``x [B, T, rope]`` at positions 0..T-1, neighbours as pairs."""
    t = x.shape[1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None], jnp.sin(ang)[None]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     -1).reshape(x.shape)


def _latent_attention(u, p, cfg):
    b, t, _ = u.shape
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, inv, scale = cfg["rms_norm_eps"], yarn_inv_freq(cfg), score_scale(cfg)
    c_q = _unit_rms(_mm(u, p["wq_a"]), eps) * p["q_norm"]
    ckr = _mm(u, p["wkv_a"])
    c = _unit_rms(ckr[..., :r], eps) * p["kv_norm"]
    k_r = _rotate_pairs(ckr[..., r:], inv)                 # [b, t, rope]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def head(h):
        q = c_q @ _columns(p["wq_b"], h * (nope + rope), nope + rope)
        kv = c @ _columns(p["wkv_b"], h * (nope + vd), nope + vd)
        q = jnp.concatenate([q[..., :nope],
                             _rotate_pairs(q[..., nope:], inv)], -1)
        k = jnp.concatenate([kv[..., :nope], k_r], -1)     # [b, t, nope+rope]
        s = q @ k.swapaxes(-1, -2) * scale
        probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return probs @ kv[..., nope:]

    out = jax.lax.map(head, jnp.arange(heads))             # [H, b, t, vd]
    out = out.transpose(1, 2, 0, 3).reshape(b, t, heads * vd)
    if cfg["gated_attention"]:
        out = out * jax.nn.sigmoid(_mm(u, p["attn_gate"]))
    return _mm(out, p["wo"])


# --------------------------------------------------------------------- FFN
def _gated(z, gate, up, down, cfg):
    """A gated MLP with the ``swiglu_limit`` clamp, ``TOKEN_BLOCK`` tokens at
    a time: at 4,096 positions the dense layer's products are 0.3 GB each."""
    limit = cfg.get("swiglu_limit")

    def some(z):
        g, u = _mm(z, gate), _mm(z, up)
        if limit is not None:
            g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
        return _mm(jax.nn.silu(g) * u, down)

    b, t, d = z.shape
    if t <= TOKEN_BLOCK or t % TOKEN_BLOCK:
        return some(z)
    blocks = z.reshape(b, t // TOKEN_BLOCK, TOKEN_BLOCK, d).swapaxes(0, 1)
    return jax.lax.map(some, blocks).swapaxes(0, 1).reshape(b, t, d)


def _sparse_ffn(z, p, experts, layer, cfg):
    """``p``: the layer's leaves; ``experts``: the three expert stacks
    ``[layers, held, ...]`` as stored, read at ``[layer, e]`` one expert at a
    time."""
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first = cfg.get("experts_held_first", 0)
    sigma = jax.nn.sigmoid(_mm(z, p["router"]))         # all the router's
    _, chosen = jax.lax.top_k(sigma + p["select_bias"], k)
    w = jnp.take_along_axis(sigma, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    # [.., E] weight of each expert for each token, zero where not chosen
    dense_w = (jax.nn.one_hot(chosen, sigma.shape[-1]) * w[..., None]).sum(-2)

    def one(acc, e):
        gate, up, down = (a[layer, e] for a in experts)
        return acc + dense_w[..., first + e, None] * _gated(
            z, gate, up, down, cfg), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    return (_gated(z, p["shared_gate"], p["shared_up"], p["shared_down"], cfg)
            + cfg["routed_scaling_factor"] * routed)


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg["tie_word_embeddings"] or cfg["n_shared_experts"] != 1 \
            or cfg["hidden_act"] != "silu" or cfg["attention_bias"] \
            or cfg["use_shared_expert_sigmoid"] or cfg["n_group"] != 1 \
            or cfg["layernorm_type"] != "pre_post" \
            or not cfg["rope_interleave"]:
        raise ValueError("this reference has an untied head, one shared "
                         "expert without a sigmoid, SiLU gates, no bias, no "
                         "expert groups, sandwich norms and interleaved "
                         "rotation")
    big = ("expert_gate", "expert_up", "expert_down")
    n_dense = cfg["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        at = {}
        # one layer at a time, in stack order: a layer's weights are cast
        # where they are used, its experts one at a time
        for layer in range(cfg["num_hidden_layers"]):
            mixer = "mla" if layer in cfg["full_attention_layers"] else "gdn"
            ffn = "dense" if layer < n_dense else "sparse"
            kind = f"{mixer}_{ffn}"
            i = at.get(kind, 0)
            at[kind] = i + 1
            # vectors in float32; a matrix is cast where it is multiplied
            p = {n: a[i] if a.ndim > 2 else _f32(a[i])
                 for n, a in params[kind].items() if n not in big}
            u = _stream_norm(x, p["attn_norm"], cfg)
            m = (_latent_attention if mixer == "mla"
                 else _gated_delta_net)(u, p, cfg)
            h = x + _stream_norm(m, p["attn_post_norm"], cfg)
            z = _stream_norm(h, p["mlp_norm"], cfg)
            if ffn == "dense":
                y = _gated(z, p["w_gate"], p["w_up"], p["w_down"], cfg)
            else:
                y = _sparse_ffn(z, p, tuple(params[kind][n] for n in big), i,
                                cfg)
            x = h + _stream_norm(y, p["mlp_post_norm"], cfg)
        x = _stream_norm(x, _f32(params["final_norm"]), cfg)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
