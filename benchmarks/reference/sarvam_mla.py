"""Plain reference of the multi-head latent attention decoder (HF
``sarvam_mla``; Sarvam-105B): float32 ``jax.numpy`` at "highest" matmul
precision; no kernel, no cache, no absorption, no sorting. It reads the
configuration's dict under its published keys and the program's parameter tree
(``embed``; ``dense.*`` and ``sparse.*`` stacked on a leading axis over the
layers of that FFN kind, the ``first_k_dense_replace`` dense layers first;
``final_norm``; ``lm_head``) and shares no code with ``deepspeed_tpu``.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    block: h = x + Attn(rms(x; g1));  y = h + FFN(rms(h; g2))
    Attn, head h of H, the DECOMPRESSED form at every position:
        q_h = u Wq[h]  = [q_nope (nope) | q_rope (rope)]
        [c | k_r] = u Wkv_a  (kv_lora_rank + rope);  c~ = rms(c; g_kv)
        [k_nope_h | v_h] = c~ Wkv_b[h]  (nope + v)
        q_rope, k_r rotated (rotate-half over rope, YaRN's frequencies);
        k_r is one row for all heads
        score_h(i, j) = s (q_nope_h(i) . k_nope_h(j) + q_rope_h(i) . k_r(j)), j <= i
        s = (nope + rope) ** -0.5 * m * m,  m = 0.1 mscale_all_dim ln(factor) + 1
        o_h = softmax_j(score_h) v_h;  out = concat_h(o_h) Wo
    YaRN (rope_scaling.type deepseek_yarn), i < rope / 2:
        f_i = theta ** (-2 i / rope)
        dim(n) = rope ln(original_max / (2 pi n)) / (2 ln theta)
        low = floor(dim(beta_fast)), high = ceil(dim(beta_slow)), in [0, rope/2 - 1]
        r_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i (1 - r_i) + f_i / factor r_i;  cos, sin unscaled
    dense FFN:  (silu(z Wg) * (z Wu)) Wd
    sparse FFN: Shared(z) + scale * sum over e in top_k(sigma + b) of
                w_e Expert_e(z),  sigma = sigmoid(z Wr),
                w = sigma_chosen / (sum of the chosen + 1e-20)

That the program's absorbed decode step over cached latent rows agrees with
this is what the comparison shows.

**The share.** The configuration holds ``num_experts`` experts of
``num_experts_published`` (the router's width), those from
``experts_held_first`` on. The router, the choice and the normalisation run
over all of them; the sum is a plain loop over the held experts, each applied
to EVERY token and masked by the token's weight for it (zero where it was not
chosen); the rest is left out, here as in the program.

Nothing here is clever but its footprint: attention runs one head at a time
and a head's queries in blocks, each against the keys up to its own end (64
heads' scores at 16,384 positions are 68 GB), a gated MLP a block of tokens
at a time, a large matrix cast to float32 a slice of its columns at a time
where it is multiplied, the experts one at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 2048


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


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


def yarn_inv_freq(cfg):
    rope, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg["rope_scaling"]
    if sc["type"] != "deepseek_yarn" or sc["mscale"] != sc["mscale_all_dim"]:
        raise ValueError("this reference rotates by deepseek_yarn with "
                         "mscale == mscale_all_dim (cos and sin unscaled)")
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
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rotate(x, inv):
    """``x [B, T, rope]`` at positions 0..T-1, rotate-half."""
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None]
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _attention(x, p, cfg):
    b, t, _ = x.shape
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    eps, inv, scale = cfg["rms_norm_eps"], yarn_inv_freq(cfg), score_scale(cfg)
    u = _rms(x, p["attn_norm"], eps)
    ckr = _mm(u, p["wkv_a"])
    c = _rms(ckr[..., :r], p["kv_norm"], eps)
    k_r = _rotate(ckr[..., r:], inv)                       # [b, t, rope]
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)

    def head(h):
        wq = jax.lax.dynamic_slice_in_dim(p["wq"], h * (nope + rope),
                                          nope + rope, 1)
        wkv = jax.lax.dynamic_slice_in_dim(p["wkv_b"], h * (nope + vd),
                                           nope + vd, 1)
        q = u @ _f32(wq)
        kv = c @ _f32(wkv)
        q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv)], -1)
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
    return x + _mm(out, p["wo"])


def _gated(z, gate, up, down):
    """A gated MLP, ``QUERY_BLOCK`` tokens at a time: at 16,384 positions the
    dense layer's three products are 1 GB each."""
    def some(z):
        return _mm(jax.nn.silu(_mm(z, gate)) * _mm(z, up), down)

    b, t, d = z.shape
    if t <= QUERY_BLOCK or t % QUERY_BLOCK:
        return some(z)
    blocks = z.reshape(b, t // QUERY_BLOCK, QUERY_BLOCK, d).swapaxes(0, 1)
    return jax.lax.map(some, blocks).swapaxes(0, 1).reshape(b, t, d)


def _sparse_ffn(z, p, experts, layer, cfg):
    """``p``: the layer's leaves; ``experts``: the three expert stacks
    ``[layers, held, ...]`` as stored, read at ``[layer, e]`` one expert at a
    time."""
    k, held = cfg["num_experts_per_tok"], cfg["num_experts"]
    first = cfg.get("experts_held_first", 0)
    sigma = jax.nn.sigmoid(_mm(z, p["router"]))         # all the router's
    _, chosen = jax.lax.top_k(sigma + p["select_bias"], k)
    w = jnp.take_along_axis(sigma, chosen, -1)
    if cfg["assumed"]["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    # [.., E] weight of each expert for each token, zero where not chosen
    dense_w = (jax.nn.one_hot(chosen, sigma.shape[-1]) * w[..., None]).sum(-2)

    def one(acc, e):
        gate, up, down = (a[layer, e] for a in experts)
        return acc + dense_w[..., first + e, None] * _gated(z, gate, up,
                                                            down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    return (_gated(z, p["shared_gate"], p["shared_up"], p["shared_down"])
            + cfg["routed_scaling_factor"] * routed)


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg["tie_word_embeddings"] or cfg["num_shared_experts"] != 1 \
            or cfg["hidden_act"] != "silu":
        raise ValueError("this reference has an untied head, one shared "
                         "expert and SiLU gates")
    eps = cfg["rms_norm_eps"]
    big = ("expert_gate", "expert_up", "expert_down")
    n_dense = cfg["first_k_dense_replace"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        for layer in range(cfg["num_hidden_layers"]):
            ffn = "dense" if layer < n_dense else "sparse"
            i = layer if layer < n_dense else layer - n_dense
            # vectors in float32; a matrix is cast where it is multiplied
            p = {n: a[i] if a.ndim > 2 else _f32(a[i])
                 for n, a in params[ffn].items() if n not in big}
            h = _attention(x, p, cfg)
            z = _rms(h, p["mlp_norm"], eps)
            if ffn == "dense":
                x = h + _gated(z, p["w_gate"], p["w_up"], p["w_down"])
            else:
                x = h + _sparse_ffn(
                    z, p, tuple(params[ffn][n] for n in big), i, cfg)
        x = _rms(x, _f32(params["final_norm"]), eps)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
