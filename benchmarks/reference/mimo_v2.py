"""Plain reference of the MiMo-V2 decoder (HF ``mimo_v2``; MiMo-V2.5): float32
``jax.numpy`` at "highest" matmul precision; no kernel, no cache, no sorting,
no padded rows. It reads the configuration's dict under its published keys
and the program's parameter tree (``embed``; one stack a pair of kinds,
``dense_global``, ``sparse_sliding``, ``sparse_global``, each stacked on a
leading axis over the layers of that pair in stack order; ``final_norm``;
``lm_head``) and shares no code with ``deepspeed_tpu``.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    layer l is sliding where hybrid_layer_pattern[l] == 1, global where 0;
    its FFN sparse where moe_layer_freq[l] == 1, dense where 0
    block: h = x + Attn(rms(x; g1)) Wo;  y = h + FFN(rms(h; g2))
    [q | k | v] = u Wqkv:  q as [H, Dk], k as [Hkv, Dk], v as [Hkv, Dv];
           Hkv = num_key_value_heads (global), swa_num_key_value_heads (sliding)
    q, k rotated (rotate-half) on their FIRST int(partial_rotary_factor Dk)
           lanes, theta rope_theta (global), swa_rope_theta (sliding)
    v <- attention_value_scale v
    head i reads key-value head i // (H / Hkv); s_ij = q_i . k_j / sqrt(Dk)
           over j <= i and, on sliding layers, i - j < sliding_window
    global:  p = softmax_j(s)
    sliding: the sink of the head as one more column of the softmax, dropped
           behind it: p_ij = exp(s_ij) / (exp(sink_h) + sum_j' exp(s_ij'))
    dense FFN:  (silu(z Wg) * (z Wu)) Wd
    sparse FFN: s * sum over e in top_k(sigma + b) of w_e Expert_e(z),
                sigma = sigmoid(z Wr), w = sigma_chosen / (sum of the chosen
                + 1e-20), s = routed_scaling_factor (null: 1); no shared one

**The share.** The configuration holds ``n_routed_experts`` experts of
``n_routed_experts_published`` (the router's width), those from
``experts_held_first`` on. The router, the choice and the normalisation run
over all of them; the sum runs over the chosen experts that are held; the
rest is left out, here as in the program.

The dispatch is another algorithm than the program's: EVERY held expert is
applied to EVERY token and the result masked by the token's weight for it
(zero where it was not chosen). What is large is walked: a matrix is cast to
float32 a slice of its columns at a time where it lies in its stack
(``_mm``), the experts one at a time, attention one query head at a time with
its queries in blocks of ``QUERY_BLOCK`` against the keys they can see, an
FFN ``QUERY_BLOCK`` tokens at a time: 16,384 positions fit beside the served
model.

What the published ``config.json`` does not say is listed in the
configuration file's ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


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


def _rotate_first(x, lanes, theta):
    """``x [B, T, D]`` at positions 0..T-1: rotate-half on its first
    ``lanes`` lanes, the rest as it is."""
    t, half = x.shape[1], lanes // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / lanes)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None]
    part = x[..., :lanes]
    turned = jnp.concatenate([-part[..., half:], part[..., :half]], -1)
    return jnp.concatenate([part * cos + turned * sin, x[..., lanes:]], -1)


def _attention(x, stack, at, cfg, sliding: bool):
    """``Attn(rms(x; g1)) Wo`` of layer ``at`` of ``stack``."""
    b, t, _ = x.shape
    heads, dk, dv = (cfg["num_attention_heads"], cfg["head_dim"],
                     cfg["v_head_dim"])
    n_kv = cfg["swa_num_key_value_heads" if sliding
               else "num_key_value_heads"]
    theta = float(cfg["swa_rope_theta" if sliding else "rope_theta"])
    lanes = int(cfg["partial_rotary_factor"] * dk)
    window = cfg["sliding_window"] if sliding else t
    rep = heads // n_kv
    y = _rms(x, _f32(stack["attn_norm"][at]), cfg["layernorm_epsilon"])
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    pos = jnp.arange(t)
    sinks = _f32(stack["sink"][at]) if sliding and \
        cfg["add_swa_attention_sink_bias"] else None

    def group(g):
        k = _rotate_first(y @ _cols(stack["wqkv"], (at,),
                                    heads * dk + g * dk, dk), lanes, theta)
        v = cfg["attention_value_scale"] * (y @ _cols(
            stack["wqkv"], (at,), (heads + n_kv) * dk + g * dv, dv))

        def head(r):
            h = g * rep + r
            q = _rotate_first(y @ _cols(stack["wqkv"], (at,), h * dk, dk),
                              lanes, theta)

            def block(i):
                # queries [i qb, (i + 1) qb) against the keys they can see
                lo, end = max(0, i * qb - window + 1), (i + 1) * qb
                s = q[:, i * qb:end] @ k[:, lo:end].swapaxes(-1, -2) \
                    / jnp.sqrt(jnp.float32(dk))
                gap = pos[i * qb:end, None] - pos[None, lo:end]
                s = jnp.where((gap >= 0) & (gap < window), s, -jnp.inf)
                if sinks is not None:
                    column = jnp.broadcast_to(sinks[h], s.shape[:-1] + (1,))
                    p = jax.nn.softmax(jnp.concatenate([s, column], -1),
                                       axis=-1)[..., :-1]
                else:
                    p = jax.nn.softmax(s, axis=-1)
                return p @ v[:, lo:end]

            return jnp.concatenate([block(i) for i in range(t // qb)], 1)

        return jax.lax.map(head, jnp.arange(rep))          # [rep, b, t, dv]

    out = jax.lax.map(group, jnp.arange(n_kv))     # [n_kv, rep, b, t, dv]
    out = out.reshape(heads, b, t, dv).transpose(1, 2, 0, 3)
    return _mm(out.reshape(b, t, heads * dv), stack["wo"], (at,))


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


def _sparse_ffn(z, stack, at, cfg):
    """The expert layer ``at`` of ``stack``; its expert stacks ``[layers,
    held, ...]`` are read at ``[at, e]`` one expert at a time."""
    held = cfg["n_routed_experts"]
    first = cfg.get("experts_held_first", 0)
    sigma = jax.nn.sigmoid(_mm(z, stack["router"], (at,)))  # all the router's
    _, chosen = jax.lax.top_k(sigma + _f32(stack["select_bias"][at]),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(sigma, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    scale = cfg["routed_scaling_factor"]
    w = w * (1.0 if scale is None else scale)
    # [.., E] weight of each expert for each token, zero where not chosen
    dense_w = (jax.nn.one_hot(chosen, sigma.shape[-1]) * w[..., None]).sum(-2)

    def one(acc, e):
        mine = jax.lax.dynamic_index_in_dim(dense_w, first + e, -1)
        return acc + mine * _gated(z, stack, "expert_", (at, e)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z),
                             jnp.arange(held, dtype=jnp.int32))
    return routed


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["tie_word_embeddings"] or cfg["n_shared_experts"] \
            or cfg["add_full_attention_sink_bias"] or cfg["attention_bias"]:
        raise ValueError("this reference scores by sigmoid, has no group "
                         "limit, no shared expert, no sink on global layers, "
                         "no bias and an untied head")
    eps = cfg["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        seen = {}
        # one layer at a time, in stack order
        for sliding, sparse in zip(cfg["hybrid_layer_pattern"],
                                   cfg["moe_layer_freq"]):
            name = ("sparse" if sparse else "dense") + \
                ("_sliding" if sliding else "_global")
            at = seen.get(name, 0)
            seen[name] = at + 1
            stack = params[name]
            h = x + _attention(x, stack, at, cfg, bool(sliding))
            z = _rms(h, _f32(stack["mlp_norm"][at]), eps)
            x = h + (_sparse_ffn(z, stack, at, cfg) if sparse
                     else _gated(z, stack, "w_", (at,)))
        x = _rms(x, _f32(params["final_norm"]), eps)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
