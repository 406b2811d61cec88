"""Plain reference of the EXAONE-MoE decoder (HF ``exaone_moe``;
K-EXAONE-236B-A23B): float32 ``jax.numpy`` at "highest" matmul precision; no
kernel, no cache, no sorting. It reads the configuration's dict under its
published keys and the program's parameter tree (``embed``; ``dense.*`` and
``sparse.*`` stacked on a leading axis over the layers of that FFN kind, in
stack order; ``final_norm``; ``lm_head``) and shares no code with
``deepspeed_tpu``.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g
    block: h = x + Attn(rms(x; g1));  y = h + FFN(rms(h; g2))
    Attn:  q = u Wq as [H, Dh], k = u Wk, v = u Wv as [Hkv, Dh];
           q <- rms(q; gq), k <- rms(k; gk) over Dh; on sliding layers q, k
           rotated (rotate-half over all of Dh, theta), on global layers not;
           head i reads key-value head i // (H / Hkv); scores q.k / sqrt(Dh)
           over j <= i and, on sliding layers, i - j < window
    dense FFN:  (silu(z Wg) * (z Wu)) Wd
    sparse FFN: Shared(z) + s * sum over e in top_k(sigma + b) of
                w_e Expert_e(z),  sigma = sigmoid(z Wr),
                w = sigma_chosen / (sum of the chosen + 1e-20)

**The share.** The configuration holds ``num_experts`` experts of
``num_experts_published`` (the router's width), those from
``experts_held_first`` on. The router, the choice and the normalisation run
over all of them; the sum runs over the chosen experts that are held; the
rest is left out, here as in the program.

The dispatch is another algorithm than the program's: EVERY held expert is
applied to EVERY token and the result masked by the token's weight for it
(zero where it was not chosen). A large matrix is cast to float32 a slice of
its columns at a time where it is multiplied (``_mm``), the experts one at a
time (``lax.scan``), and attention runs one key-value head at a time: a
float32 copy of a layer's experts (2.4 GB), of the dense layer (1.8 GB) or of
all heads' scores at 4096 positions (4.3 GB) does not fit beside the served
model.

Departures from the published code: none known. What the published
``config.json`` does not say (norm placement, the norm on queries and keys,
rotation on sliding layers only, the selection bias) is listed in the
configuration file's ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _mm(x, w, limit=1 << 25):
    """``x @ float32(w)``, a slice of ``w``'s columns at a time where ``w`` is
    large: the float32 copy of one slice is live, not of the matrix (and the
    compiler cannot hoist a cast that depends on the loop's counter)."""
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


def _rotate(x, theta):
    """``x [B, T, H, Dh]`` at positions 0..T-1, rotate-half."""
    t, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = dh // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + turned * sin


def _attention(x, p, cfg, window):
    """``window`` 0: a global layer (no rotation); else a sliding one."""
    b, t, _ = x.shape
    n_head, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    u = _rms(x, p["attn_norm"], eps)
    q = _rms(_mm(u, p["wq"]).reshape(b, t, n_head, dh), p["q_norm"], eps)
    k = _rms(_mm(u, p["wk"]).reshape(b, t, n_kv, dh), p["k_norm"], eps)
    v = _mm(u, p["wv"]).reshape(b, t, n_kv, dh)
    i = jnp.arange(t)
    mask = i[None, :] <= i[:, None]
    if window:
        theta = cfg["rope_parameters"]["rope_theta"]
        q, k = _rotate(q, theta), _rotate(k, theta)
        mask &= i[:, None] - i[None, :] < window
    rep = n_head // n_kv
    q = q.transpose(0, 2, 1, 3).reshape(b, n_kv, rep, t, dh)

    def group(qkv):     # the rep query heads that read one key-value head
        q, k, v = qkv                       # [b, rep, t, dh], [b, t, dh]
        scores = q @ k[:, None].swapaxes(-1, -2) / jnp.sqrt(jnp.float32(dh))
        scores = jnp.where(mask, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v[:, None]

    out = jax.lax.map(group, (q.swapaxes(0, 1), k.transpose(2, 0, 1, 3),
                              v.transpose(2, 0, 1, 3)))
    out = out.swapaxes(0, 1).reshape(b, n_head, t, dh)
    return x + _mm(out.transpose(0, 2, 1, 3).reshape(b, t, -1), p["wo"])


def _gated(z, gate, up, down):
    return _mm(jax.nn.silu(_mm(z, gate)) * _mm(z, up), down)


def _sparse_ffn(z, p, experts, layer, cfg):
    """``p``: the layer's leaves; ``experts``: the three expert stacks
    ``[layers, held, ...]`` as stored, read at ``[layer, e]`` one expert at a
    time (a slice of a layer's experts would be a 1.2 GB copy)."""
    k, held = cfg["num_experts_per_tok"], cfg["num_experts"]
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
        return acc + dense_w[..., first + e, None] * _gated(z, gate, up,
                                                            down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    return (_gated(z, p["shared_gate"], p["shared_up"], p["shared_down"])
            + cfg["routed_scaling_factor"] * routed)


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg["scoring_func"] != "sigmoid" or cfg["n_group"] != 1 \
            or cfg["tie_word_embeddings"]:
        raise ValueError("this reference scores by sigmoid, has no group "
                         "limit and an untied head")
    eps = cfg["rms_norm_eps"]
    big = ("expert_gate", "expert_up", "expert_down")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        at = {"dense": 0, "sparse": 0}
        # one layer at a time, in stack order: a layer's weights are cast
        # where they are used, its experts one at a time
        for window, ffn in zip(cfg["sliding_windows"], cfg["mlp_layer_types"]):
            i = at[ffn]
            at[ffn] += 1
            # vectors in float32; a matrix is cast where it is multiplied
            p = {n: a[i] if a.ndim > 2 else _f32(a[i])
                 for n, a in params[ffn].items() if n not in big}
            h = _attention(x, p, cfg, window)
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
