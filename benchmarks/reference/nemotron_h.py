"""Plain reference of the Nemotron-H hybrid decoder (HF ``nemotron_h``;
Nemotron 3 Super 120B-A12B): float32 ``jax.numpy`` at "highest" matmul
precision; no kernel, no cache, no chunking. It reads the configuration's dict
under its published keys and the program's parameter tree (``embed``;
``mamba.*``, ``attn.*`` and ``moe.*`` stacked on a leading axis over the
layers of that kind, in stack order; ``final_norm``; ``lm_head``) and shares
no code with ``deepspeed_tpu``.

Every layer is ONE sublayer, ``x <- x + Mixer(N(x; w))`` with ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``, of the kind ``hybrid_override_pattern`` names:

``M``, Mamba-2, as the **sequential recurrence**, a ``lax.scan`` over
positions, so the program's chunked form and its folded step are held to
another algorithm. ``n_groups`` groups: head ``h`` reads ``B`` and ``C`` of
group ``h // (H / G)``, and the gated norm runs over each group's ``d_in / G``
channels apart:

    [z | xBC | dt] = u W_in;  xBC = silu(causal depthwise conv(xBC) + b)
    [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t;  y_t = S_t C_t + D x_t
    y = y * silu(z);  y_g = y_g / sqrt(mean(y_g^2) + eps) * w_g;  out = y W_out

``*``, grouped-query attention, one masked softmax of ``head_dim ** -0.5 q .
k``, no rotation and no position term.

``E``, LatentMoE: ``s = sigmoid(u W_r)`` over ALL the router's outputs, the
``k`` largest of ``s + b`` chosen, ``w_e = scale * s_e / (sum of the chosen s
+ 1e-20)``; the latent ``l = u W_dn``; every HELD expert ``relu(l W1_e)^2
W2_e`` on every token under the mask of the choice (expert ``e`` here is the
router's output ``experts_held_first + e``); the routed sum through ``W_up``;
beside it the shared expert ``relu(u V1)^2 V2`` on the stream.

A matrix is cast to float32 where it is multiplied, an expert at a time, the
head a slice of the vocabulary at a time, and attention runs a query head at
a time: a float32 copy of the model, of the head or of all heads' scores at
4,096 positions does not fit beside the served model and its slots.

Departures from the published code (which the builder could not read: the
configuration's ``assumed.published_code``): ``dt`` is not clamped
(``time_step_limit`` (0, inf)); the program keeps the convolution's weight as
``[K, C]`` where a checkpoint has ``[C, 1, K]``, the loader's business.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
EXPERTS = ("expert_up", "expert_down")


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _mm(x, w, limit=1 << 26):
    """``x @ w`` with ``w`` cast to float32 a slice of its columns at a time
    where the whole would pass ``limit`` elements."""
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _mamba(u, p, cfg):
    b, t, _ = u.shape
    h, ph, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, k, eps = cfg["n_groups"], cfg["conv_kernel"], cfg["layer_norm_epsilon"]
    d_in = h * ph
    proj = _mm(u, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))   # zeros before t=0
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_w"][j]
                          for j in range(k)) + p["conv_b"])
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    # head i reads group i // (h // g): heads as [g, h // g]
    xs = xs.reshape(b, t, g, h // g, ph)
    bm, cm = bm.reshape(b, t, g, n), cm.reshape(b, t, g, n)
    dt = jax.nn.softplus(dt + p["dt_bias"]).reshape(b, t, g, h // g)
    a = -jnp.exp(p["A_log"]).reshape(g, h // g)
    d = p["D"].reshape(g, h // g, 1)

    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        s = (jnp.exp(dt_t * a)[..., None, None] * s
             + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return s, (s * c_t[:, :, None, None, :]).sum(-1) + d * x_t

    _, y = jax.lax.scan(step, jnp.zeros((b, g, h // g, ph, n), jnp.float32),
                        tuple(v.swapaxes(0, 1) for v in (xs, bm, cm, dt)))
    y = y.swapaxes(0, 1).reshape(b, t, d_in) * jax.nn.silu(z)
    # the gated norm a group: d_in / g channels each
    y = _rms(y.reshape(b, t, g, d_in // g), 1.0, eps).reshape(b, t, d_in) \
        * p["gate_norm"]
    return _mm(y, p["out_proj"])


def _attention(u, p, cfg):
    b, t, _ = u.shape
    n_head, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = cfg["head_dim"]
    q = _mm(u, p["wq"]).reshape(b, t, n_head, dh)
    k = jnp.repeat(_mm(u, p["wk"]).reshape(b, t, n_kv, dh), n_head // n_kv, 2)
    v = jnp.repeat(_mm(u, p["wv"]).reshape(b, t, n_kv, dh), n_head // n_kv, 2)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def head(qkv):                              # [b, t, dh] each
        q, k, v = qkv
        scores = q @ k.swapaxes(-1, -2) / jnp.sqrt(jnp.float32(dh))
        return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1) @ v

    out = jax.lax.map(head, tuple(a.transpose(2, 0, 1, 3) for a in (q, k, v)))
    return _mm(out.transpose(1, 2, 0, 3).reshape(b, t, -1), p["wo"])


def _latent_moe(u, p, experts, layer, cfg):
    """``p``: the layer's leaves; ``experts``: the two expert stacks
    ``[layers, held, ...]`` as stored, read at ``[layer, e]`` one expert at a
    time."""
    k, held = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    first = cfg.get("experts_held_first", 0)
    sigma = jax.nn.sigmoid(_mm(u, p["router"]))         # all the router's
    _, chosen = jax.lax.top_k(sigma + p["select_bias"], k)
    w = jnp.take_along_axis(sigma, chosen, -1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    # [.., E] weight of each expert for each token, zero where not chosen
    dense_w = (jax.nn.one_hot(chosen, sigma.shape[-1]) * w[..., None]).sum(-2)
    latent = _mm(u, p["latent_down"])

    def one(acc, e):
        up, down = (a[layer, e] for a in experts)
        return acc + dense_w[..., first + e, None] * _mm(
            _relu2(_mm(latent, up)), down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent), jnp.arange(held))
    shared = _mm(_relu2(_mm(u, p["shared_up"])), p["shared_down"])
    return shared + _mm(cfg["routed_scaling_factor"] * routed, p["latent_up"])


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys; ``n_routed_experts``
    counts the experts held here, from ``experts_held_first`` on, of the
    router's ``n_routed_experts_published``."""
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1 \
            or cfg["tie_word_embeddings"] or cfg["mlp_hidden_act"] != "relu2" \
            or cfg["mamba_hidden_act"] != "silu" \
            or cfg.get("num_nextn_predict_layers", 0):
        raise ValueError("this reference has no group limit, an untied head, "
                         "relu2 experts, a silu convolution and no "
                         "multi-token-prediction layer")
    eps = cfg["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        at = dict.fromkeys(KINDS.values(), 0)
        # one layer at a time, in stack order: a layer's weights are cast
        # where they are used, its experts one at a time
        for letter in cfg["hybrid_override_pattern"]:
            kind = KINDS[letter]
            i = at[kind]
            at[kind] += 1
            # vectors in float32; a matrix is cast where it is multiplied
            p = {n: a[i] if a.ndim > 2 and n != "conv_w" else _f32(a[i])
                 for n, a in params[kind].items() if n not in EXPERTS}
            u = _rms(x, p["norm"], eps)
            if kind == "mamba":
                x = x + _mamba(u, p, cfg)
            elif kind == "attn":
                x = x + _attention(u, p, cfg)
            else:
                x = x + _latent_moe(
                    u, p, tuple(params[kind][n] for n in EXPERTS), i, cfg)
        x = _rms(x, _f32(params["final_norm"]), eps)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
