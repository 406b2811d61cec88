"""Plain reference of the Solar-Open2 decoder (HF ``solar_open2``;
Solar-Open2-250B): float32 ``jax.numpy`` at "highest" matmul precision; no
kernel, no cache, no chunked form, no sorting. It reads the configuration's
dict under its published keys and the program's parameter tree (``embed``;
``gqa.*`` and ``kda.*`` stacked on a leading axis over the layers of that
kind, in stack order; ``final_norm``; ``lm_head``) and shares no code with
``deepspeed_tpu``.

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g;   y = rms(x; g1)
    KDA layer (every layer not in gqa_layers), H heads of K keys, K values:
        [q' | k' | v'] = conv(y Wqkv)   depthwise, causal, `taps` taps: out_t =
                         sum_j w_j in_{t - taps + 1 + j}, zeros before 0
        q, k, v = silu(q'), silu(k'), silu(v')
        q_h <- q_h / sqrt(|q_h|^2 + 1e-6) * K ** -0.5;  k_h likewise, unscaled
        g = -exp(A_log[h]) softplus((y F_a) F_b + dt_bias)    per channel
        beta = 2 sigmoid(y W_beta)
        TOKEN BY TOKEN, S_h [K, V] = 0 before position 0:
            S~ = diag(exp(g_t)) S;  S = S~ + beta_t k_t (v_t - S~^T k_t)^T
            o_t = S^T q_t
        o_h <- rms(o_h; g_o) * sigmoid((y G_a) G_b)_h;   h = x + o Wo
    GQA layer: q = y Wq [Hq, Dh], k = y Wk, v = y Wv [Hkv, Dh]; no rotation,
        no q/k norm; softmax_j(q_i . k_j / sqrt(Dh)), j <= i, head i reads
        key-value head i // (Hq / Hkv);  o <- o * sigmoid(y W_g);  h = x + o Wo
    FFN, every layer: z = rms(h; g2);  out = h + Shared(z) + s * sum over e in
        top_k(sigma + b) of w_e Expert_e(z),  sigma = sigmoid(z Wr),
        w = sigma_chosen / (sum of the chosen + 1e-20)

**The share.** The configuration holds ``n_routed_experts`` experts of
``n_routed_experts_published`` (the router's width), those from
``experts_held_first`` on. The router, the choice and the normalisation run
over all of them; the sum runs over the chosen experts that are held; the
rest is left out, here as in the program.

**In blocks, and nothing else clever**: so that 16,384 positions fit beside
the served model, the delta-rule layer runs ``HEAD_BLOCK`` heads at a time
(their columns of the projections, their convolutions, their recurrence as
one ``lax.scan`` over the positions), the softmax layer one key-value head and
``QUERY_BLOCK`` queries at a time, EVERY held expert is applied to EVERY token
one expert at a time, and a large matrix is cast to float32 a slice of its
columns at a time where it is multiplied.

Departures from the published code: none known; the builder could not read it
(no network). What the catalog's keys do not settle is listed in the
configuration file's ``assumed``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HEAD_BLOCK = 32
QUERY_BLOCK = 1024


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


def _columns(w, start, width):
    return _f32(jax.lax.dynamic_slice_in_dim(w, start, width, w.ndim - 1))


def _delta_rule(q, k, v, decay, beta):
    """The recurrence, one position a step. ``q, k, decay [B, T, H, K]``, ``v
    [B, T, H, V]``, ``beta [B, T, H]`` -> ``o [B, T, H, V]``."""
    b, _, h, dk = k.shape

    def step(s, xs):
        q_t, k_t, v_t, a_t, beta_t = xs
        s = a_t[..., None] * s                               # [B, H, K, V]
        pred = (s * k_t[..., None]).sum(-2)
        s = s + beta_t[..., None, None] * k_t[..., None] \
            * (v_t - pred)[..., None, :]
        return s, (s * q_t[..., None]).sum(-2)

    s0 = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, decay, beta)))
    return jnp.moveaxis(o, 0, 1)


def _kda(x, p, cfg):
    lin = cfg["linear_attn_config"]
    heads, dk, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    b, t, _ = x.shape
    eps = cfg["rms_norm_eps"]
    width = heads * dk
    hb = min(HEAD_BLOCK, heads)
    y = _rms(x, p["attn_norm"], eps)
    low_f, low_g = y @ _f32(p["f_a"]), y @ _f32(p["g_a"])
    beta = 2.0 * jax.nn.sigmoid(y @ _f32(p["w_beta"]))       # [B, T, H]

    def unit(a):
        return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    def block(i):
        at, n = i * hb * dk, hb * dk
        acts = []
        for part in range(3):                                # q, k, v
            raw = y @ _columns(p["w_qkv"], part * width + at, n)
            w = _columns(p["conv_w"], part * width + at, n)  # [taps, n]
            padded = jnp.pad(raw, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = sum(padded[:, j:j + t] * w[j] for j in range(taps))
            acts.append(jax.nn.silu(conv).reshape(b, t, hb, dk))
        q, k, v = acts
        q, k = unit(q) * dk ** -0.5, unit(k)
        a_log = jax.lax.dynamic_slice_in_dim(p["A_log"], i * hb, hb)
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            low_f @ _columns(p["f_b"], at, n)
            + jax.lax.dynamic_slice_in_dim(p["dt_bias"], at, n)
        ).reshape(b, t, hb, dk)
        o = _delta_rule(q, k, v, jnp.exp(g),
                        jax.lax.dynamic_slice_in_dim(beta, i * hb, hb, 2))
        gate = jax.nn.sigmoid(low_g @ _columns(p["g_b"], at, n))
        return (_rms(o, p["o_norm"], eps)
                * gate.reshape(b, t, hb, dk)).reshape(b, t, n)

    o = jax.lax.map(block, jnp.arange(heads // hb))          # [n, B, T, hb K]
    o = jnp.moveaxis(o, 0, 2).reshape(b, t, width)
    return x + _mm(o, p["wo"])


def _gqa(x, p, cfg):
    b, t, _ = x.shape
    n_head, n_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    rep = n_head // n_kv
    qb = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    y = _rms(x, p["attn_norm"], eps)
    q = _mm(y, p["wq"]).reshape(b, t, n_kv, rep, dh)
    k = _mm(y, p["wk"]).reshape(b, t, n_kv, dh)
    v = _mm(y, p["wv"]).reshape(b, t, n_kv, dh)

    def group(qkv):     # the rep query heads that read one key-value head
        q, k, v = qkv                       # [b, t, rep, dh], [b, t, dh]

        def queries(i):
            qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 1)
            scores = jnp.einsum("bqrd,bkd->brqk", qi, k) \
                / jnp.sqrt(jnp.float32(dh))
            seen = jnp.arange(t)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return jnp.einsum("brqk,bkd->bqrd", probs, v)

        out = jax.lax.map(queries, jnp.arange(t // qb))      # [n, b, qb, ..]
        return jnp.moveaxis(out, 0, 1).reshape(b, t, rep, dh)

    out = jax.lax.map(group, (jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0),
                              jnp.moveaxis(v, 2, 0)))        # [kv, b, t, ..]
    out = jnp.moveaxis(out, 0, 2).reshape(b, t, n_head * dh)
    if cfg["use_gqa_gate"]:
        out = out * jax.nn.sigmoid(_mm(y, p["w_gate"]))
    return x + _mm(out, p["wo"])


def _gated(z, gate, up, down):
    return _mm(jax.nn.silu(_mm(z, gate)) * _mm(z, up), down)


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
        return acc + dense_w[..., first + e, None] * _gated(z, gate, up,
                                                            down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(z), jnp.arange(held))
    return (_gated(z, p["shared_gate"], p["shared_up"], p["shared_down"])
            + cfg["routed_scaling_factor"] * routed)


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    if cfg["use_rope"] or cfg["tie_word_embeddings"] \
            or cfg["first_k_dense_replace"] or cfg["n_shared_experts"] != 1:
        raise ValueError("this reference has no rotation, an untied head, no "
                         "dense layer and one shared expert")
    eps = cfg["rms_norm_eps"]
    big = ("expert_gate", "expert_up", "expert_down")
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][input_ids])
        at = {"gqa": 0, "kda": 0}
        # one layer at a time, in stack order: a layer's weights are cast
        # where they are used, its experts one at a time
        for layer in range(cfg["num_hidden_layers"]):
            kind = "gqa" if layer in cfg["gqa_layers"] else "kda"
            i = at[kind]
            at[kind] += 1
            # vectors in float32; a matrix is cast where it is multiplied
            p = {n: a[i] if a.ndim > 2 else _f32(a[i])
                 for n, a in params[kind].items() if n not in big}
            h = (_gqa if kind == "gqa" else _kda)(x, p, cfg)
            x = h + _sparse_ffn(_rms(h, p["mlp_norm"], eps), p,
                                tuple(params[kind][n] for n in big), i, cfg)
        x = _rms(x, _f32(params["final_norm"]), eps)
        return _mm(x, params["lm_head"])


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
