"""Plain reference of the EvaByte decoder (HF ``evabyte``, ``attention_class``
``eva``): float32 ``jax.numpy`` at "highest" matmul precision, the equations
position by position over a whole sequence; no cache, no kernel, no blocks of
a prompt. It reads the configuration's dict under its published keys and the
program's parameter tree (``embed``; ``blocks`` stacked on a leading layer
axis: ``attn_norm``, ``wq``, ``wk``, ``wv``, ``wo``, ``phi``, ``mu``,
``mlp_norm``, ``w_gate``, ``w_up``, ``w_down``; ``final_norm``; ``lm_head``)
and shares no code with ``deepspeed_tpu``.

    N(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)
    block:  h = x + Attn(N(x; w1));  y = h + (silu(u Wg) * (u Wu)) Wd,
            u = N(h; w2);  a final N before the head
    q_t, k_t, v_t: head h is the h-th ``d`` columns of u_t Wq, u_t Wk, u_t Wv,
            d = hidden_size / num_attention_heads; q_t and k_t rotated at
            position t (rotate-half over the whole head, rope_theta)
    chunk j = positions c j .. c j + c - 1 (c = chunk_size), s = d ** -0.5:
            a_jm = softmax over m in chunk j of (s phi_h . k_m)
            ksum_j = sum_m a_jm k_m + mu_h;   vsum_j = sum_m a_jm v_m
    query t, w = t // W (W = window_size): an EXACT mask [T, T], m in
            [W w, t], and a SUMMARY mask [T, T / c], j < w W / c; one softmax
            over the concatenation [s q_t . k_m | s q_t . ksum_j];
            o_t = sum_m p_m v_m + sum_j p_j vsum_j;  Attn(u)_t = concat_h(o_t) Wo
    head i of num_pred_heads: columns V i .. V i + V - 1 of lm_head

Departures from the published description (the configuration's ``assumed``
has each with its alternative): the pooling's logits carry the softmax scale;
keys are rotated at their own positions before pooling and a summary has no
position of its own; ``mu`` is added to the pooled key and nothing to the
pooled value; the eight heads lie side by side in the head's columns.

What is large is walked: the two masks are made and the softmax taken for a
window of queries at a time and a head at a time (``jax.lax.map``); every
row of the masks is an explicit row computed from the positions, the exact
mask's over the columns of the query's own window alone (all others are
False by the mask's own lower bound: at 32,768 positions they would be
fifteen sixteenths of the scores, 0.8 PFLOP a request at "highest"), the
summary mask's over every chunk; a matrix is cast to float32 where it lies in
its stack a layer at a time. 32,768 positions fit beside the served model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _f32(a):
    return jnp.asarray(a, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f32(w), precision=HIGHEST)


def _norm(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * (1.0 + _f32(w))


def _rotate(x, theta):
    """Rotate-half at positions 0 .. T - 1: ``x [T, H, d]``."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _by_window(fn, x, window):
    """``fn`` on ``x [T, ...]`` a window of rows at a time (``T`` a multiple
    of ``window`` or less than one)."""
    t = x.shape[0]
    if t <= window:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(t // window, window, *x.shape[1:]))
    return out.reshape(t, *out.shape[2:])


def _attention(u, blk, at, cfg):
    """EVA attention on ``u [T, D]`` with layer ``at`` of the stack."""
    heads = cfg["num_attention_heads"]
    t, width = u.shape
    d = width // heads
    w, c = cfg["window_size"], cfg["chunk_size"]
    s = d ** -0.5
    q, k, v = (_mm(u, blk[n][at]).reshape(t, heads, d)
               for n in ("wq", "wk", "wv"))
    q, k = _rotate(q, cfg["rope_theta"]), _rotate(k, cfg["rope_theta"])
    phi, mu = _f32(blk["phi"][at]), _f32(blk["mu"][at])        # [H, d]
    # every chunk's summary (T padded to whole chunks; a chunk that is not
    # whole lies in the last window and is visible to no query)
    n = -(-t // c)
    pad = [(0, n * c - t), (0, 0), (0, 0)]
    kc = jnp.pad(k, pad).reshape(n, c, heads, d)
    vc = jnp.pad(v, pad).reshape(n, c, heads, d)
    a = jax.nn.softmax(
        s * jnp.einsum("nchd,hd->nch", kc, phi, precision=HIGHEST), axis=1)
    ksum = jnp.einsum("nch,nchd->nhd", a, kc, precision=HIGHEST) + mu[None]
    vsum = jnp.einsum("nch,nchd->nhd", a, vc, precision=HIGHEST)

    def window(args):
        """The queries ``qs [R, H, d]`` of ONE window at positions ``ts [R]``
        against the keys ``kw, vw [R, H, d]`` of that window (the columns of
        the exact mask outside a query's own window are False by ``m >= W w``
        and are not computed) and against every summary, a head at a time."""
        qs, kw, vw, ts = args
        exact = (ts[None, :] <= ts[:, None]) \
            & (ts[None, :] >= (ts[:, None] // w) * w)              # [R, R]
        summary = jnp.arange(n)[None, :] < (ts[:, None] // w) * (w // c)
        seen = jnp.concatenate([exact, summary], -1)               # [R, R+n]

        def head(h):
            qh, kh, vh, ksh, vsh = h
            logits = s * jnp.concatenate(
                [jnp.matmul(qh, kh.T, precision=HIGHEST),
                 jnp.matmul(qh, ksh.T, precision=HIGHEST)], -1)
            p = jax.nn.softmax(jnp.where(seen, logits, -jnp.inf), axis=-1)
            r = qh.shape[0]
            return (jnp.matmul(p[:, :r], vh, precision=HIGHEST)
                    + jnp.matmul(p[:, r:], vsh, precision=HIGHEST))

        out = jax.lax.map(head, (
            qs.transpose(1, 0, 2), kw.transpose(1, 0, 2),
            vw.transpose(1, 0, 2), ksum.transpose(1, 0, 2),
            vsum.transpose(1, 0, 2)))                              # [H, R, d]
        return out.transpose(1, 0, 2)

    if t <= w:
        o = window((q, k, v, jnp.arange(t)))
    else:
        o = jax.lax.map(window, tuple(
            a.reshape(t // w, w, *a.shape[1:])
            for a in (q, k, v, jnp.arange(t)))).reshape(t, heads, d)
    return _mm(o.reshape(t, width), blk["wo"][at])


def _hidden(params, ids, cfg):
    """One sequence ``ids [T]`` through the stack -> the final norm's
    output ``[T, D]``."""
    eps, w = cfg["rms_norm_eps"], cfg["window_size"]
    blk = params["blocks"]
    t = ids.shape[0]
    if t > w and t % w:     # whole windows: no position sees one behind it
        return _hidden(params, jnp.pad(ids, (0, -t % w)), cfg)[:t]
    x = _f32(params["embed"])[ids]
    for at in range(blk["wq"].shape[0]):
        x = x + _attention(_norm(x, blk["attn_norm"][at], eps), blk, at, cfg)

        def mlp(h, at=at):
            u = _norm(h, blk["mlp_norm"][at], eps)
            return _mm(jax.nn.silu(_mm(u, blk["w_gate"][at]))
                       * _mm(u, blk["w_up"][at]), blk["w_down"][at])

        x = x + _by_window(mlp, x, w)
    return _norm(x, params["final_norm"], eps)


def forward_all_heads(params, input_ids, cfg):
    """``[B, T, num_pred_heads, V]``: head ``i`` predicts the byte at ``t +
    1 + i``."""
    heads, vocab = cfg["num_pred_heads"], cfg["vocab_size"]
    out = []
    for row in input_ids:
        h = _hidden(params, row, cfg)
        out.append(_mm(h, params["lm_head"]).reshape(-1, heads, vocab))
    return jnp.stack(out)


def forward_logits(params, input_ids, cfg):
    """``[B, T, V]``: head 0, the next byte, the head that is served."""
    vocab = cfg["vocab_size"]
    return jnp.stack([
        _mm(_hidden(params, row, cfg), params["lm_head"][:, :vocab])
        for row in input_ids])


def loss(params, input_ids, labels, cfg):
    """Mean next-byte cross-entropy of head 0 over labels != -100."""
    logits = forward_logits(params, input_ids, cfg)
    valid = labels != -100
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], -1)[..., 0]
    return -(picked * valid).sum() / jnp.maximum(valid.sum(), 1)
