"""Plain reference of the LLaMA decoder (Touvron et al. 2023): float32
``jax.numpy``, RMSNorm, rotary positions, full softmax attention with
grouped-query heads, SwiGLU, an untied head; no kernel, no cache, no batching
tricks. It reads the program's parameter tree (``embed``, ``blocks.*``
stacked on a leading layer axis, ``final_norm``, ``lm_head``) and shares no
code with ``deepspeed_tpu``.

Rotary positions turn the pairs ``(x[2i], x[2i+1])`` of a head, the paper's
complex form and the layout the program's weights are in. The published
checkpoints of this kind of ``config.json`` store ``q_proj`` and ``k_proj``
permuted for the half-split form; loading one means undoing that permutation,
which is the loader's business and changes no logit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate(x, theta):
    """``[B, H, T, Dh]``: pair i of position t turns by t * theta**(-2i/Dh)."""
    t, dh = x.shape[-2], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    n_head = cfg["num_attention_heads"]
    n_kv = cfg.get("num_key_value_heads") or n_head
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        b, t = input_ids.shape
        embed = f32(params["embed"])
        x = embed[input_ids]
        mask = jnp.tril(jnp.ones((t, t), bool))

        def heads(a, n):
            return a.reshape(b, t, n, -1).transpose(0, 2, 1, 3)

        def block(x, p):
            p = jax.tree_util.tree_map(f32, p)
            y = _rms_norm(x, p["attn_norm"], eps)
            q = _rotate(heads(y @ p["wq"], n_head), theta)
            k = _rotate(heads(y @ p["wk"], n_kv), theta)
            v = heads(y @ p["wv"], n_kv)
            # query head h reads key-value head h // (n_head // n_kv)
            k, v = (jnp.repeat(a, n_head // n_kv, axis=1) for a in (k, v))
            scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(q.shape[-1]))
            scores = jnp.where(mask, scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            x = x + attn.transpose(0, 2, 1, 3).reshape(b, t, -1) @ p["wo"]
            y = _rms_norm(x, p["mlp_norm"], eps)
            h = jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])
            return x + h @ p["w_down"], None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = _rms_norm(x, f32(params["final_norm"]), eps)
        head = embed.T if "lm_head" not in params else f32(params["lm_head"])
        return x @ head


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
