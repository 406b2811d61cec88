"""Plain reference of GPT-2 (Radford et al. 2019; the published
``GPT2LMHeadModel``): float32 ``jax.numpy``, full softmax attention, no
kernel, no cache, no batching tricks. It reads the program's parameter tree
(``wte``, ``wpe``, ``blocks.*`` stacked on a leading layer axis, ``ln_f_*``)
and shares no code with ``deepspeed_tpu``.

Departure from the published model: none in the mathematics. Dropout is
absent because the cells run without it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def forward_logits(params, input_ids, cfg):
    """``[B, T]`` token ids to ``[B, T, V]`` float32 logits. ``cfg`` is the
    configuration file's dict under its published keys."""
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    with jax.default_matmul_precision("highest"):
        f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
        b, t = input_ids.shape
        wte = f32(params["wte"])
        x = wte[input_ids] + f32(params["wpe"])[:t][None]
        d = x.shape[-1]
        dh = d // n_head
        mask = jnp.tril(jnp.ones((t, t), bool))

        def block(x, p):
            p = jax.tree_util.tree_map(f32, p)
            y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
            qkv = y @ p["qkv_w"] + p["qkv_b"]
            q, k, v = (a.reshape(b, t, n_head, dh).transpose(0, 2, 1, 3)
                       for a in jnp.split(qkv, 3, axis=-1))
            scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(float(dh))
            scores = jnp.where(mask, scores, -jnp.inf)
            attn = jax.nn.softmax(scores, axis=-1) @ v
            attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
            x = x + attn @ p["attn_out_w"] + p["attn_out_b"]
            y = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps)
            h = _gelu_new(y @ p["mlp_fc_w"] + p["mlp_fc_b"])
            return x + h @ p["mlp_out_w"] + p["mlp_out_b"], None

        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = _layer_norm(x, f32(params["ln_f_scale"]), f32(params["ln_f_bias"]),
                        eps)
        head = wte.T if "lm_head" not in params else f32(params["lm_head"])
        return x @ head


def loss(params, input_ids, labels, cfg):
    """Mean next-token cross entropy of ``labels`` under the logits."""
    logits = forward_logits(params, input_ids, cfg)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -picked.mean()
