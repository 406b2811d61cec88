"""The Mamba-2 mixer of a decoder layer, as two families here run it
(``granite_hybrid``, ``nemotron_h``): everything from the layer's norm to its
``out_proj``, against no cache, a prompt block's cache or a decode step's.
What follows ``out_proj`` (a residual multiplier, an MLP) is the caller's.

    [z | xBC | dt] = N(x) W_in;  xBC <- silu(conv(xBC));  x, B, C = xBC
    dt <- softplus(dt + dt_bias);  S <- exp(dt A) S + (dt x) B^T;  y = S C + D x
    y <- GroupNorm(y * silu(z));  Mixer(x) = y W_out

The stack's leaves: ``norm``, ``in_proj``, ``conv_w``, ``conv_b``,
``dt_bias``, ``A_log``, ``D``, ``gate_norm``, ``out_proj``. A configuration
gives ``mamba_n_heads``, ``mamba_d_head``, ``mamba_d_state``,
``mamba_n_groups``, ``mamba_d_conv``, ``mamba_chunk_size``, ``d_inner``,
``conv_dim`` and ``eps``. ``norm_groups`` is the family's: the gated norm runs
over ``d_inner / norm_groups`` channels at a time (one norm over all of
``d_inner``, or one a group of heads).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import qdot, rms_norm
from deepspeed_tpu.ops import ssm


def decode_step(stack, c, valid, b: int, norm_groups: int = 1):
    """What the Mamba layers of one decode step (one token a slot, a cache)
    share, made once a step and not once a layer: which slots decode, their
    order for the kernel's grid (``ops/ssm.slot_order``) and, where the
    kernel route is taken and the shapes fold (``ops/ssm.step_folds``; the
    folded call norms a group of heads at a time, so the family's norm has
    to run that way), the stack's small weights as the folded call reads
    them; ``weights`` ``None`` says the layers run split."""
    active = jnp.ones((b,), bool) if valid is None else valid > 0
    folds = (ssm.default_route() == "pallas"
             and norm_groups == c.mamba_n_groups
             and ssm.step_folds(c.mamba_n_heads, c.mamba_d_head,
                                c.mamba_d_state, c.mamba_n_groups))
    return {"active": active, "walk": ssm.slot_order(active),
            "weights": ssm.fold_weights(stack, c.mamba_d_head)
            if folds else None}


def gated_norm(y, z, w, eps: float, groups: int = 1):
    """``N(y * silu(z))`` in float32, the norm over ``d_inner / groups``
    channels at a time, times ``w [d_inner]``."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    if groups == 1:
        return rms_norm(y, w, eps)
    grouped = y.reshape(y.shape[:-1] + (groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * w.astype(jnp.float32)


def mixer(x, blk, c, state=None, layer=None, idx=None, valid=None, step=None,
          norm_groups: int = 1, apart: bool = False):
    """-> ``(Mixer(x) [B, T, d], state)``. ``state``: ``None`` (no cache:
    zeros in, nothing out) or ``(ssm_full [Lm,B,H,P,N], conv_full
    [Lm,B,...])`` at ``layer``, ``idx`` and the rows' ``valid`` lengths. One
    token (``T == 1``) with a cache runs the recurrence in place on the
    stacked state, with ``step`` (:func:`decode_step`) what the step's Mamba
    layers share; where the shapes fold, everything between the two matmuls
    is one kernel (``ops/ssm.mamba_step``). A block runs the chunked form
    from the layer's state and writes the state at the true length back.

    ``apart``: hold ``in_proj``'s product apart from what reads it
    (``models/base.project_heads`` does the same for a reason of its kind).
    A family whose every run is ONE layer asks for it: its loops unroll, the
    layer's slice of the stack is static, and fused with the folded call's
    reshape to rows of lanes the matmul wanted the layer's ``in_proj``
    transposed, a copy of 152 MB a layer a step at Nemotron's sizes (the step
    compiled for the described v5e, PR 65). Runs of several layers index the
    stack inside a loop and were never copied, so the hybrid does not ask."""
    b, t, _ = x.shape
    h, p, n, g = (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
                  c.mamba_n_groups)
    d_in = c.d_inner
    u = rms_norm(x, blk["norm"], c.eps)
    zxbcdt = qdot("btd,de->bte", u, blk["in_proj"])
    if apart:
        zxbcdt = jax.lax.optimization_barrier(zxbcdt)
    folded = step is not None and step["weights"] is not None
    if step is not None:
        ssm.count_step(folded, g)
    if folded:
        y, *state = ssm.mamba_step(
            zxbcdt[:, 0], *state, layer, step["weights"], step["walk"],
            step["active"], eps=c.eps)
        return qdot("bte,ed->btd", y[:, None], blk["out_proj"]), tuple(state)
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + c.conv_dim], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + blk["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(blk["A_log"].astype(jnp.float32))
    s0 = None
    if state is None:
        conv0 = jnp.zeros((b, c.mamba_d_conv - 1, c.conv_dim), x.dtype)
    else:
        ssm_full, conv_full = state
        conv0 = ssm.rows_to_tail(
            jax.lax.dynamic_index_in_dim(conv_full, layer, 0, False),
            c.mamba_d_conv, d_in, 2 * g * n)
        if t > 1:
            # a row at position 0 has no history, whatever its slot held
            s0 = jax.lax.dynamic_index_in_dim(ssm_full, layer, 0, False)
            fresh = jnp.reshape(idx == 0, (-1, 1, 1))
            conv0 = jnp.where(fresh, 0, conv0)
            s0 = jnp.where(fresh[..., None], 0, s0)
    xbc, conv1 = ssm.causal_conv(xbc, conv0, blk["conv_w"], blk["conv_b"],
                                 valid)
    xs, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    if step is not None:
        y, ssm_full = ssm.ssm_update(
            ssm_full, layer, xs.reshape(b, h, p), dt[:, 0], a,
            bm.reshape(b, g, n), cm.reshape(b, g, n), blk["D"],
            step["active"], walk=step["walk"])
        y = y.astype(x.dtype)[:, None]
    else:
        y, s1 = ssm.ssd_prefill(
            xs.reshape(b, t, h, p), dt, a, bm.reshape(b, t, g, n),
            cm.reshape(b, t, g, n), blk["D"], chunk=c.mamba_chunk_size,
            init_state=s0, length=valid)
        if state is not None:
            ssm_full = jax.lax.dynamic_update_index_in_dim(
                ssm_full, s1.astype(ssm_full.dtype), layer, 0)
    if state is not None:
        conv_full = jax.lax.dynamic_update_index_in_dim(
            conv_full, ssm.tail_to_rows(conv1, d_in), layer, 0)
    y = gated_norm(y.reshape(b, t, d_in), z, blk["gate_norm"], c.eps,
                   norm_groups).astype(x.dtype)
    return qdot("bte,ed->btd", y, blk["out_proj"]), \
        (None if state is None else (ssm_full, conv_full))
