"""Ring attention + Ulysses sequence parallelism — the long-context core.

The reference snapshot has NO sequence parallelism (SURVEY §5.7): its
long-sequence story is Triton block-sparse attention
(``deepspeed/ops/sparse_attention/``) and curriculum seqlen. The TPU-native
long-context mechanisms are:

  * **Ring attention** (`ring_attention`): q/k/v sharded on the sequence dim
    over the 'seq' mesh axis; K/V blocks rotate around the ICI ring with
    ``ppermute`` while each device accumulates its queries' attention with an
    online (flash-style) softmax. Peak memory per device is O(T/S · T/S) per
    step instead of O(T²); compute overlaps the ring hop. Differentiable
    (the scan + ppermute transpose replays the reverse ring).
  * **Ring + Pallas flash** (`ring_flash_attention`, model
    ``attn_impl="ring_flash"``): same ring, but each hop runs the Pallas
    flash kernel (O(block) VMEM even within a hop) and the backward pass is
    an explicit custom-vjp reverse ring — per-hop ``flash_bwd_parts`` with
    the GLOBAL log-sum-exp (per-hop grads sum exactly), dk/dv accumulators
    riding the ring back to their owners. This is the multi-chip >32k
    long-context path.
  * **Ulysses-style all-to-all** (`ulysses_attention`): the later
    DeepSpeed-Ulysses design — all_to_all swaps the sequence sharding for a
    *head* sharding, runs full-sequence attention for 1/S of the heads
    (Pallas flash kernel by default — O(block) memory over the full T;
    ``inner="dense"`` for the jnp reference), and all_to_alls back.

Both are drop-in replacements for ``multihead_attention`` when the inputs'
sequence dim is sharded over 'seq'.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import SEQ_AXIS

# true -inf (not finfo.min): fully-masked blocks must zero out in the online
# softmax; the isfinite() guards below depend on it
_NEG_INF = -jnp.inf


def ring_attention(
    q: jax.Array,  # [B, T, H, Dh] — T globally sharded over 'seq'
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis: str = SEQ_AXIS,
) -> jax.Array:
    """Blockwise ring attention over the sequence mesh axis."""
    sp = mesh.shape[axis]
    if sp == 1:
        from deepspeed_tpu.ops.attention import multihead_attention

        return multihead_attention(q, k, v, causal=causal, scale=scale)
    dh = q.shape[-1]
    sc = scale if scale is not None else dh ** -0.5

    def local(ql, kl, vl):
        # per-device: ql/kl/vl [B, T/S, H, Dh]
        b, t_loc, h, _ = ql.shape
        my = jax.lax.axis_index(axis)
        q_pos = my * t_loc + jnp.arange(t_loc)          # global query positions
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        def step(carry, t):
            kl, vl, m, l, o = carry
            # kl currently came from source device (my - t) mod S
            src = (my - t) % sp
            k_pos = src * t_loc + jnp.arange(t_loc)
            s = jnp.einsum("bthd,bshd->bhts", ql, kl).astype(jnp.float32) * sc
            if causal:
                mask = k_pos[None, :] <= q_pos[:, None]  # [Tq, Tk]
                s = jnp.where(mask[None, None], s, _NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (exp(-inf - -inf))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(jnp.isfinite(s), p, 0.0)
            corr = jnp.exp(m - m_new)
            corr = jnp.where(jnp.isfinite(m), corr, 0.0)
            l = l * corr + p.sum(axis=-1)
            o = o * corr[..., None] + jnp.einsum(
                "bhts,bshd->bthd", p.astype(vl.dtype), vl).astype(jnp.float32).transpose(0, 2, 1, 3)
            kl = jax.lax.ppermute(kl, axis, perm)
            vl = jax.lax.ppermute(vl, axis, perm)
            return (kl, vl, m_new, l, o), None

        # accumulators become varying over the seq axis after step 1 — mark
        # the initial values accordingly (shard_map VMA typing)
        vary = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        m0 = vary(jnp.full((b, h, t_loc), _NEG_INF, jnp.float32))
        l0 = vary(jnp.zeros((b, h, t_loc), jnp.float32))
        o0 = vary(jnp.zeros((b, h, t_loc, dh), jnp.float32))
        (_, _, m, l, o), _ = jax.lax.scan(
            step, (kl, vl, m0, l0, o0), jnp.arange(sp))
        out = o / jnp.maximum(l, 1e-20)[..., None]
        return out.transpose(0, 2, 1, 3).astype(ql.dtype)  # [B, T/S, H, Dh]

    spec = P(None, axis)
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names={axis})(q, k, v)


def _merge_parts(lse_a, o_a, lse_b, o_b):
    """Exact merge of two softmax partials given their log-sum-exps:
    o = w_a·o_a + w_b·o_b with w_x = exp(lse_x - logaddexp(lse_a, lse_b)).
    Contract: both partials come from flash_fwd_parts, whose lse is always
    finite (the kernel clamps l >= 1e-20) — fully-masked hops must be
    SKIPPED by the caller (the ring's `live` cond does), not merged."""
    lse_new = jnp.logaddexp(lse_a, lse_b)
    w_a = jnp.exp(lse_a - lse_new)
    w_b = jnp.exp(lse_b - lse_new)
    return lse_new, w_a * o_a.astype(jnp.float32) + w_b * o_b.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention(q, k, v, mesh, causal: bool = True,
                         axis: str = SEQ_AXIS,
                         scale: Optional[float] = None):
    """Ring attention with the Pallas flash kernel per hop.

    Same semantics/sharding contract as ``ring_attention`` ([B, T, H, Dh],
    T sharded over ``axis``), but each ring hop runs the O(block)-VMEM
    flash kernel instead of dense jnp blocks, and the backward pass is an
    explicit reverse ring: per-hop ``flash_bwd_parts`` with the GLOBAL lse
    (so per-hop grads sum exactly), dk/dv accumulators riding the ring back
    to their owners. Hop structure: hop 0 is the causal diagonal (static),
    later hops are all-visible or fully-masked (skipped) by ring position.
    """
    out, _ = _ring_flash_fwd(q, k, v, mesh, causal, axis, scale)
    return out


def _ring_flash_fwd(q, k, v, mesh, causal, axis, scale=None):
    from deepspeed_tpu.ops.flash_attention import flash_fwd_parts

    sp = mesh.shape[axis]
    b, h, dh = q.shape[0], q.shape[2], q.shape[3]

    def local(ql, kl, vl):
        # flat [B*H, T/S, Dh] layout for the kernels
        t_loc = ql.shape[1]
        flat = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], dh)
        qf = flat(ql)
        my = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        # hop 0: own block — causal diagonal (static flag)
        o0, lse0 = flash_fwd_parts(qf, flat(kl), flat(vl), causal=causal,
                                   scale=scale)
        lse_run = lse0.astype(jnp.float32)
        o_run = o0.astype(jnp.float32)
        kl = jax.lax.ppermute(kl, axis, perm)
        vl = jax.lax.ppermute(vl, axis, perm)

        def hop(carry, tstep):
            kl, vl, lse_run, o_run = carry
            src = (my - tstep) % sp
            live = (src < my) if causal else jnp.bool_(True)

            def attend(args):
                kl, vl, lse_run, o_run = args
                o_h, lse_h = flash_fwd_parts(qf, flat(kl), flat(vl),
                                             causal=False, scale=scale)
                lse_new, o_new = _merge_parts(lse_run, o_run,
                                              lse_h.astype(jnp.float32),
                                              o_h.astype(jnp.float32))
                return lse_new, o_new

            lse_run, o_run = jax.lax.cond(
                live, attend, lambda args: (args[2], args[3]),
                (kl, vl, lse_run, o_run))
            kl = jax.lax.ppermute(kl, axis, perm)
            vl = jax.lax.ppermute(vl, axis, perm)
            return (kl, vl, lse_run, o_run), None

        (_, _, lse_run, o_run), _ = jax.lax.scan(
            hop, (kl, vl, lse_run, o_run), jnp.arange(1, sp))
        out = o_run.reshape(b, h, t_loc, dh).transpose(0, 2, 1, 3)
        return out.astype(ql.dtype), lse_run

    spec = P(None, axis)
    check = jax.default_backend() == "tpu"
    out, lse = shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, P(None, axis, None)), axis_names={axis},
        check_vma=check)(q, k, v)
    # residuals tagged like flash_attention's, so the save_attn remat
    # policy keeps them and a rematted block never replays the ring
    # (sp kernel launches + 2*sp ppermutes per layer) in backward
    from jax.ad_checkpoint import checkpoint_name

    res = tuple(checkpoint_name(x, "flash_res") for x in (q, k, v, out, lse))
    return out, res


def _ring_flash_bwd(mesh, causal, axis, scale, res, g):
    from deepspeed_tpu.ops.flash_attention import flash_bwd_parts

    q, k, v, out, lse = res
    sp = mesh.shape[axis]
    b, h, dh = q.shape[0], q.shape[2], q.shape[3]

    # delta = rowsum(do * out): elementwise, computed on the sharded arrays
    delta_global = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                           axis=-1)                       # [B, T, H]

    def local2(ql, kl, vl, dol, lsel, deltal):
        t_loc = ql.shape[1]
        flat = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, x.shape[1], dh)
        unflat = lambda x: x.reshape(b, h, t_loc, dh).transpose(0, 2, 1, 3)
        qf, dof = flat(ql), flat(dol)
        deltaf = deltal.transpose(0, 2, 1).reshape(-1, t_loc)[..., None]
        my = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        # hop 0: own block, causal
        dq0, dk0, dv0 = flash_bwd_parts(qf, flat(kl), flat(vl), dof, lsel,
                                        deltaf, causal=causal, scale=scale)
        dq_acc = dq0.astype(jnp.float32)
        dk_acc = dk0.astype(jnp.float32)
        dv_acc = dv0.astype(jnp.float32)
        # k/v and THEIR grad accumulators ride the ring together
        kl = jax.lax.ppermute(kl, axis, perm)
        vl = jax.lax.ppermute(vl, axis, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis, perm)

        def hop(carry, tstep):
            kl, vl, dk_acc, dv_acc, dq_acc = carry
            src = (my - tstep) % sp
            live = (src < my) if causal else jnp.bool_(True)

            def grads(args):
                kl, vl, dk_acc, dv_acc, dq_acc = args
                dq_h, dk_h, dv_h = flash_bwd_parts(
                    qf, flat(kl), flat(vl), dof, lsel, deltaf, causal=False,
                    scale=scale)
                return (dk_acc + dk_h.astype(jnp.float32),
                        dv_acc + dv_h.astype(jnp.float32),
                        dq_acc + dq_h.astype(jnp.float32))

            dk_acc, dv_acc, dq_acc = jax.lax.cond(
                live, grads, lambda args: (args[2], args[3], args[4]),
                (kl, vl, dk_acc, dv_acc, dq_acc))
            kl = jax.lax.ppermute(kl, axis, perm)
            vl = jax.lax.ppermute(vl, axis, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis, perm)
            return (kl, vl, dk_acc, dv_acc, dq_acc), None

        (kl, vl, dk_acc, dv_acc, dq_acc), _ = jax.lax.scan(
            hop, (kl, vl, dk_acc, dv_acc, dq_acc), jnp.arange(1, sp))
        # after S hops the accumulators are back at their owners
        return (unflat(dq_acc).astype(ql.dtype),
                unflat(dk_acc).astype(kl.dtype),
                unflat(dv_acc).astype(vl.dtype))

    spec = P(None, axis)
    check = jax.default_backend() == "tpu"
    dq, dk, dv = shard_map(
        local2, mesh=mesh,
        in_specs=(spec, spec, spec, spec, P(None, axis, None),
                  P(None, axis, None)),
        out_specs=(spec, spec, spec), axis_names={axis},
        check_vma=check)(q, k, v, g, lse, delta_global)
    return dq, dk, dv


ring_flash_attention.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ulysses_attention(
    q: jax.Array,  # [B, T, H, Dh] — T sharded over 'seq'; H % sp == 0
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    causal: bool = True,
    scale: Optional[float] = None,
    axis: str = SEQ_AXIS,
    inner: str = "flash",
) -> jax.Array:
    """DeepSpeed-Ulysses-style attention: all_to_all head-scatter, full-
    sequence attention for H/S heads, all_to_all back. The inner attention
    defaults to the Pallas flash kernel (O(block) memory over the FULL
    sequence — measured 36x over dense at seq 8192 single-chip); pass
    ``inner="dense"`` for the jnp reference path."""
    if inner not in ("flash", "dense"):
        raise ValueError(f"ulysses inner must be 'flash' or 'dense', got {inner!r}")
    sp = mesh.shape[axis]

    def attend(qf, kf, vf):
        if inner == "flash":
            from deepspeed_tpu.ops.flash_attention import flash_attention

            return flash_attention(qf, kf, vf, causal, scale)
        from deepspeed_tpu.ops.attention import multihead_attention

        return multihead_attention(qf, kf, vf, causal=causal, scale=scale)

    if sp == 1:
        return attend(q, k, v)
    assert q.shape[2] % sp == 0, (
        f"ulysses needs heads ({q.shape[2]}) divisible by sp ({sp})")

    def local(ql, kl, vl):
        # [B, T/S, H, Dh] → all_to_all → [B, T, H/S, Dh]
        def scatter(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def gather(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qf, kf, vf = scatter(ql), scatter(kl), scatter(vl)
        return gather(attend(qf, kf, vf))

    spec = P(None, axis)
    # check_vma off only for flash-in-INTERPRET mode: the Pallas interpreter
    # can't type kernel-internal literals against 'seq'-varying refs (jax
    # suggests this exact workaround). Compiled TPU runs keep strict vma
    # checking — that's what flash_attention._sds's vma plumbing is for.
    from deepspeed_tpu.ops.flash_attention import _interpret_default

    strict = inner != "flash" or not _interpret_default()
    return shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, axis_names={axis},
                     check_vma=strict)(q, k, v)
