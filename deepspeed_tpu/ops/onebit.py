"""1-bit (sign) compressed collectives + error-compensated optimizers.

Reference analogs:
  * ``NcclBackend.compressed_allreduce`` (runtime/comm/nccl.py:54) — the
    error-compensated two-stage sign-compressed allreduce: worker compress →
    alltoall → per-chunk average + server compress → allgather.
  * ``OnebitAdam`` (runtime/fp16/onebit/adam.py:13), ``OnebitLamb``
    (onebit/lamb.py:14), ``ZeroOneAdam`` (onebit/zoadam.py:13) — fp32-exact
    warmup, then the *momentum* is communicated 1-bit-compressed while the
    variance stays frozen (Adam) / the per-layer scaling factor learned in
    warmup is applied frozen (LAMB).

TPU-native shape: the collective runs INSIDE jit under ``shard_map`` over
the data axis — signs travel as int8 over ICI (the reference packs bits via
cupy; on TPU int8 lanes + XLA collective fusion make explicit bit-packing a
pessimization), scales are fp32 scalars per chunk.  Error feedback tensors
are functional optimizer state (per-device distinct — shard them over the
data axis, never replicate).  The warmup↔compressed switch is a
``lax.cond`` so only ONE set of collectives executes per step: exact pmean
during warmup, compressed alltoall/allgather after (``jnp.where`` would pay
both).

Engine note: ``DeepSpeedEngine``'s compiled GSPMD path communicates
gradients exactly (XLA-scheduled), so the engine constructs these with
``with_compression=False`` — exact math, no error-state memory.  The true
1-bit path needs local (per-device, unreduced) grads: run the optimizer
under ``shard_map`` passing ``axis_name`` (see tests/unit/ops/test_onebit.py
for the canonical DP loop).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _ensure_varying(x: jax.Array, axis_name: str) -> jax.Array:
    """Align shard_map's varying-manual-axes type: no-op when already
    varying over ``axis_name``."""
    try:
        vma = jax.typeof(x).vma
    except Exception:
        return x
    if axis_name in vma:
        return x
    return jax.lax.pcast(x, axis_name, to="varying")


# ----------------------------------------------------------- core compression
def _sign_compress(c: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """c → (scale, signs∈{-1,+1} int8, error). scale preserves the l1 norm
    (reference: scale = |c|.mean(), signs = c.sign())."""
    scale = jnp.mean(jnp.abs(c))
    signs = jnp.where(c >= 0, jnp.int8(1), jnp.int8(-1))
    error = c - scale * signs.astype(c.dtype)
    return scale, signs, error


def compressed_allreduce(x: jax.Array, worker_error: jax.Array,
                         server_error: jax.Array, axis_name: str):
    """Error-compensated 1-bit mean-allreduce over ``axis_name``.

    Must run under shard_map with ``axis_name`` manual. ``x`` is this
    device's local tensor (1-D); worker/server errors are PER-DEVICE state
    of the same shape (the server error is live only in this device's owned
    chunk, matching the reference's per-rank server_error chunks).

    Returns (averaged tensor, new_worker_error, new_server_error).
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    numel = x.shape[0]
    pad = (-numel) % n
    xp = jnp.pad(x + worker_error[:numel], ((0, pad),))
    chunk = xp.shape[0] // n

    # stage 1: worker compression
    scale, signs, werr = _sign_compress(xp)
    # alltoall: device j receives chunk j of every device's signs
    my_chunks_signs = signs.reshape(n, chunk)
    recv_signs = jax.lax.all_to_all(my_chunks_signs, axis_name, split_axis=0,
                                    concat_axis=0, tiled=False)
    recv_scales = jax.lax.all_gather(scale, axis_name)  # [n]
    # average my owned chunk across all senders
    avg_chunk = jnp.mean(recv_scales[:, None] *
                         recv_signs.reshape(n, chunk).astype(x.dtype), axis=0)

    # stage 2: server compression of my owned chunk (+ my server error slice)
    serr_slice = jax.lax.dynamic_slice(
        jnp.pad(server_error, ((0, pad),)), (idx * chunk,), (chunk,))
    s_scale, s_signs, s_err = _sign_compress(avg_chunk + serr_slice)

    # allgather the compressed server chunks → everyone reconstructs the mean
    all_scales = jax.lax.all_gather(s_scale, axis_name)          # [n]
    all_signs = jax.lax.all_gather(s_signs, axis_name)           # [n, chunk]
    out = (all_scales[:, None] * all_signs.astype(x.dtype)).reshape(-1)[:numel]
    # consensus reconstruction may be device-invariant in shard_map's vma
    # typing; mark it varying so it composes with per-device values in
    # lax.cond branches whose other side is varying
    out = _ensure_varying(out, axis_name)

    # scatter my server-error slice back into the full-size carrier
    new_serr = jax.lax.dynamic_update_slice(
        jnp.zeros((numel + pad,), server_error.dtype), s_err,
        (idx * chunk,))[:numel]
    new_werr = werr[:numel]
    return out, new_werr, new_serr


# --------------------------------------------------------------- shared state
class OnebitState(NamedTuple):
    step: jax.Array
    exp_avg: Any
    exp_avg_sq: Any
    worker_error: Any    # per-device distinct; shard over the data axis
    server_error: Any
    frozen_scale: Any    # per-leaf scalar (LAMB trust ratio frozen at warmup end)


OnebitAdamState = OnebitState  # back-compat alias


@dataclasses.dataclass
class _OnebitBase:
    """Shared step driver: subclasses supply the variance/sync/update policy
    (the 3 ways OnebitAdam / OnebitLamb / ZeroOneAdam differ)."""

    lr: float = 1e-3
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    freeze_step: int = 100
    with_compression: bool = True  # False: engine/GSPMD exact path, no error state

    name = "onebit_base"

    # ------------------------------------------------------------------ state
    def init(self, params) -> OnebitState:
        zeros = lambda: jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if self.with_compression:
            we, se = zeros(), zeros()
        else:  # exact-comm mode keeps the pytree structure but no memory
            empty = jax.tree_util.tree_map(
                lambda p: jnp.zeros((0,), jnp.float32), params)
            we, se = empty, empty
        return OnebitState(
            step=jnp.zeros((), jnp.int32), exp_avg=zeros(), exp_avg_sq=zeros(),
            worker_error=we, server_error=se,
            frozen_scale=jax.tree_util.tree_map(
                lambda p: jnp.ones((), jnp.float32), params))

    # --------------------------------------------------------------- policies
    def _variance_on(self, count):
        """Does the variance update this step? (Adam/LAMB: warmup only)."""
        return count <= self.freeze_step

    def _sync_on(self, count):
        """Does the compressed sync run this (post-warmup) step?"""
        return jnp.asarray(True)

    def _var_from_momentum(self) -> bool:
        """Variance signal: grads (Adam/LAMB warmup) or synced momentum
        (ZeroOneAdam's schedule)."""
        return False

    def _param_update(self, p, update, lr, warm, fscale):
        return (p.astype(jnp.float32) - lr * update).astype(p.dtype)

    def _new_frozen_scale(self, count, p, update, fscale):
        return fscale

    # ------------------------------------------------------------------- step
    def step(self, params, grads, state: OnebitState, lr=None,
             axis_name: Optional[str] = None):
        """``grads`` are LOCAL when axis_name is set (compression replaces
        the grad allreduce); exact/global otherwise."""
        lr = self.lr if lr is None else lr
        b1, b2 = self.betas
        count = state.step + 1
        warm = count <= self.freeze_step
        var_on = self._variance_on(count)
        sync_on = self._sync_on(count)

        def leaf_update(p, g, m, v, we, se, fscale):
            g = g.astype(jnp.float32)

            if axis_name is None:
                # exact mode (single device / engine GSPMD path): grads are
                # already global — same math, no collectives
                m_new = b1 * m + (1 - b1) * g
                signal = m_new * m_new if self._var_from_momentum() else g * g
                v_new = jnp.where(var_on, b2 * v + (1 - b2) * signal, v)
                we_new, se_new = we, se
            else:
                # one lax.cond per leaf so exactly ONE set of collectives
                # runs: exact pmean in warmup, compressed sync after
                def warm_branch(operands):
                    m, v, we, se, g = operands
                    ge = jax.lax.pmean(g, axis_name)
                    ev = lambda t: _ensure_varying(t, axis_name)
                    return (ev(b1 * m + (1 - b1) * ge),
                            ev(b2 * v + (1 - b2) * ge * ge), ev(we), ev(se))

                def compressed_branch(operands):
                    m, v, we, se, g = operands
                    m_local = b1 * m + (1 - b1) * g

                    def do_sync(ops):
                        m_local, we, se = ops
                        shape = m_local.shape
                        ms, we2, se2 = compressed_allreduce(
                            m_local.reshape(-1), we.reshape(-1),
                            se.reshape(-1), axis_name)
                        return ms.reshape(shape), we2.reshape(shape), \
                            se2.reshape(shape)

                    def skip_sync(ops):
                        m_local, we, se = ops
                        return m_local, we, se

                    m_sync, we2, se2 = jax.lax.cond(
                        sync_on, do_sync, skip_sync, (m_local, we, se))
                    # variance schedule in the compressed stage uses the
                    # synced momentum as its signal (ZeroOneAdam; Adam/LAMB
                    # have var_on=False here so v stays frozen)
                    v2 = jnp.where(var_on & ~warm,
                                   b2 * v + (1 - b2) * m_sync * m_sync, v)
                    ev = lambda t: _ensure_varying(t, axis_name)
                    return ev(m_sync), ev(v2), ev(we2), ev(se2)

                m_new, v_new, we_new, se_new = jax.lax.cond(
                    warm, warm_branch, compressed_branch, (m, v, we, se, g))

            bc1 = 1 - b1 ** count.astype(jnp.float32)
            bc2 = 1 - b2 ** count.astype(jnp.float32)
            update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + self.eps)
            if self.weight_decay > 0:
                update = update + self.weight_decay * p.astype(jnp.float32)
            fscale_new = self._new_frozen_scale(count, p, update, fscale)
            p_new = self._param_update(p, update, lr, warm, fscale_new)
            return p_new, m_new, v_new, we_new, se_new, fscale_new

        flat_p, treedef = jax.tree_util.tree_flatten(params)
        parts = [treedef.flatten_up_to(t) for t in
                 (grads, state.exp_avg, state.exp_avg_sq,
                  state.worker_error, state.server_error, state.frozen_scale)]
        out = [leaf_update(p, *leaves) for p, *leaves in zip(flat_p, *parts)]
        unf = lambda i: treedef.unflatten([o[i] for o in out])
        return unf(0), OnebitState(step=count, exp_avg=unf(1),
                                   exp_avg_sq=unf(2), worker_error=unf(3),
                                   server_error=unf(4), frozen_scale=unf(5))


# ----------------------------------------------------------------- OnebitAdam
@dataclasses.dataclass
class OnebitAdam(_OnebitBase):
    """reference OnebitAdam (runtime/fp16/onebit/adam.py:13): exact Adam for
    ``freeze_step`` warmup steps, then variance freezes and the momentum is
    synchronized with the 1-bit compressed allreduce."""

    name = "onebit_adam"


# ----------------------------------------------------------------- OnebitLamb
@dataclasses.dataclass
class OnebitLamb(_OnebitBase):
    """reference OnebitLamb (onebit/lamb.py:14): live per-layer trust ratio
    during warmup; at the freeze boundary the ratio is FROZEN and applied as
    a fixed per-layer scaling through the compressed stage (norm ratios of
    sign-quantized updates are too noisy to trust live)."""

    max_coeff: float = 10.0
    min_coeff: float = 0.01

    name = "onebit_lamb"

    def _live_trust(self, p, update):
        w_norm = jnp.linalg.norm(p.astype(jnp.float32))
        u_norm = jnp.linalg.norm(update)
        return jnp.where((w_norm > 0) & (u_norm > 0),
                         jnp.clip(w_norm / u_norm, self.min_coeff,
                                  self.max_coeff), 1.0)

    def _new_frozen_scale(self, count, p, update, fscale):
        # track the live ratio until the freeze boundary, then hold
        return jnp.where(count <= self.freeze_step,
                         self._live_trust(p, update), fscale)

    def _param_update(self, p, update, lr, warm, fscale):
        # warmup: live trust ratio; compressed stage: frozen ratio
        return (p.astype(jnp.float32) - lr * fscale * update).astype(p.dtype)


# ----------------------------------------------------------------- ZeroOneAdam
@dataclasses.dataclass
class ZeroOneAdam(_OnebitBase):
    """reference ZeroOneAdam (onebit/zoadam.py:13): 0/1 Adam — variance
    updates on an interval schedule until ``var_freeze_step`` and the
    compressed momentum sync runs on a local-step policy interval (steps
    without sync skip ALL communication — that is the point of 0/1 Adam)."""

    var_freeze_step: int = 100
    var_update_scaler: int = 16
    local_step_scaler: int = 32768
    local_step_clipper: int = 16

    name = "zero_one_adam"

    def __post_init__(self):
        # 0/1 Adam has no warmup/freeze split in the Adam sense: compression
        # starts immediately; freeze_step gates only the variance schedule
        self.freeze_step = 0

    def _variance_on(self, count):
        return ((count <= self.var_freeze_step) &
                (jnp.mod(count, self.var_update_scaler) == 0)) | (count == 1)

    def _sync_on(self, count):
        # clip the EXPONENT before the power: int32 2**31 wraps negative and
        # would silently disable momentum sync for the rest of training
        max_exp = int(np.log2(max(self.local_step_clipper, 1)))
        exp = jnp.minimum(count // jnp.maximum(self.local_step_scaler, 1),
                          max_exp)
        k = jnp.minimum(2 ** exp, self.local_step_clipper)
        return (count <= self.var_freeze_step) | (jnp.mod(count, k) == 0)

    def _var_from_momentum(self) -> bool:
        return True
