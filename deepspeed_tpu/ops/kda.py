"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention): a layer's one-token decode step, the one-token update alone, and
the chunked prompt form.

The recurrence, per head with state ``S [K, V]`` (keys by values, float32),
query ``q_t [K]`` and key ``k_t [K]`` (both L2-normalised, the query also times
``K ** -0.5``), value ``v_t [V]``, log decay ``g_t [K] <= 0`` PER CHANNEL and
``beta_t`` in (0, 2)::

    S~  = diag(exp(g_t)) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

Unlike the state-space recurrence of ops/ssm.py, the decayed state is READ
against the new key before it is written: the rule stores the difference
between the value and what the state already predicts for that key.

Entry points, by serving phase:

  * :func:`kda_step` — ONE token for every slot, everything a layer does
    between its projections and its output matmul, in the one Pallas call
    named ``dstpu_kda_update``, a grid cell ``BLOCK_HEADS`` heads of an ACTIVE
    slot: the three four-tap convolutions with SiLU (the new tail written in
    place), the L2 norms, ``-exp(A_log) softplus(. + dt_bias)``, the state's
    tiles through VMEM once (decay, ``S~^T k``, the rank-one write, ``S^T q``
    while the tile is there) in place on the stacked ``[L, slots, H, K, V]``
    state, the head-wise RMS norm and the sigmoid gate. The layer's small
    weights reach the call through index maps on stacked views made once a
    step (:func:`fold_weights`), the slot order once a step
    (ops/ssm.slot_order). An inactive slot's state and tails are neither read
    nor written. Taken where :func:`supports` says the shapes fit.
  * :func:`kda_update` — the update and read-out alone in plain ``jnp``,
    for a CPU, ``generate()`` and shapes that do not fold; the folded call is
    tested against the carried convolution + this.
  * :func:`kda_chunked` — a whole prompt block in the chunked form: inside a
    chunk of ``C`` positions the pairwise decays ``exp(G_i - G_j)`` are formed
    from the DIFFERENCE of the cumulative logs (``k_j / exp(G_j)`` alone
    overflows where a channel decays fast), the in-chunk dependence is a unit
    lower-triangular solve, and a short ``lax.scan`` carries the state from
    chunk to chunk. XLA's own matmuls; no kernel yet. Positions at or beyond
    ``length`` get ``g = 0`` and ``beta = 0``: the state stops at the true
    length (padding is not invisible to a recurrence).

The step is bound by memory (the state is read and written once a token, 0.87
FLOPs a byte). Serving only: no VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# heads of one slot a grid cell works on: 16 heads are one packed tile of the
# bf16 tail's rows and 1 MB of float32 state a block. On the v5e, three layers
# of 16 slots: 16 or 32 heads a cell 0.33 ms at 1 to 4 active slots and 0.72
# ms at 16 (68% of the state's traffic at the chip's peak); 64 heads a cell
# do not fit VMEM (chip run, PR 48)
BLOCK_HEADS = 16
L2_EPS = 1e-6
_HIGHEST = jax.lax.Precision.HIGHEST


def default_route() -> str:
    """The Pallas kernel on a TPU, the ``jnp`` route elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def supports(heads: int, key_dim: int, value_dim: int, taps: int) -> bool:
    """Whether a layer's decode step fits the folded call: a head's keys and
    values are each one row of lanes (so a head is a row of every operand and
    a state tile is ``[128, 128]``), and the heads split into whole cells."""
    return (key_dim == LANES and value_dim == LANES and taps >= 2
            and heads % BLOCK_HEADS == 0)


def _count(name: str) -> None:
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = {n: reg.counter("kda/traced_" + n) for n in
                ("folded_step", "split_step", "chunked_block")}
    counters[name].inc()


def count_step(folded: bool) -> None:
    """Say in the program's registry which way a one-token layer was traced:
    folded into the kernel, or split into XLA's own operations around
    :func:`kda_update`. All three counters exist from the first call on."""
    _count("folded_step" if folded else "split_step")


def count_chunked_block() -> None:
    """A prompt block traced in the chunked form."""
    _count("chunked_block")


def l2_normalize(x):
    """``x / |x|`` over the last axis, in float32 (``|x|^2 + 1e-6`` under the
    root, so a zero row stays zero)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def log_decay(g_pre, a_log, dt_bias):
    """``g = -exp(A_log[h]) softplus(g_pre + dt_bias) <= 0``: ``g_pre [...,
    H, K]``, ``a_log [H]``, ``dt_bias [H, K]`` -> float32 ``[..., H, K]``."""
    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        g_pre.astype(f32) + dt_bias.astype(f32))


# ------------------------------------------------------- one-token update
def kda_update(state, layer, q, k, v, g, beta, active=None):
    """One token for every slot against the full stacked state, plain ``jnp``.

    ``state [L, B, H, K, V]`` float32 (updated at ``layer`` only); ``q, k [B,
    H, K]`` normalised; ``v [B, H, V]``; ``g [B, H, K]`` log decay; ``beta [B,
    H]``; ``active [B]`` bool (``None``: all): an inactive slot's state does
    not move and its output is zero. -> ``(o [B, H, V] float32, state)``."""
    f32 = jnp.float32
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = old.astype(f32) * jnp.exp(g.astype(f32))[..., None]
    k, q = k.astype(f32), q.astype(f32)
    pred = jnp.sum(s * k[..., None], axis=-2)                   # [B, H, V]
    delta = beta.astype(f32)[..., None] * (v.astype(f32) - pred)
    s = s + k[..., None] * delta[..., None, :]
    o = jnp.sum(s * q[..., None], axis=-2)
    if active is not None:
        o = jnp.where(active[:, None, None], o, 0.0)
        s = jnp.where(active[:, None, None, None], s, old)
    return o, jax.lax.dynamic_update_index_in_dim(
        state, s.astype(state.dtype), layer, 0)


# ------------------------------------------- a layer's decode step, folded
def tail_shape(taps: int, heads: int, key_dim: int):
    """The trailing dimensions of the cache leaf that carries the three
    convolutions' tails, ``[L, slots, ...]``: the last ``taps - 1`` inputs of
    ``q | k | v``, a head a row of lanes, so that a cell's block is whole
    packed tiles (PERF.md, PR 44: a leaf with a small second-minor dimension
    is copied whole at a kernel's boundary)."""
    return (taps - 1, 3, heads, key_dim)


def fold_weights(stack, heads: int):
    """The layer stack's small weights as the folded call reads them through
    its index maps, float32, made once a step: the taps ``[L, taps, 3, H,
    128]``, ``A = -exp(A_log)`` a head spread over its lanes beside
    ``dt_bias`` ``[L, 2, H, 128]``, the head norm's weight ``[L, 1, 128]``."""
    f32 = jnp.float32
    lk, taps, _ = stack["conv_w"].shape
    a = -jnp.exp(stack["A_log"].astype(f32))
    return {
        "conv_w": stack["conv_w"].astype(f32).reshape(lk, taps, 3, heads,
                                                      LANES),
        "heads": jnp.stack(
            [jnp.broadcast_to(a[..., None], (lk, heads, LANES)),
             stack["dt_bias"].astype(f32).reshape(lk, heads, LANES)], axis=1),
        "o_norm": stack["o_norm"].astype(f32).reshape(lk, 1, LANES),
    }


def _columns(rows, hb: int):
    """``rows [hb, 128]`` (a head a row) as ``[128, 128]`` whose column ``h``
    is head ``h``'s vector down the sublanes: a square transpose."""
    pad = jnp.zeros((LANES - hb, LANES), rows.dtype)
    return jnp.concatenate([rows, pad], axis=0).T


def _step_kernel(layer_ref, order_ref, n_ref, zx_ref, gp_ref, bt_ref, og_ref,
                 cw_ref, hp_ref, nw_ref, s_ref, t_ref, y_ref, o_ref, u_ref,
                 y_rows, *, hb: int, taps: int, eps: float):
    """``hb`` heads of one slot, one token, between the projections and the
    output matmul. A head is a row of 128 lanes in every operand but the
    state, whose tile a head is ``[128 keys, 128 values]``."""
    del layer_ref, order_ref
    i = pl.program_id(0)
    n_active = n_ref[0]
    f32 = jnp.float32
    cdt = zx_ref.dtype

    @pl.when(i < n_active)
    def _live():
        x_in = zx_ref[...]                                   # [3, hb, 128]
        acc = x_in.astype(f32) * cw_ref[taps - 1]
        for j in range(taps - 1):
            acc = acc + t_ref[j].astype(f32) * cw_ref[j]
        act = jax.nn.silu(acc).astype(cdt).astype(f32)
        # the tails move on by one tap
        for j in range(taps - 2):
            u_ref[j] = t_ref[j + 1]
        u_ref[taps - 2] = x_in

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

        q = unit(act[0]) * (LANES ** -0.5)
        k = unit(act[1])
        v = act[2]
        g = hp_ref[0] * jax.nn.softplus(gp_ref[...].astype(f32) + hp_ref[1])
        q_c, k_c, a_c = (_columns(q, hb), _columns(k, hb),
                         _columns(jnp.exp(g), hb))
        beta = bt_ref[...]
        for h in range(hb):
            kc = k_c[:, h:h + 1]                             # [128 keys, 1]
            s = s_ref[h].astype(f32) * a_c[:, h:h + 1]
            pred = jnp.sum(s * kc, axis=0, keepdims=True)    # [1, 128 values]
            delta = beta[h:h + 1] * (v[h:h + 1] - pred)
            s = s + kc * delta
            o_ref[h] = s.astype(o_ref.dtype)
            y_rows[h:h + 1, :] = jnp.sum(s * q_c[:, h:h + 1], axis=0,
                                         keepdims=True)
        # the head-wise RMS norm and the sigmoid gate
        y = y_rows[...]
        var = jnp.sum(y * y, axis=-1, keepdims=True) / LANES
        y = y * jax.lax.rsqrt(var + eps) * nw_ref[...]
        y_ref[...] = (y * jax.nn.sigmoid(og_ref[...].astype(f32))
                      ).astype(y_ref.dtype)

    # nothing active: every cell sits on one block, which is written back
    # once at the end, so it has to hold what was read
    @pl.when((n_active == 0) & (i == 0))
    def _keep():
        o_ref[...] = s_ref[...]
        u_ref[...] = t_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def kda_step(qkv, g_pre, beta, gate_pre, state, tail, layer, weights, walk,
             active, *, eps: float, interpret: Optional[bool] = None):
    """One token for every slot through a layer's mixer, in the one Pallas
    call (see the module's head).

    ``qkv [B, 3 H K]``: the projections' result, ``q | k | v``; ``g_pre``,
    ``gate_pre [B, H K]``: the two low-rank gates before ``softplus`` /
    ``sigmoid``; ``beta [B, H]`` float32, after ``2 sigmoid``; ``state [L, B,
    H, K, V]`` float32 and ``tail [L, B, taps - 1, 3, H, K]``
    (:func:`tail_shape`), both updated at ``layer`` in place, the active
    slots' blocks only; ``weights``: :func:`fold_weights`; ``walk``:
    ``ops/ssm.slot_order`` of ``active [B]``. Returns ``(o [B, H V]`` in
    ``qkv``'s dtype, what the output matmul takes, zero for a slot that did
    not run``, state, tail)``."""
    l, b, h, dk, dv = state.shape
    taps = weights["conv_w"].shape[1]
    hb = BLOCK_HEADS
    nh = h // hb
    order, n_active = walk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def cell(i, j, layer_ref, order_ref, n_ref):
        slot = order_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]
        return slot, jnp.where(i < n_ref[0], j, nh - 1)

    def block(lead, per_layer: bool, per_slot: bool):
        """``lead + (hb, 128)``: the cell's heads of every leading entry, of
        the walk's layer and / or of the cell's slot."""
        def index(i, j, *refs):
            slot, jh = cell(i, j, *refs)
            return ((refs[0][0],) if per_layer else ()) \
                + ((slot,) if per_slot else ()) + (0,) * len(lead) + (jh, 0)
        return pl.BlockSpec((None,) * (per_layer + per_slot) + lead
                            + (hb, LANES), index)

    def state_index(i, j, *refs):
        slot, jh = cell(i, j, *refs)
        return (refs[0][0], slot, jh, 0, 0)

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), state_index)
    tail_spec = block((taps - 1, 3), True, True)
    y, state, tail = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, taps=taps, eps=eps),
        name="dstpu_kda_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nh),
            in_specs=[
                block((3,), False, True),               # q | k | v
                block((), False, True),                 # the decay's gate
                block((), False, True),                 # beta, spread
                block((), False, True),                 # the output's gate
                block((taps, 3), True, False),          # taps
                block((2,), True, False),               # A, dt_bias
                pl.BlockSpec((None, 1, LANES),
                             lambda i, j, *refs: (refs[0][0], 0, 0)),
                state_spec, tail_spec,
            ],
            out_specs=[block((), False, True), state_spec, tail_spec],
            scratch_shapes=[pltpu.VMEM((hb, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, h, LANES), qkv.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        # operands: layer, order, n_active, qkv, g, beta, gate, taps, heads,
        # norm, state, tail
        input_output_aliases={10: 1, 11: 2},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n_active,
      qkv.reshape(b, 3, h, LANES), g_pre.reshape(b, h, LANES),
      jnp.broadcast_to(beta.astype(jnp.float32)[..., None], (b, h, LANES)),
      gate_pre.reshape(b, h, LANES), weights["conv_w"], weights["heads"],
      weights["o_norm"], state, tail)
    # blocks of slots that did not run were never written
    y = jnp.where(active[:, None, None], y, 0).reshape(b, h * dv)
    return y, state, tail


# ------------------------------------------------------ chunked prompt form
def kda_chunked(q, k, v, g, beta, *, chunk: int, init_state=None,
                length=None):
    """A block of ``T`` positions in the chunked form, the recurrence's own
    numbers.

    ``q, k [B, T, H, K]`` normalised; ``v [B, T, H, V]``; ``g [B, T, H, K]``
    log decay; ``beta [B, T, H]``; ``init_state [B, H, K, V]`` (zeros if
    ``None``); ``length [B]`` or scalar: positions at or beyond it move
    nothing and the returned state is the one at ``length``. Returns ``(o [B,
    T, H, V] float32, state [B, H, K, V] float32)``.

    In a chunk from state ``S_0``, with ``G_i = sum_{j <= i} g_j`` and ``r_ij
    = exp(G_i - G_j)``::

        L_ij = beta_i sum_c k_ic r_ij,c k_jc  (j < i)
        (I + L) U = diag(beta) (V - (K * exp(G)) S_0)
        o_i = (q_i * exp(G_i))^T S_0 + sum_{j <= i} (sum_c q_ic r_ij,c k_jc) u_j
        S_C = diag(exp(G_C)) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

    Every exponent is a difference that is at most 0, so nothing overflows
    however fast a channel decays. What does not need ``S_0`` (the two
    in-chunk score matrices and ``(I + L)^-1`` applied to ``diag(beta) [V | K
    * exp(G)]``) is computed for all chunks of the block at once; the scan
    over chunks is three small matmuls a step."""
    f32 = jnp.float32
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    if length is not None:
        live = jnp.arange(t)[None, :] < jnp.reshape(
            jnp.asarray(length, jnp.int32), (-1, 1))
        g = jnp.where(live[:, :, None, None], g, 0.0)
        beta = jnp.where(live[:, :, None], beta, 0.0)
    c = min(chunk, t)
    pad = -t % c
    if pad:   # g = 0, beta = 0 there: neither state nor earlier outputs move
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(x):        # [B, T, H, ...] -> [B, N, H, C, ...]
        return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 3, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=3)                          # [B, N, H, C, K]
    # pairwise decays from differences; above the diagonal exp(-inf) = 0.
    # Written as a product and a sum so that the [C, C, K] factor lives in
    # the reduction's registers and is never an array
    tri = jnp.tril(jnp.ones((c, c), bool))
    ratio = jnp.exp(jnp.where(
        tri[:, :, None], cum[..., :, None, :] - cum[..., None, :, :],
        -jnp.inf))                                       # [.., C, C, K]
    kj = k[..., None, :, :]
    zero = jnp.zeros((), f32)
    # ONE reduction with two results: the [C, C, K] factor is made once and
    # lives in the reduction's registers, never as an array (as an operand of
    # two reductions it is written out, 4 GB a block of 2,048 tokens)
    a_kk, a_qk = jax.lax.reduce(
        (k[..., :, None, :] * ratio * kj, q[..., :, None, :] * ratio * kj),
        (zero, zero), lambda x, y: (x[0] + y[0], x[1] + y[1]),
        (ratio.ndim - 1,))
    lower = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), a_kk, 0.0) \
        * beta[..., None]
    k_in = k * jnp.exp(cum)                              # K * exp(G)
    rhs = jnp.concatenate([v, k_in], axis=-1) * beta[..., None]
    solved = jax.lax.linalg.triangular_solve(
        lower + jnp.eye(c, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]           # [.., C, V], [.., C, K]
    q_in = q * jnp.exp(cum)
    k_out = k * jnp.exp(cum[..., -1:, :] - cum)          # K * exp(G_C - G)
    across = jnp.exp(cum[..., -1, :])                    # [B, N, H, K]
    s0 = jnp.zeros((b, h, dk, dv), f32) if init_state is None \
        else init_state.astype(f32)

    def dot(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_HIGHEST,
                          preferred_element_type=f32)

    def step(s, xs):
        u0_n, w_n, qin_n, aqk_n, kout_n, across_n = xs
        u = u0_n - dot("bhck,bhkv->bhcv", w_n, s)
        o = dot("bhck,bhkv->bhcv", qin_n, s) + dot("bhcj,bhjv->bhcv", aqk_n, u)
        s = s * across_n[..., None] + dot("bhck,bhcv->bhkv", kout_n, u)
        return s, o

    s_last, o = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in
                        (u0, w, q_in, a_qk, k_out, across)))
    # [N, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        b, t + pad, h, dv)
    return o[:, :t], s_last
