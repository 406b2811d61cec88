"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention): a layer's one-token decode step, the one-token update alone, and
the chunked prompt form, in XLA's own operations and as a kernel.

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
    chunk to chunk. XLA's own operations: the route of a CPU, of training
    (it is differentiable) and of shapes the kernel does not fit. Positions
    at or beyond ``length`` get ``g = 0`` and ``beta = 0``: the state stops
    at the true length (padding is not invisible to a recurrence).
  * :func:`kda_prefill` — a cached prompt block on a TPU, everything a
    layer does between its convolutions and its output matmul, as the one
    Pallas call ``dstpu_kda_prefill``: it takes ``q | k | v`` as the
    convolution leaves them and the two gates as the low-rank matmuls leave
    them, in the stream's dtype, and hands back gated, normed heads in that
    dtype. A grid cell ``PREFILL_HEADS`` heads of a chunk, the chunk axis
    sequential with the heads' state in VMEM from the block's first chunk to
    its last. In a chunk, in float32: the L2 norms and ``-exp(A_log)
    softplus(. + dt_bias)`` (the layer's small weights from
    :func:`fold_layer`); the chunk in sub-chunks of ``SUB``, whose own
    pairwise decays are the only ones formed element by element and whose
    part of the solve is a forward substitution, all else matmuls of rows and
    columns scaled against a sub-chunk's first position; the head-wise RMS
    norm and the sigmoid gate on a head's result. A chunk of padding is
    neither fetched nor computed. Taken where :func:`supports_prefill` says
    the shapes fit; :func:`kda_chunked` between :func:`l2_normalize`,
    :func:`log_decay` and XLA's norm and gate is its reference.

The step is bound by memory (the state is read and written once a token, 0.87
FLOPs a byte). The two kernels serve only: no VJP.
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
                ("folded_step", "split_step", "chunked_block",
                 "prefill_kernel")}
    counters[name].inc()


def count_step(folded: bool) -> None:
    """Say in the program's registry which way a one-token layer was traced:
    folded into the kernel, or split into XLA's own operations around
    :func:`kda_update`. All four counters exist from the first call on."""
    _count("folded_step" if folded else "split_step")


def count_chunked_block() -> None:
    """A prompt block traced in the chunked form."""
    _count("chunked_block")


def count_prefill_kernel() -> None:
    """A prompt block traced as the Pallas call :func:`kda_prefill`."""
    _count("prefill_kernel")


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


def _fold_gates(a, dt_bias, o_norm):
    """``a = -exp(A_log)`` a head spread over its lanes beside ``dt_bias``
    ``[.., 2, H, 128]`` and the head norm's weight ``[.., 1, 128]``, float32:
    a layer's, or with a leading ``L`` a stack's."""
    f32 = jnp.float32
    rows = a.shape + (LANES,)
    return {
        "heads": jnp.stack([jnp.broadcast_to(a[..., None], rows),
                            dt_bias.astype(f32).reshape(rows)], axis=-3),
        "o_norm": o_norm.astype(f32).reshape(rows[:-2] + (1, LANES)),
    }


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
        **_fold_gates(a, stack["dt_bias"], stack["o_norm"]),
    }


def fold_layer(blk):
    """ONE layer's decay and head-norm weights as :func:`fold_weights` gives a
    stack's, for the prompt kernel: ``{"heads": [2, H, 128], "o_norm": [1,
    128]}`` float32, a few KB a call (the taps stay the convolution's)."""
    return _fold_gates(-jnp.exp(blk["A_log"].astype(jnp.float32)),
                       blk["dt_bias"], blk["o_norm"])


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


# ------------------------------------------- the chunked prompt form, fused
# positions of a sub-chunk: between sub-chunks the pairwise decays are matmuls
# of rows and columns scaled against the row sub-chunk's first position, inside
# one they are formed element by element
SUB = 16
# rows of a sub-chunk that share one traced body of its forward substitution:
# a body a row (exact slices) is 1,900 equations to trace and lower in every
# prefill program, a body for all sixteen (masked) twice the vector work
ROWS = 4
# heads of a grid cell: a position's rows of 8 heads are one float32 tile, so
# the work that is the same for every head (the cumulative logs, a sub-chunk's
# own scores and its forward substitution) runs on whole tiles a position
PREFILL_HEADS = 8


def supports_prefill(tokens: int, heads: int, key_dim: int, value_dim: int,
                     chunk: int) -> bool:
    """Whether a prompt block fits :func:`kda_prefill`: a head's keys and
    values are each one row of lanes, the heads split into whole cells, the
    block is whole chunks and a chunk whole sub-chunks whose scores fit one
    row of lanes."""
    return (key_dim == LANES and value_dim == LANES
            and heads % PREFILL_HEADS == 0 and chunk % SUB == 0
            and chunk <= LANES and tokens >= chunk
            and tokens % chunk == 0)


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, gp_ref, og_ref, b_ref,
                    hp_ref, nw_ref, s0_ref, o_ref, s1_ref, st, q_t, k_t, v_t,
                    g_t, b_t, xv_t, xk_t, t_t, a_t, *, c: int, hb: int,
                    eps: float):
    """``hb`` heads of one chunk of ``c`` positions of one row, between the
    convolution and the output matmul. The operands' blocks are ``[c, hb x
    128]`` in the stream's dtype (``q_ref``, ``k_ref``, ``v_ref`` three
    column blocks of the one activation): a head's ``[c, 128]`` is a slice of
    whole tiles; ``b_ref [c, H]`` is ``beta`` of every head. The ``_t``
    scratches are ``[c x hb, 128]`` float32, a position's ``hb`` heads one
    tile: head ``h``'s rows are every ``hb``-th. ``st`` holds the heads'
    states TRANSPOSED (``[values, keys]``: the decay then scales lanes) from
    the row's first chunk to its last."""
    f32 = jnp.float32
    row, cell, n = (pl.program_id(axis) for axis in range(3))
    length = len_ref[row]
    nsub = c // SUB

    def dot(x, y, dims):
        return jax.lax.dot_general(x, y, (dims, ((), ())), precision=_HIGHEST,
                                   preferred_element_type=f32)

    def tile(i, count=1):
        """Positions ``i .. i + count`` of a ``_t`` scratch."""
        return pl.ds(pl.multiple_of(i * hb, hb), count * hb)

    def of_head(h):
        return pl.ds(h, c, stride=hb)

    def lanes(h):
        return pl.ds(pl.multiple_of(h * LANES, LANES), LANES)

    def heads(body):
        """``body(h)`` for each of the cell's heads, one traced body."""
        def step(h, carry):
            body(h)
            return carry

        jax.lax.fori_loop(0, hb, step, 0)

    @pl.when(n == 0)
    def _load():
        @heads
        def _(h):
            st[h] = s0_ref[h].astype(f32).T
        # rows a sub-chunk's group takes behind the one it solves are masked,
        # not skipped: they hold the chunk's before, and zeros before that
        for ref in (xv_t, xk_t, t_t):
            ref[...] = jnp.zeros_like(ref)

    @pl.when(n * c >= length)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n * c < length)
    def _live():
        # ---- the mixer's row-local float32 work, a head's [c, 128] at a
        # time: the L2 norms, the query's scale, the log decay
        head = jax.lax.broadcasted_iota(jnp.int32, b_ref.shape, 1)
        live = n * c + jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) < length

        @heads
        def _(h):
            def rows(ref):
                return ref[:, lanes(h)].astype(f32)

            q_t[of_head(h), :] = l2_normalize(rows(q_ref)) * LANES ** -0.5
            k_t[of_head(h), :] = l2_normalize(rows(k_ref))
            v_t[of_head(h), :] = rows(v_ref)
            g_t[of_head(h), :] = hp_ref[0, pl.ds(h, 1), :] * jax.nn.softplus(
                rows(gp_ref) + hp_ref[1, pl.ds(h, 1), :])
            # the head's column of ``beta``, spread over the lanes here
            mine = jnp.sum(jnp.where(head == cell * hb + h, b_ref[...], 0.0),
                           axis=1, keepdims=True)
            b_t[of_head(h), :] = jnp.broadcast_to(
                jnp.where(live, mine, 0.0), (c, LANES))

        # ---- every head at once, a position a [hb, 128] tile
        def cum(i, acc):
            acc = acc + jnp.where(n * c + i < length, g_t[tile(i), :], 0.0)
            g_t[tile(i), :] = acc
            return acc

        jax.lax.fori_loop(0, c, cum, jnp.zeros((hb, LANES), f32))
        lane = jax.lax.broadcasted_iota(jnp.int32, (hb, LANES), 1)

        def sub_chunk(a, carry):
            """A sub-chunk's own part of the solve, by forward substitution:
            ``(I + L_aa)^-1`` applied to ``diag(beta) [V | K exp(G)]`` (into
            ``xv_t``, ``xk_t``) and to the identity (``t_t``, at the
            sub-chunk's lanes), and its block of ``A_qk`` (``a_t``). Rows in
            groups of ``ROWS``, one traced body a group: a row takes the
            sub-chunk's rows up to its group's end and masks those at and
            behind itself."""
            base = pl.multiple_of(a * SUB, SUB)
            for count in range(ROWS, SUB + ROWS, ROWS):
                def taken(ref):
                    return ref[tile(base, count), :].reshape(count, hb, -1)

                at = base + jax.lax.broadcasted_iota(
                    jnp.int32, (count, hb, LANES), 0)

                def row(r, carry):      # traced here, inside its group
                    i = base + count - ROWS + r
                    gi, ki, qi = (ref[tile(i), :] for ref in (g_t, k_t, q_t))
                    bi = b_t[tile(i), :][:, :1]
                    # exp(G_i - G_j) k_j: every exponent <= 0 before i, and
                    # held there behind it, where the mask drops the term
                    e = jnp.exp(jnp.minimum(gi - taken(g_t), 0.0)) * taken(k_t)
                    before = at[..., :1] < i
                    lij = jnp.where(before, bi * jnp.sum(
                        e * ki, axis=-1, keepdims=True), 0.0)
                    aij = jnp.where(at[..., :1] <= i, jnp.sum(
                        e * qi, axis=-1, keepdims=True), 0.0)
                    xv_t[tile(i), :] = bi * v_t[tile(i), :] \
                        - jnp.sum(lij * taken(xv_t), axis=0)
                    xk_t[tile(i), :] = bi * ki * jnp.exp(gi) \
                        - jnp.sum(lij * taken(xk_t), axis=0)
                    t_t[tile(i), :] = (lane == i).astype(f32) \
                        - jnp.sum(lij * taken(t_t), axis=0)
                    a_t[tile(i), :] = jnp.sum(
                        jnp.where(lane == at, aij, 0.0), axis=0)
                    return carry

                jax.lax.fori_loop(0, ROWS, row, 0, unroll=True)
            return carry

        jax.lax.fori_loop(0, nsub, sub_chunk, 0)

        # ---- a head at a time, on the MXU
        r_blk = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) // SUB
        c_blk = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1) // SUB
        below = c_blk < r_blk

        @heads
        def _(h):
            q, k = q_t[of_head(h), :], k_t[of_head(h), :]
            gc, bt = g_t[of_head(h), :], b_t[of_head(h), :]
            firsts = [jnp.broadcast_to(gc[a * SUB:a * SUB + 1], (SUB, LANES))
                      for a in range(nsub)]
            # rows against their sub-chunk's first position
            rs = jnp.exp(gc - jnp.concatenate(firsts, axis=0))
            kr, qr = bt * k * rs, q * rs
            zeros = jnp.zeros((SUB, c), f32)
            n_off, a_off = [zeros], [zeros]
            for a in range(1, nsub):
                # columns against the same position: <= 0 before it; at and
                # behind it the block is masked
                first = jnp.broadcast_to(gc[a * SUB:a * SUB + 1], (c, LANES))
                kc = k * jnp.exp(jnp.minimum(first - gc, 0.0))
                rows = slice(a * SUB, (a + 1) * SUB)
                s = dot(jnp.concatenate([kr[rows], qr[rows]], axis=0), kc,
                        ((1,), (1,)))                        # [2 SUB, c]
                n_off.append(s[:SUB])
                a_off.append(s[SUB:])
            lower = jnp.where(below, jnp.concatenate(n_off, axis=0), 0.0)
            a_qk = a_t[of_head(h), :][:, :c] + jnp.where(
                below, jnp.concatenate(a_off, axis=0), 0.0)
            # (I + D^-1 N)^-1 D^-1 R, sub-chunk after sub-chunk
            lower = dot(t_t[of_head(h), :][:, :c], lower, ((1,), (0,)))
            x = jnp.concatenate([xv_t[of_head(h), :], xk_t[of_head(h), :]],
                                axis=1)
            xs = [x[a * SUB:(a + 1) * SUB] for a in range(nsub)]
            for a in range(1, nsub):
                # rows of ``lower`` are zero from their own sub-chunk on
                xs[a] = xs[a] - dot(lower[a * SUB:(a + 1) * SUB],
                                    jnp.concatenate(xs, axis=0),
                                    ((1,), (0,)))
            x = jnp.concatenate(xs, axis=0)
            u0, w = x[:, :LANES], x[:, LANES:]
            s_t = st[h]                                      # [V, K]
            ws = dot(jnp.concatenate([w, q * jnp.exp(gc)], axis=0), s_t,
                     ((1,), (1,)))                           # [2 c, V]
            u = u0 - ws[:c]
            o = ws[c:] + dot(a_qk, u, ((1,), (0,)))
            # the head-wise RMS norm and the sigmoid gate, one rounding
            var = jnp.sum(o * o, axis=-1, keepdims=True) / LANES
            o = o * jax.lax.rsqrt(var + eps) * nw_ref[...] * jax.nn.sigmoid(
                og_ref[:, lanes(h)].astype(f32))
            o_ref[:, lanes(h)] = o.astype(o_ref.dtype)
            last = gc[c - 1:c]
            k_out = k * jnp.exp(last - gc)
            st[h] = s_t * jnp.exp(last) + dot(u, k_out, ((0,), (0,)))

    @pl.when(n == pl.num_programs(2) - 1)
    def _store():
        @heads
        def _(h):
            s1_ref[h] = st[h].T.astype(s1_ref.dtype)


def kda_prefill(act, g_pre, beta, gate_pre, weights, init_state, *,
                chunk: int, eps: float, length=None,
                interpret: Optional[bool] = None):
    """A cached prompt block through a layer's mixer between the convolution
    and the output matmul, as the one Pallas call ``dstpu_kda_prefill``.

    ``act [B, T, 3 H 128]``: ``q | k | v`` as ``causal_conv`` returns them,
    in the stream's dtype; ``g_pre``, ``gate_pre [B, T, H 128]``: the two
    low-rank gates before ``softplus`` / ``sigmoid``, in the stream's dtype;
    ``beta [B, T, H]`` float32, after ``2 sigmoid``; ``weights``:
    :func:`fold_layer`; ``init_state [B, H, 128, 128]``. Returns ``(o [B, T,
    H 128]`` in ``act``'s dtype, what the output matmul takes``, state [B, H,
    128, 128] float32)``: :func:`l2_normalize`, :func:`log_decay`,
    :func:`kda_chunked`, the head-wise RMS norm (``eps``) and the gate, every
    intermediate of a chunk in VMEM and float32, ``o`` rounded once.

    A grid cell is ``PREFILL_HEADS`` heads of one chunk; the chunk axis is
    sequential and carries the heads' state in a scratch, read from
    ``init_state`` at the first chunk and written once behind the last. The
    operands are fetched where the projections and the convolution left them
    (a position a row: a block is ``[chunk, heads x 128]`` and a head's part
    of it whole tiles; ``q``, ``k`` and ``v`` are three column blocks of the
    one ``act``, ``beta`` a chunk's ``[chunk, H]``: no split, no float32
    copy, no transposed copy, nothing spread over lanes is made outside). In
    a live chunk, first the row-local work a head at a time: the L2 norms,
    the query's ``128 ** -0.5``, ``g = -exp(A_log) softplus(g_pre +
    dt_bias)``, the head's column of ``beta``. Then the cumulative logs, each sub-chunk's
    own scores (the only ``exp(G_i - G_j)`` formed element by element) and
    its forward substitution run for the cell's heads at once, on copies in
    VMEM that hold a position's heads as one tile; then, a head at a time on
    the MXU: the scores between sub-chunks (rows times ``exp(G_i - G_r)``
    against columns times ``exp(G_r - G_j)``, ``r`` the row sub-chunk's first
    position: both exponents at most 0), the solve across sub-chunks,
    :func:`kda_chunked`'s chunk step and, on its result, the head norm and
    the gate. Positions at or beyond ``length`` get ``g = 0``, ``beta = 0``;
    a chunk that holds none before ``length`` is not fetched, does no
    arithmetic, writes zeros and leaves the state as it is. Float32 scores,
    solve, state and accumulations, matmuls at ``Precision.HIGHEST``. Serving
    only: no VJP."""
    b, t, _ = g_pre.shape
    h = init_state.shape[1]
    assert supports_prefill(t, h, *init_state.shape[2:], chunk) \
        and act.shape == (b, t, 3 * h * LANES), (act.shape, init_state.shape,
                                                 chunk)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    length = jnp.full((b,), t, jnp.int32) if length is None else \
        jnp.broadcast_to(jnp.asarray(length, jnp.int32), (b,))
    return _prefill_call(length, act, g_pre, gate_pre, beta, weights["heads"],
                         weights["o_norm"], init_state, chunk=chunk,
                         eps=float(eps), interpret=interpret)


# jitted (and inlined where it is called), so that the kernel's body, some 700
# equations, is traced once a process and not once in each of the four prefill
# programs that hold it: set-up time on every warm start (PERF.md, PR 49)
@functools.partial(jax.jit, static_argnames=("chunk", "eps", "interpret"),
                   inline=True)
def _prefill_call(length, act, g_pre, gate_pre, beta, gates, o_norm,
                  init_state, *, chunk: int, eps: float, interpret: bool):
    f32 = jnp.float32
    b, t, _ = g_pre.shape
    h, dk = init_state.shape[1:3]
    c, hb = chunk, PREFILL_HEADS
    nh = h // hb

    def live_chunk(i, n, len_ref):
        # chunks of padding stay on the last live one: nothing is fetched
        return jnp.minimum(n, jnp.maximum(len_ref[i] - 1, 0) // c)

    def rows(part):
        """The cell's heads of a chunk in column block ``part`` of an
        operand's blocks of ``H x 128``."""
        return pl.BlockSpec((None, c, hb * LANES), lambda i, j, n, len_ref: (
            i, live_chunk(i, n, len_ref), part * nh + j))

    state = pl.BlockSpec((None, hb, dk, LANES),
                         lambda i, j, n, len_ref: (i, j, 0, 0))
    tile = pltpu.VMEM((c * hb, LANES), f32)
    return pl.pallas_call(
        functools.partial(_prefill_kernel, c=c, hb=hb, eps=eps),
        name="dstpu_kda_prefill",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nh, t // c),
            in_specs=[
                rows(0), rows(1), rows(2),              # q | k | v of ``act``
                rows(0), rows(0),                       # the two gates
                pl.BlockSpec((None, c, h), lambda i, j, n, len_ref: (
                    i, live_chunk(i, n, len_ref), 0)),      # beta, every head
                pl.BlockSpec((2, hb, LANES),
                             lambda i, j, n, len_ref: (0, j, 0)),
                pl.BlockSpec((1, LANES), lambda i, j, n, len_ref: (0, 0)),
                state],
            out_specs=[pl.BlockSpec((None, c, hb * LANES),
                                    lambda i, j, n, len_ref: (i, n, j)),
                       state],
            scratch_shapes=[pltpu.VMEM((hb, LANES, dk), f32)] + [tile] * 9),
        out_shape=[jax.ShapeDtypeStruct((b, t, h * LANES), act.dtype),
                   jax.ShapeDtypeStruct((b, h, dk, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length, act, act, act, g_pre, gate_pre, beta.astype(f32), gates, o_norm,
      init_state.astype(f32))
