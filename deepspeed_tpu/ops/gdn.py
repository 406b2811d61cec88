"""Gated delta-rule linear attention with ONE decay a head (GatedDeltaNet) and
fewer key heads than value heads: a layer's one-token decode step, the
one-token update alone, and the chunked prompt form.

The recurrence is ops/kda.py's with the decay constant over a head's
channels. Per VALUE head ``h`` with state ``S [K, V]`` (float32), the query
and key of key head ``h // (Hv / Hk)`` (both L2-normalised, the query also
times ``K ** -0.5``), value ``v_t [V]``, log decay ``g_t <= 0`` a SCALAR and
``beta_t`` in (0, 1)::

    S~  = exp(g_t) S_{t-1}
    S_t = S~ + beta_t k_t (v_t - S~^T k_t)^T
    o_t = S_t^T q_t

**Why a file beside ops/kda.py and not that file widened.** Its two kernels'
index maps take a head a row of EVERY operand, keys and values one to one, and
a decay a channel (``g [.., H, K]``); its prompt forms build pairwise decays
element by element, ``[C, C, K]`` a head, because a per-channel ratio cannot
be pulled out of the key product. Here a key head's row serves two value
heads' tiles, the decay is a number a head, and the pairwise decays are ``[C,
C]``: the prompt form is matmuls alone. Widened, ``kda.py`` would carry both
sets of index maps and both prompt forms behind a switch and another family's
compiled programs would ride on it; apart, that family's programs stay the
text they were. What is the same is taken from there: the tile's update in the
step kernel is that file's body, :func:`kda.l2_normalize` and the slot walk.

Entry points, by serving phase:

  * :func:`gdn_step` — ONE token for every slot, everything a layer does
    between its projections and its output matmul, in the one Pallas call
    named ``dstpu_gdn_update``, a grid cell ``BLOCK_HEADS`` value heads of an
    ACTIVE slot: the four-tap convolution with SiLU over ``q | k | v`` (the
    new tail written in place), the L2 norms, ``-exp(A_log) softplus(. +
    dt_bias)`` and ``sigmoid`` for the head's two scalars, the state's tiles
    through VMEM once (decay, ``S~^T k``, the rank-one write, ``S^T q`` while
    the tile is there) in place on the stacked ``[L, slots, Hv, K, V]``
    state, the head-wise RMS norm and the scaled sigmoid gate. A slot's
    ``q | k | v`` row and its tails are ONE block a slot (they are small, and
    a cell's three row ranges of them are not one range): fetched once a
    slot, the tails written back once behind the slot's last cell. An
    inactive slot's state and tails are neither read nor written. Taken
    where :func:`supports` says the shapes fit.
  * :func:`gdn_update` — the update and read-out alone in plain ``jnp``, for
    a CPU, ``generate()`` and shapes that do not fold; the folded call is
    tested against the carried convolution + this.
  * :func:`gdn_chunked` — a whole prompt block in the chunked form, XLA's own
    operations: in a chunk of ``C`` positions the pairwise decays ``exp(G_i -
    G_j)`` are one ``[C, C]`` matrix a head that multiplies the key products,
    the in-chunk dependence is a unit lower-triangular solve, and a short
    ``lax.scan`` carries the state from chunk to chunk. Positions at or
    beyond ``length`` get ``g = 0`` and ``beta = 0``: the state stops at the
    true length.

The step is bound by memory (the state is read and written once a token, 0.87
FLOPs a byte). The kernel serves only: no VJP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.kda import L2_EPS, LANES, _columns, default_route, l2_normalize

__all__ = ["default_route", "l2_normalize", "supports", "count_step",
           "count_chunked_block", "record_traced", "log_decay", "gdn_update", "conv_rows",
           "tail_shape", "fold_weights", "gdn_step", "gdn_chunked"]

# value heads of one slot a grid cell works on: with two value heads a key
# head, 16 key heads, one packed tile of the bf16 rows of ``q`` and of ``k``,
# and 2 MB of float32 state a block (in and out, double-buffered: 8 MB of
# VMEM)
BLOCK_HEADS = 32
_HIGHEST = jax.lax.Precision.HIGHEST


def supports(key_heads: int, value_heads: int, key_dim: int, value_dim: int,
             taps: int) -> bool:
    """Whether a layer's decode step fits the folded call: a head's keys and
    values are each one row of lanes, the value heads split into whole cells,
    and a cell's key heads are whole packed tiles of 16 rows."""
    if key_heads < 1 or value_heads % key_heads:
        return False
    group = value_heads // key_heads
    return (key_dim == LANES and value_dim == LANES and taps >= 2
            and value_heads % BLOCK_HEADS == 0 and BLOCK_HEADS % group == 0
            and (BLOCK_HEADS // group) % 16 == 0)


_TRACED = ("folded_step", "split_step", "chunked_block")


def _count(name: str) -> None:
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = {n: reg.counter("gdn/traced_" + n) for n in _TRACED}
    counters[name].inc()


def count_step(folded: bool) -> None:
    """Say in the program's registry which way a one-token layer was traced:
    folded into the kernel, or split into XLA's own operations around
    :func:`gdn_update`. All three counters exist from the first call on."""
    _count("folded_step" if folded else "split_step")


def count_chunked_block() -> None:
    """A prompt block traced in the chunked form."""
    _count("chunked_block")


def record_traced(telemetry) -> None:
    """The ``gdn/traced_*`` counters brought level in ``telemetry``
    (telemetry/registry.level_counters)."""
    from deepspeed_tpu.telemetry.registry import level_counters

    level_counters(telemetry, ["gdn/traced_" + n for n in _TRACED])


def log_decay(a, a_log, dt_bias):
    """``g = -exp(A_log) softplus(a + dt_bias) <= 0``, one number a value
    head: ``a [..., H]``, ``a_log``, ``dt_bias [H]`` -> float32 ``[..., H]``."""
    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))


def _per_value_head(x, value_heads: int, axis: int):
    """Key head ``j``'s row for value heads ``j r .. j r + r - 1``."""
    return jnp.repeat(x, value_heads // x.shape[axis], axis=axis)


# ------------------------------------------------------- one-token update
def gdn_update(state, layer, q, k, v, g, beta, active=None):
    """One token for every slot against the full stacked state, plain ``jnp``.

    ``state [L, B, Hv, K, V]`` float32 (updated at ``layer`` only); ``q, k [B,
    Hk, K]`` normalised; ``v [B, Hv, V]``; ``g``, ``beta [B, Hv]``; ``active
    [B]`` bool (``None``: all): an inactive slot's state does not move and
    its output is zero. -> ``(o [B, Hv, V] float32, state)``."""
    f32 = jnp.float32
    hv = v.shape[1]
    old = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = old.astype(f32) * jnp.exp(g.astype(f32))[..., None, None]
    k = _per_value_head(k.astype(f32), hv, 1)
    q = _per_value_head(q.astype(f32), hv, 1)
    pred = jnp.sum(s * k[..., None], axis=-2)                   # [B, Hv, V]
    delta = beta.astype(f32)[..., None] * (v.astype(f32) - pred)
    s = s + k[..., None] * delta[..., None, :]
    o = jnp.sum(s * q[..., None], axis=-2)
    if active is not None:
        o = jnp.where(active[:, None, None], o, 0.0)
        s = jnp.where(active[:, None, None, None], s, old)
    return o, jax.lax.dynamic_update_index_in_dim(
        state, s.astype(state.dtype), layer, 0)


# ------------------------------------------- a layer's decode step, folded
def conv_rows(key_heads: int, value_heads: int) -> int:
    """Rows of lanes of a token's ``q | k | v``: a head a row."""
    return 2 * key_heads + value_heads


def tail_shape(taps: int, key_heads: int, value_heads: int, key_dim: int):
    """The trailing dimensions of the cache leaf that carries the
    convolution's tail, ``[L, slots, ...]``: the last ``taps - 1`` inputs of
    ``q | k | v``, a head a row of lanes in the projection's own column
    order, so that a slot's block is whole packed tiles."""
    return (taps - 1, conv_rows(key_heads, value_heads), key_dim)


def fold_weights(stack, key_heads: int, value_heads: int):
    """The layer stack's small weights as the folded call reads them through
    its index maps, float32, made once a step: the taps ``[L, taps, rows,
    128]``, ``A = -exp(A_log)`` beside ``dt_bias``, each a head spread over
    its row's lanes ``[L, 2, Hv, 128]``, the head norm's weight ``1 + w_o``
    ``[L, 1, 128]``."""
    f32 = jnp.float32
    lg, taps, _ = stack["conv_w"].shape
    spread = (lg, value_heads, LANES)
    a = -jnp.exp(stack["A_log"].astype(f32))
    return {
        "conv_w": stack["conv_w"].astype(f32).reshape(
            lg, taps, conv_rows(key_heads, value_heads), LANES),
        "heads": jnp.stack(
            [jnp.broadcast_to(a[..., None], spread),
             jnp.broadcast_to(stack["dt_bias"].astype(f32)[..., None],
                              spread)], axis=1),
        "o_norm": 1.0 + stack["o_norm"].astype(f32).reshape(lg, 1, LANES),
    }


def _step_kernel(layer_ref, order_ref, n_ref, zx_ref, ab_ref, og_ref, cw_ref,
                 hp_ref, nw_ref, s_ref, t_ref, y_ref, o_ref, u_ref, y_rows,
                 *, hb: int, kb: int, key_heads: int, taps: int, eps: float,
                 gate_scale: float):
    """``hb`` value heads (``kb`` key heads) of one slot, one token, between
    the projections and the output matmul. A head is a row of 128 lanes in
    every operand but the state, whose tile a value head is ``[128 keys, 128
    values]``; the slot's ``q | k | v`` and tails are whole here, a cell takes
    its three row ranges of them."""
    del layer_ref, order_ref
    i, j = pl.program_id(0), pl.program_id(1)
    n_active = n_ref[0]
    f32 = jnp.float32
    cdt = zx_ref.dtype
    group = hb // kb

    @pl.when(i < n_active)
    def _live():
        def conv(first, count):
            """The carried convolution with SiLU over ``count`` rows from
            ``first``; the tails of those rows move on by one tap."""
            rows = pl.ds(pl.multiple_of(first, 16), count)
            x_in = zx_ref[rows, :]
            acc = x_in.astype(f32) * cw_ref[taps - 1, rows, :]
            for tap in range(taps - 1):
                acc = acc + t_ref[tap, rows, :].astype(f32) \
                    * cw_ref[tap, rows, :]
            for tap in range(taps - 2):
                u_ref[tap, rows, :] = t_ref[tap + 1, rows, :]
            u_ref[taps - 2, rows, :] = x_in
            return jax.nn.silu(acc).astype(cdt).astype(f32)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

        q = unit(conv(j * kb, kb)) * (LANES ** -0.5)
        k = unit(conv(key_heads + j * kb, kb))
        v = conv(2 * key_heads + j * hb, hb)
        # a head's two numbers, spread over its row's lanes
        g = hp_ref[0] * jax.nn.softplus(ab_ref[0].astype(f32) + hp_ref[1])
        beta = jax.nn.sigmoid(ab_ref[1].astype(f32))
        q_c, k_c, a_c = (_columns(q, kb), _columns(k, kb),
                         _columns(jnp.exp(g), hb))
        for h in range(hb):
            kh = h // group
            kc = k_c[:, kh:kh + 1]                           # [128 keys, 1]
            s = s_ref[h].astype(f32) * a_c[:, h:h + 1]
            pred = jnp.sum(s * kc, axis=0, keepdims=True)    # [1, 128 values]
            delta = beta[h:h + 1] * (v[h:h + 1] - pred)
            s = s + kc * delta
            o_ref[h] = s.astype(o_ref.dtype)
            y_rows[h:h + 1, :] = jnp.sum(s * q_c[:, kh:kh + 1], axis=0,
                                         keepdims=True)
        # the head-wise RMS norm and the scaled sigmoid gate
        y = y_rows[...]
        var = jnp.sum(y * y, axis=-1, keepdims=True) / LANES
        y = y * jax.lax.rsqrt(var + eps) * nw_ref[...]
        y_ref[...] = (y * (gate_scale * jax.nn.sigmoid(
            og_ref[...].astype(f32)))).astype(y_ref.dtype)

    # nothing active: every cell sits on one block, which is written back
    # once at the end, so it has to hold what was read
    @pl.when((n_active == 0) & (i == 0))
    def _keep():
        o_ref[...] = s_ref[...]
        u_ref[...] = t_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


def gdn_step(qkv, ab, gate_pre, state, tail, layer, weights, walk, active, *,
             eps: float, gate_scale: float,
             interpret: Optional[bool] = None):
    """One token for every slot through a layer's mixer, in the one Pallas
    call (see the module's head).

    ``qkv [B, (2 Hk + Hv) K]``: the projection's result, ``q | k | v``; ``ab
    [B, 2, Hv]``: the decay's and beta's inputs before ``softplus`` /
    ``sigmoid``; ``gate_pre [B, Hv V]``: the output gate before ``sigmoid``;
    ``state [L, B, Hv, K, V]`` float32 and ``tail [L, B, taps - 1, 2 Hk + Hv,
    K]`` (:func:`tail_shape`), both updated at ``layer`` in place, the active
    slots' blocks only; ``weights``: :func:`fold_weights`; ``walk``:
    ``ops/ssm.slot_order`` of ``active [B]``. Returns ``(o [B, Hv V]`` in
    ``qkv``'s dtype, what the output matmul takes, zero for a slot that did
    not run``, state, tail)``."""
    l, b, hv, dk, dv = state.shape
    taps, rows = weights["conv_w"].shape[1:3]
    key_heads = (rows - hv) // 2
    hb = BLOCK_HEADS
    kb = hb * key_heads // hv
    nh = hv // hb
    order, n_active = walk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def cell(i, j, layer_ref, order_ref, n_ref):
        slot = order_ref[jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0))]
        return slot, jnp.where(i < n_ref[0], j, nh - 1)

    def heads(lead, per_layer: bool):
        """``lead + (hb, 128)``: the cell's value heads of the walk's layer
        or of the cell's slot."""
        def index(i, j, *refs):
            slot, jh = cell(i, j, *refs)
            return (refs[0][0] if per_layer else slot,) \
                + (0,) * len(lead) + (jh, 0)
        return pl.BlockSpec((None,) + lead + (hb, LANES), index)

    def of_slot(i, j, *refs):
        return (cell(i, j, *refs)[0], 0, 0)

    def state_index(i, j, *refs):
        slot, jh = cell(i, j, *refs)
        return (refs[0][0], slot, jh, 0, 0)

    state_spec = pl.BlockSpec((None, None, hb, dk, dv), state_index)
    tail_spec = pl.BlockSpec(
        (None, None, taps - 1, rows, LANES),
        lambda i, j, *refs: (refs[0][0], cell(i, j, *refs)[0], 0, 0, 0))
    y, state, tail = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, kb=kb, key_heads=key_heads,
                          taps=taps, eps=eps, gate_scale=gate_scale),
        name="dstpu_gdn_update",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nh),
            in_specs=[
                pl.BlockSpec((None, rows, LANES), of_slot),     # q | k | v
                heads((2,), False),                 # decay's, beta's inputs
                heads((), False),                   # the output's gate
                pl.BlockSpec((None, taps, rows, LANES),
                             lambda i, j, *refs: (refs[0][0], 0, 0, 0)),
                heads((2,), True),                  # A, dt_bias
                pl.BlockSpec((None, 1, LANES),
                             lambda i, j, *refs: (refs[0][0], 0, 0)),
                state_spec, tail_spec,
            ],
            out_specs=[heads((), False), state_spec, tail_spec],
            scratch_shapes=[pltpu.VMEM((hb, LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hv, LANES), qkv.dtype),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        # operands: layer, order, n_active, qkv, ab, gate, taps, heads, norm,
        # state, tail
        input_output_aliases={9: 1, 10: 2},
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, n_active,
      qkv.reshape(b, rows, LANES),
      jnp.broadcast_to(ab[..., None], (b, 2, hv, LANES)),
      gate_pre.reshape(b, hv, LANES), weights["conv_w"], weights["heads"],
      weights["o_norm"], state, tail)
    # blocks of slots that did not run were never written
    y = jnp.where(active[:, None, None], y, 0).reshape(b, hv * dv)
    return y, state, tail


# ------------------------------------------------------ chunked prompt form
def gdn_chunked(q, k, v, g, beta, *, chunk: int, init_state=None,
                length=None):
    """A block of ``T`` positions in the chunked form, the recurrence's own
    numbers, matmuls alone.

    ``q, k [B, T, Hk, K]`` normalised; ``v [B, T, Hv, V]``; ``g``, ``beta [B,
    T, Hv]``; ``init_state [B, Hv, K, V]`` (zeros if ``None``); ``length [B]``
    or scalar: positions at or beyond it move nothing and the returned state
    is the one at ``length``. Returns ``(o [B, T, Hv, V] float32, state [B,
    Hv, K, V] float32)``.

    In a chunk from state ``S_0``, with ``G_i = sum_{j <= i} g_j`` and ``r_ij
    = exp(G_i - G_j)``, one number a pair of positions::

        L_ij = beta_i r_ij (k_i . k_j)  (j < i)
        (I + L) U = diag(beta) (V - diag(exp(G)) K S_0)
        o_i = exp(G_i) q_i^T S_0 + sum_{j <= i} r_ij (q_i . k_j) u_j
        S_C = exp(G_C) S_0 + sum_j exp(G_C - G_j) k_j u_j^T

    Every exponent is a difference that is at most 0, so nothing overflows
    however fast a head decays. What does not need ``S_0`` (the two in-chunk
    score matrices and ``(I + L)^-1`` applied to ``diag(beta) [V | exp(G)
    K]``) is computed for all chunks of the block at once; the scan over
    chunks is three small matmuls a step."""
    f32 = jnp.float32
    b, t, _, dk = k.shape
    hv, dv = v.shape[2:]
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    q, k = _per_value_head(q, hv, 2), _per_value_head(k, hv, 2)
    if length is not None:
        live = jnp.arange(t)[None, :, None] < jnp.reshape(
            jnp.asarray(length, jnp.int32), (-1, 1, 1))
        g = jnp.where(live, g, 0.0)
        beta = jnp.where(live, beta, 0.0)
    c = min(chunk, t)
    pad = -t % c
    if pad:   # g = 0, beta = 0 there: neither state nor earlier outputs move
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    n = (t + pad) // c

    def chunks(x):        # [B, T, H, ...] -> [B, N, H, C, ...]
        return jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 3, 2)

    def dot(eq, x, y):
        return jnp.einsum(eq, x, y, precision=_HIGHEST,
                          preferred_element_type=f32)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=3)                          # [B, N, H, C]
    # pairwise decays from differences; above the diagonal exp(-inf) = 0
    tri = jnp.tril(jnp.ones((c, c), bool))
    ratio = jnp.exp(jnp.where(tri, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                 # [.., C, C]
    a_kk = dot("bnhik,bnhjk->bnhij", k, k) * ratio
    a_qk = dot("bnhik,bnhjk->bnhij", q, k) * ratio
    lower = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), a_kk, 0.0) \
        * beta[..., None]
    grown = jnp.exp(cum)[..., None]                      # exp(G)
    rhs = jnp.concatenate([v, k * grown], axis=-1) * beta[..., None]
    solved = jax.lax.linalg.triangular_solve(
        lower + jnp.eye(c, dtype=f32), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    u0, w = solved[..., :dv], solved[..., dv:]           # [.., C, V], [.., C, K]
    q_in = q * grown
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]  # exp(G_C - G) K
    across = jnp.exp(cum[..., -1])                       # [B, N, H]
    s0 = jnp.zeros((b, hv, dk, dv), f32) if init_state is None \
        else init_state.astype(f32)

    def step(s, xs):
        u0_n, w_n, qin_n, aqk_n, kout_n, across_n = xs
        u = u0_n - dot("bhck,bhkv->bhcv", w_n, s)
        o = dot("bhck,bhkv->bhcv", qin_n, s) + dot("bhcj,bhjv->bhcv", aqk_n, u)
        s = s * across_n[..., None, None] + dot("bhck,bhcv->bhkv", kout_n, u)
        return s, o

    s_last, o = jax.lax.scan(
        step, s0, tuple(jnp.moveaxis(x, 1, 0) for x in
                        (u0, w, q_in, a_qk, k_out, across)))
    # [N, B, H, C, V] -> [B, T, H, V]
    o = jnp.moveaxis(o, 0, 1).transpose(0, 1, 3, 2, 4).reshape(
        b, t + pad, hv, dv)
    return o[:, :t], s_last
