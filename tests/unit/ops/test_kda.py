"""Gated delta-rule operators (ops/kda.py) on the CPU: the chunked prompt form
against the token-by-token recurrence written out here (also under fast decay
and with ``beta`` near 2), the one-token ``jnp`` update as one step of that
recurrence, a layer's whole decode step folded into the Pallas call, in
interpret mode, against the carried convolution + the ``jnp`` update, and the
prompt block between the convolution and the output matmul as the Pallas call
``dstpu_kda_prefill``, in interpret mode with bf16 operands, against the split
route (``l2_normalize`` -> ``log_decay`` -> ``kda_chunked`` -> the head norm x
the gate) and the recurrence, at the cell's key and value width with one group
of heads."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda
from deepspeed_tpu.ops.ssm import causal_conv, slot_order

pytestmark = pytest.mark.quick


def _unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _inputs(b, t, h, dk, dv, seed=0, g_range=(0.01, 0.5), beta_range=(0.1, 1.9)):
    rng = np.random.RandomState(seed)
    q = _unit(rng.randn(b, t, h, dk)) * dk ** -0.5
    k = _unit(rng.randn(b, t, h, dk))
    v = rng.randn(b, t, h, dv)
    g = -rng.uniform(*g_range, (b, t, h, dk))
    beta = rng.uniform(*beta_range, (b, t, h))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _sequential(q, k, v, g, beta, s0=None, length=None):
    """S~ = diag(exp g) S;  S = S~ + beta k (v - S~^T k)^T;  o = S^T q."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, t, h, dk = k.shape
    dv = v.shape[-1]
    s = np.zeros((b, h, dk, dv)) if s0 is None else np.asarray(s0, np.float64)
    out = np.zeros((b, t, h, dv))
    for i in range(t if length is None else length):
        s = np.exp(g[:, i])[..., None] * s
        pred = np.einsum("bhkv,bhk->bhv", s, k[:, i])
        s = s + beta[:, i, :, None, None] * k[:, i, :, :, None] \
            * (v[:, i] - pred)[:, :, None, :]
        out[:, i] = np.einsum("bhkv,bhk->bhv", s, q[:, i])
    return out, s


@pytest.mark.parametrize("t,chunk", [(16, 8), (13, 8), (5, 8), (24, 4)])
def test_chunked_form_matches_the_recurrence(t, chunk):
    args = _inputs(2, t, 3, 8, 16)
    o, s = kda.kda_chunked(*args, chunk=chunk)
    o_ref, s_ref = _sequential(*args)
    np.testing.assert_allclose(np.asarray(o), o_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4, atol=1e-5)


def test_chunked_form_is_finite_and_right_under_fast_decay():
    """A channel whose ``a`` is 0.05 for a whole chunk: ``G`` reaches -190
    inside the chunk, ``k / exp(G)`` would overflow, the differences do not."""
    q, k, v, g, beta = _inputs(1, 64, 2, 8, 8, seed=3)
    g = g.at[:, :, :, 0].set(np.log(0.05)).at[:, :, 1, 3].set(np.log(0.05))
    o, s = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    o_ref, s_ref = _sequential(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), o_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4, atol=1e-5)


def test_chunked_form_holds_with_beta_near_two():
    """``kda_allow_neg_eigval``: ``I - beta k k^T`` then has an eigenvalue
    near -1 and the in-chunk solve alternates in sign."""
    args = _inputs(1, 48, 2, 8, 8, seed=4, g_range=(0.001, 0.05),
                   beta_range=(1.9, 1.999))
    o, s = kda.kda_chunked(*args, chunk=16)
    o_ref, s_ref = _sequential(*args)
    np.testing.assert_allclose(np.asarray(o), o_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4, atol=1e-4)


def test_chunked_form_continues_from_a_state_and_stops_at_the_length():
    """Two passes, the second from the first's state, give what one pass
    gives; positions behind ``length`` move neither the state nor what came
    before them."""
    q, k, v, g, beta = _inputs(1, 20, 2, 8, 8, seed=5)
    o_all, s_all = kda.kda_chunked(q, k, v, g, beta, chunk=8)
    first = tuple(x[:, :11] for x in (q, k, v, g, beta))
    rest = tuple(x[:, 11:] for x in (q, k, v, g, beta))
    o1, s1 = kda.kda_chunked(*first, chunk=8)
    o2, s2 = kda.kda_chunked(*rest, chunk=8, init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(o_all), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), rtol=1e-4,
                               atol=1e-5)
    o_cut, s_cut = kda.kda_chunked(q, k, v, g, beta, chunk=8, length=13)
    o_ref, s_ref = _sequential(q, k, v, g, beta, length=13)
    np.testing.assert_allclose(np.asarray(o_cut)[:, :13], o_ref[:, :13],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_cut), s_ref, rtol=1e-4, atol=1e-5)


def test_one_token_update_is_one_step_of_the_recurrence():
    q, k, v, g, beta = _inputs(3, 1, 2, 8, 8, seed=6)
    rng = np.random.RandomState(7)
    state = jnp.asarray(rng.randn(2, 3, 2, 8, 8), jnp.float32)
    active = jnp.asarray([True, False, True])
    o, new = kda.kda_update(state, 1, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0], active)
    o_ref, s_ref = _sequential(q, k, v, g, beta, s0=state[1])
    np.testing.assert_allclose(np.asarray(o)[[0, 2]], o_ref[[0, 2], 0],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new[1])[[0, 2]], s_ref[[0, 2]],
                               rtol=1e-5, atol=1e-6)
    # the idle slot and the other layer, bit for bit
    np.testing.assert_array_equal(np.asarray(new[1, 1]),
                                  np.asarray(state[1, 1]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert not np.asarray(o)[1].any()


def _step_operands(b, l, h, taps, seed):
    d = kda.LANES
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    stack = {"conv_w": jnp.asarray(rng.uniform(-0.5, 0.5, (l, taps, 3 * h * d)),
                                   jnp.float32),
             "A_log": jnp.asarray(np.log(rng.uniform(1, 16, (l, h))),
                                  jnp.float32),
             "dt_bias": f(l, h * d) - 3.0, "o_norm": 1.0 + 0.1 * f(l, d)}
    return (stack, f(b, 3 * h * d), f(b, h * d), jnp.asarray(
        rng.uniform(0.1, 1.9, (b, h)), jnp.float32), f(b, h * d),
        f(l, b, h, d, d), f(l, b, *kda.tail_shape(taps, h, d)))


def _split_step(stack, qkv, g_pre, beta, gate_pre, state, tail, layer, active,
                eps):
    """The same step out of XLA's own operations: the carried convolution,
    the norms, the ``jnp`` update, the head norm and the gate."""
    l, b, h, d, _ = state.shape
    taps = stack["conv_w"].shape[1]
    valid = active.astype(jnp.int32)
    act, tail1 = causal_conv(
        qkv[:, None], tail[layer].reshape(b, taps - 1, -1),
        stack["conv_w"][layer], jnp.zeros((3 * h * d,)), valid)
    q, k, v = (x.reshape(b, h, d) for x in jnp.split(act[:, 0], 3, axis=-1))
    q, k = kda.l2_normalize(q) * d ** -0.5, kda.l2_normalize(k)
    g = kda.log_decay(g_pre.reshape(b, h, d), stack["A_log"][layer],
                      stack["dt_bias"][layer].reshape(h, d))
    o, state = kda.kda_update(state, layer, q, k, v, g, beta, active)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * stack["o_norm"][layer] * jax.nn.sigmoid(gate_pre.reshape(b, h, d))
    return (o.reshape(b, h * d),
            state, tail.at[layer].set(tail1.reshape(tail.shape[1:])))


@pytest.mark.parametrize("active,layer", [((True, False, True), 1),
                                          ((False, False, False), 0),
                                          ((True, True, True), 0)])
def test_folded_step_in_interpret_mode_matches_the_split_route(active, layer):
    b, l, h, taps = 3, 2, 16, 4
    assert kda.supports(h, kda.LANES, kda.LANES, taps)
    stack, qkv, g_pre, beta, gate_pre, state, tail = _step_operands(
        b, l, h, taps, seed=11)
    active = jnp.asarray(active)
    want = _split_step(stack, qkv, g_pre, beta, gate_pre, state, tail, layer,
                       active, 1e-5)
    got = kda.kda_step(qkv, g_pre, beta, gate_pre, state, tail, layer,
                       kda.fold_weights(stack, h), slot_order(active), active,
                       eps=1e-5, interpret=True)
    for w, g_ in zip(want, got):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w), rtol=2e-5,
                                   atol=2e-5)
    # an inactive slot's state and tails, and the other layer: bit-identical
    idle = np.flatnonzero(~np.asarray(active))
    np.testing.assert_array_equal(np.asarray(got[1][layer])[idle],
                                  np.asarray(state[layer])[idle])
    np.testing.assert_array_equal(np.asarray(got[2][layer])[idle],
                                  np.asarray(tail[layer])[idle])
    np.testing.assert_array_equal(np.asarray(got[1][1 - layer]),
                                  np.asarray(state[1 - layer]))
    np.testing.assert_array_equal(np.asarray(got[2][1 - layer]),
                                  np.asarray(tail[1 - layer]))
    assert not np.asarray(got[0])[idle].any()


def test_supports_says_from_shapes_what_folds():
    assert kda.supports(64, 128, 128, 4)
    assert not kda.supports(4, 16, 16, 4)        # a tiny model's heads
    assert not kda.supports(64, 128, 64, 4)      # values narrower than a row
    assert not kda.supports(24, 128, 128, 4)     # heads that split no cell
    assert kda.tail_shape(4, 64, 128) == (3, 3, 64, 128)


COUNTERS = ("folded_step", "split_step", "chunked_block", "prefill_kernel")


def _counters():
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    return {n: reg.counter("kda/traced_" + n).value for n in COUNTERS}


def test_traced_counters_name_the_route():
    before = _counters()
    kda.count_step(True)
    kda.count_step(False)
    kda.count_chunked_block()
    kda.count_prefill_kernel()
    assert _counters() == {n: was + 1 for n, was in before.items()}


def test_the_kernel_has_a_stable_name_of_its_own():
    import inspect

    src = inspect.getsource(kda)
    assert src.count("pl.pallas_call(") == 2
    assert 'name="dstpu_kda_update"' in src
    assert 'name="dstpu_kda_prefill"' in src


# ------------------------------------------------ the prompt block's kernel
HB, D, CHUNK, EPS = kda.PREFILL_HEADS, kda.LANES, 64, 1e-5
BF16 = jnp.bfloat16


@functools.lru_cache(maxsize=None)
def _prefill():
    """One compilation a block length for every case below."""
    return jax.jit(functools.partial(kda.kda_prefill, chunk=CHUNK, eps=EPS,
                                     interpret=True))


def _state(seed, b=1):
    return jnp.asarray(np.random.RandomState(seed).randn(b, HB, D, D),
                       jnp.float32)


def _prompt_operands(b, t, seed, beta_range=(0.1, 1.9), bias=-3.0,
                     dtype=BF16):
    """What the mixer holds behind the convolution, in the stream's dtype:
    ``(act [B, T, 3 H 128], g_pre, beta float32, gate_pre)`` and the layer's
    small weights; ``bias`` shifts ``dt_bias``, the decay's speed."""
    rng = np.random.RandomState(seed)
    w = HB * D
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    blk = {"A_log": jnp.asarray(np.log(rng.uniform(1, 4, (HB,))), jnp.float32),
           "dt_bias": f(w) + bias, "o_norm": 1.0 + 0.1 * f(D)}
    beta = jnp.asarray(rng.uniform(*beta_range, (b, t, HB)), jnp.float32)
    return (f(b, t, 3 * w).astype(dtype), f(b, t, w).astype(dtype), beta,
            f(b, t, w).astype(dtype)), blk


def _split_operands(ops, blk):
    """``l2_normalize`` and ``log_decay`` as the split route applies them:
    ``(q, k, v, g, beta)``, what ``kda_chunked`` and ``_sequential`` take."""
    act, g_pre, beta, _ = ops
    b, t, _ = g_pre.shape
    q, k, v = (x.reshape(b, t, HB, D) for x in jnp.split(act, 3, -1))
    g = kda.log_decay(g_pre.reshape(b, t, HB, D), blk["A_log"],
                      blk["dt_bias"].reshape(HB, D))
    return (kda.l2_normalize(q) * D ** -0.5, kda.l2_normalize(k),
            v.astype(jnp.float32), g, beta)


def _head_norm_and_gate(o, ops, blk):
    """``rms_norm(o; o_norm) * sigmoid(gate_pre)`` in float64 -> ``[B, T, H x
    128]``, not rounded."""
    o = np.asarray(o, np.float64)
    gate = np.asarray(ops[3], np.float64).reshape(o.shape)
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + EPS) \
        * np.asarray(blk["o_norm"], np.float64) / (1.0 + np.exp(-gate))
    return o.reshape(o.shape[:2] + (HB * D,))


def _assert_rounded_once(o, want, slack=2e-5):
    """``o`` in the stream's dtype lies within HALF a unit of its last place
    of the unrounded ``want`` (and the float32 arithmetic's ``slack``):
    rounded once, and from float32. A second rounding on the way (an ``o``
    stored in bf16 before the norm, a gate applied in bf16) doubles that."""
    bits = 7 if o.dtype == BF16 else 23
    o = np.asarray(o.astype(jnp.float32), np.float64)
    unit = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - bits)
    room = unit / 2 + slack * (1 + np.abs(want))
    worst = np.max(np.abs(o - want) - room)
    assert worst <= 0, worst


def _assert_prefill(ops, blk, s0, length, slack=2e-5):
    """The folded call against the split route (``l2_normalize`` ->
    ``log_decay`` -> ``kda_chunked`` -> the head norm x the gate) and against
    the recurrence up to ``length``; zeros behind the last chunk that holds a
    real position."""
    o, s = _prefill()(*ops, kda.fold_layer(blk), s0,
                      length=jnp.asarray([length]))
    assert o.dtype == ops[0].dtype and o.shape == ops[1].shape
    assert s.dtype == jnp.float32
    assert np.isfinite(np.asarray(o.astype(jnp.float32))).all() \
        and np.isfinite(np.asarray(s)).all()
    args = _split_operands(ops, blk)
    o_c, s_c = kda.kda_chunked(*args, chunk=CHUNK, init_state=s0,
                               length=length)
    o_r, s_r = _sequential(*args, s0=s0, length=length)
    live = -(-length // CHUNK) * CHUNK
    # inside a live chunk the padded positions read the state as the chunked
    # form's do
    _assert_rounded_once(o[:, :live], _head_norm_and_gate(o_c, ops, blk)[
        :, :live], slack)
    _assert_rounded_once(o[:, :length], _head_norm_and_gate(o_r, ops, blk)[
        :, :length], slack)
    assert not np.asarray(o[:, live:].astype(jnp.float32)).any()
    s = np.asarray(s)
    np.testing.assert_allclose(s, np.asarray(s_c), rtol=1e-4, atol=slack)
    np.testing.assert_allclose(s, s_r, rtol=1e-4, atol=slack)
    return o, s


@pytest.mark.parametrize("t,length,carried", [
    (128, 128, False), (128, 128, True), (128, 37, True), (128, 64, True),
    (128, 1, False), (256, 70, True), (256, 192, False)],
    ids=["fresh", "carried", "inside-a-sub-chunk", "on-a-chunk-edge",
         "one-position", "chunks-of-padding", "a-chunk-of-padding"])
def test_prefill_kernel_matches_the_split_route_and_the_recurrence(
        t, length, carried):
    ops, blk = _prompt_operands(1, t, seed=21)
    s0 = _state(22) if carried else jnp.zeros((1, HB, D, D), jnp.float32)
    _assert_prefill(ops, blk, s0, length)


def test_prefill_kernel_leaves_a_row_of_padding_alone():
    """``length`` 0: zeros out, the state bit for bit, nothing non-finite
    even where the operands of the padding are."""
    (act, g_pre, beta, gate_pre), blk = _prompt_operands(1, 128, seed=23)
    act = act.at[:, 5].set(jnp.inf)
    s0 = _state(24)
    o, s = _prefill()(act, g_pre, beta, gate_pre, kda.fold_layer(blk),
                      s0, length=jnp.asarray([0]))
    assert not np.asarray(o.astype(jnp.float32)).any()
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))


def test_prefill_kernel_is_finite_and_right_under_fast_decay():
    """The case the chunked form is held to: a channel near ``log 0.05`` a
    position for a whole chunk (``G`` to -190), so that neither a sub-chunk's
    own ``exp(G_i - G_j)`` nor the scalings between sub-chunks may be formed
    as a quotient."""
    (act, g_pre, beta, gate_pre), blk = _prompt_operands(1, 128, seed=25)
    # g = -exp(A_log) softplus(3) = -3.05 on channel 0 of every head, and on
    # channel 3 of head 1 for the first chunk
    blk["A_log"] = jnp.zeros_like(blk["A_log"])
    blk["dt_bias"] = blk["dt_bias"].reshape(HB, D).at[:, 0].set(0.0).at[
        1, 3].set(0.0).reshape(-1)
    g_pre = g_pre.reshape(1, 128, HB, D).at[:, :, :, 0].set(3.0).at[
        :, :64, 1, 3].set(3.0).reshape(1, 128, HB * D)
    _assert_prefill((act, g_pre, beta, gate_pre), blk, _state(26), 128)


def test_prefill_kernel_holds_with_beta_near_two():
    ops, blk = _prompt_operands(1, 128, seed=27, beta_range=(1.9, 1.999),
                                bias=-6.0)
    _assert_prefill(ops, blk, _state(28), 128, slack=2e-4)


def test_prefill_kernel_two_blocks_in_a_row_equal_one_call():
    """A token block continues from the state the one before it returned, as
    ``forward_with_cache`` walks a long prompt."""
    ops, blk = _prompt_operands(1, 256, seed=29)
    weights = kda.fold_layer(blk)
    s0 = _state(30)
    length = 200
    o_all, s_all = _prefill()(*ops, weights, s0, length=jnp.asarray([length]))
    o1, s1 = _prefill()(*(x[:, :128] for x in ops), weights, s0,
                        length=jnp.asarray([128]))
    o2, s2 = _prefill()(*(x[:, 128:] for x in ops), weights, s1,
                        length=jnp.asarray([length - 128]))
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([o1, o2], 1).astype(jnp.float32)),
        np.asarray(o_all.astype(jnp.float32)))
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all), rtol=1e-5,
                               atol=1e-6)


def test_prefill_kernel_takes_rows_of_their_own_lengths():
    ops, blk = _prompt_operands(2, 128, seed=31)
    s0 = _state(32, b=2)
    o, s = _prefill()(*ops, kda.fold_layer(blk), s0,
                      length=jnp.asarray([128, 50]))
    args = _split_operands(ops, blk)
    for row, length in enumerate((128, 50)):
        o_r, s_r = _sequential(*(x[row:row + 1] for x in args),
                               s0=s0[row:row + 1], length=length)
        one = tuple(x[row:row + 1] for x in ops)
        _assert_rounded_once(o[row:row + 1, :length], _head_norm_and_gate(
            o_r, one, blk)[:, :length])
        np.testing.assert_allclose(np.asarray(s)[row], s_r[0], rtol=1e-4,
                                   atol=2e-5)


def test_prefill_kernel_keeps_a_zero_row_zero_under_the_l2_norm():
    """``|x|^2 + 1e-6`` under the root: a head whose query, key or all three
    are zero at a position (a convolution over zeros) divides nothing by
    zero; a zero key writes nothing, a zero query reads nothing."""
    (act, g_pre, beta, gate_pre), blk = _prompt_operands(1, 128, seed=33)
    w = HB * D
    act = act.reshape(1, 128, 3, HB, D).at[:, 7, 0, 2].set(0.0).at[
        :, 9, 1, 3].set(0.0).at[:, 70, :, 5].set(0.0).at[:, 100].set(
            0.0).reshape(1, 128, 3 * w)
    o, _ = _assert_prefill((act, g_pre, beta, gate_pre), blk, _state(34), 128)
    o = np.asarray(o.astype(jnp.float32)).reshape(128, HB, D)
    # a zero query reads nothing: the head norm of zeros is zero
    assert not o[7, 2].any() and not o[70, 5].any() and not o[100].any()
    assert o[9, 3].any()


@pytest.mark.parametrize("dtype", [BF16, jnp.float32], ids=["bf16", "f32"])
def test_prefill_kernel_rounds_its_result_once(dtype):
    """``o`` leaves the call in the stream's dtype, rounded from float32
    behind the norm and the gate, where ``.astype(qkv.dtype)`` rounds the
    split route's; with float32 operands nothing is rounded at all. Against
    the split route's own float32 result the two agree to a unit of the last
    place, and all but a few elements bit for bit."""
    ops, blk = _prompt_operands(1, 128, seed=35, dtype=dtype)
    s0 = _state(36)
    o, _ = _assert_prefill(ops, blk, s0, 128)
    o_c, _ = kda.kda_chunked(*_split_operands(ops, blk), chunk=CHUNK,
                             init_state=s0, length=128)
    gate = jax.nn.sigmoid(ops[3].reshape(o_c.shape).astype(jnp.float32))
    want = (o_c * jax.lax.rsqrt(jnp.mean(o_c * o_c, -1, keepdims=True) + EPS)
            * blk["o_norm"] * gate).astype(dtype).reshape(o.shape)
    o, want = (np.asarray(x.astype(jnp.float32)) for x in (o, want))
    if dtype == BF16:
        np.testing.assert_allclose(o, want, rtol=2.0 ** -7, atol=1e-6)
        assert (o == want).mean() > 0.98
    else:
        np.testing.assert_allclose(o, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("shape,fits", [
    ((2048, 64, 128, 128, 64), True),      # the cell's token block
    ((128, 8, 128, 128, 64), True),        # one group of heads, two chunks
    ((16, 4, 16, 16, 8), False),           # a tiny model: kda_chunk 8
    ((2048, 64, 128, 64, 64), False),      # values narrower than a row
    ((2048, 60, 128, 128, 64), False),     # heads that split no group
    ((2000, 64, 128, 128, 64), False),     # a block that is no whole chunks
    ((32, 64, 128, 128, 64), False),       # shorter than a chunk
    ((2048, 64, 128, 128, 24), False),     # a chunk of no whole sub-chunks
    ((2048, 64, 128, 128, 256), False)],   # a chunk wider than a row of lanes
    ids=str)
def test_supports_prefill_says_from_shapes_what_routes(shape, fits):
    assert kda.supports_prefill(*shape) is fits


@pytest.fixture
def kernel_route(monkeypatch):
    """The model picks the kernel where ``jax.default_backend()`` says tpu
    (steered here, not through an option of the program); the call itself
    runs in the Pallas interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kda, "kda_prefill", functools.partial(
        kda.kda_prefill, interpret=True))


def _mixer(kda_chunk=CHUNK, heads=HB, dim=D):
    """``SolarKdaModel._kda_mixer`` on one layer's small weights, a prompt
    block of two chunks in rows of their own lengths."""
    from deepspeed_tpu.models.solar_kda import SolarKdaConfig, SolarKdaModel

    c = SolarKdaConfig.tiny(kda_chunk=kda_chunk)
    c.kda_heads, c.kda_head_dim = heads, dim
    model = SolarKdaModel(c, compute_dtype=jnp.float32)
    b, t, w, lk = 2, 2 * kda_chunk, heads * dim, 2
    rng = np.random.RandomState(41)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    blk = {"conv_w": jnp.asarray(rng.uniform(-0.5, 0.5, (c.kda_conv, 3 * w)),
                                 jnp.float32),
           "A_log": jnp.asarray(np.log(rng.uniform(1, 16, (heads,))),
                                jnp.float32),
           "dt_bias": f(w) - 3.0, "o_norm": 1.0 + 0.1 * f(dim)}
    operands = (f(b, t, 3 * w), f(b, t, w), jnp.asarray(
        rng.uniform(0.1, 1.9, (b, t, heads)), jnp.float32), f(b, t, w))
    state = f(lk, b, heads, dim, dim)
    tail = f(lk, b, *kda.tail_shape(c.kda_conv, heads, dim))

    def run(cached=True):
        before = _counters()
        out = model._kda_mixer(
            *operands, blk, state if cached else None,
            tail if cached else None, 1, jnp.asarray([3, 0]),
            jnp.asarray([t, kda_chunk + 5]), None)
        return out, {n: v - before[n] for n, v in _counters().items()}

    return run


def test_the_model_takes_the_kernel_for_a_cached_prompt_block_on_a_tpu(
        kernel_route, monkeypatch):
    run = _mixer()
    (o, state, tail), counted = run()
    assert counted == {"folded_step": 0, "split_step": 0, "chunked_block": 0,
                       "prefill_kernel": 1}
    monkeypatch.undo()                    # a CPU: the chunked form
    (o_c, state_c, tail_c), counted = run()
    assert counted == {"folded_step": 0, "split_step": 0, "chunked_block": 1,
                       "prefill_kernel": 0}
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(o_c[0]),
                               rtol=1e-4, atol=1e-4)
    live = CHUNK + 5
    np.testing.assert_allclose(np.asarray(o[1, :live]),
                               np.asarray(o_c[1, :live]), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_c),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(tail_c))


@pytest.mark.parametrize("why,kw,cached", [
    ("no cache", {}, False), ("kda_chunk 8", {"kda_chunk": 8}, True),
    ("a tiny model's heads", {"kda_chunk": 16, "heads": 4, "dim": 16}, True)])
def test_the_model_keeps_the_chunked_form_where_the_kernel_does_not_fit(
        kernel_route, why, kw, cached):
    """Training and ``forward_hidden`` carry no cache and need a VJP; a tiny
    configuration's shapes fit no tile."""
    _, counted = _mixer(**kw)(cached)
    assert counted == {"folded_step": 0, "split_step": 0, "chunked_block": 1,
                       "prefill_kernel": 0}, why
