"""Scalar-decay gated delta-rule operators (ops/gdn.py) on the CPU: the chunked
prompt form against the position-by-position recurrence written out here (with
decays near 0 and near 1, ``beta`` at both ends, a padded tail whose state
stops at the true length), the one-token ``jnp`` update as one step of that
recurrence, and a layer's whole decode step folded into the Pallas call
``dstpu_gdn_update``, in interpret mode, against the carried convolution + the
``jnp`` update, with a key head shared by two value heads and an inactive slot
left unread and unwritten."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gdn
from deepspeed_tpu.ops.ssm import causal_conv, slot_order

pytestmark = pytest.mark.quick


def _unit(x):
    return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _inputs(b, t, hk, hv, dk, dv, seed=0, g_range=(0.01, 0.5),
            beta_range=(0.05, 0.95)):
    rng = np.random.RandomState(seed)
    q = _unit(rng.randn(b, t, hk, dk)) * dk ** -0.5
    k = _unit(rng.randn(b, t, hk, dk))
    v = rng.randn(b, t, hv, dv)
    g = -rng.uniform(*g_range, (b, t, hv))
    beta = rng.uniform(*beta_range, (b, t, hv))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _sequential(q, k, v, g, beta, s0=None, length=None):
    """S~ = exp(g) S;  S = S~ + beta k (v - S~^T k)^T;  o = S^T q; key head j
    serves value heads j r .. j r + r - 1."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    b, t, hk, dk = k.shape
    hv, dv = v.shape[2:]
    q, k = (np.repeat(x, hv // hk, axis=2) for x in (q, k))
    s = np.zeros((b, hv, dk, dv)) if s0 is None else np.asarray(s0, np.float64)
    out = np.zeros((b, t, hv, dv))
    for i in range(t if length is None else length):
        s = np.exp(g[:, i])[..., None, None] * s
        pred = np.einsum("bhkv,bhk->bhv", s, k[:, i])
        s = s + beta[:, i, :, None, None] * k[:, i, :, :, None] \
            * (v[:, i] - pred)[:, :, None, :]
        out[:, i] = np.einsum("bhkv,bhk->bhv", s, q[:, i])
    return out, s


def _close(got, want, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(np.asarray(got), want, rtol=rtol, atol=atol)


@pytest.mark.parametrize("t,chunk", [(16, 8), (13, 8), (5, 8), (24, 4)])
def test_chunked_form_matches_the_recurrence(t, chunk):
    args = _inputs(2, t, 2, 4, 8, 16)
    o, s = gdn.gdn_chunked(*args, chunk=chunk)
    o_ref, s_ref = _sequential(*args)
    _close(o, o_ref)
    _close(s, s_ref)


@pytest.mark.parametrize("g_range,beta_range", [
    ((3.0, 6.0), (0.05, 0.95)),          # decays near 0: exp(g) 0.0025 to 0.05
    ((1e-5, 1e-3), (0.05, 0.95)),        # decays near 1
    ((0.01, 0.5), (0.0, 0.002)),         # beta at its lower end
    ((0.001, 0.05), (0.998, 1.0)),       # beta at its upper end
])
def test_chunked_form_at_the_ends_of_decay_and_beta(g_range, beta_range):
    """A head that decays to nothing inside a chunk (``G`` reaches -380, ``k /
    exp(G)`` would overflow, the differences do not), one that never forgets,
    a write that stores nothing and one that stores the whole difference."""
    args = _inputs(1, 64, 1, 2, 8, 8, seed=3, g_range=g_range,
                   beta_range=beta_range)
    o, s = gdn.gdn_chunked(*args, chunk=64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s)).all()
    o_ref, s_ref = _sequential(*args)
    _close(o, o_ref, atol=1e-4)
    _close(s, s_ref, atol=1e-4)


def test_chunked_form_continues_from_a_state_and_stops_at_the_length():
    """Two passes, the second from the first's state, give what one pass
    gives; positions behind ``length`` (a bucket's padding) move neither the
    state nor what came before them."""
    q, k, v, g, beta = _inputs(2, 20, 2, 4, 8, 8, seed=5)
    o_all, s_all = gdn.gdn_chunked(q, k, v, g, beta, chunk=8)
    first = tuple(x[:, :11] for x in (q, k, v, g, beta))
    rest = tuple(x[:, 11:] for x in (q, k, v, g, beta))
    o1, s1 = gdn.gdn_chunked(*first, chunk=8)
    o2, s2 = gdn.gdn_chunked(*rest, chunk=8, init_state=s1)
    _close(jnp.concatenate([o1, o2], 1), np.asarray(o_all))
    _close(s2, np.asarray(s_all))
    # each row of the batch at its own length
    o_cut, s_cut = gdn.gdn_chunked(q, k, v, g, beta, chunk=8,
                                   length=jnp.asarray([13, 4]))
    for row, n in enumerate((13, 4)):
        one = tuple(x[row:row + 1] for x in (q, k, v, g, beta))
        o_ref, s_ref = _sequential(*one, length=n)
        _close(np.asarray(o_cut)[row, :n], o_ref[0, :n])
        _close(np.asarray(s_cut)[row], s_ref[0])


def test_one_token_update_is_one_step_of_the_recurrence():
    q, k, v, g, beta = _inputs(3, 1, 2, 4, 8, 8, seed=6)
    rng = np.random.RandomState(7)
    state = jnp.asarray(rng.randn(2, 3, 4, 8, 8), jnp.float32)
    active = jnp.asarray([True, False, True])
    o, new = gdn.gdn_update(state, 1, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0], active)
    o_ref, s_ref = _sequential(q, k, v, g, beta, s0=state[1])
    _close(np.asarray(o)[[0, 2]], o_ref[[0, 2], 0], 1e-5, 1e-6)
    _close(np.asarray(new[1])[[0, 2]], s_ref[[0, 2]], 1e-5, 1e-6)
    # the idle slot and the other layer, bit for bit
    np.testing.assert_array_equal(np.asarray(new[1, 1]),
                                  np.asarray(state[1, 1]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert not np.asarray(o)[1].any()


HK, HV, TAPS, EPS, SCALE = 32, 64, 4, 1e-6, 2.0


def _step_operands(b, l, seed, g_shift=0.0, b_shift=0.0):
    d = gdn.LANES
    rows = gdn.conv_rows(HK, HV)
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    stack = {"conv_w": jnp.asarray(rng.uniform(-0.5, 0.5, (l, TAPS, rows * d)),
                                   jnp.float32),
             "A_log": jnp.asarray(np.log(rng.uniform(1, 16, (l, HV))),
                                  jnp.float32),
             "dt_bias": f(l, HV) - 3.0 + g_shift, "o_norm": 0.2 * f(l, d)}
    ab = f(b, 2, HV) + jnp.asarray([0.0, b_shift])[None, :, None]
    return (stack, f(b, rows * d), ab, f(b, HV * d),
            f(l, b, HV, d, d), f(l, b, *gdn.tail_shape(TAPS, HK, HV, d)))


def _split_step(stack, qkv, ab, gate_pre, state, tail, layer, active):
    """The same step out of XLA's own operations: the carried convolution,
    the norms, the ``jnp`` update, the head norm and the gate."""
    l, b, hv, d, _ = state.shape
    rows = gdn.conv_rows(HK, HV)
    act, tail1 = causal_conv(
        qkv[:, None], tail[layer].reshape(b, TAPS - 1, -1),
        stack["conv_w"][layer], jnp.zeros((rows * d,)),
        active.astype(jnp.int32))
    act = act[:, 0].reshape(b, rows, d)
    q, k, v = act[:, :HK], act[:, HK:2 * HK], act[:, 2 * HK:]
    q, k = gdn.l2_normalize(q) * d ** -0.5, gdn.l2_normalize(k)
    g = gdn.log_decay(ab[:, 0], stack["A_log"][layer], stack["dt_bias"][layer])
    o, state = gdn.gdn_update(state, layer, q, k, v, g,
                              jax.nn.sigmoid(ab[:, 1]), active)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + EPS) \
        * (1.0 + stack["o_norm"][layer]) \
        * (SCALE * jax.nn.sigmoid(gate_pre.reshape(b, hv, d)))
    return (o.reshape(b, hv * d),
            state, tail.at[layer].set(tail1.reshape(tail.shape[1:])))


@pytest.mark.parametrize("active,layer,g_shift,b_shift", [
    ((True, False, True), 1, 0.0, 0.0),
    ((False, False, False), 0, 0.0, 0.0),
    ((True, True, True), 0, 6.0, 8.0),       # decay near 0, beta near 1
    ((False, True, True), 1, -8.0, -8.0),    # decay near 1, beta near 0
])
def test_folded_step_in_interpret_mode_matches_the_split_route(
        active, layer, g_shift, b_shift):
    b, l = 3, 2
    assert gdn.supports(HK, HV, gdn.LANES, gdn.LANES, TAPS)
    stack, qkv, ab, gate_pre, state, tail = _step_operands(
        b, l, 11, g_shift, b_shift)
    active = jnp.asarray(active)
    want = _split_step(stack, qkv, ab, gate_pre, state, tail, layer, active)
    got = gdn.gdn_step(qkv, ab, gate_pre, state, tail, layer,
                       gdn.fold_weights(stack, HK, HV), slot_order(active),
                       active, eps=EPS, gate_scale=SCALE, interpret=True)
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
    assert gdn.supports(32, 64, 128, 128, 4)
    assert gdn.supports(64, 64, 128, 128, 4)     # heads one to one
    assert not gdn.supports(2, 4, 16, 16, 4)     # a tiny model's heads
    assert not gdn.supports(32, 64, 128, 64, 4)  # values narrower than a row
    assert not gdn.supports(24, 48, 128, 128, 4)  # heads that split no cell
    assert not gdn.supports(8, 64, 128, 128, 4)  # a cell's keys no whole tile
    assert not gdn.supports(48, 64, 128, 128, 4)  # keys that divide no values
    assert gdn.tail_shape(4, 32, 64, 128) == (3, 128, 128)


COUNTERS = ("folded_step", "split_step", "chunked_block")


def _counters():
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    return {n: reg.counter("gdn/traced_" + n).value for n in COUNTERS}


def test_traced_counters_name_the_route():
    before = _counters()
    gdn.count_step(True)
    gdn.count_step(False)
    gdn.count_chunked_block()
    assert _counters() == {n: was + 1 for n, was in before.items()}


def test_the_kernel_has_a_stable_name_of_its_own():
    import inspect

    src = inspect.getsource(gdn)
    assert src.count("pl.pallas_call(") == 1
    assert 'name="dstpu_gdn_update"' in src
