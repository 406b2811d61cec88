"""Gated delta-rule operators (ops/kda.py) on the CPU: the chunked prompt form
against the token-by-token recurrence written out here (also under fast decay
and with ``beta`` near 2), the one-token ``jnp`` update as one step of that
recurrence, and a layer's whole decode step folded into the Pallas call, in
interpret mode, against the carried convolution + the ``jnp`` update."""

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


def test_traced_counters_name_the_route():
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    before = {n: reg.counter("kda/traced_" + n).value for n in
              ("folded_step", "split_step", "chunked_block")}
    kda.count_step(True)
    kda.count_step(False)
    kda.count_chunked_block()
    for name, was in before.items():
        assert reg.counter("kda/traced_" + name).value == was + 1


def test_the_kernel_has_a_stable_name_of_its_own():
    import inspect

    src = inspect.getsource(kda)
    assert src.count("pl.pallas_call(") == 1
    assert 'name="dstpu_kda_update"' in src
