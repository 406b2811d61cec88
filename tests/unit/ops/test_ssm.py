"""State-space operators (ops/ssm.py) on the CPU: the Pallas one-token update
in interpret mode against the plain ``jnp`` route, a layer's whole decode step
folded into that call against the split route (``causal_conv``, the ``jnp``
update, the gate and the norm in ``jnp``), the chunked prompt form against the
sequential recurrence written out here, and the carried causal convolution."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.ssm import causal_conv, ssd_prefill, ssm_update

pytestmark = pytest.mark.quick


def _inputs(b, t, h, p, n, g, seed=0):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    x, bm, cm = f(b, t, h, p), f(b, t, g, n), f(b, t, g, n)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (b, t, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 8.0, (h,)), jnp.float32)
    return x, dt, a, bm, cm, f(h)


def _sequential(x, dt, a, bm, cm, d, s0=None):
    """S_t = exp(dt A) S_{t-1} + dt x (outer) B;  y_t = S_t C + D x."""
    b, t, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    s = np.zeros((b, h, p, n)) if s0 is None else np.asarray(s0, np.float64)
    x, dt, a, bm, cm, d = (np.asarray(v, np.float64)
                           for v in (x, dt, a, bm, cm, d))
    ys = np.zeros((b, t, h, p))
    for i in range(t):
        bh = np.repeat(bm[:, i], h // g, axis=1)
        ch = np.repeat(cm[:, i], h // g, axis=1)
        s = (np.exp(dt[:, i] * a)[..., None, None] * s
             + (dt[:, i, :, None] * x[:, i])[..., None] * bh[:, :, None, :])
        ys[:, i] = (s * ch[:, :, None, :]).sum(-1) + d[:, None] * x[:, i]
    return ys, s


@pytest.mark.parametrize("t,chunk,g", [(16, 8, 1), (13, 8, 1), (5, 8, 2),
                                       (24, 4, 2)])
def test_chunked_form_matches_the_sequential_recurrence(t, chunk, g):
    args = _inputs(2, t, 4, 8, 16, g)
    y, s = ssd_prefill(*args, chunk=chunk)
    y_ref, s_ref = _sequential(*args)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), s_ref, rtol=1e-4, atol=1e-4)


def test_chunked_form_continues_from_an_initial_state():
    """Two passes, the second started from the first's state, give what one
    pass over the whole block gives: what a chunked prefill does."""
    x, dt, a, bm, cm, d = _inputs(1, 20, 4, 8, 16, 1, seed=1)
    y_all, s_all = ssd_prefill(x, dt, a, bm, cm, d, chunk=8)
    y1, s1 = ssd_prefill(x[:, :11], dt[:, :11], a, bm[:, :11], cm[:, :11],
                         d, chunk=8)
    y2, s2 = ssd_prefill(x[:, 11:], dt[:, 11:], a, bm[:, 11:], cm[:, 11:],
                         d, chunk=8, init_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_all), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_all),
                               rtol=1e-4, atol=1e-4)


def test_chunked_form_stops_the_state_at_the_true_length():
    """Rows at or beyond ``length`` contribute nothing: the state is the one
    at the true length and the outputs before it do not move."""
    x, dt, a, bm, cm, d = _inputs(2, 16, 4, 8, 16, 1, seed=2)
    length = jnp.asarray([9, 16], jnp.int32)
    y, s = ssd_prefill(x, dt, a, bm, cm, d, chunk=8, length=length)
    y9, s9 = ssd_prefill(x[:1, :9], dt[:1, :9], a, bm[:1, :9], cm[:1, :9],
                         d, chunk=8)
    y16, s16 = ssd_prefill(x[1:], dt[1:], a, bm[1:], cm[1:], d, chunk=8)
    np.testing.assert_allclose(np.asarray(s[0]), np.asarray(s9[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y[0, :9]), np.asarray(y9[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[1]), np.asarray(s16[0]),
                               rtol=1e-5, atol=1e-5)


MASKS = pytest.mark.parametrize("active", [
    [True, False, True, True, False], [False] * 5, [True] * 5,
    [False, False, False, False, True]], ids=["mixed", "none", "all", "last"])


def _update_inputs(slots, h, p, n, g, layers=3, seed=3):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    state = f(layers, slots, h, p, n)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (slots, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 8.0, (h,)), jnp.float32)
    return state, f(slots, h, p), dt, a, f(slots, g, n), f(slots, g, n), f(h)


@MASKS
@pytest.mark.parametrize("g,block_heads", [(1, 2), (2, 4)])
def test_pallas_update_in_interpret_mode_matches_the_jnp_route(
        active, g, block_heads):
    state, x, dt, a, bm, cm, d = _update_inputs(5, 4, 8, 128, g)
    act = jnp.asarray(active)
    layer = jnp.int32(1)
    y_ref, s_ref = ssm_update(state, layer, x, dt, a, bm, cm, d, act,
                              impl="jnp")
    y, s = ssm_update(state, layer, x, dt, a, bm, cm, d, act, impl="pallas",
                      interpret=True, block_heads=block_heads)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-5,
                               atol=1e-5)
    # an inactive slot's state and every other layer are untouched, bit for
    # bit, and an inactive slot reads out nothing
    idle = ~np.asarray(active)
    np.testing.assert_array_equal(np.asarray(s)[1, idle],
                                  np.asarray(state)[1, idle])
    np.testing.assert_array_equal(np.asarray(s)[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    assert not np.asarray(y)[idle].any()


def _layer_inputs(slots, h, p, n, g=1, layers=3, k=4, seed=5):
    """A stack of Mamba mixers between their matmuls at small lane-aligned
    sizes: the weights as the model's stack holds them, ``in_proj``'s result
    for one token a slot, the state and the convolution's tail as the cache
    keeps them."""
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    d_in, c = h * p, h * p + 2 * g * n
    stack = {"conv_w": f(layers, k, c) * 0.5, "conv_b": f(layers, c) * 0.1,
             "dt_bias": f(layers, h), "D": f(layers, h),
             "A_log": jnp.log(jnp.asarray(rng.uniform(1.0, 8.0, (layers, h)),
                                          jnp.float32)),
             "gate_norm": 1.0 + 0.1 * f(layers, d_in)}
    state = f(layers, slots, h, p, n)
    conv = jnp.stack([ssm.tail_to_rows(f(slots, k - 1, c), d_in)
                      for _ in range(layers)])
    return stack, f(slots, 2 * d_in + 2 * g * n + h), state, conv


def _split_step(stack, zxbcdt, state, conv, layer, active, h, p, n, eps, g=1):
    """The layer as ``models/mamba.mixer`` runs it split: a head reads its
    group's ``B`` and ``C``, the gated norm runs over a group's channels."""
    b, d_in = zxbcdt.shape[0], h * p
    blk = {name: v[layer] for name, v in stack.items()}
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    dt = jax.nn.softplus(dt + blk["dt_bias"])
    tail = ssm.rows_to_tail(conv[layer], blk["conv_w"].shape[0], d_in,
                            2 * g * n)
    xbc, tail = causal_conv(xbc[:, None], tail, blk["conv_w"], blk["conv_b"],
                            active.astype(jnp.int32))
    xs, bm, cm = jnp.split(xbc[:, 0], [d_in, d_in + g * n], axis=-1)
    y, state = ssm_update(state, layer, xs.reshape(b, h, p), dt,
                          -jnp.exp(blk["A_log"]), bm.reshape(b, g, n),
                          cm.reshape(b, g, n), blk["D"], active, impl="jnp")
    y = (y.reshape(b, d_in) * jax.nn.silu(z)).reshape(b, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
    return y.reshape(b, d_in) * blk["gate_norm"], state, \
        conv.at[layer].set(ssm.tail_to_rows(tail, d_in))


@MASKS
@pytest.mark.parametrize("layer,g", [(0, 1), (2, 1), (1, 2), (2, 8)])
def test_folded_step_in_interpret_mode_matches_the_split_route(active, layer,
                                                               g):
    """Everything between ``in_proj`` and ``out_proj`` inside the one call:
    ``y``, the state and the convolution's tail as the split route leaves
    them, at one group, two and eight (a head reads its group's ``B`` and
    ``C``; the norm runs a group); a slot that is not decoding keeps its
    state AND its tail, and so does every other layer, bit for bit."""
    h, p, n, eps = 2 * max(g, 2), 64, 128, 1e-5
    assert ssm.step_folds(h, p, n, g) \
        and not ssm.step_folds(h, p, n, g, block_heads=h // g // 2) \
        and not ssm.step_folds(h, p, n, h)      # a head is half a row
    stack, zxbcdt, state, conv = _layer_inputs(5, h, p, n, g)
    act = jnp.asarray(active)
    y_ref, s_ref, c_ref = _split_step(stack, zxbcdt, state, conv, layer, act,
                                      h, p, n, eps, g)
    y, s, c = ssm.mamba_step(
        zxbcdt, state, conv, jnp.int32(layer), ssm.fold_weights(stack, p),
        ssm.slot_order(act), act, eps=eps, interpret=True)
    for got, want in ((y, y_ref), (s, s_ref), (c, c_ref)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    idle, others = ~np.asarray(active), [i for i in range(3) if i != layer]
    for got, was in ((s, state), (c, conv)):
        np.testing.assert_array_equal(np.asarray(got)[layer, idle],
                                      np.asarray(was)[layer, idle])
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(was)[others])
    assert not np.asarray(y)[idle].any()


def test_the_tail_leaf_keeps_lane_aligned_widths_as_rows():
    """``[K-1, C]`` and the cache leaf's rows are each other's inverse; the
    x part of every tap comes first, whole packed tiles of 16 rows; widths
    that are no whole rows of lanes stay as they are."""
    tail = jnp.asarray(np.random.RandomState(6).randn(2, 3, 4096 + 256),
                       jnp.float32)
    rows = ssm.tail_to_rows(tail, 4096)
    assert rows.shape[1:] == ssm.conv_tail_shape(4, 4096, 256) == (112, 128)
    np.testing.assert_array_equal(
        np.asarray(rows[:, 32:64].reshape(2, 4096)),
        np.asarray(tail[:, 1, :4096]))
    np.testing.assert_array_equal(np.asarray(rows[:, 100]),
                                  np.asarray(tail[:, 2, 4096:4224]))
    assert not np.asarray(rows[:, 102:]).any()
    np.testing.assert_array_equal(
        np.asarray(ssm.rows_to_tail(rows, 4, 4096, 256)), np.asarray(tail))
    odd = tail[:, :, :160]
    assert ssm.conv_tail_shape(4, 128, 32) == (3, 160)
    assert ssm.tail_to_rows(odd, 128) is odd \
        and ssm.rows_to_tail(odd, 4, 128, 32) is odd


def test_one_token_update_is_one_step_of_the_recurrence():
    state, x, dt, a, bm, cm, d = _update_inputs(3, 4, 8, 16, 1, layers=1)
    y, s = ssm_update(state, 0, x, dt, a, bm, cm, d, impl="jnp")
    y_ref, s_ref = _sequential(x[:, None], dt[:, None], a, bm[:, None],
                               cm[:, None], d, s0=state[0])
    np.testing.assert_allclose(np.asarray(y), y_ref[:, 0], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(s[0]), s_ref, rtol=1e-5, atol=1e-5)


def test_causal_conv_carries_its_tail_to_the_true_length():
    rng = np.random.RandomState(4)
    b, t, c, k = 2, 10, 6, 4
    x = jnp.asarray(rng.randn(b, t, c), jnp.float32)
    w = jnp.asarray(rng.randn(k, c), jnp.float32)
    bias = jnp.asarray(rng.randn(c), jnp.float32)
    zeros = jnp.zeros((b, k - 1, c), jnp.float32)
    whole, tail = causal_conv(x, zeros, w, bias)
    # by hand: zeros before the sequence
    xp = np.concatenate([np.zeros((b, k - 1, c)), np.asarray(x)], 1)
    want = sum(xp[:, j:j + t] * np.asarray(w)[j] for j in range(k)) \
        + np.asarray(bias)
    np.testing.assert_allclose(np.asarray(whole),
                               np.asarray(jax.nn.silu(jnp.asarray(want))),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail), np.asarray(x[:, -3:]))
    # two pieces, the second fed the first's tail, equal the whole
    first, tail1 = causal_conv(x[:, :6], zeros, w, bias)
    second, tail2 = causal_conv(x[:, 6:], tail1, w, bias)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, second], 1)), np.asarray(whole),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(tail2), np.asarray(tail))
    # a padded block stops its tail at the true length; 0 valid rows keep it
    _, cut = causal_conv(x, zeros, w, bias, jnp.asarray([6, 0], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cut[0]), np.asarray(x[0, 3:6]))
    np.testing.assert_array_equal(np.asarray(cut[1]), np.zeros((k - 1, c)))


def test_the_kernel_has_a_stable_name_of_its_own():
    """What tests/unit/benchmarks/test_tracing_metrics.py asks of every
    ``pallas_call`` under ops/: one constant ``name=`` of the form
    ``dstpu_*``, distinct from the others'. The device trace names the
    kernel's event after it, and ``kernel.ssm_update_*`` find it by it."""
    import ast
    import os
    import re

    ops_dir = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "deepspeed_tpu", "ops")
    names = {}
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        with open(os.path.join(ops_dir, fname)) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and "pallas_call" in (
                    getattr(node.func, "attr", None),
                    getattr(node.func, "id", None)):
                found = [k.value for k in node.keywords if k.arg == "name"]
                assert len(found) == 1 and isinstance(found[0], ast.Constant)
                names.setdefault(found[0].value, []).append(fname)
    assert names["dstpu_ssm_update"] == ["ssm.py"]
    # counted from below: a later kernel adds a name (PR 46:
    # ``dstpu_mla_decode_step``, the eleventh) and edits nothing here
    assert all(len(files) == 1 for files in names.values()) and len(names) >= 11
    assert names["dstpu_mla_decode_step"] == ["mla_decode_step.py"]
    assert all(re.match(r"^dstpu_[a-z0-9_]+$", n) for n in names)
