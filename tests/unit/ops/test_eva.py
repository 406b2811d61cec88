"""ops/eva.py: the chunk pooling, the one-token step and the prompt form,
each against the layer equations written position by position in numpy (a
query's exact keys ``[W w, t]`` and the summaries of the chunks of the windows
before, one softmax), and the two Pallas kernels in interpret mode against
the routes in XLA's own operations. The equations take four faults by name
(a stale window row read after the window starts over, a chunk visible before
its window closes, ``mu`` dropped, pooling by a mean): each moves the result
by orders of magnitude more than the tolerance the ops are held to, so an op
with that fault fails here."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import eva
from deepspeed_tpu.ops.decode_step import slot_walk

W, C, H, D = 32, 4, 2, 8
SCALE = D ** -0.5
FAULTS = ("stale_row", "early_chunk", "no_mu", "mean_pool")


def _softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def _summary(k, v, phi, mu, rows, fault=None):
    """One head's pooled key and value of the chunk rows ``k, v [rows, D]``."""
    a = np.full(len(rows), 1.0 / len(rows)) if fault == "mean_pool" \
        else _softmax(SCALE * k[rows] @ phi)
    ksum = a @ k[rows] + (0.0 if fault == "no_mu" else mu)
    return ksum, a @ v[rows]


def equations(q, k, v, phi, mu, fault=None):
    """``q, k, v [T, H, D]`` (rotated already) -> ``o [T, H, D]``, position by
    position, head by head."""
    t_all = q.shape[0]
    out = np.zeros_like(q)
    for h in range(H):
        for t in range(t_all):
            w = t // W
            exact = list(range(W * w, t + 1))
            if fault == "stale_row" and w > 0:
                # the rows behind t mod W still hold the window before
                exact += list(range(W * (w - 1) + t % W + 1, W * w))
            chunks = list(range(w * W // C))
            if fault == "early_chunk":
                chunks += [j for j in range(w * W // C, t // C)]
            keys = [k[m, h] for m in exact]
            vals = [v[m, h] for m in exact]
            for j in chunks:
                ks, vs = _summary(k[:, h], v[:, h], phi[h], mu[h],
                                  list(range(C * j, C * j + C)), fault)
                keys.append(ks)
                vals.append(vs)
            p = _softmax(SCALE * np.asarray(keys) @ q[t, h])
            out[t, h] = p @ np.asarray(vals)
    return out


@pytest.fixture(scope="module")
def drawn():
    rng = np.random.RandomState(0)
    t = 3 * W
    q, k, v = (rng.randn(t, H, D).astype(np.float32) for _ in range(3))
    phi, mu = (np.clip(rng.randn(H, D), -1, 1).astype(np.float32)
               for _ in range(2))
    return q, k, v, phi, mu, equations(q, k, v, phi, mu)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_fault_moves_the_equations_far_beyond_the_tolerance(drawn, fault):
    q, k, v, phi, mu, want = drawn
    assert np.abs(equations(q, k, v, phi, mu, fault) - want).max() > 1e-2


def test_pool_chunks_is_the_chunks_softmax_and_two_weighted_sums(drawn):
    _, k, v, phi, mu, _ = drawn
    kh, vh = (jnp.asarray(a.transpose(1, 0, 2))[None] for a in (k, v))
    ksum, vsum = eva.pool_chunks(kh, vh, jnp.asarray(phi), jnp.asarray(mu),
                                 chunk=C, scale=SCALE)
    assert ksum.shape == vsum.shape == (1, H, 3 * W // C, D)
    for h in range(H):
        for j in (0, 5, 3 * W // C - 1):
            ks, vs = _summary(k[:, h], v[:, h], phi[h], mu[h],
                              list(range(C * j, C * j + C)))
            np.testing.assert_allclose(ksum[0, h, j], ks, atol=1e-5)
            np.testing.assert_allclose(vsum[0, h, j], vs, atol=1e-5)
    # a chunk the request has not filled: the rows so far alone take part
    live = (jnp.arange(3 * W) % C < 3)[None, None]
    ksum, _ = eva.pool_chunks(kh, vh, jnp.asarray(phi), jnp.asarray(mu),
                              chunk=C, scale=SCALE, live=live)
    np.testing.assert_allclose(
        ksum[0, 1, 2], _summary(k[:, 1], v[:, 1], phi[1], mu[1],
                                [8, 9, 10])[0], atol=1e-5)


def _leaves(slots: int, max_len: int, layers: int = 2):
    rows = -(-max_len // W) * (W // C)
    zeros = lambda n: jnp.zeros((layers, slots, H, n, D), jnp.float32)
    return [zeros(W), zeros(W), zeros(rows), zeros(rows)]


def _prompt(leaves, q, k, v, phi, mu, layer, slot, n):
    """Blocks of a window through the prompt form, as the model passes a
    prompt of ``n`` real positions padded to whole windows: attention, the
    block's summaries, its rows as the window's where it holds a real one."""
    k_win, v_win, k_sum, v_sum = leaves
    out = []
    for i in range(-(-n // W)):
        blk = slice(i * W, (i + 1) * W)
        qb, kb, vb = (jnp.asarray(a[blk])[None] for a in (q, k, v))
        o = eva.eva_prompt_block(
            qb, kb, vb, k_sum[:, slot:slot + 1], v_sum[:, slot:slot + 1],
            layer, i * (W // C), scale=SCALE)
        out.append(np.asarray(o[0]))
        kh, vh = (a.transpose(0, 2, 1, 3) for a in (kb, vb))
        ks, vs = eva.pool_chunks(kh, vh, jnp.asarray(phi), jnp.asarray(mu),
                                 chunk=C, scale=SCALE)
        at = slice(i * (W // C), (i + 1) * (W // C))
        k_sum = k_sum.at[layer, slot, :, at].set(ks[0])
        v_sum = v_sum.at[layer, slot, :, at].set(vs[0])
        k_win = k_win.at[layer, slot].set(kh[0])
        v_win = v_win.at[layer, slot].set(vh[0])
    return np.concatenate(out)[:n], [k_win, v_win, k_sum, v_sum]


# (real positions of the prompt): one that ends mid-chunk in its second
# window, one that ends on a window's last row, one shorter than a window
@pytest.mark.parametrize("n", [W + 14, 2 * W, 11])
def test_prompt_form_then_steps_are_the_equations(drawn, n):
    """The prompt form over whole windows (rows behind ``n`` are padding:
    the drawn values stand in for it, and no real query may see them), then
    one-token steps to the end of the third window: across chunk boundaries
    and across window boundaries, a second slot inactive and untouched."""
    q, k, v, phi, mu, want = drawn
    pad = -(-n // W) * W
    got, leaves = _prompt(_leaves(2, 3 * W), q[:pad], k[:pad], v[:pad], phi,
                          mu, 1, 0, n)
    np.testing.assert_allclose(got, want[:n], atol=2e-5)
    before = [np.asarray(a) for a in leaves]
    active = jnp.asarray([True, False])
    for t in range(n, 3 * W):
        pos = jnp.asarray([t, 7], jnp.int32)
        new = lambda a: jnp.stack([jnp.asarray(a[t]), jnp.ones((H, D))])
        attn, *leaves = eva.eva_decode_step(
            new(q), *leaves, new(k), new(v), jnp.asarray(phi),
            jnp.asarray(mu), 1, pos, chunk=C, scale=SCALE, active=active)
        np.testing.assert_allclose(attn[0], want[t], atol=2e-5)
        assert not np.asarray(attn[1]).any()
    for a, b in zip(before, leaves):    # the other slot, the other layer
        np.testing.assert_array_equal(a[:, 1], np.asarray(b)[:, 1])
        np.testing.assert_array_equal(a[0], np.asarray(b)[0])


def test_the_traced_routes_are_counted():
    from deepspeed_tpu.telemetry.registry import MetricsRegistry, get_registry

    reg = get_registry()
    before = {n: reg.counter("eva/traced_" + n).value for n in eva._TRACED}
    leaves = _leaves(2, W)
    x = jnp.ones((2, H, D))
    eva.eva_decode_step(x, *leaves, x, x, x[0], x[0], 0,
                        jnp.asarray([3, 4]), chunk=C, scale=SCALE)
    after = {n: reg.counter("eva/traced_" + n).value for n in eva._TRACED}
    assert after["split_step"] == before["split_step"] + 1
    assert after["fused_step"] == before["fused_step"]
    mine = MetricsRegistry()
    eva.record_traced(mine)
    snap = mine.snapshot()["counters"]
    assert snap["eva/traced_split_step"] == after["split_step"]
    assert "eva/traced_fused_step" in snap and \
        "eva/traced_prompt_block" in snap


def test_live_and_fetched_rows():
    assert eva.live_rows(0, 2048, 16) == (1, 0)
    assert eva.live_rows(2047, 2048, 16) == (2048, 0)
    assert eva.live_rows(2048, 2048, 16) == (1, 128)
    assert eva.live_rows(12000, 2048, 16) == (12000 - 10240 + 1, 640)
    assert eva.rows_fetched(12000, 2048, 16) == 640 + 1792 + 1
    assert eva.rows_fetched(4096, 2048, 16) == 256 + 1
    assert eva.supports_step(32, 128, 2048, 16, 2048)
    assert not eva.supports_step(4, 16, 32, 4, 16)
    assert eva.supports_prompt(2048, 128, 256)
    assert not eva.supports_prompt(32, 16, 8)


# ------------------------------------------- the kernels, interpret mode
KW, KC, KD = 2048, 16, 128


def _big(rng, *shape):
    return jnp.asarray(rng.randn(*shape).astype(np.float32))


@pytest.mark.parametrize("pos,active", [
    ([5000, 100, 2048 + 7, 0], [1, 1, 1, 1]),
    ([4095, 2048, 17, 3000], [1, 0, 1, 1]),
    ([1, 2, 3, 4], [0, 0, 0, 0])], ids=["all", "one-idle", "none"])
@pytest.mark.parametrize("cs", [512, 128])
def test_fused_step_is_the_split_step(pos, active, cs):
    """``dstpu_eva_decode_step`` at the published window and chunk, two
    heads: slots at the start of a window, on its last row, in the first
    window (no summary) and at a request's first position; an inactive slot
    neither read nor written, whatever length it carries."""
    rng = np.random.RandomState(1)
    l, b, h = 2, 4, 2
    rows = 3 * KW // KC
    leaves = [_big(rng, l, b, h, KW, KD), _big(rng, l, b, h, KW, KD),
              _big(rng, l, b, h, rows, KD), _big(rng, l, b, h, rows, KD)]
    q, kn, vn = (_big(rng, b, h, KD) for _ in range(3))
    phi, mu = _big(rng, h, KD), _big(rng, h, KD)
    pos, active = jnp.asarray(pos, jnp.int32), jnp.asarray(active) > 0
    want = eva.split_eva_decode_step(q, *leaves, kn, vn, phi, mu, 1, pos,
                                     chunk=KC, scale=0.1, active=active)
    got = eva.fused_eva_decode_step(
        q, *leaves, kn, vn, phi, mu, 1, pos, chunk=KC, scale=0.1,
        active=slot_walk(pos, active), interpret=True, cs=cs)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=2e-6)
    idle = np.flatnonzero(~np.asarray(active))
    for new, old in zip(got[1:], leaves):
        np.testing.assert_array_equal(np.asarray(new)[:, idle],
                                      np.asarray(old)[:, idle])


@pytest.mark.parametrize("visible", [0, 128, 384])
def test_fused_prompt_block_is_the_plain_one(visible):
    rng = np.random.RandomState(2)
    h, rows = 2, 384
    q, k, v = (_big(rng, 1, KW, h, KD) for _ in range(3))
    ks, vs = _big(rng, 2, 1, h, rows, KD), _big(rng, 2, 1, h, rows, KD)
    want = eva.plain_eva_prompt_block(q, k, v, ks, vs, 1, visible, scale=0.1)
    got = eva.fused_eva_prompt_block(q, k, v, ks, vs, 1, visible, scale=0.1,
                                     interpret=True)
    np.testing.assert_allclose(got, want, atol=5e-6)
