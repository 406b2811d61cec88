"""Fused decode step (ops/decode_step.py) + packed KV-cache semantics.

The TPU numerics of the Mosaic kernel are exercised on-chip by
scripts/check_decode_step.py; here the interpret-mode kernel and the
packed-cache routing/fallback contract are pinned on CPU.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import (
    alloc_kv_cache, cache_seq_len, cached_attention, decode_attention,
    kv_pack_factor, write_kv_cache)
from deepspeed_tpu.ops.decode_step import fused_decode_step, supports


def test_kv_pack_factor():
    assert kv_pack_factor(64) == 2
    assert kv_pack_factor(32) == 4
    assert kv_pack_factor(128) == 1
    assert kv_pack_factor(256) == 1
    assert kv_pack_factor(96) == 1  # 128 % 96 != 0 -> unpacked


def test_alloc_kv_cache_shapes():
    # packed: dh=64 pair=2 at batch >= 2
    c = alloc_kv_cache(4, 2, 8, 256, 64, jnp.bfloat16)
    assert c.shape == (4, 2, 8, 128, 128)
    assert cache_seq_len(c, 64) == 256
    # batch 1 stays unpacked (einsum decode path wins there)
    c1 = alloc_kv_cache(4, 1, 8, 256, 64, jnp.bfloat16)
    assert c1.shape == (4, 1, 8, 256, 64)
    # explicit unpacked (ALiBi / windowed models)
    cu = alloc_kv_cache(4, 2, 8, 256, 64, jnp.bfloat16, packed=False)
    assert cu.shape == (4, 2, 8, 256, 64)
    # dh >= 128 never packs
    c128 = alloc_kv_cache(4, 2, 8, 256, 128, jnp.bfloat16)
    assert c128.shape == (4, 2, 8, 256, 128)


def test_supports():
    assert supports(12, 12, 640, 64)
    assert supports(32, 4, 640, 128)
    assert not supports(12, 12, 636, 64)   # S not 128-aligned
    assert not supports(12, 12, 640, 96)   # dh doesn't tile
    assert not supports(12, 5, 640, 64)    # hq % hkv


def _ref_step(q, kf, vf, kn, vn, layer, idx):
    kf, vf, kl, vl = write_kv_cache(kf, vf, kn, vn, layer, idx)
    return decode_attention(q, kl, vl, idx), kf, vf


@pytest.mark.parametrize("b,l,hq,hkv,s,dh,idx", [
    (2, 3, 4, 4, 256, 64, 100),    # MHA packed (pair=2)
    (2, 2, 8, 2, 256, 128, 200),   # GQA rep=4, dh=128
    (1, 2, 4, 4, 256, 128, 0),     # first decode step
    (2, 2, 4, 4, 256, 64, 255),    # last position
])
def test_fused_decode_step_matches_einsum(b, l, hq, hkv, s, dh, idx):
    rng = np.random.RandomState(0)
    pair = kv_pack_factor(dh)
    q = jnp.asarray(rng.randn(b, 1, hq, dh), jnp.bfloat16)
    kf = jnp.asarray(rng.randn(l, b, hkv, s, dh), jnp.bfloat16)
    vf = jnp.asarray(rng.randn(l, b, hkv, s, dh), jnp.bfloat16)
    kn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.bfloat16)
    vn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.bfloat16)
    layer = jnp.int32(l - 1)
    a0, k0, v0 = _ref_step(q, kf, vf, kn, vn, layer, jnp.int32(idx))
    packed = (l, b, hkv, s // pair, dh * pair)
    a1, k1, v1 = fused_decode_step(
        q, kf.reshape(packed), vf.reshape(packed), kn, vn, layer,
        jnp.int32(idx), interpret=True)
    np.testing.assert_allclose(
        np.asarray(a1, np.float32), np.asarray(a0, np.float32), atol=0.06)
    np.testing.assert_array_equal(
        np.asarray(k1.reshape(kf.shape), np.float32),
        np.asarray(k0, np.float32))
    np.testing.assert_array_equal(
        np.asarray(v1.reshape(vf.shape), np.float32),
        np.asarray(v0, np.float32))


def _check_per_slot(b, l, hq, hkv, s, dh, idxs, active, plan,
                    poison=False, interpret=True):
    rng = np.random.RandomState(0)
    pair = kv_pack_factor(dh)
    act = np.ones(b, bool) if active is None else np.asarray(active, bool)
    q = jnp.asarray(rng.randn(b, 1, hq, dh), jnp.bfloat16)
    kf, vf = rng.randn(2, l, b, hkv, s, dh).astype(np.float32)
    kn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.bfloat16)
    vn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.bfloat16)
    layer = jnp.int32(l - 1)
    idx = jnp.asarray(idxs, jnp.int32)
    a0 = _ref_step(q, jnp.asarray(kf, jnp.bfloat16),
                   jnp.asarray(vf, jnp.bfloat16), kn, vn, layer, idx)[0]
    if poison:   # every row the walk has no business fetching
        for i in range(b):
            dead = 0 if not act[i] else -(-idxs[i] // plan["cs"]) * plan["cs"]
            kf[:, i, :, dead:] = vf[:, i, :, dead:] = np.nan
    kf, vf = jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)
    _, k0, v0 = _ref_step(q, kf, vf, kn, vn, layer, idx)
    packed = (l, b, hkv, s // pair, dh * pair)
    a1, k1, v1 = fused_decode_step(
        q, kf.reshape(packed), vf.reshape(packed), kn, vn, layer, idx,
        active=None if active is None else jnp.asarray(active),
        interpret=interpret, plan=plan)
    a0, a1 = np.asarray(a0, np.float32), np.asarray(a1, np.float32)
    assert np.isfinite(a1).all()
    np.testing.assert_allclose(a1[act], a0[act], atol=0.06)
    np.testing.assert_array_equal(a1[~act], 0.0)
    for new, ref, old in ((k1, k0, kf), (v1, v0, vf)):
        new = np.asarray(new.reshape(old.shape), np.float32)
        # the reference wrote every slot's token; the kernel, the active
        # slots' alone (NaN compares equal to NaN here)
        np.testing.assert_array_equal(new[:, act],
                                      np.asarray(ref, np.float32)[:, act])
        np.testing.assert_array_equal(new[:, ~act],
                                      np.asarray(old, np.float32)[:, ~act])


# stale lengths of freed slots: longer than every active slot's
_STALE = [1000, 130, 5, 258, 700, 1023, 0, 128]
_MHA = (8, 2, 4, 4, 1024, 64)     # b, l, hq, hkv, s, dh (pair = 2)
_GQA = (8, 2, 8, 2, 1024, 64)     # rep = 4: the MXU path


@pytest.mark.serving
@pytest.mark.parametrize("b,l,hq,hkv,s,dh,idxs,active,plan", [
    pytest.param(4, 2, 4, 4, 256, 64, [100, 3, 255, 0], None, None,
                 id="mha-mixed"),
    pytest.param(2, 2, 8, 2, 256, 128, [200, 17], None, None,
                 id="gqa-dh128"),
    pytest.param(4, 3, 4, 2, 512, 64, [511, 130, 0, 258], None, None,
                 id="chunk-bounds"),
    # one to eight chunks of 128 in one batch, groups of two by length
    pytest.param(*_MHA, [1023, 130, 5, 258, 700, 900, 127, 128], None,
                 {"bg": 2, "cs": 128}, id="mha-1-to-8-chunks"),
    pytest.param(*_GQA, [1023, 130, 5, 258, 700, 900, 127, 128], None,
                 {"bg": 4, "cs": 128}, id="gqa-1-to-8-chunks"),
    # freed slots keep lengths longer than every active slot's
    pytest.param(*_MHA, _STALE, [0, 1, 1, 1, 0, 0, 1, 1],
                 {"bg": 2, "cs": 128}, id="mha-stale-longer"),
    pytest.param(*_GQA, _STALE, [0, 1, 1, 1, 0, 0, 1, 1],
                 {"bg": 4, "cs": 256}, id="gqa-stale-longer"),
    # three active of eight in groups of two: groups with no active slot
    pytest.param(*_MHA, _STALE, [0, 1, 0, 1, 0, 0, 0, 1],
                 {"bg": 2, "cs": 128}, id="mha-empty-groups"),
    pytest.param(*_GQA, _STALE, [0, 0, 0, 1, 0, 0, 0, 0],
                 {"bg": 4, "cs": 128}, id="gqa-one-active"),
    pytest.param(*_MHA, _STALE, [0, 0, 0, 0, 0, 0, 1, 0],
                 {"bg": 2, "cs": 128}, id="mha-one-active-empty-cache"),
    pytest.param(*_MHA, _STALE, [0] * 8, {"bg": 2, "cs": 128},
                 id="mha-none-active"),
    pytest.param(*_GQA, _STALE, [0] * 8, None, id="gqa-none-active"),
])
def test_fused_decode_step_per_slot_matches_einsum(b, l, hq, hkv, s, dh,
                                                   idxs, active, plan):
    """Per-slot valid-length vector (continuous batching): the fused
    kernel's per-row write and masking == the einsum reference with the
    same vector index on the active slots; an inactive slot's output is
    zero and its cache rows are the bits they were."""
    _check_per_slot(b, l, hq, hkv, s, dh, idxs, active, plan)


@pytest.mark.serving
def test_fused_decode_step_skipped_rows_cannot_poison():
    """Hazard of the per-row walk: a buffer row whose DMA was skipped holds
    stale or uninitialised VMEM, and ``0 * NaN`` in the PV product is NaN.
    The TPU interpreter hands out NaN for uninitialised scratch; the cache
    is NaN wherever the walk must not fetch (freed slots whole, active
    slots past their last chunk). A long and a short slot share a group, so
    the short one's row is skipped on a buffer no DMA ever filled."""
    from jax.experimental.pallas import tpu as pltpu

    _check_per_slot(
        *_MHA, _STALE, [0, 0, 1, 0, 0, 1, 0, 0], {"bg": 2, "cs": 128},
        poison=True,
        interpret=pltpu.InterpretParams(uninitialized_memory="nan"))


def test_slot_walk_orders_active_slots_by_length():
    from deepspeed_tpu.ops.decode_step import slot_walk

    walk = slot_walk(jnp.asarray(_STALE), jnp.asarray([0, 1, 1, 1, 0, 1, 1, 1]))
    assert np.asarray(walk.order).tolist() == [5, 3, 1, 7, 2, 6, 0, 4]
    assert np.asarray(walk.n_active).tolist() == [6]
    walk = slot_walk(jnp.asarray([3, 9, 9, 1]))            # all active
    assert np.asarray(walk.order).tolist() == [1, 2, 0, 3]
    assert np.asarray(walk.n_active).tolist() == [4]


def test_cached_attention_packed_fallback_matches_unpacked():
    """On CPU the fused kernel is not routed; cached_attention must give
    identical results for packed and unpacked allocations (the unpack
    view path)."""
    rng = np.random.RandomState(1)
    b, l, h, s, dh = 2, 3, 4, 256, 64
    q = jnp.asarray(rng.randn(b, 1, h, dh), jnp.bfloat16)
    kf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.bfloat16)
    vf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.bfloat16)
    kn = jnp.asarray(rng.randn(b, 1, h, dh), jnp.bfloat16)
    vn = jnp.asarray(rng.randn(b, 1, h, dh), jnp.bfloat16)
    layer, idx = jnp.int32(1), jnp.int32(77)
    a0, k0, v0 = cached_attention(q, kf, vf, kn, vn, layer, idx)
    pk = kf.reshape(l, b, h, s // 2, dh * 2)
    pv = vf.reshape(l, b, h, s // 2, dh * 2)
    a1, k1, v1 = cached_attention(q, pk, pv, kn, vn, layer, idx)
    np.testing.assert_array_equal(np.asarray(a0, np.float32),
                                  np.asarray(a1, np.float32))
    np.testing.assert_array_equal(np.asarray(k0, np.float32),
                                  np.asarray(k1.reshape(kf.shape), np.float32))
    assert k1.shape == pk.shape and v1.shape == pv.shape


def test_generate_packed_cache_end_to_end():
    """GPT-2 tiny generate() with a batch-2 (packed-cache) prompt matches
    the no-cache full forward argmax at each step (greedy)."""
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
    from deepspeed_tpu.utils import groups

    groups.reset()
    cfg = GPT2Config.tiny()
    engine = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="fp32",
                                          max_out_tokens=64)
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                           size=(2, 8)).astype(np.int32)
    out = engine.generate(ids, max_new_tokens=4)
    cur = ids
    for _ in range(4):
        logits = np.asarray(engine.forward(cur), np.float32)
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out, cur)


def _two_width_reference(q, kf, vf, kn, vn, idxs, sink, window,
                         scale=192 ** -0.5):
    """Plain einsum in float32: each slot's ``idx`` cached positions (the
    last ``window`` of them on a ring) and the new one, ``sink [Hq]`` in the
    denominator."""
    b, _, hq, dk = q.shape
    hkv = kf.shape[1]
    out = []
    for i in range(b):
        n = int(idxs[i])
        k = np.concatenate([kf[i, :, :n], kn[i].transpose(1, 0, 2)], 1)
        v = np.concatenate([vf[i, :, :n], vn[i].transpose(1, 0, 2)], 1)
        if window is not None:
            k, v = k[:, -window:], v[:, -window:]
        k, v = (np.repeat(a, hq // hkv, 0) for a in (k, v))
        s = np.einsum("hd,hsd->hs", q[i, 0], k) * scale
        if sink is not None:
            s = np.concatenate([s, sink[:, None]], 1)
        p = np.exp(s - s.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out.append(np.einsum("hs,hsd->hd", p[:, :v.shape[1]], v))
    return np.stack(out)[:, None]


@pytest.mark.serving
@pytest.mark.parametrize("sink", [False, True], ids=["plain", "sink"])
@pytest.mark.parametrize("ring,hkv,s,idxs,active", [
    # rows that grow, 4 key-value heads of 16 query heads each
    pytest.param(False, 4, 512, [300, 5, 511, 129], [1, 1, 0, 1], id="rows"),
    # a ring of 128: one not yet full, one full and wrapped, one just full
    pytest.param(True, 8, 128, [77, 1000, 128, 0], [1, 1, 1, 1], id="ring"),
])
def test_per_slot_walks_keys_192_values_128(ring, hkv, s, idxs, active,
                                            sink):
    """Both per-slot walks with keys and values of two widths: a key row of
    192 live lanes in a leaf of 256 (``key_row_width``), value rows of 128,
    ``rep`` 16 on 4 heads and 8 on 8, with and without a sink that starts
    the running softmax."""
    from deepspeed_tpu.ops.attention import key_row_width, pad_lanes

    rng = np.random.RandomState(3)
    b, l, hq, dk, dv = 4, 2, 64, 192, 128
    row = key_row_width(dk)
    assert row == 256 and supports(hq, hkv, s, row, dv)
    assert not supports(hq, hkv, s, dk, dv)     # no DMA slice of 192 lanes
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
    q, kn = bf(rng.randn(b, 1, hq, dk)), bf(rng.randn(b, 1, hkv, dk))
    vn = bf(rng.randn(b, 1, hkv, dv))
    kf, vf = bf(rng.randn(b, hkv, s, dk)), bf(rng.randn(b, hkv, s, dv))
    sinks = rng.randn(hq).astype(np.float32) if sink else None
    if ring:    # position p at row p % s: lay the last s positions out so
        def at_rows(a):
            out = np.zeros_like(a)
            for i, n in enumerate(idxs):
                for p in range(max(0, n - s), n):
                    out[i, :, p % s] = a[i, :, p - max(0, n - s)]
            return out
        k_leaf, v_leaf = at_rows(kf), at_rows(vf)
    else:
        k_leaf, v_leaf = kf, vf
    want = _two_width_reference(q, kf, vf, kn, vn,
                                [min(n, s) for n in idxs] if ring else idxs,
                                sinks, s if ring else None)
    stacked = lambda a: jnp.asarray(np.stack([np.zeros_like(a), a]),
                                    jnp.bfloat16)
    got, k1, v1 = fused_decode_step(
        pad_lanes(jnp.asarray(q, jnp.bfloat16), row),
        pad_lanes(stacked(k_leaf), row), stacked(v_leaf),
        pad_lanes(jnp.asarray(kn, jnp.bfloat16), row),
        jnp.asarray(vn, jnp.bfloat16), jnp.int32(1),
        jnp.asarray(idxs, jnp.int32), active=jnp.asarray(active), ring=ring,
        scale=dk ** -0.5, interpret=True,
        sink=None if sinks is None else jnp.asarray(sinks))
    act = np.asarray(active, bool)
    got = np.asarray(got, np.float32)
    assert got.shape == (b, 1, hq, dv)
    np.testing.assert_allclose(got[act], want[act], atol=0.03)
    np.testing.assert_array_equal(got[~act], 0.0)
    # the new rows landed, each leaf at its own width
    for i in np.flatnonzero(act):
        at = idxs[i] % s if ring else idxs[i]
        np.testing.assert_array_equal(
            np.asarray(k1[1, i, :, at, :dk], np.float32), kn[i, 0])
        np.testing.assert_array_equal(
            np.asarray(v1[1, i, :, at], np.float32), vn[i, 0])
    assert k1.shape[-1] == row and v1.shape[-1] == dv


# (b, hkv, s_max, dk, dv, hq) of the two cells whose rows run to 16k, and the
# same cut in ``b`` and ``s_max`` for the interpreter
_LONG_ROWS = {
    "mimo-global": ((16, 4, 16384, 256, 128, 64), (8, 4, 1536)),
    "solar": ((16, 8, 16384, 128, 128, 64), (8, 8, 1536)),
}


def _long_plan(family):
    from deepspeed_tpu.ops.decode_step import _SLOT_CHUNK, _slot_plan

    (b, hkv, s_max, dk, dv, hq), _ = _LONG_ROWS[family]
    bg, cs = _slot_plan(b, hkv, s_max, dk, 2, dv=dv, hq=hq)
    assert cs > _SLOT_CHUNK, "the long step is what these cases are for"
    return bg, cs


@functools.lru_cache(maxsize=None)
def _interpreted_step(bg, cs):
    """One jitted program a plan and a family's shapes: an interpreted call
    outside ``jit`` is traced and compiled anew every time."""
    return jax.jit(functools.partial(fused_decode_step, interpret=True,
                                     plan={"bg": bg, "cs": cs}))


def _edge_lengths(cs, s):
    """Lengths on both sides of every DMA and step edge."""
    return [0, 1, 127, 128, 129, cs - 1, cs, cs + 1, 2 * cs - 1, 2 * cs,
            s - 129, s - 1]


def _long_cases():
    for family in _LONG_ROWS:
        for name, bg, pick in [
                # twelve lengths in two draws of eight slots, all active
                ("edges-a", None, lambda e: (e[:8], [1] * 8)),
                ("edges-b", None, lambda e: (e[4:], [1] * 8)),
                # inactive slots between active ones, their stale lengths
                # longer than every active slot's
                ("inactive-between", None, lambda e: (
                    [e[-1], e[5], e[-1], e[7], e[1], e[-1], e[8], e[-1]],
                    [0, 1, 0, 1, 1, 0, 1, 0])),
                # rows of one group of four that end in different steps:
                # three steps, two, two and one, then one, one and none
                ("group-ends-apart", 4, lambda e: (
                    [e[7], 2 * e[6], 130, 3 * e[6] - 3, 5, e[6], 0, 700],
                    [1, 1, 1, 1, 1, 1, 1, 0]))]:
            yield pytest.param(family, bg, pick, id=f"{family}-{name}")


@pytest.mark.serving
@pytest.mark.parametrize("family,group,pick", list(_long_cases()))
def test_long_step_matches_einsum_and_writes_as_the_128_walk(family, group,
                                                             pick):
    """The plan of a cache whose rows run to 16k (a loop step of more than
    128 rows, DMAs of 128) at the cell's own widths and heads, the cache cut
    in ``b`` and ``s_max``: against the float32 einsum on the active slots,
    and the cache written in place bit for bit as the ``(4, 128)`` plan
    writes it. ``group``: rows a group where not the plan's own."""
    (_, _, _, dk, dv, hq), (b, hkv, s) = _LONG_ROWS[family]
    bg, cs = _long_plan(family)
    bg = group or bg
    idxs, active = pick(_edge_lengths(cs, s))
    rng = np.random.RandomState(7)
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
    q, kn = bf(rng.randn(b, 1, hq, dk)), bf(rng.randn(b, 1, hkv, dk))
    vn = bf(rng.randn(b, 1, hkv, dv))
    kf, vf = bf(rng.randn(b, hkv, s, dk)), bf(rng.randn(b, hkv, s, dv))
    want = _two_width_reference(q, kf, vf, kn, vn, idxs, None, None,
                                scale=dk ** -0.5)
    stacked = lambda a: jnp.asarray(np.stack([np.zeros_like(a), a]),
                                    jnp.bfloat16)

    def step(bg, cs):
        return _interpreted_step(bg, cs)(
            jnp.asarray(q, jnp.bfloat16), stacked(kf), stacked(vf),
            jnp.asarray(kn, jnp.bfloat16), jnp.asarray(vn, jnp.bfloat16),
            jnp.int32(1), jnp.asarray(idxs, jnp.int32),
            active=jnp.asarray(active))

    got, k1, v1 = step(bg, cs)
    _, k0, v0 = step(4, 128)
    act = np.asarray(active, bool)
    got = np.asarray(got, np.float32)
    assert got.shape == (b, 1, hq, dv) and np.isfinite(got).all()
    np.testing.assert_allclose(got[act], want[act], atol=0.03)
    np.testing.assert_array_equal(got[~act], 0.0)
    for new, old in ((k1, k0), (v1, v0)):
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(old, np.float32))
    for i in np.flatnonzero(act):       # and the token is where it belongs
        np.testing.assert_array_equal(
            np.asarray(k1[1, i, :, idxs[i]], np.float32), kn[i, 0])
        np.testing.assert_array_equal(
            np.asarray(v1[1, i, :, idxs[i]], np.float32), vn[i, 0])


@pytest.mark.parametrize("b,hq,hkv,s,dk,dv,ring,long_step", [
    pytest.param(16, 64, 4, 16384, 256, 128, False, True, id="mimo-global"),
    pytest.param(16, 64, 8, 128, 256, 128, True, False, id="mimo-ring"),
    pytest.param(16, 64, 8, 16384, 128, 128, False, True, id="solar"),
    pytest.param(32, 64, 8, 4096, 128, 128, False, True, id="k-exaone"),
    pytest.param(64, 32, 8, 2048, 64, 64, False, False, id="hybrid"),
    pytest.param(32, 20, 20, 1024, 64, 64, False, False, id="serve-chat"),
])
def test_traced_walk_counters_say_which_walk(b, hq, hkv, s, dk, dv, ring,
                                             long_step):
    """``decode/traced_walk_long`` and ``decode/traced_walk_128`` in the
    global registry, bumped once a trace of the per-slot step at each cell's
    full geometry (traced only: shapes, nothing runs)."""
    from deepspeed_tpu.telemetry.registry import get_registry

    pair = kv_pack_factor(dk)
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    reg = get_registry()
    names = ["decode/traced_walk_128", "decode/traced_walk_long"]
    before = [reg.counter(n).value for n in names]
    jax.eval_shape(
        lambda q, k, v, kn, vn, idx: fused_decode_step(
            q, k, v, kn, vn, jnp.int32(0), idx, ring=ring, interpret=True),
        sds(b, 1, hq, dk), sds(1, b, hkv, s // pair, dk * pair),
        sds(1, b, hkv, s // pair, dv * pair), sds(b, 1, hkv, dk),
        sds(b, 1, hkv, dv), jax.ShapeDtypeStruct((b,), jnp.int32))
    after = [reg.counter(n).value for n in names]
    assert [a - c for a, c in zip(after, before)] \
        == [int(not long_step), int(long_step)]
