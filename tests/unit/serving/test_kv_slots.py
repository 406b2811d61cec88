"""Slot-paged KV cache + per-slot ops-layer semantics on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.base import cache_positions
from deepspeed_tpu.ops.attention import (alloc_kv_cache, decode_attention,
                                         write_kv_cache, write_slot_rows)
from deepspeed_tpu.serving.kv_slots import SlotKVCache

pytestmark = [pytest.mark.serving, pytest.mark.quick]


def test_cache_positions():
    assert cache_positions(jnp.int32(5), 3).tolist() == [5, 6, 7]
    v = cache_positions(jnp.asarray([2, 9], jnp.int32), 1)
    assert v.shape == (2, 1) and v.tolist() == [[2], [9]]


def test_slot_kv_cache_shapes_and_capacity():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config.tiny(), compute_dtype=jnp.float32)
    c = SlotKVCache(model, num_slots=4, max_len=128)
    # tiny gpt2: Dh=16 -> pair=8 packed rows
    assert c.pair == 8
    assert c.k.shape == (2, 4, 4, 128 // 8, 16 * 8)
    assert c.lengths.shape == (4,) and int(c.lengths.sum()) == 0
    assert c.capacity_for(100, 28)
    assert not c.capacity_for(100, 29)
    assert c.hbm_bytes() == 2 * c.k.size * 4


def test_capacity_reserves_speculative_lookahead():
    """Boundary regression (ISSUE 4 satellite): with speculation the
    verify step writes k draft candidates BEYOND the committed length
    before acceptance, so a request that exactly fills the slot without
    the k-row reserve would overflow max_len on its final verify —
    capacity_for(…, lookahead=k) must reject it at the boundary."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    model = GPT2Model(GPT2Config.tiny(), compute_dtype=jnp.float32)
    c = SlotKVCache(model, num_slots=2, max_len=128)
    k = 8
    # fits without speculation ...
    assert c.capacity_for(100, 28)
    # ... but the last verify would write rows up to
    # 100 + 28 - 1 + 8 = 135 > 127: rejected with the reserve
    assert not c.capacity_for(100, 28, lookahead=k)
    assert c.capacity_for(100, 28 - k, lookahead=k)       # exact boundary
    assert not c.capacity_for(100, 28 - k + 1, lookahead=k)
    assert c.capacity_for(100, 28, lookahead=0)           # default intact


def test_multi_token_per_slot_write_matches_per_row_loop():
    """The speculative verify path's block scatter: a [B, T] write at
    per-slot offsets == T scalar writes per row; positions past the
    allocation are DROPPED, never wrapped or clamped onto live rows."""
    rng = np.random.RandomState(4)
    l, b, h, s, dh, t = 2, 3, 2, 16, 8, 4
    kf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.float32)
    vf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.float32)
    kn = jnp.asarray(rng.randn(b, t, h, dh), jnp.float32)
    vn = jnp.asarray(rng.randn(b, t, h, dh), jnp.float32)
    idx = jnp.asarray([5, 0, 14], jnp.int32)   # row 2 runs off the end
    kv, vv, _, _ = write_kv_cache(kf, vf, kn, vn, jnp.int32(1), idx)
    k_ref = np.asarray(kf).copy()
    v_ref = np.asarray(vf).copy()
    for i in range(b):
        for j in range(t):
            p = int(idx[i]) + j
            if p < s:                           # OOB writes must drop
                k_ref[1, i, :, p] = np.asarray(kn)[i, j]
                v_ref[1, i, :, p] = np.asarray(vn)[i, j]
    np.testing.assert_array_equal(np.asarray(kv), k_ref)
    np.testing.assert_array_equal(np.asarray(vv), v_ref)


def test_per_slot_write_matches_per_row_scalar_writes():
    """The vector-idx scatter write == one scalar slice write per row."""
    rng = np.random.RandomState(0)
    l, b, h, s, dh = 3, 4, 2, 32, 8
    kf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.float32)
    vf = jnp.asarray(rng.randn(l, b, h, s, dh), jnp.float32)
    kn = jnp.asarray(rng.randn(b, 1, h, dh), jnp.float32)
    vn = jnp.asarray(rng.randn(b, 1, h, dh), jnp.float32)
    layer = jnp.int32(1)
    idx = jnp.asarray([7, 0, 31, 12], jnp.int32)
    kv, vv, _, _ = write_kv_cache(kf, vf, kn, vn, layer, idx)
    k_ref, v_ref = np.asarray(kf).copy(), np.asarray(vf).copy()
    for i in range(b):
        k_ref[1, i, :, int(idx[i])] = np.asarray(kn)[i, 0]
        v_ref[1, i, :, int(idx[i])] = np.asarray(vn)[i, 0]
    np.testing.assert_array_equal(np.asarray(kv), k_ref)
    np.testing.assert_array_equal(np.asarray(vv), v_ref)


def test_per_slot_decode_attention_matches_per_row_scalar():
    """Vector cache_index masking == running each row alone with its
    scalar index (per-slot length isolation at the op level)."""
    rng = np.random.RandomState(1)
    b, hq, hkv, s, dh = 3, 4, 2, 64, 8
    q = jnp.asarray(rng.randn(b, 1, hq, dh), jnp.float32)
    kc = jnp.asarray(rng.randn(b, hkv, s, dh), jnp.float32)
    vc = jnp.asarray(rng.randn(b, hkv, s, dh), jnp.float32)
    idx = jnp.asarray([50, 0, 17], jnp.int32)
    out = decode_attention(q, kc, vc, idx)
    for i in range(b):
        solo = decode_attention(q[i:i + 1], kc[i:i + 1], vc[i:i + 1],
                                jnp.int32(int(idx[i])))
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(solo[0]),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("pair_packed", [False, True])
def test_write_slot_rows(pair_packed):
    """Bucket-prefix insert lands in exactly the target slot's leading
    rows, packed or unpacked, and touches nothing else."""
    rng = np.random.RandomState(2)
    l, slots, h, s, dh, bucket = 2, 3, 4, 128, 16, 16
    if pair_packed:
        kf = alloc_kv_cache(l, slots, h, s, dh, jnp.float32)  # pair=8
        assert kf.shape[3] == s // 8
    else:
        kf = alloc_kv_cache(l, slots, h, s, dh, jnp.float32, packed=False)
    vf = kf + 1.0
    kp = jnp.asarray(rng.randn(l, 1, h, bucket, dh), jnp.float32)
    vp = jnp.asarray(rng.randn(l, 1, h, bucket, dh), jnp.float32)
    k2, v2 = (write_slot_rows(full, prefix, jnp.int32(1))
              for full, prefix in ((kf, kp), (vf, vp)))
    ku = np.asarray(k2).reshape(l, slots, h, s, dh)
    vu = np.asarray(v2).reshape(l, slots, h, s, dh)
    np.testing.assert_array_equal(ku[:, 1, :, :bucket], np.asarray(kp)[:, 0])
    np.testing.assert_array_equal(vu[:, 1, :, :bucket], np.asarray(vp)[:, 0])
    # untouched: other slots + rows past the bucket
    base_k = np.asarray(kf).reshape(l, slots, h, s, dh)
    np.testing.assert_array_equal(ku[:, 0], base_k[:, 0])
    np.testing.assert_array_equal(ku[:, 2], base_k[:, 2])
    np.testing.assert_array_equal(ku[:, 1, :, bucket:],
                                  base_k[:, 1, :, bucket:])


def test_vector_rotary_offset_matches_per_row():
    from deepspeed_tpu.ops.rotary import apply_rotary_pos_emb, rope_frequencies

    rng = np.random.RandomState(3)
    b, t, h, dh = 3, 1, 2, 16
    x = jnp.asarray(rng.randn(b, t, h, dh), jnp.float32)
    cos, sin = rope_frequencies(dh, 64)
    offs = [5, 0, 63]
    out = apply_rotary_pos_emb(x, cos, sin,
                               position_offset=jnp.asarray(offs, jnp.int32))
    for i, o in enumerate(offs):
        solo = apply_rotary_pos_emb(x[i:i + 1], cos, sin, position_offset=o)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(solo[0]),
                                   rtol=1e-6, atol=1e-6)
