"""MimoV2Model against the plain reference (``benchmarks/reference/mimo_v2.py``)
at tiny size in float32 and piece by piece: the sixteen shares of an expert
layer against the uncut one, the sink in the band and in the softmax, the
rotation's lanes and thetas by the layer's kind, the cache's four leaves of
two head counts and two widths in one walk, a padded key row, and what the
config and the family refuse."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import mimo_v2 as family
from benchmarks.reference import mimo_v2 as reference
from deepspeed_tpu.models.mimo_v2 import GLOBAL, SLIDING, MimoV2Config, MimoV2Model
from deepspeed_tpu.moe.grouped import held_experts, sigmoid_topk_route
from deepspeed_tpu.ops.attention import key_row_width, window_cached_attention
from deepspeed_tpu.ops.rotary import apply_rotary_half

pytestmark = pytest.mark.quick

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
with open(os.path.join(ROOT, "benchmarks", "configs", "mimo-v2.5.json")) as f:
    PUBLISHED = json.load(f)
# the cell's seven layers at hidden 64: 4 query heads over 1 (global) and 2
# (sliding) key-value heads, keys 24 and values 16 wide, window 8, 2 of 16
# experts held, 4 a token
CFG = family.tiny(PUBLISHED)
TOL = dict(rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def built():
    model = family.build_model(CFG, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 29)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: reference.forward_logits(p, x, CFG))(
            params, ids)
        out = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
            params, ids)
    return model, params, ids, ref, out


def test_the_tiny_model_is_the_stated_stack(built):
    model, params, *_ = built
    c = model.config
    assert c.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert c.moe_layer_freq == (0, 1, 1, 1, 1, 1, 1)
    assert c.held == (0, 2) and c.num_experts == 16
    # ((ffn, attention), first of the pair's stack, first of the cache, count)
    assert model.runs() == ((("dense", GLOBAL), 0, 0, 1),
                            (("sparse", SLIDING), 0, 0, 4),
                            (("sparse", GLOBAL), 0, 1, 1),
                            (("sparse", SLIDING), 4, 4, 1))
    assert model.stacks == ("dense_global", "sparse_sliding", "sparse_global")
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    # the fused projection's columns by attention kind; a sink a head on
    # sliding layers alone; no shared expert anywhere
    assert params["sparse_global"]["wqkv"].shape == (1, 64, 4 * 24 + 24 + 16)
    assert params["sparse_sliding"]["wqkv"].shape == (5, 64,
                                                      4 * 24 + 2 * (24 + 16))
    assert params["sparse_sliding"]["sink"].shape == (5, 4)
    assert "sink" not in params["sparse_global"]
    assert not [k for s in model.stacks for k in params[s] if "shared" in k]


def test_full_forward_matches_the_reference(built):
    *_, ref, out = built
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


def test_leaving_the_drawn_sinks_out_moves_the_logits(built):
    """A sink of -inf is the plain softmax, in the program and in the
    reference alike; the drawn sinks (normal(0, 1)) move the logits far past
    the tolerance the two are held to."""
    model, params, ids, ref, _ = built
    gone = jax.tree_util.tree_map(lambda a: a, params)
    gone["sparse_sliding"] = dict(
        params["sparse_sliding"],
        sink=jnp.full_like(params["sparse_sliding"]["sink"], -jnp.inf))
    no_sink = dict(CFG, add_swa_attention_sink_bias=False)
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(lambda p, x: reference.forward_logits(p, x, no_sink))(
            params, ids)
        out = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
            gone, ids)
    np.testing.assert_allclose(out, plain, **TOL)
    # logits of this size are a few tenths: thirty times the tolerance
    assert float(jnp.abs(plain - ref).max()) > 30 * TOL["rtol"]


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts of ``held=(2r, 2)``, r = 0..15, give the layer with
    all 32 experts, which is what the reference computes for the uncut layer
    (no shared expert: nothing is counted once); the reference's own shares
    add up the same way."""
    rng = np.random.RandomState(2)
    d, m, e, k = 64, 32, 32, 4
    z = jnp.asarray(rng.randn(2, 9, d), jnp.float32)
    stack = {"router": jnp.asarray(rng.randn(1, d, e) * 0.2, jnp.float32),
             "select_bias": jnp.asarray(rng.randn(1, e) * 0.05, jnp.float32),
             "expert_gate": jnp.asarray(rng.randn(1, e, d, m) * 0.1),
             "expert_up": jnp.asarray(rng.randn(1, e, d, m) * 0.1),
             "expert_down": jnp.asarray(rng.randn(1, e, m, d) * 0.1)}
    cfg = dict(CFG, n_routed_experts=e, n_routed_experts_published=e,
               experts_held_first=0)
    flat = z.reshape(-1, d)

    def share(r, n):
        return {**stack, **{name: stack[name][:, r:r + n] for name in
                            ("expert_gate", "expert_up", "expert_down")}}

    with jax.default_matmul_precision("highest"):
        routing = sigmoid_topk_route(flat, stack["router"][0],
                                     stack["select_bias"][0], k, scale=1.0)
        parts = [held_experts(flat, routing, *(share(r, 2)[n][0] for n in (
            "expert_gate", "expert_up", "expert_down")), (r, 2))
            for r in range(0, e, 2)]
        uncut = reference._sparse_ffn(z, stack, 0, cfg)
        ref_shares = [reference._sparse_ffn(
            z, share(r, 2), 0, dict(cfg, n_routed_experts=2,
                                    experts_held_first=r))
            for r in range(0, e, 2)]
    assert len(parts) == 16
    np.testing.assert_allclose(
        sum(y for y, _ in parts).reshape(z.shape), uncut, **TOL)
    np.testing.assert_allclose(sum(ref_shares), uncut, **TOL)
    assert sum(int(c.assignments_held) for _, c in parts) == 18 * k


def test_the_band_takes_a_sink_and_minus_infinity_is_none():
    """``window_cached_attention``'s band prefill with a sink against a plain
    banded softmax with the sink as a dropped column, keys and values of two
    widths, across chunks so that the ring wraps; ``-inf`` is no sink."""
    rng = np.random.RandomState(5)
    b, t, hq, hkv, dk, dv, w = 2, 40, 4, 2, 24, 16, 8
    q = jnp.asarray(rng.randn(b, t, hq, dk), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, dk), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, dv), jnp.float32)
    sink = jnp.asarray(rng.randn(hq), jnp.float32)

    def plain(sink):
        kk, vv = (jnp.repeat(a, hq // hkv, 2) for a in (k, v))
        s = jnp.einsum("bthd,bshd->bhts", q, kk) * dk ** -0.5
        i = jnp.arange(t)
        ok = (i[:, None] >= i[None, :]) & (i[:, None] - i[None, :] < w)
        s = jnp.where(ok, s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink[None, :, None, None], (b, hq, t, 1))], -1)
        p = jax.nn.softmax(s, -1)[..., :t]
        return jnp.einsum("bhts,bshd->bthd", p, vv)

    band = jax.jit(lambda q, kr, vr, k, v, at, sink: window_cached_attention(
        q, kr, vr, k, v, 0, at, sink=sink))

    def served(sink, chunks):
        kr = jnp.zeros((1, b, hkv, w, dk), jnp.float32)
        vr = jnp.zeros((1, b, hkv, w, dv), jnp.float32)
        outs, at = [], 0
        for n in chunks:
            o, kr, vr = band(
                q[:, at:at + n], kr, vr, k[:, at:at + n], v[:, at:at + n],
                jnp.full((b,), at, jnp.int32), sink)
            outs.append(o)
            at += n
        return jnp.concatenate(outs, 1)

    with jax.default_matmul_precision("highest"):
        out = served(sink, (16, 24))
        assert out.shape == (b, t, hq, dv)
        np.testing.assert_allclose(out, plain(sink), **TOL)
        np.testing.assert_allclose(
            served(jnp.full((hq,), -jnp.inf), (16, 24)), plain(None), **TOL)
        assert float(jnp.abs(plain(sink) - plain(None)).max()) > 0.01


@pytest.mark.parametrize("attn", [GLOBAL, SLIDING])
def test_rotation_touches_the_first_lanes_at_the_kinds_theta(built, attn):
    """Of a key's 24 lanes the first int(0.334 x 24) = 8 are rotated
    (rotate-half), at ``rope_theta`` on global and ``swa_rope_theta`` on
    sliding layers; the others and the values do not move with position. At
    the published sizes that is lanes 0 to 63 of 192."""
    model, params, *_ = built
    c = model.config
    assert c.rotary_dim == 8 and MimoV2Config().rotary_dim == 64
    stack = "sparse_" + attn
    blk = {k: v[0] for k, v in params[stack].items()}
    y = jnp.asarray(np.random.RandomState(7).randn(1, 6, 64), jnp.float32)
    zero, pos = jnp.zeros((6,), jnp.int32), jnp.arange(6) + 11
    q0, k0, v0 = model._qkv(y, blk, attn, zero)
    q1, k1, v1 = model._qkv(y, blk, attn, pos)
    assert k0.shape == (1, 6, c.kv_heads(attn), 24) and \
        v0.shape == (1, 6, c.kv_heads(attn), 16)
    np.testing.assert_array_equal(q0[..., 8:], q1[..., 8:])
    np.testing.assert_array_equal(k0[..., 8:], k1[..., 8:])
    np.testing.assert_array_equal(v0, v1)
    theta = c.swa_rope_theta if attn == SLIDING else c.rope_theta
    assert theta == (1e4 if attn == SLIDING else 1e7)
    np.testing.assert_allclose(
        k1[..., :8], apply_rotary_half(k0[..., :8], pos, theta), **TOL)
    other = c.rope_theta if attn == SLIDING else c.swa_rope_theta
    assert float(jnp.abs(k1[..., :8] - apply_rotary_half(
        k0[..., :8], pos, other)).max()) > 1e-3
    # the value scale, as written
    raw = (y @ blk["wqkv"])[..., -c.kv_heads(attn) * 16:]
    np.testing.assert_allclose(v0.reshape(1, 6, -1), 0.707 * raw, **TOL)


def test_the_cache_holds_four_leaves_of_two_head_counts_and_two_widths():
    """At the published sizes: rows on the two global layers at 4 heads,
    rings on the five sliding ones at 8, a key row of 256 lanes (192 live)
    and a value row of 128."""
    cell = dict(PUBLISHED)
    model = family.build_model(cell, {})
    cache = jax.eval_shape(
        lambda: model.init_cache(16, 16384, dtype=jnp.bfloat16))
    assert key_row_width(192) == 256 and key_row_width(128) == 128 \
        and key_row_width(24) == 24 and key_row_width(576) == 640
    assert {k: v.shape for k, v in cache.items() if k != "index"} == {
        "k": (2, 16, 4, 16384, 256), "v": (2, 16, 4, 16384, 128),
        "k_win": (5, 16, 8, 128, 256), "v_win": (5, 16, 8, 128, 128)}
    assert model.slot_state_keys == ("k", "v", "k_win", "v_win")
    assert model.window_state_keys == ("k_win", "v_win")
    # what a slot holds: 6,144 bytes a token of rows, 3.93 MB of rings
    rows = 2 * 4 * (256 + 128) * 2
    rings = 5 * 8 * 128 * (256 + 128) * 2
    assert (rows, rings) == (6144, 3932160)
    assert model.num_params() == 3429955392


def test_a_padded_key_row_serves_what_the_plain_forward_gives():
    """Keys 192 wide in rows of 256 lanes on this backend too: prefill in
    token blocks through the blocked prompt attention and the band, then
    decode steps, against the full forward."""
    c = MimoV2Config.tiny(head_dim=192, v_head_dim=128,
                          hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1))
    model = MimoV2Model(c, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(1))
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 512, (2, 36)),
                      jnp.int32)
    step = jax.jit(model.forward_with_cache)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: model.logits(
            p, model.forward_hidden(p, x)))(params, ids)
        cache = model.init_cache(2, 64, dtype=jnp.float32)
        assert cache["k"].shape == (1, 2, 1, 64, 256)
        assert cache["v_win"].shape == (1, 2, 2, 8, 128)
        cache["valid_len"] = jnp.asarray([32, 32])
        logits, cache = step(params, ids[:, :32], cache)
        np.testing.assert_allclose(logits[:, 0], want[:, 31], **TOL)
        assert not np.asarray(cache["k"][..., 192:]).any()   # the zero lanes
        cache["index"] = jnp.full((2,), 32, jnp.int32)
        for t in range(32, 34):
            cache["valid_len"] = jnp.asarray([1, 1])
            cache.pop("step_counters")
            logits, cache = step(params, ids[:, t:t + 1], cache)
            np.testing.assert_allclose(logits[:, 0], want[:, t], **TOL)


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("change,needle", [
    (dict(n_group=2), "group limit"),
    (dict(topk_group=2), "group limit"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(n_shared_experts=1), "shared expert"),
    (dict(add_full_attention_sink_bias=True), "global layers"),
    (dict(held=(8, 16)), "not a range"),
    (dict(hybrid_layer_pattern=(1,)), "same, non-zero"),
    (dict(hybrid_layer_pattern=(0, 2, 1, 0, 1)), "0 and 1 alone"),
    (dict(partial_rotary_factor=0.0), "rotates"),
    (dict(swa_num_kv_heads=3), "divide the heads"),
])
def test_the_config_refuses_what_the_program_does_not_compute(change, needle):
    with pytest.raises(ValueError, match=needle):
        MimoV2Config.tiny(**change)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("swa_head_dim", 32),
    ("swa_v_head_dim", 32), ("sliding_window_size", 16),
    ("rope_scaling", {"rope_type": "yarn"}), ("n_shared_experts", 1),
    ("scoring_func", "softmax"), ("n_group", 4),
    ("add_full_attention_sink_bias", True), ("num_hidden_layers", 6),
    ("hybrid_layer_pattern", [0, 1, 0, 1, 1, 0, 1]),
])
def test_the_family_refuses_a_key_it_cannot_honour(key, value):
    with pytest.raises(ValueError):
        family.build_model(dict(CFG, **{key: value}), {})
