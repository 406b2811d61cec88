"""ExaoneMoeModel against the plain reference (every held expert on every
token, masked: ``benchmarks/reference/exaone_moe.py``) at tiny size in
float32: full forward, prefill then decode through the cache with prompts
longer than the window and decodes that cross it, the serving engine's slot
programs over ``SlotKVCache``'s two sizes of key-value state, the router
alone, the eight shares against the uncut layer, the ring's attention against
a plain banded one, and what the config, the family and the engine refuse."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import exaone_moe as family
from benchmarks.reference import exaone_moe as reference
from deepspeed_tpu.models.exaone_moe import ExaoneMoeConfig, ExaoneMoeModel
from deepspeed_tpu.moe.grouped import held_experts, sigmoid_topk_route

pytestmark = pytest.mark.quick

# the published keys at the sizes of the tests: a dense layer and three sparse
# ones, sliding, sliding, global, sliding at window 8; hidden 64, 4 query over
# 2 key-value heads of 32; 2 of 16 experts held, 4 a token
CFG = family.tiny({
    "family": "exaone_moe", "hidden_act": "silu", "first_k_dense_replace": 1,
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
    "num_shared_experts": 1, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "tie_word_embeddings": False, "num_nextn_predict_layers": 0})
TOL = dict(rtol=1e-4, atol=1e-5)


# The reference as ONE program a shape: op by op (eager) every operation of
# its layers compiles anew for each new sequence length, a minute a test.
_reference_logits = jax.jit(
    lambda params, ids: reference.forward_logits(params, ids, CFG))


@pytest.fixture(scope="module")
def built():
    model = family.build_model(CFG, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 29)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = _reference_logits(params, ids)
    return model, params, ids, ref


_STEP = {}


def _jitted_step(model):
    """``forward_with_cache`` jitted once for the module: op by op, a
    decode step of four scans takes seconds on this backend."""
    if id(model) not in _STEP:
        def step(params, ids, cache):
            with jax.default_matmul_precision("highest"):
                return model.forward_with_cache(params, ids, cache)

        _STEP[id(model)] = jax.jit(step)
    return _STEP[id(model)]


def test_the_tiny_model_is_the_stated_stack(built):
    model, params, _, _ = built
    c = model.config
    assert c.layer_types == ("sliding_attention", "sliding_attention",
                             "full_attention", "sliding_attention")
    assert c.mlp_layer_types == ("dense", "sparse", "sparse", "sparse")
    assert c.held == (0, 2) and c.num_experts == 16
    # ((ffn, attention), first of the ffn's stack, first of the cache, count)
    assert model.runs() == ((("dense", "sliding_attention"), 0, 0, 1),
                            (("sparse", "sliding_attention"), 0, 1, 1),
                            (("sparse", "full_attention"), 1, 0, 1),
                            (("sparse", "sliding_attention"), 2, 2, 1))
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    assert params["sparse"]["router"].shape == (3, 64, 16)
    assert params["sparse"]["expert_gate"].shape == (3, 2, 64, 32)


def test_full_forward_matches_the_reference(built):
    model, params, ids, ref = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
            params, ids)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


@pytest.mark.parametrize("prompt", [5, 8, 19])
def test_prefill_then_decode_matches_the_reference(built, prompt):
    """A prompt shorter than, equal to and longer than the window of 8, then
    decodes that cross it, by a per-slot index vector as the server has."""
    model, params, ids, ref = built
    step = _jitted_step(model)
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, 128, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray([prompt, prompt])
        logits, cache = step(params, ids[:, :prompt], cache)
        np.testing.assert_allclose(logits, ref[:, :prompt], **TOL)
        cache["index"] = jnp.full((2,), prompt, jnp.int32)
        for t in range(prompt, ids.shape[1]):
            cache["valid_len"] = jnp.asarray([1, 1])
            cache.pop("step_counters")
            logits, cache = step(params, ids[:, t:t + 1], cache)
            np.testing.assert_allclose(logits[:, 0], ref[:, t], **TOL)
    assert cache["k_win"].shape == (3, 2, 2, 8, 32)     # the ring: 8 rows
    assert cache["k"].shape[0] == 1                     # one global layer


@pytest.mark.parametrize("length,bucket", [(11, 16), (3, 16), (16, 16)])
def test_a_padded_prompt_leaves_the_rings_of_the_unpadded_one(built, length,
                                                              bucket):
    """Bucket padding behind a prompt is not in the ring: the last 8 REAL
    positions are, and padding is routed to no expert."""
    model, params, ids, _ = built
    row = ids[:1]

    def prefill(n_ids, valid):
        cache = model.init_cache(1, 32, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(valid)
        return _jitted_step(model)(params, row[:, :n_ids], cache)

    with jax.default_matmul_precision("highest"):
        lp, padded = prefill(bucket, length)
        lu, bare = prefill(length, length)
    np.testing.assert_allclose(lp[:, :length], lu, **TOL)
    for name in ("k_win", "v_win"):
        np.testing.assert_allclose(padded[name], bare[name], **TOL)
    np.testing.assert_array_equal(padded["step_counters"],
                                  bare["step_counters"])
    assert int(bare["step_counters"][3]) == 3 * 4 * length  # layers x k x T


# what the server served for the five requests of the test below before the
# attention projections were fenced from the per-head work behind them
# (models/base.project_heads, merge_heads: an ordering, no arithmetic); the
# reference's best logit leads its second by 8.7e-3 at least along them
@pytest.fixture(scope="module")
def eng():
    """One ``InferenceEngine`` for the module (float32, 64 positions, seed 3):
    its weights are made once, and every ``ServingEngine`` built on it shares
    the programs it has compiled."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    groups.reset()
    return deepspeed_tpu.init_inference(family.build_model(CFG, {}),
                                        dtype="fp32", max_out_tokens=64,
                                        seed=3)


SERVED_BEFORE_THE_FENCE = [
    [318, 299, 107, 10, 32, 372],
    [242, 357, 401, 372, 454, 377, 172, 187, 475, 190, 118, 272],
    [497, 170, 273, 455, 344, 478, 189, 161, 106],
    [140, 50, 198, 489, 116, 190, 118, 272, 50, 198, 489, 116, 190, 118, 272,
     50, 198, 489, 116, 190],
    [219, 348, 49, 57, 357]]


def test_the_serving_engine_serves_it_over_two_sizes_of_state(built, eng):
    """init_inference + ServingEngine: bucketed slot prefill, per-slot
    decode, slots reused; every served token is the reference's argmax, and
    the one served before the fence."""
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=3, max_len=64, buckets=(16, 32),
                        telemetry=reg, tenants=False)
    assert srv.cache.keys == ("k", "v", "k_win", "v_win")
    assert srv.cache.recurrent_keys == ("k_win", "v_win")
    assert (srv.cache.window, srv.cache.window_layers) == (8, 3)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, 512, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(5, 6), (20, 12), (31, 9), (12, 20),
                                        (27, 5)])]
    results = srv.run(reqs)
    assert len(results) == 5
    assert [list(r.tokens) for r in sorted(results, key=lambda r: r.rid)] \
        == SERVED_BEFORE_THE_FENCE
    with jax.default_matmul_precision("highest"):
        for r in results:
            prompt = reqs[r.rid].prompt
            seq = jnp.asarray([prompt + list(r.tokens)], jnp.int32)
            rows = _reference_logits(eng.params, seq)[0][
                len(prompt) - 1:len(prompt) - 1 + len(r.tokens)]
            gap = rows.max(-1) - rows[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < 1e-4, r.rid
    c = reg.snapshot()["counters"]
    steps = c["serving/decode_steps"]
    # three sparse layers of two held experts a decode step; the grouped
    # matmul reads the touched ones only; a token has 4 experts a layer; an
    # eighth of the pairs is expected here
    assert 0 < c["serving/moe_experts_touched"] == \
        c["serving/moe_experts_streamed"] < 3 * 2 * steps
    assert c["serving/moe_assignments"] == \
        3 * 4 * c["serving/slot_iterations_active"]
    assert 0 < c["serving/moe_assignments_held"] < \
        c["serving/moe_assignments"] / 3
    # five prompts, each whole through three sparse layers, counted on the
    # device and fetched with the first token; a bucket of 16 or 32 tokens is
    # too short for a compact buffer (a row tile is 128 rows), so none spills,
    # and every prompt holds real tokens
    assert c["serving/moe_prompt_blocks"] == 3 * 5
    assert c["serving/moe_prompt_blocks_spilled"] == 0
    assert c["serving/moe_prompt_blocks_empty"] <= 3 * 5 // 2


# (query heads, key-value heads, head size): multi-head, rep 4, rep 8
HEAD_SHAPES = [(4, 4, 32), (8, 2, 32), (8, 1, 16)]


@pytest.mark.parametrize("hq,hkv,dh", HEAD_SHAPES,
                         ids=["multi-head", "rep-4", "rep-8"])
def test_project_and_merge_heads_are_the_einsum_and_the_reshape(hq, hkv, dh):
    """The helpers alone, values and gradients: the fence passes both (the
    training walk runs the same block)."""
    from deepspeed_tpu.models.base import merge_heads, project_heads

    b, t, d = 2, 5, 48
    keys = jax.random.split(jax.random.PRNGKey(hq + hkv), 4)
    x = jax.random.normal(keys[0], (b, t, d))
    wq, wk, wo = (jax.random.normal(k, shape) * 0.1 for k, shape in zip(
        keys[1:], [(d, hq * dh), (d, hkv * dh), (hq * dh, d)]))

    def helped(x, wq, wk, wo):
        q = project_heads(x, wq, hq, dh)
        k = jnp.repeat(project_heads(x, wk, hkv, dh), hq // hkv, axis=2)
        return merge_heads(q * jnp.tanh(k), wo)

    def plain(x, wq, wk, wo):
        q = jnp.einsum("btd,de->bte", x, wq).reshape(b, t, hq, dh)
        k = jnp.repeat(jnp.einsum("btd,de->bte", x, wk).reshape(
            b, t, hkv, dh), hq // hkv, axis=2)
        return jnp.einsum("bte,ed->btd",
                          (q * jnp.tanh(k)).reshape(b, t, hq * dh), wo)

    assert project_heads(x, wq, hq, dh).shape == (b, t, hq, dh)
    np.testing.assert_array_equal(jax.jit(helped)(x, wq, wk, wo),
                                  jax.jit(plain)(x, wq, wk, wo))
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = jax.jit(jax.grad(loss(helped), argnums=(0, 1, 2, 3)))(x, wq, wk, wo)
    want = jax.jit(jax.grad(loss(plain), argnums=(0, 1, 2, 3)))(x, wq, wk, wo)
    for g, w in zip(got, want):
        assert float(jnp.abs(w).max()) > 1e-3
        np.testing.assert_allclose(g, w, **TOL)


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculative={"mode": "ngram"}),
                                    dict(preemption="swap"),
                                    dict(prefix_cache=True, kv_dtype="int8")])
def test_the_engine_refuses_what_addresses_a_ring_by_token_rows(eng, option):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError, match="k_win"):
        ServingEngine(eng, num_slots=2, max_len=64, buckets=(16,),
                      telemetry=None, **option)


def test_a_slots_window_bytes_do_not_grow_with_max_len():
    """``serving/state_bytes_per_slot`` at two values of ``max_len``: the
    global layer's rows double, the three sliding layers' rings stay."""
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model = family.build_model(CFG, {})
    row = 2 * 2 * 32 * 4            # k and v, 2 kv heads of 32, float32
    sizes = {}
    for max_len in (128, 256):
        cache = SlotKVCache(model, 4, max_len, dtype=jnp.float32)
        assert cache.state["k_win"].shape == (3, 4, 2, 8, 32)
        sizes[max_len] = cache.hbm_bytes() // 4
        assert sizes[max_len] == row * (max_len + 3 * 8)
    assert sizes[256] - sizes[128] == row * 128


# ------------------------------------------------------------ the router
def _router_inputs(n=6, d=16, e=16, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, d), jnp.float32),
            jnp.asarray(rng.randn(d, e) * 0.3, jnp.float32))


def test_the_embedding_is_drawn_at_the_streams_scale():
    """Rows of 1.0, matrices of 0.02: beside what attention (a context's
    average) and the dense layer add at the published widths, a row of 0.02
    left the router the same input for every token of a request, all of them
    picked the same experts and a layer's load was one draw a request
    (PERF.md, PR 35; the effect needs the widths, so this holds the scales)."""
    params = ExaoneMoeModel(ExaoneMoeConfig.tiny()).init(
        jax.random.PRNGKey(0))
    assert 0.9 < float(params["embed"].std()) < 1.1
    for matrix in (params["lm_head"], params["sparse"]["router"]):
        assert 0.015 < float(matrix.std()) < 0.025
    assert not params["sparse"]["select_bias"].any()


def test_the_selection_bias_picks_and_does_not_weigh():
    x, w = _router_inputs()
    sigma = np.asarray(jax.nn.sigmoid(x @ w))
    bias = np.zeros(16, np.float32)
    plain = sigmoid_topk_route(x, w, jnp.asarray(bias), 4, scale=2.5)
    top = np.argsort(-sigma, -1)[:, :4]
    assert (np.sort(np.asarray(plain.experts), -1) == np.sort(top, -1)).all()
    chosen = np.take_along_axis(sigma, np.asarray(plain.experts), -1)
    np.testing.assert_allclose(
        plain.weights, 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(plain.weights).sum(-1), 2.5,
                               rtol=1e-5)
    # a bias of 10 on the weakest expert of token 0 puts it among the four,
    # at the weight its own sigma gives, not sigma + 10
    weakest = int(np.argmin(sigma[0]))
    bias[weakest] = 10.0
    biased = sigmoid_topk_route(x, w, jnp.asarray(bias), 4, scale=2.5)
    assert weakest in np.asarray(biased.experts)[0]
    j = list(np.asarray(biased.experts)[0]).index(weakest)
    rest = np.sort(sigma[0])[::-1][:3].sum()
    np.testing.assert_allclose(
        biased.weights[0, j],
        2.5 * sigma[0, weakest] / (sigma[0, weakest] + rest), rtol=1e-5)
    raw = sigmoid_topk_route(x, w, jnp.zeros(16), 4, normalize=False)
    np.testing.assert_allclose(raw.weights, chosen, rtol=1e-5)


def test_no_pair_is_dropped_when_every_token_picks_one_expert():
    """Every token of 40 routed to expert 3 first: a capacity dispatch would
    drop most of them; here the group is 40 rows."""
    rng = np.random.RandomState(1)
    n, d, m, e = 40, 16, 8, 4
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    wg, wu = (jnp.asarray(rng.randn(e, d, m) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(e, m, d) * 0.3, jnp.float32)
    from deepspeed_tpu.moe.grouped import Routing
    routing = Routing(jnp.tile(jnp.asarray([[3, 1]], jnp.int32), (n, 1)),
                      jnp.tile(jnp.asarray([[0.7, 0.3]], jnp.float32),
                               (n, 1)))
    with jax.default_matmul_precision("highest"):
        y, counts = held_experts(x, routing, wg, wu, wd, (0, e))
        want = sum(wt * (jax.nn.silu(x @ wg[i]) * (x @ wu[i])) @ wd[i]
                   for i, wt in ((3, 0.7), (1, 0.3)))
    np.testing.assert_allclose(y, want, **TOL)
    assert (int(counts.touched), int(counts.assignments_held),
            int(counts.assignments)) == (2, 80, 80)
    # tokens that are not real get nothing and count for nothing
    valid = jnp.arange(n) < 10
    with jax.default_matmul_precision("highest"):
        y2, counts2 = held_experts(x, routing, wg, wu, wd, (0, e),
                                   valid=valid)
    np.testing.assert_allclose(y2[:10], want[:10], **TOL)
    assert not np.asarray(y2[10:]).any()
    assert int(counts2.assignments_held) == 20


def test_the_whole_stack_is_addressed_by_group():
    """Layer-stacked expert weights handed whole (``__whole__``) give what
    the layer's own slice gives: layer ``i``'s experts are groups ``i * count
    ..`` of the flattened stack, every other group empty."""
    rng = np.random.RandomState(5)
    n, d, m, e, k = 7, 16, 8, 16, 4
    x, w = _router_inputs(n, d, e, seed=5)
    wg, wu = (jnp.asarray(rng.randn(3, 4, d, m) * 0.3, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(3, 4, m, d) * 0.3, jnp.float32)
    valid = jnp.arange(n) != 2
    with jax.default_matmul_precision("highest"):
        routing = sigmoid_topk_route(x, w, jnp.zeros(e), k, scale=2.5)
        whole = [{"__whole__": a, "__layer__": jnp.asarray(1)}
                 for a in (wg, wu, wd)]
        stacked, cs = held_experts(x, routing, *whole, (4, 4), valid=valid)
        plain, cp = held_experts(x, routing, wg[1], wu[1], wd[1], (4, 4),
                                 valid=valid)
    np.testing.assert_allclose(stacked, plain, **TOL)
    assert not np.asarray(stacked[2]).any()
    assert tuple(map(int, cs)) == tuple(map(int, cp))
    assert int(cs.streamed) == int(cs.touched) <= 4


def test_the_eight_shares_add_up_to_the_uncut_layer(built):
    """The routed parts of ``held=(2r, 2)``, r = 0..7, give the routed part
    of the layer with all 16 experts; with the shared expert counted once
    that is what the reference computes for the uncut layer, and the
    reference's own shares add up the same way."""
    rng = np.random.RandomState(2)
    d, m, e, k = 64, 32, 16, 4
    z = jnp.asarray(rng.randn(2, 9, d), jnp.float32)
    p = {"router": jnp.asarray(rng.randn(d, e) * 0.2, jnp.float32),
         "select_bias": jnp.asarray(rng.randn(e) * 0.05, jnp.float32),
         **{f"shared_{n}": jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
            for n, s in (("gate", (d, m)), ("up", (d, m)),
                         ("down", (m, d)))}}
    wg, wu = (jnp.asarray(rng.randn(1, e, d, m) * 0.1, jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.randn(1, e, m, d) * 0.1, jnp.float32)
    cfg = dict(CFG, num_experts=e, experts_held_first=0)
    flat = z.reshape(-1, d)
    with jax.default_matmul_precision("highest"):
        routing = sigmoid_topk_route(flat, p["router"], p["select_bias"], k,
                                     scale=2.5)
        whole, counts = held_experts(flat, routing, wg[0], wu[0], wd[0],
                                     (0, e))
        parts = [held_experts(flat, routing, wg[0, r:r + 2], wu[0, r:r + 2],
                              wd[0, r:r + 2], (r, 2))
                 for r in range(0, e, 2)]
        shared = reference._gated(z, p["shared_gate"], p["shared_up"],
                                  p["shared_down"])
        uncut = reference._sparse_ffn(z, p, (wg, wu, wd), 0, cfg)
        ref_shares = [reference._sparse_ffn(
            z, p, (wg[:, r:r + 2], wu[:, r:r + 2], wd[:, r:r + 2]), 0,
            dict(cfg, num_experts=2, experts_held_first=r))
            for r in range(0, e, 2)]
    np.testing.assert_allclose(sum(y for y, _ in parts), whole, **TOL)
    np.testing.assert_allclose(
        shared + sum(y for y, _ in parts).reshape(z.shape), uncut, **TOL)
    np.testing.assert_allclose(sum(ref_shares) - 7 * shared, uncut, **TOL)
    assert sum(int(c.assignments_held) for _, c in parts) == \
        int(counts.assignments_held) == int(counts.assignments) == 18 * k
    # and a share is not nothing: each holds some of the pairs
    assert all(int(c.assignments_held) > 0 for _, c in parts)


# -------------------------------------------------------------- the ring
def _banded(q, k, v, window):
    """Plain attention over a whole sequence, ``i - j < window``."""
    b, t, hq, dh = q.shape
    rep = hq // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    s = jnp.einsum("bthd,bshd->bhts", q, k) * dh ** -0.5
    i = jnp.arange(t)
    ok = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < window)
    return jnp.einsum("bhts,bshd->bthd",
                      jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1), v)


@pytest.mark.parametrize("chunks", [(40,), (7, 33), (16, 16, 8), (3, 1, 36),
                                    (32, 8)])
def test_the_ring_attends_the_window_across_chunks(chunks):
    """A sequence fed to ``window_cached_attention`` in chunks (a whole
    prompt, chunked prefill, a decode token, blocks of the band form)
    attends what one banded attention over the whole sequence does."""
    from deepspeed_tpu.ops.attention import window_cached_attention

    rng = np.random.RandomState(3)
    b, t, hq, hkv, dh, w = 2, sum(chunks), 4, 2, 16, 8
    q = jnp.asarray(rng.randn(b, t, hq, dh), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, hkv, dh), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, hkv, dh), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = _banded(q, k, v, w)
        kr = jnp.zeros((2, b, hkv, w, dh), jnp.float32)
        vr = jnp.zeros_like(kr)
        got, at = [], 0
        for n in chunks:
            # per-slot indices, and a scalar for the first chunk
            idx = jnp.full((b,), at, jnp.int32) if at else 0
            out, kr, vr = window_cached_attention(
                q[:, at:at + n], kr, vr, k[:, at:at + n], v[:, at:at + n],
                1, idx)
            got.append(out)
            at += n
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, **TOL)
    assert not np.asarray(kr[0]).any()      # the other layer's ring untouched


def test_the_fused_step_on_a_ring_matches_the_einsum():
    """The per-slot decode kernel with ``ring=True`` (interpreted) against
    ``window_cached_attention``'s einsum at head size 128 and window 128:
    slots before, at and far past the window, one of them not decoding."""
    from deepspeed_tpu.ops.attention import window_cached_attention
    from deepspeed_tpu.ops.decode_step import fused_decode_step, slot_walk

    rng = np.random.RandomState(0)
    l, b, hkv, hq, w, dh = 2, 6, 2, 16, 128, 128
    kr = jnp.asarray(rng.randn(l, b, hkv, w, dh), jnp.float32)
    vr = jnp.asarray(rng.randn(l, b, hkv, w, dh), jnp.float32)
    q = jnp.asarray(rng.randn(b, 1, hq, dh), jnp.float32)
    kn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.float32)
    vn = jnp.asarray(rng.randn(b, 1, hkv, dh), jnp.float32)
    idx = jnp.asarray([0, 5, 127, 128, 300, 1000], jnp.int32)
    active = np.asarray([1, 1, 1, 1, 0, 1], bool)
    with jax.default_matmul_precision("highest"):
        a0, k0, v0 = window_cached_attention(
            q, kr, vr, kn, vn, 1, idx, valid=jnp.asarray(active, jnp.int32))
        a1, k1, v1 = fused_decode_step(
            q, kr, vr, kn, vn, 1, idx, active=slot_walk(idx, active),
            ring=True, interpret=True)
    np.testing.assert_allclose(a1[active], a0[active], rtol=1e-4, atol=1e-5)
    assert not np.asarray(a1[~active]).any()
    np.testing.assert_array_equal(k1, k0)
    np.testing.assert_array_equal(v1, v0)
    # slot 3 at position 128 wrote row 0; the slot that does not decode kept
    # its ring
    np.testing.assert_array_equal(k1[1, 3, :, 0], kn[3, 0])
    np.testing.assert_array_equal(k1[1, 4], kr[1, 4])


def test_a_long_prompt_block_is_attended_in_query_blocks(monkeypatch):
    """``decode_attention`` walks the queries of a block whose scores pass
    its limit in blocks, to the same result."""
    from deepspeed_tpu.ops import attention

    rng = np.random.RandomState(4)
    b, t, hq, hkv, s, dh = 1, 32, 4, 2, 48, 16
    q = jnp.asarray(rng.randn(b, t, hq, dh), jnp.float32)
    k = jnp.asarray(rng.randn(b, hkv, s, dh), jnp.float32)
    v = jnp.asarray(rng.randn(b, hkv, s, dh), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = attention.decode_attention(q, k, v, jnp.asarray(7))
        monkeypatch.setattr(attention, "_SCORE_BLOCK_BYTES",
                            b * hq * 8 * s * 4)
        blocked = attention.decode_attention(q, k, v, jnp.asarray(7))
        per_slot = attention.decode_attention(q, k, v, jnp.asarray([7]))
    np.testing.assert_allclose(blocked, whole, **TOL)
    np.testing.assert_allclose(per_slot, whole, **TOL)


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("change,needle", [
    (dict(n_group=2), "group limit"),
    (dict(topk_group=2), "group limit"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(scoring_func="softmax"), "sigmoid"),
    (dict(num_shared_experts=2), "shared expert"),
    (dict(held=(8, 16)), "not a range"),
    (dict(layer_types=("sliding_attention",)), "same, non-zero"),
    (dict(layer_types=("linear", "full_attention", "full_attention",
                       "full_attention")), "unknown layer kinds"),
])
def test_the_config_refuses_what_the_program_does_not_compute(change, needle):
    with pytest.raises(ValueError, match=needle):
        ExaoneMoeConfig.tiny(**change)


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("num_nextn_predict_layers", 1),
    ("first_k_dense_replace", 2), ("sliding_windows", [8, 8, 8, 8]),
    ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}),
    ("scoring_func", "softmax"), ("n_group", 4),
])
def test_the_family_refuses_a_key_it_cannot_honour(key, value):
    with pytest.raises(ValueError):
        family.build_model(dict(CFG, **{key: value}), {})


def test_topkgate_names_the_layer_for_more_than_two_experts_a_token():
    from deepspeed_tpu.moe.sharded_moe import TopKGate

    with pytest.raises(AssertionError, match="moe/grouped.py"):
        TopKGate(16, 8, k=8)


def test_generate_runs_the_whole_path_on_one_request(built, eng):
    """``InferenceEngine.generate`` (a scalar cache index) greedy-decodes
    what the reference's argmax gives."""
    prompt = np.random.RandomState(6).randint(0, 512, (1, 13))
    out = np.asarray(eng.generate(jnp.asarray(prompt, jnp.int32),
                                  max_new_tokens=12))
    assert out.shape == (1, 25)
    with jax.default_matmul_precision("highest"):
        rows = _reference_logits(eng.params, jnp.asarray(out))[0]
    gap = rows[12:24].max(-1) - rows[jnp.arange(12, 24), out[0, 13:]]
    assert float(gap.max()) < 1e-4
