"""GigaChat35Model against the plain reference (the delta rule position by
position with a key head for two value heads, the latent layer decompressed
with a plain masked softmax, every held expert on every token: ``benchmarks/
reference/gigachat35.py``) at tiny size in float32: full forward, prefill then
decode through the cache with prompts that walk several token blocks, chunks
and key blocks, the slot cache's PAIR of leaves (latent rows beside recurrent
state) under the serving engine and under the slot programs' own pieces
(logits, not tokens), padding behind ``valid_len``, the sixteen shares against
the uncut layer, the sliced vocabulary, the ``swiglu_limit`` clamp where it
binds, the zero-centred gated norm, and what the engine refuses."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.families import gigachat35 as family
from benchmarks.reference import gigachat35 as reference
from deepspeed_tpu.models.gigachat35 import GDN, MLA, GigaChat35Config, GigaChat35Model
from deepspeed_tpu.models.moe_ffn import DENSE, SPARSE, ffn
from deepspeed_tpu.ops.attention import insert_slot_row, write_slot_rows
from deepspeed_tpu.ops.decode_step import slot_walk

pytestmark = pytest.mark.quick

# the published keys at the sizes of the tests: the dense delta-rule layer and
# one period (a latent layer, three delta-rule layers); hidden 64, 4 latent
# heads of 16 + 8 / 16 over a latent of 32, 2 key and 4 value delta-rule heads
# of 16 x 16; 2 of 16 experts held, 4 a token; token blocks of 16, chunks and
# key blocks of 8
PUBLISHED = harness.load_json("configs", "gigachat3.5-432b-a28b.json")
CFG = family.tiny(PUBLISHED)
TOL = dict(rtol=1e-4, atol=2e-5)


# The reference as ONE program a shape: op by op (eager) every operation of
# its layers compiles anew for each new sequence length, a minute a test.
_reference_logits = jax.jit(
    lambda params, ids: reference.forward_logits(params, ids, CFG))
T = 48


@pytest.fixture(scope="module")
def built():
    model = family.build_model(CFG, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, T)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = _reference_logits(params, ids)
    return model, params, ids, ref


_STEP = {}


def _jitted_step(model):
    if id(model) not in _STEP:
        def step(params, ids, cache):
            with jax.default_matmul_precision("highest"):
                return model.forward_with_cache(params, ids, cache)

        _STEP[id(model)] = jax.jit(step)
    return _STEP[id(model)]


def test_the_tiny_model_is_the_stated_stack(built):
    model, params, _, _ = built
    c = model.config
    assert c.layer_kinds() == ("gdn_dense", "mla_sparse", "gdn_sparse",
                               "gdn_sparse", "gdn_sparse")
    assert c.runs() == (("gdn_dense", 0, 0, 1), ("mla_sparse", 0, 0, 1),
                        ("gdn_sparse", 0, 1, 3))
    assert (c.count(GDN), c.count(MLA)) == (4, 1)
    assert c.held == (0, 2) and c.num_experts == 16
    assert (c.prompt_block, c.key_block, c.gdn_chunk) == (16, 8, 8)
    assert (c.swiglu_limit, c.norm_gate, c.gdn_gate_scale) == (10.0, 2.0, 2.0)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    assert "mla_dense" not in params
    # q (2 heads) | k (2 heads) | v (4 heads), 16 lanes a head
    assert params["gdn_sparse"]["w_qkv"].shape == (3, 64, 8 * 16)
    assert params["gdn_sparse"]["conv_w"].shape == (3, 4, 8 * 16)
    assert params["gdn_sparse"]["w_ba"].shape == (3, 64, 8)
    assert params["gdn_sparse"]["A_log"].shape == (3, 4)
    assert params["gdn_dense"]["w_up"].shape == (1, 64, 128)
    assert params["mla_sparse"]["attn_gate"].shape == (1, 64, 64)
    assert params["mla_sparse"]["wq_b"].shape == (1, 16, 4 * 24)
    assert params["mla_sparse"]["expert_gate"].shape == (1, 2, 64, 32)
    # the published stack: three dense delta-rule layers, then periods of a
    # latent layer and three delta-rule layers
    wide = GigaChat35Config()
    assert wide.runs()[:4] == (("gdn_dense", 0, 0, 3), ("mla_sparse", 0, 0, 1),
                               ("gdn_sparse", 0, 3, 3),
                               ("mla_sparse", 1, 1, 1))
    assert (wide.count(GDN), wide.count(MLA), wide.gdn_rows) == (30, 10, 128)
    assert wide.score_scale == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(8) + 1) ** 2)


def test_full_forward_matches_the_reference(built):
    model, params, ids, ref = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
            params, ids)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    np.testing.assert_allclose(out, ref, **TOL)


@pytest.mark.parametrize("prompt", [5, 16, 32])
def test_prefill_then_decode_matches_the_reference(built, prompt):
    """A prompt inside one token block, of one whole block and of two (the
    walk inside the program, the state, the tails and the latent rows carried
    from block to block), then decodes by a per-slot index vector."""
    model, params, ids, ref = built
    step = _jitted_step(model)
    cache = model.init_cache(2, 64, dtype=jnp.float32)
    logits, cache = step(params, ids[:, :prompt], cache)
    np.testing.assert_allclose(logits, ref[:, :prompt], **TOL)
    cache["index"] = jnp.full((2,), prompt, jnp.int32)
    for t in range(prompt, T):
        cache["valid_len"] = jnp.asarray([1, 1])
        cache.pop("step_counters")
        logits, cache = step(params, ids[:, t:t + 1], cache)
        np.testing.assert_allclose(logits[:, 0], ref[:, t], **TOL)
    assert cache["latent"].shape == (1, 2, 64, 128)     # ONE layer has rows
    assert cache["gdn"].shape == (4, 2, 4, 16, 16)
    assert cache["gdn"].dtype == jnp.float32
    assert cache["gdn_conv"].shape == (4, 2, 3, 8, 16)


@pytest.mark.parametrize("length,bucket", [(11, 16), (19, 32), (32, 32),
                                           (9, 32)])
def test_padding_behind_valid_len_changes_neither_state_nor_tails(
        built, length, bucket):
    """Told the true length, a prefill returns the logits of the last real
    position alone, and its state and tails are those of a prefill of exactly
    that many tokens: padding is masked (g = 0, beta = 0) and routed to no
    expert. At 9 of 32 the whole second token block is padding."""
    model, params, ids, ref = built
    step = _jitted_step(model)
    cache = model.init_cache(1, 32, dtype=jnp.float32)
    cache["valid_len"] = jnp.asarray(length)
    logits, out = step(params, ids[:1, :bucket], cache)
    assert logits.shape == (1, 1, 512)
    np.testing.assert_allclose(logits[0, 0], ref[0, length - 1], **TOL)
    assert int(out["step_counters"][3]) == 4 * 4 * length   # layers x k x T
    _, exact = step(params, ids[:1, :length],
                    model.init_cache(1, 32, dtype=jnp.float32))
    np.testing.assert_allclose(out["gdn"], exact["gdn"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(out["gdn_conv"], exact["gdn_conv"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(out["latent"][:, :, :length],
                               exact["latent"][:, :, :length], rtol=1e-5,
                               atol=1e-6)


def test_slot_programs_pieces_give_the_references_logits(built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    three leaves, by ``SlotKVCache``'s own tree: bucketed prefills written
    into slots (latent rows as a prefix, recurrent leaves whole), two slots of
    unequal length decoding together with a third inactive, a slot reused by
    a shorter request (state and tails start from zero at position 0
    whatever the slot held, and the rows behind its length are dead). Logits,
    not tokens."""
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref = built
    step = _jitted_step(model)
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == ("latent", "gdn", "gdn_conv")
    assert slots.row_keys == ("latent",)
    assert slots.recurrent_keys == ("gdn", "gdn_conv")
    assert slots.pair == 1 and not slots.fused_walk     # 64 rows: no chunk
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    # a slot's last tenant leaves its state and rows behind: the next starts
    # from zero and never attends a row it did not write
    state["gdn"] = state["gdn"] + 7.0
    state["gdn_conv"] = state["gdn_conv"] - 3.0
    state["latent"] = state["latent"] + 5.0

    def prefill(row, length, bucket, slot):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        for name in slots.row_keys:
            state[name] = write_slot_rows(state[name], cache[name], slot)
        for name in slots.recurrent_keys:
            state[name] = insert_slot_row(state[name], cache[name], slot)
        lengths[slot] = length

    def decode(rows_of, active):
        """One step: slot i feeds row ``rows_of[i]``'s token at its length."""
        act = jnp.asarray(active, bool)
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([ids[r, n] for r, n in zip(rows_of, lengths)])
        before = {n: np.asarray(state[n]) for n in slots.recurrent_keys}
        cache = dict(state, index=idx, valid_len=act.astype(jnp.int32),
                     slot_walk=slot_walk(idx, act))
        logits, cache = step(params, tokens[:, None], cache)
        for i, (r, on) in enumerate(zip(rows_of, active)):
            if on:
                np.testing.assert_allclose(logits[i, 0], ref[r, lengths[i]],
                                           **TOL)
                lengths[i] += 1
            else:       # an idle slot's state and tails do not move
                for n in slots.recurrent_keys:
                    np.testing.assert_array_equal(
                        np.asarray(cache[n])[:, i], before[n][:, i])
        for n in slots.keys:
            state[n] = cache[n]

    prefill(0, 20, 32, 1)
    prefill(1, 7, 16, 0)
    for _ in range(6):
        decode((1, 0, 0), (True, True, False))
    # slot 1 is reused by a shorter request while slot 0 goes on
    prefill(1, 5, 16, 1)
    for _ in range(5):
        decode((1, 1, 0), (True, True, False))
    assert list(lengths) == [18, 10, 0]


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


def _assert_served_tokens_are_the_references(eng, reqs, results):
    with jax.default_matmul_precision("highest"):
        for r in results:
            prompt = reqs[r.rid].prompt
            assert len(r.tokens) == reqs[r.rid].max_new_tokens
            seq = jnp.asarray([prompt + list(r.tokens)], jnp.int32)
            rows = _reference_logits(eng.params, seq)[0][
                len(prompt) - 1:len(prompt) - 1 + len(r.tokens)]
            gap = rows.max(-1) - rows[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < 1e-4, r.rid


def test_the_serving_engine_holds_a_latent_leaf_beside_recurrent_state(
        built, eng):
    """init_inference + ServingEngine over the PAIR of leaves: a model that
    declares ``latent`` beside a recurrent state is admitted, prefilled by
    bucket, decoded by slot, freed and re-admitted (two slots for six
    requests: every slot is reused, by shorter and by longer requests) with
    no stale state in a reused slot: every served token is the reference's
    argmax."""
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        telemetry=reg, tenants=False)
    assert srv.cache.keys == ("latent", "gdn", "gdn_conv")
    assert srv.cache.row_keys == ("latent",)
    assert srv.cache.recurrent_keys == ("gdn", "gdn_conv")
    # all three leaves: latent rows of ONE layer (24 live lanes in 128),
    # float32 state and tails of four
    per_slot = (64 * 128 + 4 * 4 * 16 * 16 + 4 * 3 * 8 * 16) * 4
    assert reg.snapshot()["gauges"]["serving/state_bytes_per_slot"] == \
        per_slot == srv.cache.hbm_bytes() // 2
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, 512, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(20, 12), (31, 9), (5, 6), (12, 20),
                                        (27, 5), (7, 8)])]
    results = srv.run(reqs)
    assert sorted(r.rid for r in results) == list(range(6))
    _assert_served_tokens_are_the_references(eng, reqs, results)
    c = reg.snapshot()["counters"]
    steps = c["serving/decode_steps"]
    assert 0 < c["serving/moe_experts_touched"] == \
        c["serving/moe_experts_streamed"] <= 4 * 2 * steps
    assert c["serving/moe_assignments"] == \
        4 * 4 * c["serving/slot_iterations_active"]
    assert 0 < c["serving/moe_assignments_held"] < c["serving/moe_assignments"]
    assert c["serving/prefill_rows_run"] == 32 + 32 + 16 + 16 + 32 + 16
    assert c["serving/prefill_rows_padding"] == 12 + 1 + 11 + 4 + 5 + 9
    # which way the delta-rule layers were traced reaches the engine's own
    # registry with the step's counters: split on a CPU, never folded
    assert c["gdn/traced_split_step"] > 0 == c["gdn/traced_folded_step"]
    assert c["gdn/traced_chunked_block"] > 0


def test_the_sixteen_shares_add_up_to_the_uncut_layer(built):
    """The sparse FFN with all 16 experts held against the sum of the sixteen
    shares ``(0, 1) .. (15, 1)``, the shared expert counted once: what ties
    one chip's share to the model (moe/grouped.py leaves the exchange out).
    At the published sizes the shares are ``(0, 16) .. (240, 16)``."""
    model, _, _, _ = built
    whole = GigaChat35Model(GigaChat35Config.tiny(held=(0, 16)),
                            compute_dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(5))
    blk = jax.tree_util.tree_map(lambda a: a[1], params["gdn_sparse"])
    z = jnp.asarray(np.random.RandomState(2).randn(2, 9, 64), jnp.float32)
    experts = ("expert_gate", "expert_up", "expert_down")

    def layer(held, leaves=None):
        c = types.SimpleNamespace(
            num_experts_per_tok=4, routed_scaling_factor=2.5,
            norm_topk_prob=True, held=held, swiglu_limit=10.0)
        first, count = held
        share = dict(blk, **(leaves or {n: blk[n][first:first + count]
                                        for n in experts}))
        with jax.default_matmul_precision("highest"):
            return ffn(z, share, SPARSE, None, c)

    uncut, counts = layer((0, 16))
    assert int(counts[2]) == int(counts[3]) == 2 * 9 * 4
    shared = layer((0, 1), {n: jnp.zeros_like(blk[n][:1])
                            for n in experts})[0]
    shares = [layer((first, 1)) for first in range(16)]
    total = shared + sum(y - shared for y, _ in shares)
    assert float(jnp.abs(uncut - shared).max()) > 1e-3   # experts do add
    np.testing.assert_allclose(total, uncut, **TOL)
    assert sum(int(n[2]) for _, n in shares) == int(counts[2])
    assert model.config.held == (0, 2)
    assert 16 * PUBLISHED["n_routed_experts"] == \
        PUBLISHED["n_routed_experts_published"] == 256
    assert [(r * 16, 16) for r in range(16)][-1] == (240, 16)


def test_the_sliced_vocabularys_logits_are_the_uncut_heads_first_columns(
        built):
    """An eighth of the vocabulary is a smaller vocabulary: with the head's
    first columns and the embedding's first rows, ids from the slice give the
    uncut model's logits over the slice."""
    model, params, ids, _ = built
    cut = GigaChat35Model(GigaChat35Config.tiny(held=(0, 2), vocab_size=64),
                          compute_dtype=jnp.float32)
    sliced = dict(params, embed=params["embed"][:64],
                  lm_head=params["lm_head"][:, :64])
    few = ids[:, :12] % 64
    with jax.default_matmul_precision("highest"):
        whole = family.engine_logits(model, params, few)
        part = family.engine_logits(cut, sliced, few)
    assert part.shape == (2, 12, 64)
    np.testing.assert_allclose(part, whole[..., :64], rtol=1e-5, atol=1e-6)
    assert 8 * PUBLISHED["vocab_size"] == PUBLISHED["vocab_size_published"]


@pytest.mark.parametrize("kind", [DENSE, SPARSE])
def test_the_swiglu_limit_clamp_binds_where_it_should(built, kind):
    """At the initial values ``swiglu_limit`` 10 never binds; at 0.02 it does:
    the gate's input from above, the linear half on both sides, in the dense
    layer, the shared expert and every routed expert alike, and the reference
    clamps in the same places."""
    _, params, _, _ = built
    stack = params["gdn_dense" if kind == DENSE else "gdn_sparse"]
    blk = jax.tree_util.tree_map(lambda a: a[0], stack)
    z = jnp.asarray(np.random.RandomState(3).randn(2, 7, 64), jnp.float32)

    def layer(limit):
        c = types.SimpleNamespace(
            num_experts_per_tok=4, routed_scaling_factor=2.5,
            norm_topk_prob=True, held=(0, 2), swiglu_limit=limit)
        with jax.default_matmul_precision("highest"):
            return ffn(z, blk, kind, None, c)[0]

    loose, none, tight = layer(10.0), layer(None), layer(0.02)
    np.testing.assert_array_equal(np.asarray(loose), np.asarray(none))
    assert float(jnp.abs(tight - loose).max()) > 1e-3
    cfg = dict(CFG, swiglu_limit=0.02)
    with jax.default_matmul_precision("highest"):
        if kind == DENSE:
            want = reference._gated(z, blk["w_gate"], blk["w_up"],
                                    blk["w_down"], cfg)
        else:
            want = reference._sparse_ffn(
                z, blk, tuple(stack[n] for n in
                              ("expert_gate", "expert_up", "expert_down")),
                0, cfg)
    np.testing.assert_allclose(tight, want, **TOL)
    # by hand, the dense layer: silu(min(g, L)) * clip(u, -L, L)
    if kind == DENSE:
        g = np.minimum(np.asarray(z) @ np.asarray(blk["w_gate"]), 0.02)
        u = np.clip(np.asarray(z) @ np.asarray(blk["w_up"]), -0.02, 0.02)
        hand = (g / (1 + np.exp(-g)) * u) @ np.asarray(blk["w_down"])
        np.testing.assert_allclose(tight, hand, rtol=1e-4, atol=1e-6)


def test_a_stream_norm_is_two_sigmoid_of_its_weight(built):
    """``N(x; w) = x / rms(x) * (2 sigmoid(w))``, in the program and in the
    reference; with ``w`` drawn normal(0, 0.5) the readings ``w`` and ``1 +
    w`` are far from it, and so are the logits of a model that takes them."""
    model, params, ids, ref = built
    x = jnp.asarray(np.random.RandomState(4).randn(3, 5, 64), jnp.float32)
    w = params["gdn_sparse"]["mlp_norm"][1]
    assert float(jnp.std(w)) > 0.3
    unit = np.asarray(x) / np.sqrt((np.asarray(x) ** 2).mean(-1,
                                                              keepdims=True)
                                   + CFG["rms_norm_eps"])
    want = unit * (2.0 / (1.0 + np.exp(-np.asarray(w))))
    np.testing.assert_allclose(model._norm(x, w), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(reference._stream_norm(x, w, CFG), want,
                               rtol=1e-5, atol=1e-6)
    for other in (unit * np.asarray(w), unit * (1.0 + np.asarray(w))):
        assert np.abs(other - want).max() > 0.1
    # end to end: a program that read the weight plainly would be caught
    for wrong in (lambda x, w: x * w, lambda x, w: x * (1.0 + w)):
        class Misread(GigaChat35Model):
            def _norm(self, x, w, wrong=wrong):
                x32 = x.astype(jnp.float32)
                return wrong(x32 * jax.lax.rsqrt(jnp.mean(
                    x32 * x32, -1, keepdims=True) + self.config.eps), w)

        with jax.default_matmul_precision("highest"):
            out = family.engine_logits(
                Misread(model.config, compute_dtype=jnp.float32), params,
                ids[:, :8])
        assert float(jnp.abs(out - ref[:, :8]).max()) > 1e-2


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculative={"mode": "ngram"}),
                                    dict(preemption="swap"),
                                    dict(prefix_cache=True, kv_dtype="int8")])
def test_the_engine_refuses_what_addresses_token_rows(eng, option):
    """With the engine's existing message for recurrent state, which names
    both recurrent leaves and not the latent one."""
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError,
                       match=r"keeps state \['gdn', 'gdn_conv'\] that has "
                             r"none \(recurrent state"):
        ServingEngine(eng, num_slots=2, max_len=64, buckets=(16,),
                      telemetry=None, **option)


def test_generate_takes_the_jnp_route_over_the_same_leaves(built, eng):
    """``generate()``: a uniform batch, scalar index; greedy tokens are the
    reference's argmax along the way."""
    prompt = jnp.asarray(np.random.RandomState(4).randint(0, 512, (2, 9)),
                         jnp.int32)
    out = np.asarray(eng.generate(prompt, max_new_tokens=6))
    assert out.shape == (2, 15) and (out[:, :9] == np.asarray(prompt)).all()
    with jax.default_matmul_precision("highest"):
        rows = _reference_logits(eng.params, jnp.asarray(out))
    gap = rows[:, 8:14].max(-1) - jnp.take_along_axis(
        rows[:, 8:14], jnp.asarray(out[:, 9:])[..., None], -1)[..., 0]
    assert float(gap.max()) < 1e-4


def test_a_chunked_prefill_continues_state_tails_and_rows(built, eng):
    """``prefill_token_budget``: a prompt of 31 tokens prefilled as chunks
    of 16 through ``slot_chunk_prefill_program`` (the slot's state sliced
    out, continued from ``start`` and written back) between decode steps of
    the other slot; every served token is still the reference's argmax."""
    from deepspeed_tpu.serving import Request, ServingEngine

    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        prefill_token_budget=16, telemetry=None,
                        tenants=False)
    rng = np.random.RandomState(7)
    reqs = [Request(rid=i, prompt=rng.randint(0, 512, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(9, 12), (31, 6), (24, 5)])]
    results = srv.run(reqs)
    assert sorted(r.rid for r in results) == [0, 1, 2]
    _assert_served_tokens_are_the_references(eng, reqs, results)
