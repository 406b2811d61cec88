"""SolarKdaModel against the plain reference (the delta rule token by token,
plain causal softmax, every held expert on every token: ``benchmarks/
reference/solar_kda.py``) at tiny size in float32: full forward, prefill then
decode through the cache with prompts that walk several token blocks, chunks
and key blocks, the slot cache's recurrent leaves under the serving engine and
under the slot programs' own pieces (logits, not tokens), padding behind
``valid_len``, the eight shares against the uncut layer, and what the engine
refuses."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.families import solar_kda as family
from benchmarks.reference import solar_kda as reference
from deepspeed_tpu.models.moe_ffn import SPARSE, ffn
from deepspeed_tpu.models.solar_kda import GQA, KDA, SolarKdaConfig, SolarKdaModel
from deepspeed_tpu.ops.attention import insert_slot_row, write_slot_rows
from deepspeed_tpu.ops.decode_step import slot_walk

pytestmark = pytest.mark.quick

# the published keys at the sizes of the tests: one period (a softmax layer,
# three delta-rule layers); hidden 64, 4 / 2 heads of 16, 4 delta-rule heads
# of 16 x 16; 2 of 16 experts held, 4 a token; token blocks of 16, chunks and
# key blocks of 8
CFG = family.tiny(harness.load_json("configs", "solar-open2-250b.json"))
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
    assert c.runs() == ((GQA, 0, 1), (KDA, 0, 3))
    assert c.held == (0, 2) and c.num_experts == 16
    assert (c.prompt_block, c.key_block, c.kda_chunk) == (16, 8, 8)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    assert params[KDA]["w_qkv"].shape == (3, 64, 3 * 64)
    assert params[KDA]["conv_w"].shape == (3, 4, 3 * 64)
    assert params[KDA]["f_b"].shape == (3, 16, 64)
    assert params[GQA]["w_gate"].shape == (1, 64, 64)
    assert params[KDA]["expert_gate"].shape == (3, 2, 64, 32)
    # the published period: one softmax layer in four
    wide = SolarKdaConfig(layer_types=(GQA, KDA, KDA, KDA) * 2)
    assert wide.runs() == ((GQA, 0, 1), (KDA, 0, 3), (GQA, 1, 1), (KDA, 3, 3))
    assert wide.kda_width == 8192


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
    walk inside the program, the state and tails carried from block to
    block), then decodes by a per-slot index vector."""
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
    assert cache["k"].shape == (1, 2, 2, 64, 16)        # ONE layer has rows
    assert cache["kda"].shape == (3, 2, 4, 16, 16)
    assert cache["kda"].dtype == jnp.float32
    assert cache["kda_conv"].shape == (3, 2, 3, 3, 4, 16)


@pytest.mark.parametrize("length,bucket", [(11, 16), (19, 32), (32, 32),
                                           (9, 32)])
def test_padding_behind_valid_len_changes_neither_state_nor_tails(
        built, length, bucket):
    """Told the true length, a prefill returns the logits of the last real
    position alone, and its state and tails are those of a prefill of exactly
    that many tokens: padding is masked (a = 1, beta = 0) and routed to no
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
    np.testing.assert_allclose(out["kda"], exact["kda"], rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(out["kda_conv"], exact["kda_conv"], rtol=1e-5,
                               atol=1e-6)


def test_slot_programs_pieces_give_the_references_logits(built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    four leaves, by ``SlotKVCache``'s own tree: bucketed prefills written
    into slots (rows as a prefix, recurrent leaves whole), two slots of
    unequal length decoding together with a third inactive, a slot reused by
    a shorter request (state and tails start from zero at position 0
    whatever the slot held). Logits, not tokens."""
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref = built
    step = _jitted_step(model)
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == ("k", "v", "kda", "kda_conv")
    assert slots.row_keys == ("k", "v")
    assert slots.recurrent_keys == ("kda", "kda_conv")
    state, lengths = dict(slots.state), np.zeros(3, np.int32)
    # a slot's last tenant leaves its state behind: the next starts from zero
    state["kda"] = state["kda"] + 7.0
    state["kda_conv"] = state["kda_conv"] - 3.0

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


def test_the_serving_engine_serves_it_over_recurrent_leaves(built, eng):
    """init_inference + ServingEngine: bucketed slot prefill, per-slot
    decode, three slots for five requests; every served token is the
    reference's argmax."""
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=3, max_len=64, buckets=(16, 32),
                        telemetry=reg, tenants=False)
    assert srv.cache.keys == ("k", "v", "kda", "kda_conv")
    assert srv.cache.recurrent_keys == ("kda", "kda_conv")
    # all four leaves: rows of ONE layer, float32 state and tails of three
    per_slot = (2 * 2 * 64 * 16 + 3 * 4 * 16 * 16 + 3 * 3 * 3 * 4 * 16) * 4
    assert reg.snapshot()["gauges"]["serving/state_bytes_per_slot"] == \
        per_slot == srv.cache.hbm_bytes() // 3
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, 512, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(5, 6), (20, 12), (31, 9), (12, 20),
                                        (27, 5)])]
    results = srv.run(reqs)
    assert len(results) == 5
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
    c = reg.snapshot()["counters"]
    steps = c["serving/decode_steps"]
    assert 0 < c["serving/moe_experts_touched"] == \
        c["serving/moe_experts_streamed"] <= 4 * 2 * steps
    assert c["serving/moe_assignments"] == \
        4 * 4 * c["serving/slot_iterations_active"]
    assert 0 < c["serving/moe_assignments_held"] < c["serving/moe_assignments"]
    assert c["serving/prefill_rows_run"] == 16 + 32 + 32 + 16 + 32
    assert c["serving/prefill_rows_padding"] == 11 + 12 + 1 + 4 + 5


def test_the_eight_shares_add_up_to_the_uncut_layer(built):
    """The sparse FFN with all 16 experts held against the sum of the eight
    shares ``(0, 2) .. (14, 2)``, the shared expert counted once: what ties
    one chip's share to the model (moe/grouped.py leaves the exchange out).
    At the published sizes the shares are ``(0, 40) .. (280, 40)``."""
    model, _, _, _ = built
    whole = SolarKdaModel(SolarKdaConfig.tiny(held=(0, 16)),
                          compute_dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(5))
    blk = jax.tree_util.tree_map(lambda a: a[1], params[KDA])
    z = jnp.asarray(np.random.RandomState(2).randn(2, 9, 64), jnp.float32)
    experts = ("expert_gate", "expert_up", "expert_down")

    def layer(held, leaves=None):
        c = types.SimpleNamespace(
            num_experts_per_tok=4, routed_scaling_factor=1.0,
            norm_topk_prob=True, held=held)
        first, count = held
        share = dict(blk, **(leaves or {n: blk[n][first:first + count]
                                        for n in experts}))
        with jax.default_matmul_precision("highest"):
            return ffn(z, share, SPARSE, None, c)

    uncut, counts = layer((0, 16))
    assert int(counts[2]) == int(counts[3]) == 2 * 9 * 4
    shared = layer((0, 2), {n: jnp.zeros_like(blk[n][:2])
                            for n in experts})[0]
    shares = [layer((first, 2)) for first in range(0, 16, 2)]
    total = shared + sum(y - shared for y, _ in shares)
    assert float(jnp.abs(uncut - shared).max()) > 1e-3   # experts do add
    np.testing.assert_allclose(total, uncut, **TOL)
    assert sum(int(n[2]) for _, n in shares) == int(counts[2])
    assert model.config.held == (0, 2)
    published = harness.load_json("configs", "solar-open2-250b.json")
    assert [(r * published["n_routed_experts"], published["n_routed_experts"])
            for r in range(8)][-1] == (280, 40)
    assert 8 * published["n_routed_experts"] == \
        published["n_routed_experts_published"]


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculative={"mode": "ngram"}),
                                    dict(preemption="swap"),
                                    dict(prefix_cache=True, kv_dtype="int8")])
def test_the_engine_refuses_what_addresses_token_rows(eng, option):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError, match="kda"):
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


def test_a_chunked_prefill_continues_state_tails_and_rows(built):
    """``prefill_token_budget``: a prompt of 31 tokens prefilled as chunks
    of 16 through ``slot_chunk_prefill_program`` (the slot's state sliced
    out, continued from ``start`` and written back) between decode steps of
    the other slots; every served token is still the reference's argmax."""
    import deepspeed_tpu
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.utils import groups

    groups.reset()
    eng = deepspeed_tpu.init_inference(family.build_model(CFG, {}),
                                       dtype="fp32", max_out_tokens=64,
                                       seed=3)
    srv = ServingEngine(eng, num_slots=2, max_len=64, buckets=(16, 32),
                        prefill_token_budget=16, telemetry=None,
                        tenants=False)
    rng = np.random.RandomState(7)
    reqs = [Request(rid=i, prompt=rng.randint(0, 512, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(9, 12), (31, 6), (24, 5)])]
    results = srv.run(reqs)
    assert sorted(r.rid for r in results) == [0, 1, 2]
    with jax.default_matmul_precision("highest"):
        for r in results:
            prompt = reqs[r.rid].prompt
            seq = jnp.asarray([prompt + list(r.tokens)], jnp.int32)
            rows = _reference_logits(eng.params, seq)[0][
                len(prompt) - 1:len(prompt) - 1 + len(r.tokens)]
            gap = rows.max(-1) - rows[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < 1e-4, r.rid
    groups.reset()


def test_a_cached_prefill_by_the_prompt_kernel_is_the_loops(monkeypatch):
    """The softmax layer's prompt block as ``dstpu_gqa_prefill`` (the route a
    TPU takes, steered here; the call in the Pallas interpreter) against the
    ``lax`` loop, through the whole stack: a prompt of 41 in a bucket of 64
    walks two token blocks of 32, the second one's last query tile all
    padding, which the kernel returns as zeros and nothing real reads. The
    last real position's logits agree inside the cell's ``mean_gap_tol``, and
    so do the rows and the state the prefill leaves."""
    import dataclasses
    import functools

    from deepspeed_tpu.ops import gqa_prefill
    from deepspeed_tpu.telemetry.registry import get_registry

    # head size 128: the kernel takes whole rows of lanes only
    c = dataclasses.replace(
        SolarKdaConfig.tiny(prompt_block=32, key_block=16), head_dim=128)
    model = SolarKdaModel(c, compute_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(4))
    ids = jnp.asarray(np.random.RandomState(6).randint(0, 512, (1, 64)),
                      jnp.int32)
    length = 41

    def prefill():
        cache = model.init_cache(1, 64, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        with jax.default_matmul_precision("highest"):
            return jax.jit(model.forward_with_cache)(params, ids, cache)

    def counted():
        n = get_registry().snapshot()["counters"]
        return (n.get("gqa/traced_prefill_kernel", 0),
                n.get("gqa/traced_blocked_block", 0))

    before = counted()
    want, cache_w = prefill()
    assert counted() == (before[0], before[1] + 1)      # a CPU: 0 and n
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(gqa_prefill, "_QUERY_TILE", 16)
    monkeypatch.setattr(gqa_prefill, "gqa_prefill", functools.partial(
        gqa_prefill.gqa_prefill, interpret=True))
    got, cache_g = prefill()
    assert counted() == (before[0] + 1, before[1] + 1)  # the scan's one body
    assert want.shape == got.shape == (1, 1, 512)
    tol = harness.load_json("traffic", "serve-agent-contexts.json")["check"]
    gap = np.abs(np.asarray(got) - np.asarray(want))
    assert float(np.abs(np.asarray(want)).max()) > 0.1
    assert gap.mean() < tol["mean_gap_tol"] and gap.max() < 1e-4
    for name in ("k", "v"):      # the real positions' rows, and the state
        np.testing.assert_allclose(cache_g[name][..., :length, :],
                                   cache_w[name][..., :length, :], **TOL)
    for name in ("kda", "kda_conv"):
        np.testing.assert_allclose(cache_g[name], cache_w[name], **TOL)
