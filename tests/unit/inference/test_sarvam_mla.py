"""SarvamMlaModel against the plain reference (the decompressed form at every
position, every held expert on every token: ``benchmarks/reference/
sarvam_mla.py``) at tiny size in float32: full forward, prefill then decode
through the latent cache with prompts that walk several token blocks and
positions past the rotation's trained range, the slot cache's leaf of latent
rows under the serving engine and under the slot programs' own pieces (logits,
not tokens), the absorbed step against the decompressed block on the same
rows, YaRN's frequencies and score scale against the closed form, the eight
shares against the uncut layer, and what the engine refuses."""

import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.families import sarvam_mla as family
from benchmarks.reference import sarvam_mla as reference
from deepspeed_tpu.models.moe_ffn import SPARSE, ffn
from deepspeed_tpu.models.sarvam_mla import SarvamMlaConfig, SarvamMlaModel
from deepspeed_tpu.ops.attention import write_slot_rows
from deepspeed_tpu.ops.decode_step import slot_walk
from deepspeed_tpu.ops.rotary import (apply_rotary_half_freqs, yarn_inv_freq,
                                      yarn_mscale)

pytestmark = pytest.mark.quick

# the published keys at the sizes of the tests: a dense layer and two sparse
# ones; hidden 64, 4 heads, latent 32, head sizes 16 + 8 / 16; 2 of 16 experts
# held, 4 a token; 64 positions over 16 trained ones; token blocks of 16 and
# key blocks of 8
CFG = family.tiny(harness.load_json("configs", "sarvam-105b.json"))
TOL = dict(rtol=1e-4, atol=1e-5)


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
    # (kind, first of its stack, first of the cache leaf, count)
    assert model.runs() == (("dense", 0, 0, 1), ("sparse", 0, 1, 2))
    assert c.held == (0, 2) and c.num_experts == 16
    assert (c.q_head_dim, c.row_width, c.prompt_block, c.key_block) == \
        (24, 128, 16, 8)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(
            model.logical_axes(), is_leaf=lambda a: isinstance(a, tuple))
    assert params["sparse"]["wkv_a"].shape == (2, 64, 40)
    assert params["sparse"]["wkv_b"].shape == (2, 32, 4 * 32)
    assert params["sparse"]["expert_gate"].shape == (2, 2, 64, 32)
    # the published widths: one row of 512 + 64 a token, padded to 640 lanes
    wide = SarvamMlaConfig()
    assert (wide.q_head_dim, wide.row_width) == (192, 640)
    assert wide.score_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)


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
    walk inside the program), then decodes by a per-slot index vector to
    position 47 of 64: three times the rotation's 16 trained positions."""
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
    assert cache["latent"].shape == (3, 2, 64, 128)     # one row a token
    assert not np.asarray(cache["latent"][..., 40:]).any()   # the zero lanes


@pytest.mark.parametrize("length,bucket", [(11, 16), (19, 32), (32, 32)])
def test_a_padded_prompt_gives_the_last_real_positions_logits(built, length,
                                                              bucket):
    """Told the true length, a prefill returns the logits of the last real
    position alone; padding behind it is routed to no expert."""
    model, params, ids, ref = built
    cache = model.init_cache(1, 32, dtype=jnp.float32)
    cache["valid_len"] = jnp.asarray(length)
    logits, out = _jitted_step(model)(params, ids[:1, :bucket], cache)
    assert logits.shape == (1, 1, 512)
    np.testing.assert_allclose(logits[0, 0], ref[0, length - 1], **TOL)
    assert int(out["step_counters"][3]) == 2 * 4 * length  # layers x k x T


def test_slot_programs_pieces_give_the_references_logits(built):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    latent leaf, by ``SlotKVCache``'s own tree: bucketed prefills written as
    prefixes into slots, two slots of unequal length decoding together with
    a third inactive, a slot reused by a shorter request. Logits, not
    tokens."""
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref = built
    step = _jitted_step(model)
    slots = SlotKVCache(model, 3, 64, dtype=jnp.float32)
    assert slots.keys == slots.row_keys == ("latent",)
    assert slots.recurrent_keys == () and slots.pair == 1
    assert not slots.fused_walk            # 64 rows are no whole chunk
    state, lengths = dict(slots.state), np.zeros(3, np.int32)

    def prefill(row, length, bucket, slot):
        cache = model.init_cache(1, bucket, dtype=jnp.float32)
        cache["valid_len"] = jnp.asarray(length)
        logits, cache = step(params, ids[row:row + 1, :bucket], cache)
        np.testing.assert_allclose(logits[0, 0], ref[row, length - 1], **TOL)
        state["latent"] = write_slot_rows(state["latent"], cache["latent"],
                                          slot)
        lengths[slot] = length

    def decode(rows_of, active):
        """One step: slot i feeds row ``rows_of[i]``'s token at its length."""
        act = jnp.asarray(active, bool)
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([ids[r, n] for r, n in zip(rows_of, lengths)])
        cache = dict(state, index=idx, valid_len=act.astype(jnp.int32),
                     slot_walk=slot_walk(idx, act))
        logits, cache = step(params, tokens[:, None], cache)
        for i, (r, on) in enumerate(zip(rows_of, active)):
            if on:
                np.testing.assert_allclose(logits[i, 0], ref[r, lengths[i]],
                                           **TOL)
                lengths[i] += 1
        state["latent"] = cache["latent"]

    prefill(0, 20, 32, 1)
    prefill(1, 7, 16, 0)
    for _ in range(6):
        decode((1, 0, 0), (True, True, False))
    # slot 1 is reused by a shorter request while slot 0 goes on: the rows
    # its first tenant left behind the new length are dead
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


def test_the_serving_engine_serves_it_over_a_latent_leaf(built, eng):
    """init_inference + ServingEngine: bucketed slot prefill, per-slot
    decode, three slots for five requests; every served token is the
    reference's argmax."""
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=3, max_len=64, buckets=(16, 32),
                        telemetry=reg, tenants=False)
    assert srv.cache.keys == ("latent",) and not srv.cache.recurrent_keys
    assert srv.cache.state["latent"].shape == (3, 3, 64, 128)
    assert reg.snapshot()["gauges"]["serving/state_bytes_per_slot"] == \
        3 * 64 * 128 * 4 == srv.cache.hbm_bytes() // 3
    assert srv.cache.capacity_for(40, 24) and not srv.cache.capacity_for(
        40, 25)
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
        c["serving/moe_experts_streamed"] <= 2 * 2 * steps
    assert c["serving/moe_assignments"] == \
        2 * 4 * c["serving/slot_iterations_active"]
    assert 0 < c["serving/moe_assignments_held"] < c["serving/moe_assignments"]
    # 64 rows are no whole chunk of the fused walk: its two counters stay out
    assert "serving/decode_rows_live" not in c
    assert c["serving/prefill_rows_run"] == 16 + 32 + 32 + 16 + 32


def test_the_row_counters_follow_the_latent_walk(built):
    """Where the slot cache's shape routes to the fused absorbed step (rows
    in whole chunks of 128), the engine keeps the walk's two counters for the
    latent leaf as it does for key-value rows."""
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model = built[0]
    assert SlotKVCache(model, 4, 256, dtype=jnp.float32).fused_walk
    assert not SlotKVCache(model, 1, 256, dtype=jnp.float32).fused_walk
    assert model.fused_row_walk(
        {"latent": jnp.zeros((3, 4, 128, 128))}, 4)


@pytest.mark.parametrize("lengths", [(0, 9, 33), (63, 1, 17)], ids=str)
def test_the_absorbed_step_is_the_decompressed_block_on_the_same_rows(
        built, lengths):
    """One token a slot by both forms of attention over one latent leaf: the
    absorbed einsum route (key and value are the cached row) and the
    decompressed block walk (keys and values up-projected a block at a
    time), ragged lengths."""
    model, params, _, _ = built
    c = model.config
    rng = np.random.RandomState(sum(lengths))
    b, h = 3, c.num_heads
    blk = jax.tree_util.tree_map(lambda a: a[1], {
        k: v for k, v in params["sparse"].items() if k == "wkv_b"})
    latent = np.zeros((3, b, 64, c.row_width), np.float32)
    latent[..., :40] = rng.randn(3, b, 64, 40)
    row = np.zeros((b, c.row_width), np.float32)
    row[:, :40] = rng.randn(b, 40)
    q_nope = jnp.asarray(rng.randn(b, h, 16), jnp.float32)
    q_rope = jnp.asarray(rng.randn(b, h, 8), jnp.float32)
    idx = jnp.asarray(lengths, jnp.int32)
    with jax.default_matmul_precision("highest"):
        step, written = model._token_attention(
            q_nope, q_rope, jnp.asarray(row), jnp.asarray(latent), 2, idx,
            blk, None)
        block = model._prompt_attention(
            q_nope[:, None], q_rope[:, None], written, 2, idx[:, None], blk)
    np.testing.assert_allclose(step, block[:, 0], **TOL)
    for i, n in enumerate(lengths):     # the row at the length, nothing else
        np.testing.assert_array_equal(written[2, i, n], row[i])
        np.testing.assert_array_equal(np.delete(written[2, i], n, 0),
                                      np.delete(latent[2, i], n, 0))
    np.testing.assert_array_equal(written[:2], latent[:2])


def _closed_form_inv_freq(rope, theta, factor, original, fast, slow):
    half = rope // 2

    def dim(n):
        return rope * math.log(original / (2 * math.pi * n)) / \
            (2 * math.log(theta))

    low = max(math.floor(dim(fast)), 0)
    high = min(math.ceil(dim(slow)), half - 1)
    out = []
    for i in range(half):
        f = theta ** (-2 * i / rope)
        r = min(max((i - low) / ((high - low) or 0.001), 0.0), 1.0)
        out.append(f * (1 - r) + f / factor * r)
    return np.asarray(out)


@pytest.mark.parametrize("rope,original", [(8, 16), (64, 4096)])
def test_yarn_frequencies_and_the_score_scale_against_the_closed_form(
        rope, original):
    want = _closed_form_inv_freq(rope, 10000.0, 40.0, original, 32.0, 1.0)
    got = np.asarray(yarn_inv_freq(rope, 10000.0, 40.0, original))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    cfg = dict(CFG, qk_rope_head_dim=rope, rope_scaling=dict(
        CFG["rope_scaling"], original_max_position_embeddings=original))
    np.testing.assert_allclose(reference.yarn_inv_freq(cfg), want, rtol=1e-6)
    # the fastest pair keeps its frequency, the slowest is slowed by 40
    assert got[0] == pytest.approx(1.0)
    assert got[-1] == pytest.approx(10000.0 ** (-(rope - 2) / rope) / 40)
    assert yarn_mscale(40.0) == pytest.approx(0.1 * math.log(40) + 1)
    assert yarn_mscale(1.0) == 1.0
    assert reference.score_scale(CFG) == pytest.approx(
        24 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    # rotation past the trained range: positions 16 to 63 of the tiny model
    pos = np.arange(16, 64)
    x = np.random.RandomState(rope).randn(1, len(pos), 2, rope)
    ang = pos[:, None] * want[None, :]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
    turned = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(
        apply_rotary_half_freqs(jnp.asarray(x, jnp.float32),
                                jnp.asarray(pos), jnp.asarray(want,
                                                              jnp.float32)),
        turned, rtol=1e-4, atol=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(built):
    """The sparse FFN with all 16 experts held against the sum of the eight
    shares ``(0, 2) .. (14, 2)``, the shared expert counted once: what ties
    one chip's share to the model (moe/grouped.py leaves the exchange out)."""
    model, _, _, _ = built
    whole = SarvamMlaModel(SarvamMlaConfig.tiny(held=(0, 16)),
                           compute_dtype=jnp.float32)
    params = whole.init(jax.random.PRNGKey(5))
    blk = jax.tree_util.tree_map(lambda a: a[1], params["sparse"])
    z = jnp.asarray(np.random.RandomState(2).randn(2, 9, 64), jnp.float32)
    experts = ("expert_gate", "expert_up", "expert_down")

    def layer(held):
        c = types.SimpleNamespace(
            num_experts_per_tok=4, routed_scaling_factor=2.5,
            norm_topk_prob=True, held=held)
        first, count = held
        share = dict(blk, **{n: blk[n][first:first + count] for n in experts})
        with jax.default_matmul_precision("highest"):
            return ffn(z, share, SPARSE, None, c)

    uncut, counts = layer((0, 16))
    assert int(counts[2]) == int(counts[3]) == 2 * 9 * 4
    only_shared = dict(blk, **{n: jnp.zeros_like(blk[n][:2])
                               for n in experts})
    c0 = types.SimpleNamespace(num_experts_per_tok=4,
                               routed_scaling_factor=2.5,
                               norm_topk_prob=True, held=(0, 2))
    with jax.default_matmul_precision("highest"):
        shared = ffn(z, only_shared, SPARSE, None, c0)[0]
    shares = [layer((first, 2)) for first in range(0, 16, 2)]
    total = shared + sum(y - shared for y, _ in shares)
    assert float(jnp.abs(uncut - shared).max()) > 1e-3   # experts do add
    np.testing.assert_allclose(total, uncut, **TOL)
    assert sum(int(n[2]) for _, n in shares) == int(counts[2])
    assert model.config.held == (0, 2)


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculative={"mode": "ngram"}),
                                    dict(preemption="swap"),
                                    dict(prefix_cache=True, kv_dtype="int8")])
def test_the_engine_refuses_what_addresses_rows_of_key_value_pairs(eng, option):
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError, match="one latent row a token"):
        ServingEngine(eng, num_slots=2, max_len=64, buckets=(16,),
                      telemetry=None, **option)


def test_generate_takes_the_einsum_route_over_the_same_leaf(built, eng):
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


def test_a_chunked_prefill_continues_the_latent_rows(built):
    """``prefill_token_budget``: a prompt of 31 tokens prefilled as chunks
    of 16 through ``slot_chunk_prefill_program`` (the slot's rows sliced
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
