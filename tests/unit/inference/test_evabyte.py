"""models/evabyte.py at tiny size in float32 (window 32, chunks of 4): the
program against the plain reference (full forward, all eight heads; prefill
then decode through ``SlotKVCache`` across a bucket boundary, a chunk
boundary and two window boundaries), the new kind of leaf held end to end by
``ServingEngine`` (admitted, prefilled, decoded, freed and re-admitted with a
shorter request; a chunked prefill; the refusals), and the readings the
comparison would catch (a norm's scale as ``w``, ``mu`` dropped, pooling by a
mean)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness
from deepspeed_tpu.models.evabyte import STEP_COUNTERS, EvaByteConfig, EvaByteModel
from deepspeed_tpu.ops import eva

family = harness.module("families", "evabyte")
reference = harness.module("reference", "evabyte")
PUBLISHED = harness.load_json("configs", "evabyte.json")
CFG = dict(family.tiny(PUBLISHED), window_size=32, max_position_embeddings=128)
TOL = dict(rtol=1e-4, atol=1e-5)
W, C = 32, 4


@pytest.fixture(scope="module")
def built():
    """The tiny program in float32 and the reference's eight heads' logits
    of 2 x 96 ids (three windows)."""
    model = family.build_model(CFG, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 320, (2, 96)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: reference.forward_all_heads(p, x, CFG))(
            params, ids)

    def step(params, ids, cache):
        with jax.default_matmul_precision("highest"):
            return model.forward_with_cache(params, ids, cache)

    return model, params, ids, ref, jax.jit(step)


def test_the_tiny_model_is_the_stated_stack(built):
    model, params, _, ref, _ = built
    c = model.config
    assert (c.num_layers, c.hidden_size, c.num_heads, c.head_dim,
            c.window_size, c.chunk_size, c.prompt_block, c.num_pred_heads,
            c.vocab_size) == (2, 64, 4, 16, 32, 4, 32, 8, 320)
    assert params["lm_head"].shape == (64, 8 * 320)
    assert params["blocks"]["phi"].shape == (2, 4, 16)
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == \
        model.num_params()
    assert model.slot_state_keys == ("k_win", "v_win", "k_sum", "v_sum")
    assert ref.shape == (2, 96, 8, 320)
    assert float(jnp.abs(ref).max()) > 0.1      # not a dead model
    # the norms' w, the direction and the offset are drawn where a misreading
    # shows
    assert float(jnp.std(params["blocks"]["attn_norm"])) > 0.3
    assert float(jnp.abs(params["blocks"]["mu"]).mean()) > 0.3
    assert float(jnp.abs(params["blocks"]["phi"]).max()) <= 1.0


@pytest.mark.parametrize("t", [96, 80, 32, 20])
def test_full_forward_matches_the_reference_on_all_eight_heads(built, t):
    """Three whole windows, a sequence that ends mid-window, one window and
    less than one."""
    model, params, ids, ref, _ = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: model.all_logits(
            p, model.forward_hidden(p, x)))(params, ids[:, :t])
        head0 = family.engine_logits(model, params, ids[:, :t])
    want = ref[:, :t] if t == 96 else jax.jit(
        lambda p, x: reference.forward_all_heads(p, x, CFG))(
            params, ids[:, :t])
    np.testing.assert_allclose(out, want, **TOL)
    np.testing.assert_allclose(head0, want[:, :, 0], **TOL)
    if t < 96:      # causal: what lies behind changes nothing before it
        np.testing.assert_allclose(want, ref[:, :t], **TOL)
    assert out.dtype == jnp.float32


# (real positions, bucket): a prompt that ends mid-chunk in its third window
# (two whole blocks behind it: the bucket's boundary is crossed by the walk),
# one that ends on a window's last row, one shorter than a window
@pytest.mark.parametrize("length,bucket", [(70, 96), (64, 64), (11, 32)])
def test_prefill_then_decode_through_the_slot_cache_matches_the_reference(
        built, length, bucket):
    """What ``slot_prefill_program`` and ``slot_decode_program`` do with the
    four leaves, by ``SlotKVCache``'s own tree: a bucketed prefill written
    into a slot (every leaf a slot's row), then that slot decoding to the end
    of the third window beside an inactive one: across chunk boundaries and
    every window boundary left."""
    from deepspeed_tpu.ops.attention import insert_slot_row
    from deepspeed_tpu.ops.decode_step import slot_walk
    from deepspeed_tpu.serving.kv_slots import SlotKVCache

    model, params, ids, ref, step = built
    slots = SlotKVCache(model, 2, 128, dtype=jnp.float32)
    assert slots.keys == slots.recurrent_keys == \
        ("k_win", "v_win", "k_sum", "v_sum")
    assert slots.row_keys == () and not slots.fused_walk
    assert (slots.restart_window, slots.summary_chunk) == (32, 4)
    assert {k: v.shape for k, v in slots.state.items()} == {
        "k_win": (2, 2, 4, 32, 16), "v_win": (2, 2, 4, 32, 16),
        "k_sum": (2, 2, 4, 32, 16), "v_sum": (2, 2, 4, 32, 16)}
    # stale rows of an earlier, longer request in every leaf of the slot
    state = {k: jnp.full_like(v, 3.0) for k, v in slots.state.items()}
    cache = model.init_cache(1, bucket, dtype=jnp.float32)
    cache["valid_len"] = jnp.asarray(length)
    pad = jnp.zeros((1, bucket), jnp.int32).at[:, :length].set(
        ids[:1, :length])
    logits, cache = step(params, pad, cache)
    assert logits.shape == (1, 1, 320)
    np.testing.assert_allclose(logits[0, 0], ref[0, length - 1, 0], **TOL)
    for name in state:
        state[name] = insert_slot_row(state[name], cache[name], 1)
    assert slots.live_rows(length) == (length % 32, 8 * (length // 32))
    lengths = np.asarray([50, length], np.int32)
    for _ in range(96 - length):
        active = jnp.asarray([False, True])
        idx = jnp.asarray(lengths)
        tokens = jnp.asarray([0, ids[0, lengths[1]]])
        cache = dict(state, index=idx, valid_len=active.astype(jnp.int32),
                     slot_walk=slot_walk(idx, active))
        logits, cache = step(params, tokens[:, None], cache)
        np.testing.assert_allclose(logits[1, 0], ref[0, lengths[1], 0],
                                   **TOL)
        rows, summaries = eva.live_rows(int(lengths[1]), 32, 4)
        assert list(np.asarray(cache["step_counters"])) == [
            2 * (rows + summaries),
            2 * int(eva.rows_fetched(int(lengths[1]), 32, 4)), 2 * summaries]
        lengths[1] += 1
        state = {name: cache[name] for name in state}
    # the inactive slot was neither read nor written
    for leaf in state.values():
        assert (np.asarray(leaf)[:, 0] == 3.0).all()


@pytest.fixture(scope="module")
def eng():
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    groups.reset()
    return deepspeed_tpu.init_inference(family.build_model(CFG, {}),
                                        dtype="fp32", max_out_tokens=128,
                                        seed=3)


def _assert_served_tokens_are_the_references(eng, reqs, results):
    with jax.default_matmul_precision("highest"):
        for r in results:
            prompt = reqs[r.rid].prompt
            assert len(r.tokens) == reqs[r.rid].max_new_tokens
            seq = jnp.asarray([prompt + list(r.tokens)], jnp.int32)
            rows = reference.forward_logits(eng.params, seq, CFG)[0][
                len(prompt) - 1:len(prompt) - 1 + len(r.tokens)]
            gap = rows.max(-1) - rows[jnp.arange(len(r.tokens)),
                                      jnp.asarray(r.tokens)]
            assert float(gap.max()) < 1e-4, r.rid


def test_the_serving_engine_holds_summary_rows_beside_a_restarting_window(
        eng):
    """init_inference + ServingEngine over the fifth kind of leaf: requests
    are admitted, prefilled by bucket, decoded by slot, freed and re-admitted
    (two slots for six requests: a slot that held 90 positions is taken by a
    request of 9, which reads no summary and no window row of the one
    before): every served token is the reference's argmax."""
    from deepspeed_tpu.serving import Request, ServingEngine
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    srv = ServingEngine(eng, num_slots=2, max_len=128, buckets=(32, 64, 96),
                        telemetry=reg, tenants=False)
    assert srv.cache.keys == ("k_win", "v_win", "k_sum", "v_sum")
    assert srv.cache.recurrent_keys == srv.cache.keys
    assert (srv.cache.restart_window, srv.cache.summary_chunk) == (32, 4)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, 320, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(70, 20), (31, 40), (9, 30), (64, 5),
                                        (90, 12), (33, 35)])]
    results = srv.run(reqs)
    assert sorted(r.rid for r in results) == list(range(6))
    _assert_served_tokens_are_the_references(eng, reqs, results)
    c = reg.snapshot()["counters"]
    assert c["serving/prefill_rows_run"] == 96 + 32 + 32 + 64 + 96 + 64
    # the step's own counters reach the engine's registry, and with them
    # which way its layers were traced: split on a CPU, never fused
    assert c["eva/traced_split_step"] > 0 == c["eva/traced_fused_step"]
    assert c["eva/traced_prompt_block"] > 0
    assert 0 < c["serving/eva_summary_rows_live"] < \
        c["serving/eva_rows_live"] <= c["serving/eva_rows_fetched"]
    assert "serving/decode_rows_live" not in c


def test_a_chunked_prefill_passes_in_whole_windows(eng):
    """``prefill_token_budget`` 32: a prompt of 75 positions prefilled as
    chunks of a window through ``slot_chunk_prefill_program`` (the slot's
    leaves sliced out, continued from ``start`` and written back) between
    decode steps of the other slot."""
    from deepspeed_tpu.serving import Request, ServingEngine

    srv = ServingEngine(eng, num_slots=2, max_len=128, buckets=(32, 64),
                        prefill_token_budget=32, telemetry=None,
                        tenants=False)
    rng = np.random.RandomState(7)
    reqs = [Request(rid=i, prompt=rng.randint(0, 320, size=n).tolist(),
                    max_new_tokens=m, arrival_time=0.0)
            for i, (n, m) in enumerate([(9, 30), (75, 8), (40, 6)])]
    results = srv.run(reqs)
    assert sorted(r.rid for r in results) == [0, 1, 2]
    _assert_served_tokens_are_the_references(eng, reqs, results)


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculative={"mode": "ngram"}),
                                    dict(preemption="swap"),
                                    dict(prefix_cache=True, kv_dtype="int8")])
def test_the_engine_refuses_what_addresses_token_rows(eng, option):
    """With the engine's existing message for state without token rows,
    which names all four leaves."""
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError,
                       match=r"keeps state \['k_win', 'v_win', 'k_sum', "
                             r"'v_sum'\] that has none"):
        ServingEngine(eng, num_slots=2, max_len=128, buckets=(32,),
                      telemetry=None, **option)


@pytest.mark.parametrize("kw", [dict(buckets=(32, 48)),
                                dict(buckets=(16, 64)),
                                dict(buckets=(48, 96),
                                     prefill_token_budget=48)])
def test_the_engine_refuses_a_bucket_that_is_no_whole_windows(eng, kw):
    """A bucket, and so a prefill chunk cap (the largest bucket the budget
    holds), that ``window_size`` does not divide."""
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.errors import EngineConfigError

    with pytest.raises(EngineConfigError,
                       match=r"must be a multiple of the window of 32 "
                             r"positions that EvaByteModel's cache starts "
                             r"over at"):
        ServingEngine(eng, num_slots=2, max_len=128, telemetry=None, **kw)


def test_a_prompt_of_no_whole_windows_is_refused(built):
    model, params, ids, _, _ = built
    with pytest.raises(ValueError, match="no whole number of windows"):
        model.forward_with_cache(params, ids[:, :40],
                                 model.init_cache(2, 128, dtype=jnp.float32))


def test_generate_takes_the_same_leaves(eng):
    """``generate()``: a uniform batch, scalar index, a prompt shorter than
    a window; greedy tokens are the reference's argmax along the way."""
    prompt = jnp.asarray(np.random.RandomState(4).randint(0, 320, (2, 27)),
                         jnp.int32)
    out = np.asarray(eng.generate(prompt, max_new_tokens=9))
    assert out.shape == (2, 36) and (out[:, :27] == np.asarray(prompt)).all()
    with jax.default_matmul_precision("highest"):
        rows = reference.forward_logits(eng.params, jnp.asarray(out), CFG)
    gap = rows[:, 26:35].max(-1) - jnp.take_along_axis(
        rows[:, 26:35], jnp.asarray(out[:, 27:])[..., None], -1)[..., 0]
    assert float(gap.max()) < 1e-4


def test_a_norm_adds_a_unit_offset(built):
    """``N(x; w) = x / rms(x) * (1 + w)`` in the program and in the
    reference; with ``w`` drawn normal(0, 0.5) the reading ``w`` is far from
    it, and so are the logits of a model that takes it."""
    model, params, ids, ref, _ = built
    x = jnp.asarray(np.random.RandomState(4).randn(3, 5, 64), jnp.float32)
    w = params["blocks"]["mlp_norm"][1]
    unit = np.asarray(x) / np.sqrt(
        (np.asarray(x) ** 2).mean(-1, keepdims=True) + CFG["rms_norm_eps"])
    want = unit * (1.0 + np.asarray(w))
    np.testing.assert_allclose(model._norm(x, w), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(reference._norm(x, w, CFG["rms_norm_eps"]),
                               want, rtol=1e-5, atol=1e-6)
    assert np.abs(unit * np.asarray(w) - want).max() > 0.1

    class Misread(EvaByteModel):
        def _norm(self, x, w):
            return EvaByteModel._norm(self, x, w - 1.0)

    with jax.default_matmul_precision("highest"):
        out = family.engine_logits(
            Misread(model.config, compute_dtype=jnp.float32), params,
            ids[:, :40])
    assert float(jnp.abs(out - ref[:, :40, 0]).max()) > 1e-2


@pytest.mark.parametrize("fault", ["no_mu", "mean_pool"])
def test_the_comparison_catches_a_pooling_that_is_another(built, fault,
                                                          monkeypatch):
    """A program that drops ``mu`` or pools by a mean is far from the
    reference on every position that sees a summary, and equal before."""
    model, params, ids, ref, _ = built
    pool = eva.pool_chunks

    def wrong(k, v, phi, mu, **kw):
        if fault == "no_mu":
            return pool(k, v, phi, jnp.zeros_like(mu), **kw)
        return pool(k, v, jnp.zeros_like(phi), mu, **kw)

    monkeypatch.setattr(eva, "pool_chunks", wrong)
    with jax.default_matmul_precision("highest"):
        out = family.engine_logits(
            EvaByteModel(model.config, compute_dtype=jnp.float32), params,
            ids[:, :64])
    np.testing.assert_allclose(out[:, :32], ref[:, :32, 0], **TOL)
    assert float(jnp.abs(out[:, 32:] - ref[:, 32:64, 0]).max()) > 1e-2


def test_the_config_refuses_a_chunk_that_does_not_divide_the_window():
    with pytest.raises(ValueError, match="does not divide"):
        EvaByteConfig.tiny(chunk_size=5)
    assert STEP_COUNTERS == ("eva_rows_live", "eva_rows_fetched",
                             "eva_summary_rows_live")


@pytest.mark.parametrize("key,value", [
    ("num_chunks", 4), ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("chunk_size", 5), ("attention_class", "mha"),
    ("norm_add_unit_offset", False), ("num_key_value_heads", 2)])
def test_build_model_refuses_what_the_program_would_drop(key, value):
    with pytest.raises(ValueError):
        family.build_model(dict(CFG, **{key: value}), {})
    with pytest.raises(ValueError):
        family.build_model(CFG, {"attn_impl": "flash"})
