"""GraniteHybridModel against the plain reference (the sequential recurrence,
``benchmarks/reference/granite_hybrid.py``) at tiny size in float32: full
forward, prefill then decode through the cache, a padded prompt against the
same prompt unpadded, gradients, and what the config refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import granite_hybrid as family
from benchmarks.reference import granite_hybrid as reference
from deepspeed_tpu.models.granite_hybrid import (GraniteHybridConfig,
                                                 GraniteHybridModel)

pytestmark = pytest.mark.quick

# the published keys at the sizes of the tests: 2 Mamba + 1 attention + 1
# Mamba layer, hidden 64, H 4, P 32, N 16, chunk 8, vocabulary 512
CFG = family.tiny({
    "family": "granite_hybrid", "attention_bias": False,
    "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "logits_scaling": 8, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_experts_per_tok": 0, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "rope_scaling": None, "tie_word_embeddings": True})
# logits of a random model of this family are small (the embedding is drawn at
# 0.02 / 12 and the head divides by 8): 1e-4 of their size, not of 1
TOL = dict(rtol=1e-4, atol=1e-6)


# The reference as ONE program a shape: op by op (eager) every operation of
# its layers compiles anew for each new sequence length.
_reference_logits = jax.jit(
    lambda params, ids: reference.forward_logits(params, ids, CFG))


@pytest.fixture(scope="module")
def built():
    model = family.build_model(CFG, {})
    model.compute_dtype = jnp.float32
    params = model.init(jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 21)),
                      jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = _reference_logits(params, ids)
    return model, params, ids, ref


def test_the_tiny_model_is_the_stated_stack(built):
    model, params, _, _ = built
    c = model.config
    assert c.layer_types == ("mamba", "mamba", "attention", "mamba")
    # (kind, first of its stack, first of its cache leaves, count)
    assert model.runs() == (("mamba", 0, 0, 2), ("attention", 0, 0, 1),
                            ("mamba", 2, 2, 1))
    assert (c.mamba_n_heads, c.mamba_d_head, c.mamba_d_state,
            c.mamba_chunk_size) == (4, 32, 16, 8)
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == model.num_params() == family.shapes(CFG)["params"]
    assert set(jax.tree_util.tree_structure(params).node_data()[1]) == \
        set(model.logical_axes())
    # the recurrence is neither dead nor saturated: decays inside (0, 1)
    a = -np.exp(np.asarray(params["mamba"]["A_log"]))
    dt = np.log1p(np.exp(np.asarray(params["mamba"]["dt_bias"])))
    assert (1.0 <= -a).all() and (-a <= 16.0).all()
    assert (0.001 <= dt + 1e-6).all() and (dt <= 0.1 + 1e-6).all()


def test_full_forward_matches_the_reference(built):
    model, params, ids, ref = built
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda p, x: family.engine_logits(model, p, x))(
            params, ids)
    assert float(jnp.abs(ref).max()) > 1e-3
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_prefill_then_decode_matches_the_reference(built):
    """The chunked form for the prompt, the one-token recurrence after it,
    both against the sequential recurrence over the whole sequence."""
    model, params, ids, ref = built
    with jax.default_matmul_precision("highest"):
        cache = model.init_cache(2, 32, dtype=jnp.float32)
        logits, cache = model.forward_with_cache(params, ids[:, :13], cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref[:, :13]), **TOL)
        for t in range(13, 21):
            logits, cache = model.forward_with_cache(params, ids[:, t:t + 1],
                                                     cache)
            np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                       np.asarray(ref[:, t]), **TOL)
    assert int(cache["index"]) == 21
    assert cache["k"].shape[0] == 1 and cache["ssm"].shape[0] == 3


@pytest.mark.parametrize("length,bucket", [(11, 16), (5, 16), (16, 16)])
def test_a_padded_prompt_leaves_the_state_of_the_unpadded_one(built, length,
                                                              bucket):
    """Bucket padding is not causally invisible to a recurrence: with the
    true length the state stops there, without it the padding is folded in."""
    model, params, ids, ref = built
    pad = jnp.zeros((1, bucket), jnp.int32).at[0, :length].set(
        ids[0, :length])
    with jax.default_matmul_precision("highest"):
        cache = dict(model.init_cache(1, 32, dtype=jnp.float32),
                     valid_len=jnp.int32(length))
        lp, cp = model.forward_with_cache(params, pad, cache)
        lu, cu = model.forward_with_cache(
            params, ids[:1, :length],
            model.init_cache(1, 32, dtype=jnp.float32))
        np.testing.assert_allclose(np.asarray(lp[:, :length]),
                                   np.asarray(ref[:1, :length]), **TOL)
        for name in ("ssm", "conv"):
            np.testing.assert_allclose(np.asarray(cp[name]),
                                       np.asarray(cu[name]), **TOL)
        if length < bucket:
            _, folded = model.forward_with_cache(
                params, pad, model.init_cache(1, 32, dtype=jnp.float32))
            assert float(jnp.abs(folded["ssm"] - cu["ssm"]).max()) > \
                1e-2 * float(jnp.abs(cu["ssm"]).max())


def test_gradients_match_the_reference_loss(built):
    model, params, ids, _ = built
    labels = jnp.roll(ids, -1, axis=1)
    with jax.default_matmul_precision("highest"):
        (loss, aux), grads = jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, {"input_ids": ids, "labels": labels}),
            has_aux=True))(params)
        ref_loss, ref_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.loss(p, ids, labels, CFG)))(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert int(aux["ntokens"]) == ids.size
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_ref = jax.tree_util.tree_leaves(ref_grads)
    for (path, g), g_ref in zip(flat, flat_ref):
        scale = max(float(jnp.abs(g_ref).max()), 1e-6)
        err = float(jnp.abs(g - g_ref).max())
        assert err <= 1e-4 * scale + 1e-9, (path, err, scale)
        assert float(jnp.abs(g_ref).max()) > 0, path   # every leaf is used


@pytest.mark.parametrize("policy", [None, "dots_no_batch"])
def test_remat_changes_no_loss_and_no_gradient(built, policy):
    """The walk the hybrid shares with the other decoders wraps each layer
    in ``jax.checkpoint`` by policy: the same numbers, recomputed."""
    model, params, ids, _ = built
    batch = {"input_ids": ids, "labels": jnp.roll(ids, -1, axis=1)}

    def loss_and_grads(remat):
        m = GraniteHybridModel(model.config, compute_dtype=jnp.float32,
                               remat=remat, remat_policy=policy)
        return jax.jit(jax.value_and_grad(lambda p: m.apply(p, batch)[0]))

    plain, wrapped = loss_and_grads(False), loss_and_grads(True)
    assert "remat" not in str(jax.make_jaxpr(plain)(params))
    assert "remat" in str(jax.make_jaxpr(wrapped)(params))
    (loss, grads), (loss_r, grads_r) = plain(params), wrapped(params)
    np.testing.assert_allclose(float(loss_r), float(loss), rtol=1e-6)
    for g, g_r in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(grads_r)):
        np.testing.assert_allclose(np.asarray(g_r), np.asarray(g),
                                   rtol=1e-5, atol=1e-9)


@pytest.mark.parametrize("change,needle", [
    (dict(num_local_experts=4), "experts"),
    (dict(position_embedding_type="rope"), "position"),
    (dict(mamba_n_groups=3), "mamba_n_groups"),
    (dict(layer_types=("mamba", "window")), "layer_types"),
    (dict(mamba_d_head=16), "mamba_expand"),
])
def test_the_config_refuses_what_the_program_does_not_compute(change, needle):
    with pytest.raises(ValueError, match=needle):
        GraniteHybridConfig.tiny(**change) if "mamba_d_head" not in change \
            else GraniteHybridConfig(hidden_size=64, num_heads=4,
                                     num_kv_heads=2, mamba_n_heads=4,
                                     **change)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("mamba_proj_bias", True),
    ("mamba_conv_bias", False), ("num_experts_per_tok", 2),
    ("tie_word_embeddings", False), ("hidden_act", "gelu"),
    ("num_local_experts", 8), ("position_embedding_type", "rope")])
def test_the_family_refuses_a_key_it_cannot_honour(key, value):
    with pytest.raises(ValueError):
        family.build_model(dict(CFG, **{key: value}), {})


def test_generate_runs_the_whole_path_on_one_request(built):
    """``init_inference`` + ``generate``: the bucket-free path, scalar index,
    greedy; its tokens are the reference's argmax, teacher-forced."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    model, params, ids, _ = built
    groups.reset()
    eng = deepspeed_tpu.init_inference(
        GraniteHybridModel(model.config), dtype="fp32", max_out_tokens=64,
        params=params)
    out = np.asarray(eng.generate(ids[:1, :9], max_new_tokens=6))
    seq = jnp.asarray(out[:, :15])
    with jax.default_matmul_precision("highest"):
        ref = _reference_logits(params, seq)
    assert out[0, 9:15].tolist() == \
        np.asarray(ref[0, 8:14].argmax(-1)).tolist()
