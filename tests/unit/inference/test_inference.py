"""Inference-engine tests — analog of reference tests/unit/inference/
(test_inference.py model-injection correctness + kernel numerics).

Key parity checks:
  * KV-cache decode == full-forward argmax rollout (the softmax_context
    kernel's correctness criterion)
  * HF weight mapping: converted GPT-2/LLaMA logits match transformers'
    torch forward (the module_inject replace-layer equivalence test)
  * TP serving gives identical generations
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model
from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel
from deepspeed_tpu.parallel.topology import build_topology
from deepspeed_tpu.utils import groups


def make_engine(model, tp=1, dtype="fp32", **kw):
    groups.reset()
    topo = build_topology(tp=tp)
    return InferenceEngine(model, DeepSpeedInferenceConfig(
        dtype=dtype, tensor_parallel={"tp_size": tp}, **kw), topology=topo)


@pytest.fixture(scope="module")
def gpt2():
    """The tiny float32 GPT-2 and its engine on one device's worth of mesh
    (``tp=1``), as six tests take them unchanged: built once for the module."""
    model = GPT2Model(GPT2Config.tiny(), compute_dtype=jnp.float32)
    return model, make_engine(model)


def full_forward_rollout(model, params, input_ids, n_new):
    """Reference loop: re-run the full (no-cache) forward for every token."""
    ids = np.asarray(input_ids)

    # one program a length, not one an operation a length (eager, a MoE
    # model's six lengths compiled for a minute)
    @jax.jit
    def last_logits(params, ids):
        hidden = model.forward_hidden(params, ids, train=False)
        return model.logits(params, hidden)[:, -1].astype(jnp.float32)

    params = jax.tree_util.tree_map(jnp.asarray, params)
    for _ in range(n_new):
        nxt = np.asarray(jnp.argmax(last_logits(params, jnp.asarray(ids)),
                                    axis=-1))
        ids = np.concatenate([ids, nxt[:, None].astype(np.int32)], axis=1)
    return ids


@pytest.mark.parametrize("model_cls,cfg", [
    (GPT2Model, GPT2Config.tiny()),
    (LlamaModel, LlamaConfig.tiny()),
])
def test_kv_cache_decode_matches_full_forward(model_cls, cfg):
    model = model_cls(cfg, compute_dtype=jnp.float32)
    engine = make_engine(model)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    out = engine.generate(prompt, max_new_tokens=6)
    ref = full_forward_rollout(model, engine.params, prompt, 6)
    np.testing.assert_array_equal(out, ref)


def test_prefill_logits_match_forward(gpt2):
    model, engine = gpt2
    cfg = model.config
    ids = np.random.RandomState(1).randint(0, cfg.vocab_size, (2, 10)).astype(np.int32)
    full = np.asarray(engine.forward(ids).astype(jnp.float32))
    cache = model.init_cache(2, 16, dtype=jnp.float32)
    logits, cache = jax.jit(model.forward_with_cache)(engine.params, jnp.asarray(ids), cache)
    np.testing.assert_allclose(np.asarray(logits, np.float32), full, rtol=2e-4, atol=2e-4)
    assert int(cache["index"]) == 10


def test_tp_generation_matches_single_device(gpt2):
    cfg = GPT2Config.tiny()
    prompt = np.random.RandomState(2).randint(0, cfg.vocab_size, (2, 8)).astype(np.int32)

    _, e1 = gpt2
    params_host = jax.device_get(e1.params)
    out1 = e1.generate(prompt, max_new_tokens=5)

    groups.reset()
    topo = build_topology(tp=2)
    e2 = InferenceEngine(GPT2Model(cfg, compute_dtype=jnp.float32),
                         DeepSpeedInferenceConfig(dtype="fp32",
                                                  tensor_parallel={"tp_size": 2}),
                         params=params_host, topology=topo)
    spec = str(e2.params["blocks"]["mlp_fc_w"].sharding.spec)
    assert "model" in spec, spec
    out2 = e2.generate(prompt, max_new_tokens=5)
    np.testing.assert_array_equal(out1, out2)


def test_sampling_reproducible_and_topk(gpt2):
    _, engine = gpt2
    prompt = np.zeros((1, 4), np.int32)
    a = engine.generate(prompt, max_new_tokens=8, do_sample=True, top_k=5, seed=7)
    b = engine.generate(prompt, max_new_tokens=8, do_sample=True, top_k=5, seed=7)
    np.testing.assert_array_equal(a, b)
    c = engine.generate(prompt, max_new_tokens=8, do_sample=True, top_k=5, seed=8)
    assert a.shape == c.shape == (1, 12)


def test_compiled_programs_accessor_and_kv_padding():
    """compiled_programs() exposes the exact prefill/decode programs
    generate() uses (benches time them directly), and
    the KV allocation pads to a multiple of 128 (flash-decode tiling)
    while masking keeps padded positions inert: the accessor-driven
    two-program path must reproduce generate()'s tokens exactly."""
    groups.reset()
    cfg = GPT2Config.tiny()
    engine = deepspeed_tpu.init_inference(GPT2Model(cfg), dtype="bf16",
                                          max_out_tokens=40)
    ids = np.random.RandomState(3).randint(0, cfg.vocab_size,
                                           size=(2, 8)).astype(np.int32)
    ref = engine.generate(ids, max_new_tokens=6)
    pf, dec = engine.compiled_programs(2, 8, 6)
    tok, cache, rng = pf(engine.params, jnp.asarray(ids),
                         jnp.float32(1.0), jax.random.PRNGKey(0))
    # padded cache: every cache leaf's TOKEN capacity is a multiple of 128
    # (caches may be token-pair packed [L, B, H, S/pair, Dh*pair] —
    # ops/attention.kv_pack_factor)
    for leaf in jax.tree_util.tree_leaves(cache):
        if getattr(leaf, "ndim", 0) >= 4:
            tokens = leaf.shape[-2] * (leaf.shape[-1] // cfg.head_dim)
            assert tokens % 128 == 0, leaf.shape
    toks = dec(engine.params, tok, cache, jnp.float32(1.0), rng)
    np.testing.assert_array_equal(np.asarray(toks), ref[:, 8:])


def test_max_tokens_guard():
    engine = make_engine(GPT2Model(GPT2Config.tiny(), compute_dtype=jnp.float32),
                         max_out_tokens=16)
    with pytest.raises(RuntimeError, match="max_tokens"):
        engine.generate(np.zeros((1, 10), np.int32), max_new_tokens=10)


def test_eos_stops_and_pads(gpt2):
    model, engine = gpt2
    cfg = model.config
    prompt = np.random.RandomState(3).randint(0, cfg.vocab_size, (1, 6)).astype(np.int32)
    ref = full_forward_rollout(model, engine.params, prompt, 8)
    gen = ref[0, 6:]
    # prefer a mid-sequence eos whose value didn't occur earlier (so the stop
    # position is unambiguous); fall back to the first token
    pos = next((i for i in range(1, len(gen) - 1) if gen[i] not in gen[:i]), 0)
    eos = int(gen[pos])
    out = engine.generate(prompt, max_new_tokens=8, eos_token_id=eos, pad_token_id=0)
    assert out[0, 6 + pos] == eos
    assert (out[0, 6 + pos + 1:] == 0).all()


def test_top_p_filter_matches_hf_warper():
    """Support-set parity with transformers' TopPLogitsWarper (the filter the
    reference's serving path applies inside HF generate)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from transformers.generation.logits_process import TopPLogitsWarper

    from deepspeed_tpu.inference.engine import filter_logits

    rng = np.random.RandomState(0)
    logits = rng.randn(4, 64).astype(np.float32) * 3.0
    for top_p in (0.1, 0.5, 0.9, 0.999):
        ours = np.asarray(filter_logits(jnp.asarray(logits), top_p=top_p))
        theirs = TopPLogitsWarper(top_p=top_p)(
            None, torch.from_numpy(logits)).numpy()
        np.testing.assert_array_equal(np.isfinite(ours), np.isfinite(theirs),
                                      err_msg=f"top_p={top_p}")
        kept = np.isfinite(ours)
        np.testing.assert_allclose(ours[kept], logits[kept], rtol=1e-6)


def test_top_p_generate_reproducible(gpt2):
    model, engine = gpt2
    cfg = model.config
    prompt = np.zeros((2, 4), np.int32)
    a = engine.generate(prompt, max_new_tokens=8, do_sample=True, top_p=0.9, seed=7)
    b = engine.generate(prompt, max_new_tokens=8, do_sample=True, top_p=0.9, seed=7)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 12)
    assert (a >= 0).all() and (a < cfg.vocab_size).all()
    with pytest.raises(ValueError, match="top_p"):
        engine.generate(prompt, max_new_tokens=4, do_sample=True, top_p=0.0)


def test_eos_early_exit_matches_scan_path(gpt2):
    """The while_loop EOS path must emit exactly what the scan path emits up
    to (and including) EOS, padding after — and stop early when every row is
    done (behavioral check: outputs agree with the no-eos rollout prefix)."""
    model, engine = gpt2
    cfg = model.config
    prompt = np.random.RandomState(5).randint(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    free = engine.generate(prompt, max_new_tokens=10)  # no eos: scan path
    # pick an eos that appears in row 0's continuation; row 1 may not hit it
    gen0 = free[0, 6:]
    eos = int(gen0[2])
    out = engine.generate(prompt, max_new_tokens=10, eos_token_id=eos,
                          pad_token_id=0)
    for row in range(2):
        gen_free = free[row, 6:]
        gen_eos = out[row, 6:]
        hits = np.where(gen_free == eos)[0]
        stop = hits[0] if len(hits) else len(gen_free) - 1
        np.testing.assert_array_equal(gen_eos[:stop + 1], gen_free[:stop + 1])
        assert (gen_eos[stop + 1:] == 0).all()


def test_checkpoint_roundtrip_to_inference(tmp_path):
    """Train briefly → save_checkpoint → serve from the checkpoint
    (the reference's checkpoint-sharing between engine and InferenceEngine)."""
    groups.reset()
    cfg = GPT2Config.tiny()
    model = GPT2Model(cfg, compute_dtype=jnp.float32)
    engine, *_ = deepspeed_tpu.initialize(model=model, config={
        "train_batch_size": 8,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 0,
    })
    rng = np.random.RandomState(0)
    ids = ((rng.randint(0, 512, (1, 8, 1)) + np.arange(33)) % 512).astype(np.int32)
    engine.train_batch_from_stacked({"input_ids": ids[:, :, :-1], "labels": ids[:, :, 1:]})
    engine.save_checkpoint(str(tmp_path))

    inf = make_engine(GPT2Model(cfg, compute_dtype=jnp.float32),
                      checkpoint=str(tmp_path))
    trained = jax.device_get(engine.state.params["wte"])
    served = jax.device_get(inf.params["wte"])
    np.testing.assert_allclose(served, trained, rtol=1e-6)
    out = inf.generate(np.zeros((1, 4), np.int32), max_new_tokens=4)
    assert out.shape == (1, 8)


def test_dtype_parsing_and_errors():
    assert DeepSpeedInferenceConfig(dtype="fp16").jax_dtype() == jnp.float16
    assert DeepSpeedInferenceConfig(dtype="bfloat16").jax_dtype() == jnp.bfloat16
    with pytest.raises(ValueError, match="unknown inference dtype"):
        DeepSpeedInferenceConfig(dtype="fp64").jax_dtype()


# ------------------------------------------------------------ HF parity
def test_hf_gpt2_policy_matches_transformers():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
    with torch.no_grad():
        hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    ids = np.random.RandomState(0).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()

    engine = deepspeed_tpu.init_inference(hf, dtype="fp32")
    ours = np.asarray(engine.forward(ids.astype(np.int32)).astype(jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_hf_llama_policy_matches_transformers():
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=64,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64)
    with torch.no_grad():
        hf = transformers.LlamaForCausalLM(hf_cfg).eval()
    ids = np.random.RandomState(1).randint(0, 128, (2, 12))
    with torch.no_grad():
        ref = hf(torch.tensor(ids)).logits.numpy()

    engine = deepspeed_tpu.init_inference(hf, dtype="fp32")
    ours = np.asarray(engine.forward(ids.astype(np.int32)).astype(jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_unknown_hf_arch_raises():
    torch = pytest.importorskip("torch")

    class Mystery(torch.nn.Module):
        pass

    with pytest.raises(ValueError, match="no inference policy"):
        deepspeed_tpu.init_inference(Mystery(), dtype="fp32")


def test_int8_stream_init_matches_one_shot():
    """Round-4: random-init int8 serving stream-initializes (one fused
    init→quantize program per block leaf, so the full bf16 tree never
    materializes — the difference between fitting and OOMing a 16 GB chip
    at 6.7B). The claim is bit-identical values vs init-then-quantize:
    assert it."""
    from deepspeed_tpu.utils import groups

    cfg = LlamaConfig.tiny()
    groups.reset()
    stream = deepspeed_tpu.init_inference(LlamaModel(cfg), dtype="int8")
    stream_params = stream.params

    groups.reset()
    from deepspeed_tpu.inference.engine import InferenceEngine

    model = LlamaModel(cfg)
    one_shot = InferenceEngine(
        model, {"dtype": "int8"},
        params=jax.jit(lambda k: jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if x.dtype == jnp.float32 else x,
            model.init(k)))(jax.random.PRNGKey(0)))

    leaves1 = jax.tree_util.tree_leaves_with_path(stream_params)
    leaves2 = jax.tree_util.tree_leaves_with_path(one_shot.params)
    assert len(leaves1) == len(leaves2) and len(leaves1) > 0
    for (p1, a), (p2, b) in zip(leaves1, leaves2):
        assert p1 == p2
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=str(p1))
    # and at least one leaf really is quantized
    assert any(isinstance(v, dict) and "__q__" in v
               for v in stream_params["blocks"].values())


def test_int8_weight_only_serving():
    """dtype='int8' = weight-only quantization (reference GroupQuantizer):
    int8 block weights + per-column scales in HBM, bf16 compute, logits
    close to the full-precision model."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")

    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4)
    torch.manual_seed(0)   # absolute tolerances below need fixed weights
    with torch.no_grad():
        hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    ids = np.random.RandomState(0).randint(0, 128, (2, 10))

    ref_engine = deepspeed_tpu.init_inference(hf, dtype="fp32")
    ref = np.asarray(ref_engine.forward(ids.astype(np.int32))
                     .astype(jnp.float32))
    from deepspeed_tpu.utils import groups
    groups.reset()
    engine = deepspeed_tpu.init_inference(hf, dtype="int8")
    assert engine.weight_quant and engine.dtype == jnp.bfloat16
    qkv = engine.params["blocks"]["qkv_w"]
    assert isinstance(qkv, dict) and qkv["__q__"].dtype == jnp.int8
    assert qkv["__scale__"].shape == (2, 1, 96)
    ours = np.asarray(engine.forward(ids.astype(np.int32))
                      .astype(jnp.float32))
    # int8 weights + bf16 compute: loose but meaningful tolerance
    assert np.abs(ours - ref).max() < 0.15, np.abs(ours - ref).max()
    # greedy argmax should be stable under weight-only quantization
    agree = (ours.argmax(-1) == ref.argmax(-1)).mean()
    assert agree > 0.9, agree
