"""The multi-head latent attention family (HF ``sarvam_mla``; Sarvam-105B):
from a configuration file (the keys of that kind of published
``config.json``: ``hidden_size``, ``num_attention_heads``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``q_head_dim``,
``head_dim``, ``intermediate_size``, ``moe_intermediate_size``,
``first_k_dense_replace``, ``num_experts``, ``num_experts_per_tok``,
``num_shared_experts``, ``routed_scaling_factor``, ``rope_theta``,
``rope_scaling``, ``rms_norm_eps``, ``vocab_size``, ``tie_word_embeddings``,
``max_position_embeddings``; what the file's ``assumed`` adds: ``scoring_func``,
``norm_topk_prob``, ``n_group``, ``topk_group``) to the program's
``SarvamMlaConfig`` / ``SarvamMlaModel``.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``num_experts`` then counts the experts held here, ``num_experts_published``
the router's width and ``experts_held_first`` the first held expert
(default 0); ``vocab_size`` the rows of the vocabulary held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. The cached row
    serves ALL heads: ``kv_heads`` is 1 and ``cache_row_dim`` the elements one
    token holds in one layer (``kv_lora_rank + qk_rope_head_dim`` = the
    published ``head_dim``, 576), while ``head_dim`` and ``v_head_dim`` are
    the DECOMPRESSED sizes a prompt's attention runs at (192 and 128).
    ``params`` is every parameter HELD HERE; ``active_params`` those a token
    passes through on average. ``width``, ``experts``, ``experts_held``,
    ``experts_per_token``, ``expert_mlp`` and ``sparse_layers`` are for
    ``work/moe_experts.py``; ``latent`` and ``rope_dim`` for
    ``work/mla_decode.py``."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope, vd = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    if cfg["q_head_dim"] != nope + rope or cfg["head_dim"] != r + rope:
        raise ValueError("q_head_dim is qk_nope + qk_rope and head_dim the "
                         "cached row, kv_lora_rank + qk_rope")
    m, em, vocab = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                    cfg["vocab_size"])
    held = cfg["num_experts"]
    experts = cfg.get("num_experts_published", held)
    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    n_sparse = layers - n_dense
    # wq, wkv_a, the latent's norm, wkv_b, wo, the two norms over hidden
    attn = (d * heads * (nope + rope) + d * (r + rope) + r
            + r * heads * (nope + vd) + heads * vd * d + 2 * d)
    dense = attn + 3 * d * m
    outside = attn + d * experts + experts + 3 * d * em     # router, bias,
    expert = 3 * d * em                                     # shared expert
    params = (2 * vocab * d + d + n_dense * dense
              + n_sparse * (outside + held * expert))
    active = (2 * vocab * d + d + n_dense * dense
              + n_sparse * (outside + k * held / experts * expert))
    return {"layers": layers, "hidden": d, "width": d, "heads": heads,
            "kv_heads": 1, "head_dim": nope + rope, "v_head_dim": vd,
            "cache_row_dim": r + rope, "latent": r, "rope_dim": rope,
            "mlp": m, "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": params, "active_params": int(active),
            "experts": experts, "experts_held": held,
            "experts_per_token": k, "expert_mlp": em,
            "sparse_layers": n_sparse, "dense_layers": n_dense}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``SarvamMlaConfig.tiny`` sizes, float32 weights: what
    a rehearsal in the sandbox runs; 2 of 16 experts held, as 16 of 128; 64
    positions over 16 original ones, so that rotation past the trained range
    is rehearsed too. Never a configuration of a cell."""
    return dict(cfg, num_hidden_layers=3, first_k_dense_replace=1,
                hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                q_head_dim=24, head_dim=40, intermediate_size=128,
                moe_intermediate_size=32, num_experts=2,
                num_experts_published=16, experts_held_first=0,
                num_experts_per_tok=4, vocab_size=512,
                max_position_embeddings=64,
                rope_scaling=dict(cfg["rope_scaling"],
                                  original_max_position_embeddings=16),
                # the program's own block sizes, so that a prompt of 32 walks
                # two token blocks and several key blocks
                program={"prompt_block": 16, "key_block": 8},
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has its
    own attention routes and no rematerialisation option here, so
    ``attn_impl`` other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.sarvam_mla import (SarvamMlaConfig,
                                                 SarvamMlaModel)

    s = shapes(cfg)
    assumed = cfg.get("assumed", {})
    only = {"hidden_act": "silu", "tie_word_embeddings": False,
            "use_qk_norm": True, "moe_router_enable_expert_bias": True}
    for key, want in only.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: SarvamMlaModel computes "
                             f"{want!r} only")
    for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1)):
        if assumed.get(key, want) != want:
            raise ValueError(f"assumed.{key}={assumed[key]!r}: the router "
                             f"computes {want!r} only")
    rope = cfg["rope_scaling"]
    if rope["type"] != "deepseek_yarn":
        raise ValueError(f"rope_scaling.type={rope['type']!r}: "
                         "deepseek_yarn only")
    if cfg.get("default_theta", cfg["rope_theta"]) != cfg["rope_theta"]:
        raise ValueError("default_theta and rope_theta differ")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("SarvamMlaModel has its own attention routes and "
                         "no rematerialisation option in a cell")
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    config = SarvamMlaConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"], num_heads=s["heads"],
        kv_lora_rank=s["latent"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=s["rope_dim"], v_head_dim=s["v_head_dim"],
        intermediate_size=s["mlp"], moe_intermediate_size=s["expert_mlp"],
        num_layers=s["layers"], first_k_dense=s["dense_layers"],
        num_experts=s["experts"], num_experts_per_tok=s["experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=assumed.get("norm_topk_prob", True),
        rope_theta=float(cfg["rope_theta"]), rope_factor=float(rope["factor"]),
        rope_original_max=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        eps=cfg["rms_norm_eps"],
        **cfg.get("program", {}))
    model = SarvamMlaModel(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])
    if "weights_seed" in assumed:
        # one checkpoint whatever --seed, as the sibling family serves one
        # (a decode step costs what its routing touches: PERF.md, PR 35)
        from benchmarks.families.exaone_moe import _one_checkpoint

        _one_checkpoint(model, assumed["weights_seed"])
    return model


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route."""
    return model.logits(params, model.forward_hidden(params, input_ids))
