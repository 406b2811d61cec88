"""The shortcut-connected double-layer family (LongCat-Flash-Chat): from a
configuration file (the keys of that published ``config.json``:
``hidden_size``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``num_layers``, ``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``mla_scale_q_lora``, ``mla_scale_kv_lora``, ``n_routed_experts``,
``zero_expert_num``, ``zero_expert_type``, ``moe_topk``,
``routed_scaling_factor``, ``rope_theta``, ``rms_norm_eps``, ``vocab_size``,
``max_position_embeddings``, ``attention_bias``, ``attention_method``; what
the file's ``assumed`` adds: ``scoring_func``, ``norm_topk_prob``) to the
program's ``LongcatFlashConfig`` / ``LongcatFlashModel``.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the real experts held here,
``n_routed_experts_published`` the real experts of the model and
``experts_held_first`` the first held one (default 0); the router is as wide as
the published real experts and the ``zero_expert_num`` identity experts
together, and the identity experts are whole on every chip (they hold no
weight). ``vocab_size`` counts the rows of the vocabulary held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``layers`` counts the
    ATTENTION layers, two a double layer (``work/mla_decode.py`` and
    ``work/mla_prefill.py`` multiply by it), ``sparse_layers`` the expert
    layers, one a double layer, which ``double_layers`` names too.
    ``experts`` is the router's WIDTH, identity experts included
    (``work/moe_experts.py`` takes the held share of a token's
    ``experts_per_token`` pairs from it); ``real_experts`` and
    ``zero_experts`` split it. As ``sarvam_mla``: the cached row serves all
    heads (``kv_heads`` 1, ``cache_row_dim`` the elements a token holds in one
    attention layer), ``head_dim`` / ``v_head_dim`` the DECOMPRESSED sizes.
    ``params`` is every parameter HELD HERE, ``params_published`` the whole
    model's by the same formula; ``active_params`` those a token passes
    through on average under a uniform router."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    m, em, vocab = (cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"],
                    cfg["vocab_size"])
    held = cfg["n_routed_experts"]
    real = cfg.get("n_routed_experts_published", held)
    zero, k = cfg["zero_expert_num"], cfg["moe_topk"]
    double = cfg["num_layers"]
    # wq_a, its norm, wq_b, wkv_a, the latent's norm, wkv_b, wo
    attn = (d * ql + ql + ql * heads * (nope + rope) + d * (r + rope) + r
            + r * heads * (nope + vd) + heads * vd * d)
    dense, expert = 3 * d * m, 3 * d * em
    # two sublayers with two norms over hidden each, the router and its bias
    outside = 2 * (attn + dense) + 4 * d + d * (real + zero) + real + zero

    def count(layers, experts, rows):
        return layers * (outside + experts * expert) + 2 * rows * d + d

    params = count(double, held, vocab)
    return {"layers": 2 * double, "double_layers": double, "hidden": d,
            "width": d, "heads": heads, "kv_heads": 1,
            "head_dim": nope + rope, "v_head_dim": vd,
            "cache_row_dim": r + rope, "latent": r, "rope_dim": rope,
            "q_latent": ql, "mlp": m, "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": params,
            "params_published": count(
                cfg.get("num_layers_published", double), real,
                cfg.get("vocab_size_published", vocab)),
            "active_params": int(params - double * expert
                                 * (held - k * held / (real + zero))),
            "experts": real + zero, "real_experts": real,
            "zero_experts": zero, "experts_held": held,
            "experts_per_token": k, "expert_mlp": em,
            "sparse_layers": double}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``LongcatFlashConfig.tiny`` sizes, float32 weights:
    what a rehearsal in the sandbox runs; 2 of 16 real experts held and 8
    identity experts behind them, 4 choices a token, two double layers. Never
    a configuration of a cell."""
    return dict(cfg, num_layers=2, hidden_size=64, num_attention_heads=4,
                q_lora_rank=16, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, ffn_hidden_size=128,
                expert_ffn_hidden_size=32, n_routed_experts=2,
                n_routed_experts_published=16, experts_held_first=0,
                zero_expert_num=8, moe_topk=4, vocab_size=512,
                max_position_embeddings=64,
                # the program's own block sizes, so that a prompt of 32 walks
                # two token blocks and several key blocks
                program={"prompt_block": 16, "key_block": 8},
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has its
    own attention routes and no rematerialisation option here, so
    ``attn_impl`` other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.longcat_flash import (LongcatFlashConfig,
                                                    LongcatFlashModel)

    s = shapes(cfg)
    assumed = cfg.get("assumed", {})
    only = {"attention_bias": False, "attention_method": "MLA",
            "zero_expert_type": "identity", "rope_scaling": None}
    for key, want in only.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: LongcatFlashModel computes "
                             f"{want!r} only")
    for key, want in (("scoring_func", "softmax"), ("norm_topk_prob", False)):
        if assumed.get(key, want) != want:
            raise ValueError(f"assumed.{key}={assumed[key]!r}: the router "
                             f"computes {want!r} only")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("LongcatFlashModel has its own attention routes and "
                         "no rematerialisation option in a cell")
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    config = LongcatFlashConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"], num_heads=s["heads"],
        q_lora_rank=s["q_latent"], kv_lora_rank=s["latent"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=s["rope_dim"], v_head_dim=s["v_head_dim"],
        ffn_hidden_size=s["mlp"], expert_ffn_hidden_size=s["expert_mlp"],
        num_layers=s["double_layers"], n_routed_experts=s["real_experts"],
        zero_experts=s["zero_experts"],
        num_experts_per_tok=s["experts_per_token"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=cfg["routed_scaling_factor"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        **cfg.get("program", {}))
    model = LongcatFlashModel(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])
    if "weights_seed" in assumed:
        # one checkpoint whatever --seed, as the sibling families serve one
        # (a decode step costs what its routing touches: PERF.md, PR 35)
        from benchmarks.families.exaone_moe import _one_checkpoint

        _one_checkpoint(model, assumed["weights_seed"])
    return model


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route."""
    return model.logits(params, model.forward_hidden(params, input_ids))
