"""The EXAONE-MoE family (HF ``exaone_moe``; K-EXAONE-236B-A23B): from a
configuration file (the keys of that kind of published ``config.json``:
``layer_types``, ``sliding_windows``, ``mlp_layer_types``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``num_experts``,
``num_experts_per_tok``, ``num_shared_experts``, ``routed_scaling_factor``,
``norm_topk_prob``, ``scoring_func``, ``n_group``, ``topk_group``,
``rope_parameters``, ``rms_norm_eps``, ``vocab_size``,
``tie_word_embeddings``, ``max_position_embeddings``) to the program's
``ExaoneMoeConfig`` / ``ExaoneMoeModel``.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``num_experts`` then counts the experts held here, ``num_experts_published``
the router's width and ``experts_held_first`` the first held expert
(default 0); ``vocab_size`` the rows of the vocabulary held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``params`` is every
    parameter HELD HERE (the held experts, the held vocabulary rows, an
    untied head); ``active_params`` those a token passes through on average:
    of its ``experts_per_token`` experts the held share. ``experts``,
    ``experts_held``, ``experts_per_token``, ``expert_mlp``, ``window``,
    ``sliding_layers`` and ``global_layers`` are for ``work/moe_experts.py``.

    ``hidden`` is the residual stream's width, the published ``hidden_size``
    = 6144; attention is ``heads x head_dim`` = 8192 wide on it, which
    ``flops.py`` computes from those two. ``width`` says the same as
    ``hidden`` and stays for ``work/moe_experts.py``, which reads it."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    m, em, vocab = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                    cfg["vocab_size"])
    held = cfg["num_experts"]
    experts = cfg.get("num_experts_published", held)
    k = cfg["num_experts_per_tok"]
    kinds, ffns = cfg["layer_types"], cfg["mlp_layer_types"]
    n_dense, n_sparse = ffns.count("dense"), ffns.count("sparse")
    # projections, the two norms over the head dimension, the two over hidden
    attn = d * dh * (2 * heads + 2 * kv_heads) + 2 * dh + 2 * d
    dense = attn + 3 * d * m
    outside = attn + d * experts + experts + 3 * d * em     # router, bias,
    expert = 3 * d * em                                     # shared expert
    params = (2 * vocab * d + d + n_dense * dense
              + n_sparse * (outside + held * expert))
    active = (2 * vocab * d + d + n_dense * dense
              + n_sparse * (outside + k * held / experts * expert))
    return {"layers": len(kinds), "hidden": d, "width": d, "heads": heads,
            "kv_heads": kv_heads, "head_dim": dh, "mlp": m, "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": params, "active_params": int(active),
            "experts": experts, "experts_held": held,
            "experts_per_token": k, "expert_mlp": em,
            "sparse_layers": n_sparse, "window": cfg["sliding_window"],
            "sliding_layers": kinds.count("sliding_attention"),
            "global_layers": kinds.count("full_attention")}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``ExaoneMoeConfig.tiny`` sizes, float32 weights: what
    a rehearsal in the sandbox runs; 2 of 16 experts held, as 16 of 128.
    Never a configuration of a cell."""
    kinds = ["sliding_attention", "sliding_attention", "full_attention",
             "sliding_attention"]
    return dict(cfg, layer_types=kinds, sliding_windows=[8, 8, 0, 8],
                mlp_layer_types=["dense", "sparse", "sparse", "sparse"],
                num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=32, intermediate_size=128,
                moe_intermediate_size=32, sliding_window=8,
                num_experts=2, num_experts_published=16,
                experts_held_first=0, num_experts_per_tok=4,
                vocab_size=512, max_position_embeddings=128,
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has one
    attention route and no rematerialisation option here, so ``attn_impl``
    other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                 ExaoneMoeModel)

    s = shapes(cfg)
    kinds, windows = cfg["layer_types"], cfg["sliding_windows"]
    if not (len(kinds) == len(windows) == len(cfg["mlp_layer_types"])
            == cfg["num_hidden_layers"]):
        raise ValueError("layer_types, sliding_windows and mlp_layer_types "
                         "do not each name num_hidden_layers layers")
    for kind, window in zip(kinds, windows):
        want = cfg["sliding_window"] if kind == "sliding_attention" else 0
        if window != want:
            raise ValueError(f"sliding_windows gives {window} for a {kind} "
                             f"layer, sliding_window says {want}")
    refused = {"hidden_act": "silu", "num_nextn_predict_layers": 0,
               "first_k_dense_replace": cfg["mlp_layer_types"].count("dense")}
    for key, only in refused.items():
        if cfg.get(key, only) != only:
            raise ValueError(f"{key}={cfg[key]!r}: ExaoneMoeModel computes "
                             f"{only!r} only")
    if cfg["mlp_layer_types"][:refused["first_k_dense_replace"]].count(
            "dense") != refused["first_k_dense_replace"]:
        raise ValueError("the dense layers lead the stack")
    rope = cfg["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type={rope['rope_type']!r}: plain rotation "
                         "only")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("ExaoneMoeModel has the dense attention route and "
                         "no rematerialisation option in a cell")
    assumed = cfg.get("assumed", {})
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    # scoring_func, n_group, topk_group, tie_word_embeddings and
    # num_shared_experts are refused by the config itself
    config = ExaoneMoeConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["width"], num_heads=s["heads"],
        num_kv_heads=s["kv_heads"], head_dim=s["head_dim"],
        intermediate_size=s["mlp"], moe_intermediate_size=s["expert_mlp"],
        layer_types=tuple(kinds),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=s["window"], num_experts=s["experts"],
        num_experts_per_tok=s["experts_per_token"],
        num_shared_experts=cfg["num_shared_experts"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        scoring_func=cfg["scoring_func"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], rope_theta=float(rope["rope_theta"]),
        eps=cfg["rms_norm_eps"],
        tie_word_embeddings=cfg["tie_word_embeddings"])
    model = ExaoneMoeModel(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])
    if "weights_seed" in assumed:
        _one_checkpoint(model, assumed["weights_seed"])
    return model


def _one_checkpoint(model, seed: int) -> None:
    """``model.init`` draws the weights of ``assumed.weights_seed`` whatever
    key it is given: a deployment serves one checkpoint and its traffic
    varies, so ``--seed`` draws the tokens and the configuration the weights.
    A step of this model costs what it touches (0.19 ms a held expert with a
    token), and weights drawn anew a run route 10.5 to 14.1% of the pairs to
    the 16 held experts: the decode gap followed the run's seed (PERF.md, PR
    35). The key is folded as the kinds fold ``--seed``, so these are the
    weights that ``--seed <weights_seed>`` drew before."""
    import jax

    from benchmarks import traffic_gen

    draw = model.init
    key = traffic_gen.fold_seed(seed)
    model.init = lambda rng: draw(jax.random.PRNGKey(key))


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route."""
    return model.logits(params, model.forward_hidden(params, input_ids))
