"""The MiMo-V2 family (HF ``mimo_v2``; MiMo-V2.5): from a configuration file
(the keys of that kind of published ``config.json``: ``hybrid_layer_pattern``,
``moe_layer_freq``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``swa_num_key_value_heads``, ``head_dim``,
``v_head_dim``, ``swa_*``, ``intermediate_size``, ``moe_intermediate_size``,
``n_routed_experts``, ``num_experts_per_tok``, ``n_shared_experts``,
``routed_scaling_factor``, ``norm_topk_prob``, ``scoring_func``, ``n_group``,
``topk_group``, ``rope_theta``, ``swa_rope_theta``, ``partial_rotary_factor``,
``attention_value_scale``, ``add_swa_attention_sink_bias``,
``add_full_attention_sink_bias``, ``sliding_window``, ``layernorm_epsilon``,
``vocab_size``, ``tie_word_embeddings``, ``max_position_embeddings``) to the
program's ``MimoV2Config`` / ``MimoV2Model``.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the experts held here,
``n_routed_experts_published`` the router's width and ``experts_held_first``
the first held expert (default 0); ``vocab_size`` the rows of the vocabulary
held here, ``vocab_size_published`` all of them."""
from __future__ import annotations

from typing import Mapping


def _layer_params(cfg: Mapping, sliding: int, sparse: int, experts: float):
    """One layer's parameters with ``experts`` of its experts counted."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    kv = cfg["swa_num_key_value_heads" if sliding else "num_key_value_heads"]
    # the fused projection, the output projection, the two norms
    n = d * (heads * dk + kv * (dk + dv)) + heads * dv * d + 2 * d
    if sliding and cfg["add_swa_attention_sink_bias"]:
        n += heads
    if not sparse:
        return n + 3 * d * cfg["intermediate_size"]
    width = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    return (n + d * width + width               # the router and its bias
            + experts * 3 * d * cfg["moe_intermediate_size"])


def _count(cfg: Mapping, pattern, freq, vocab: int, experts: float):
    return 2 * vocab * cfg["hidden_size"] + cfg["hidden_size"] + sum(
        _layer_params(cfg, s, m, experts) for s, m in zip(pattern, freq))


def published_params(cfg: Mapping) -> int:
    """Every parameter of the WHOLE published model, from the configuration's
    own keys: the published depth (a leading dense global layer, then
    periods of five sliding layers and a global one, as the held lists
    begin), every expert, every vocabulary row."""
    depth = cfg.get("num_hidden_layers_published", cfg["num_hidden_layers"])
    pattern = [0 if layer == 0 or layer % 6 == 5 else 1
               for layer in range(depth)]
    freq = [0] + [1] * (depth - 1)
    held = len(cfg["hybrid_layer_pattern"])
    if pattern[:held] != list(cfg["hybrid_layer_pattern"]) or \
            freq[:held] != list(cfg["moe_layer_freq"]):
        raise ValueError("the held layers are not the published lists' "
                         "first entries")
    return int(_count(
        cfg, pattern, freq, cfg.get("vocab_size_published",
                                    cfg["vocab_size"]),
        cfg.get("n_routed_experts_published", cfg["n_routed_experts"])))


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``params`` is every
    parameter HELD HERE (the held experts, the held vocabulary rows, an
    untied head); ``active_params`` those a token passes through on average:
    of its ``experts_per_token`` experts the held share; ``published_params``
    the whole model's. ``kv_heads`` is the global layers' (the rows that grow
    with the request), ``global_kv_heads`` and ``sliding_kv_heads`` say both;
    ``head_dim`` is a query's and a key's LIVE width, ``v_head_dim`` a
    value's; ``cache_row_dim`` the live elements a cached token holds in one
    global layer. ``experts``, ``experts_held``, ``experts_per_token``,
    ``expert_mlp``, ``width`` and ``sparse_layers`` are for
    ``work/moe_experts.py``; ``window``, ``sliding_layers``,
    ``global_layers`` and the per-kind heads for ``work/window_decode.py``
    and ``work/global_prefill.py``."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    g_kv, s_kv = cfg["num_key_value_heads"], cfg["swa_num_key_value_heads"]
    held = cfg["n_routed_experts"]
    experts = cfg.get("n_routed_experts_published", held)
    k = cfg["num_experts_per_tok"]
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    vocab = cfg["vocab_size"]
    return {"layers": len(pattern), "hidden": d, "width": d, "heads": heads,
            "kv_heads": g_kv, "head_dim": dk, "v_head_dim": dv,
            "cache_row_dim": g_kv * (dk + dv),
            "global_kv_heads": g_kv, "sliding_kv_heads": s_kv,
            "mlp": cfg["intermediate_size"], "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": int(_count(cfg, pattern, freq, vocab, held)),
            "active_params": int(_count(cfg, pattern, freq, vocab,
                                        k * held / experts)),
            "published_params": published_params(cfg),
            "experts": experts, "experts_held": held,
            "experts_per_token": k,
            "expert_mlp": cfg["moe_intermediate_size"],
            "sparse_layers": sum(freq), "window": cfg["sliding_window"],
            "sliding_layers": sum(pattern),
            "global_layers": len(pattern) - sum(pattern)}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``MimoV2Config.tiny`` sizes, float32 weights: what a
    rehearsal in the sandbox runs; the cell's seven layers, 2 of 16 experts
    held, as 16 of 256. Never a configuration of a cell."""
    return dict(cfg, hidden_size=64,
                num_attention_heads=4, swa_num_attention_heads=4,
                num_key_value_heads=1, swa_num_key_value_heads=2,
                head_dim=24, swa_head_dim=24, v_head_dim=16,
                swa_v_head_dim=16, intermediate_size=128,
                moe_intermediate_size=32, sliding_window=8,
                sliding_window_size=8, n_routed_experts=2,
                n_routed_experts_published=16, experts_held_first=0,
                num_experts_per_tok=4, vocab_size=512,
                vocab_size_published=4096, max_position_embeddings=128,
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32",
                             prompt_block=16, key_block=8))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has one
    attention route and no rematerialisation option here, so ``attn_impl``
    other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.mimo_v2 import MimoV2Config, MimoV2Model

    s = shapes(cfg)
    if not (len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"])
            == cfg["num_hidden_layers"]):
        raise ValueError("hybrid_layer_pattern and moe_layer_freq do not "
                         "each name num_hidden_layers layers")
    # what the program computes one way only: the sliding layers' own head
    # count and sizes are the global layers' but for the key-value heads
    same = {"hidden_act": "silu", "attention_bias": False,
            "swa_num_attention_heads": s["heads"],
            "swa_head_dim": s["head_dim"], "swa_v_head_dim": s["v_head_dim"],
            "sliding_window_size": s["window"], "hybrid_block_size": None,
            "attention_projection_layout": "fused_qkv",
            "topk_method": "noaux_tc"}
    for key, only in same.items():
        if cfg.get(key, only) != only:
            raise ValueError(f"{key}={cfg[key]!r}: MimoV2Model computes "
                             f"{only!r} only")
    rope = cfg.get("rope_scaling") or {}
    if rope.get("rope_type", "default") != "default":
        raise ValueError(f"rope_type={rope['rope_type']!r}: plain rotation "
                         "only")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("MimoV2Model has the dense attention route and no "
                         "rematerialisation option in a cell")
    assumed = cfg.get("assumed", {})
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    blocks = {k: assumed[k] for k in ("prompt_block", "key_block")
              if k in assumed}
    # scoring_func, n_group, topk_group, tie_word_embeddings,
    # n_shared_experts and a sink on global layers are refused by the config
    config = MimoV2Config(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"], num_heads=s["heads"],
        num_kv_heads=s["global_kv_heads"],
        swa_num_kv_heads=s["sliding_kv_heads"], head_dim=s["head_dim"],
        v_head_dim=s["v_head_dim"], intermediate_size=s["mlp"],
        moe_intermediate_size=s["expert_mlp"],
        hybrid_layer_pattern=tuple(cfg["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(cfg["moe_layer_freq"]),
        sliding_window=s["window"], num_experts=s["experts"],
        num_experts_per_tok=s["experts_per_token"],
        n_shared_experts=cfg["n_shared_experts"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=cfg["routed_scaling_factor"],
        norm_topk_prob=cfg["norm_topk_prob"],
        scoring_func=cfg["scoring_func"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        partial_rotary_factor=cfg["partial_rotary_factor"],
        attention_value_scale=cfg["attention_value_scale"],
        add_swa_attention_sink_bias=cfg["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=cfg["add_full_attention_sink_bias"],
        eps=cfg["layernorm_epsilon"],
        tie_word_embeddings=cfg["tie_word_embeddings"], **blocks)
    model = MimoV2Model(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])
    if "weights_seed" in assumed:
        _one_checkpoint(model, assumed["weights_seed"])
    return model


def _one_checkpoint(model, seed: int) -> None:
    """``model.init`` draws the weights of ``assumed.weights_seed`` whatever
    key it is given: a deployment serves one checkpoint and its traffic
    varies, so ``--seed`` draws the tokens and the configuration the weights
    (as ``families/exaone_moe.py``: a step of a model with routed experts
    costs what its weights route here)."""
    import jax

    from benchmarks import traffic_gen

    draw = model.init
    key = traffic_gen.fold_seed(seed)
    model.init = lambda rng: draw(jax.random.PRNGKey(key))


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route."""
    return model.logits(params, model.forward_hidden(params, input_ids))
