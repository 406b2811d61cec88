"""The Nemotron-H family (HF ``nemotron_h``; Nemotron 3 Super 120B-A12B): from
a configuration file (the keys of that kind of published ``config.json``:
``hybrid_override_pattern``, ``hidden_size``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``mamba_num_heads``, ``mamba_head_dim``,
``ssm_state_size``, ``n_groups``, ``conv_kernel``, ``chunk_size``,
``moe_intermediate_size``, ``moe_latent_size``,
``moe_shared_expert_intermediate_size``, ``n_routed_experts``,
``num_experts_per_tok``, ``n_shared_experts``, ``routed_scaling_factor``,
``norm_topk_prob``, ``n_group``, ``topk_group``, ``mlp_hidden_act``,
``layer_norm_epsilon``, ``vocab_size``, ``tie_word_embeddings``,
``max_position_embeddings``) to the program's ``NemotronHConfig`` /
``NemotronHModel``: layers of ONE sublayer each, Mamba-2 (``M``), LatentMoE
(``E``) or position-free grouped-query attention (``*``), an untied head.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the experts held here,
``n_routed_experts_published`` the router's width and ``experts_held_first``
the first held expert (default 0); ``vocab_size`` the rows of the vocabulary
held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``params`` is every
    parameter HELD HERE (the held experts, the held vocabulary rows, an
    untied head); ``active_params`` those a token passes through on average:
    of its ``experts_per_token`` experts the held share. ``mamba_layers`` and
    the ``ssm_*`` sizes are for ``work/ssm_update.py``; ``expert_matrices``
    and ``expert_in_width`` (an expert is TWO matrices of ``latent x
    expert_mlp``), ``expert_mlp``, ``experts``, ``experts_held``,
    ``experts_per_token`` and ``sparse_layers`` for ``work/moe_experts.py``.
    ``layers`` counts the ATTENTION layers, the only ones whose cache
    ``flops.py`` computes from; ``total_layers`` every layer. ``mlp`` is the
    shared expert's width."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, p, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, k = cfg["n_groups"], cfg["conv_kernel"]
    lat, em = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    sm, vocab = cfg["moe_shared_expert_intermediate_size"], cfg["vocab_size"]
    held = cfg["n_routed_experts"]
    experts = cfg.get("n_routed_experts_published", held)
    per_token = cfg["num_experts_per_tok"]
    pattern = cfg["hybrid_override_pattern"]
    n_mamba, n_moe, n_attn = (pattern.count(c) for c in "ME*")
    d_in = h * p
    conv = d_in + 2 * g * n
    mamba = (d + d * (2 * d_in + 2 * g * n + h) + (k + 1) * conv + 3 * h
             + d_in + d_in * d)
    attn = d + d * dh * (2 * heads + 2 * kv_heads)
    outside = d + d * experts + experts + 2 * d * lat + 2 * d * sm
    expert = 2 * lat * em
    fixed = (2 * vocab * d + d + n_mamba * mamba + n_attn * attn
             + n_moe * outside)
    state = 4       # float32, which build_model holds the file's assumed to
    return {"layers": n_attn, "total_layers": len(pattern), "hidden": d,
            "heads": heads, "kv_heads": kv_heads, "head_dim": dh, "mlp": sm,
            "vocab": vocab, "positions": cfg["max_position_embeddings"],
            "params": fixed + n_moe * held * expert,
            "active_params": int(fixed + n_moe * per_token * held / experts
                                 * expert),
            "mamba_layers": n_mamba, "attn_layers": n_attn,
            "ssm_heads": h, "ssm_head_dim": p, "ssm_state": n,
            "ssm_groups": g, "conv_width": conv, "ssm_state_bytes": state,
            # a slot's recurrent state: the state and the convolution's tail
            "state_bytes_per_slot": n_mamba * (h * p * n * state
                                               + (k - 1) * conv * 2),
            "latent": lat, "expert_mlp": em, "experts": experts,
            "expert_matrices": 2, "expert_in_width": lat,
            "experts_held": held, "experts_per_token": per_token,
            "sparse_layers": n_moe,
            # by kind, for the tests that hold the count to the published one
            "mamba_layer_params": mamba, "attn_layer_params": attn,
            "moe_layer_params": outside, "expert_params": expert}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``NemotronHConfig.tiny`` sizes, float32 weights: what
    a rehearsal in the sandbox runs; 4 groups of heads, 3 of 16 experts a
    token of which experts 4 to 7 are held. Never a configuration of a
    cell."""
    return dict(cfg, hybrid_override_pattern="MEM*EME", num_hidden_layers=7,
                hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
                ssm_state_size=16, n_groups=4, chunk_size=8,
                moe_intermediate_size=48, intermediate_size=48,
                moe_latent_size=32, moe_shared_expert_intermediate_size=96,
                n_routed_experts=4, n_routed_experts_published=16,
                experts_held_first=4, num_experts_per_tok=3, vocab_size=512,
                max_position_embeddings=128,
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32",
                             prompt_block=16))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has one
    attention route and no rematerialisation option here, so ``attn_impl``
    other than dense and ``remat`` are refused, not dropped; so is every key
    the program cannot honour (a bias, a group-limited router, a
    multi-token-prediction layer, another activation)."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.nemotron_h import NemotronHConfig, NemotronHModel

    s = shapes(cfg)
    if len(cfg["hybrid_override_pattern"]) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern does not name "
                         "num_hidden_layers layers")
    refused = {"attention_bias": False, "mlp_bias": False, "use_bias": False,
               "mamba_proj_bias": False, "use_conv_bias": True,
               "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
               "n_shared_experts": 1, "num_nextn_predict_layers": 0,
               "mtp_hybrid_override_pattern": "",
               "tie_word_embeddings": False, "sliding_window": None,
               "residual_in_fp32": False, "moe_shared_expert_overlap": False,
               "norm_eps": cfg["layer_norm_epsilon"],
               "intermediate_size": cfg["moe_intermediate_size"]}
    for key, only in refused.items():
        if cfg.get(key, only) != only:
            raise ValueError(f"{key}={cfg[key]!r}: NemotronHModel computes "
                             f"{only!r} only")
    if s["ssm_heads"] * s["ssm_head_dim"] != cfg["expand"] * s["hidden"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("NemotronHModel has the dense attention route and "
                         "no rematerialisation option in a cell")
    assumed = cfg.get("assumed", {})
    if assumed.get("state_dtype", "float32") != "float32":
        raise ValueError(f"state_dtype={assumed['state_dtype']!r}: "
                         "NemotronHModel keeps its recurrent state in "
                         "float32")
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    # n_group, topk_group and groups that do not divide the heads are refused
    # by the config itself
    extra = {"prompt_block": assumed["prompt_block"]} \
        if "prompt_block" in assumed else {}
    config = NemotronHConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"],
        hybrid_override_pattern=cfg["hybrid_override_pattern"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], mamba_n_heads=s["ssm_heads"],
        mamba_d_head=s["ssm_head_dim"], mamba_d_state=s["ssm_state"],
        mamba_n_groups=s["ssm_groups"], mamba_d_conv=cfg["conv_kernel"],
        mamba_chunk_size=cfg["chunk_size"],
        moe_intermediate_size=s["expert_mlp"], moe_latent_size=s["latent"],
        shared_intermediate_size=s["mlp"], num_experts=s["experts"],
        num_experts_per_tok=s["experts_per_token"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], eps=cfg["layer_norm_epsilon"], **extra)
    model = NemotronHModel(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])
    if "weights_seed" in assumed:
        _one_checkpoint(model, assumed["weights_seed"])
    return model


def _one_checkpoint(model, seed: int) -> None:
    """``model.init`` draws the weights of ``assumed.weights_seed`` whatever
    key it is given: a deployment serves one checkpoint and its traffic
    varies, so ``--seed`` draws the tokens and the configuration the weights.
    A step of this model costs by the experts its tokens choose (half of 128
    held experts a layer are read at 16 live slots), and weights drawn anew a
    run would move the decode gap by the seed (PERF.md, PR 35). The key is
    folded as the kinds fold ``--seed``, so these are the weights that
    ``--seed <weights_seed>`` would draw."""
    import jax

    from benchmarks import traffic_gen

    draw = model.init
    key = traffic_gen.fold_seed(seed)
    model.init = lambda rng: draw(jax.random.PRNGKey(key))


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route (chunked SSD)."""
    return model.logits(params, model.forward_hidden(params, input_ids))
