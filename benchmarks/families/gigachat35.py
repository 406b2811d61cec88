"""The GigaChat 3.5 family (HF ``gigachat3_5``; GigaChat3.5-432B-A28B): from a
configuration file (the keys of that published ``config.json``:
``hidden_size``, ``num_hidden_layers``, ``full_attention_layers``,
``first_k_dense_replace``, ``num_attention_heads``, ``q_lora_rank``,
``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``rope_theta``, ``rope_scaling``, ``rope_interleave``, ``gated_attention``,
``use_mla_scaling_factor``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``linear_sigmoid_gate_scale``, ``linear_attn_o_norm_eps``,
``intermediate_size``, ``moe_intermediate_size``, ``n_routed_experts``,
``n_shared_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``swiglu_limit``, ``layernorm_gating_weight``,
``rms_norm_eps``, ``vocab_size``, ``max_position_embeddings``) to the
program's ``GigaChat35Config`` / ``GigaChat35Model``: scalar-decay gated
delta-rule layers, gated latent attention on the layers
``full_attention_layers`` names, sandwich norms, ``first_k_dense_replace``
dense layers and sparse ones behind them.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the experts held here,
``n_routed_experts_published`` the router's width and ``experts_held_first``
the first held expert (default 0); ``vocab_size`` the rows of the vocabulary
held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``layers`` counts the
    LATENT layers, the ones that hold token rows (``work/mla_decode.py`` and
    ``work/mla_prefill.py`` multiply by it; ``total_layers`` is the stack's
    depth), ``gdn_layers`` the delta-rule ones (``work/gdn_update.py``),
    ``sparse_layers`` the expert layers (``work/moe_experts.py``, with
    ``width``, ``experts``, ``experts_held``, ``experts_per_token``,
    ``expert_mlp``). As ``sarvam_mla``: the cached row serves all heads
    (``kv_heads`` 1, ``cache_row_dim`` the elements a token holds in one
    latent layer), ``head_dim`` / ``v_head_dim`` the DECOMPRESSED sizes.
    ``params`` is every parameter HELD HERE; ``active_params`` those a token
    passes through on average under a uniform router."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    m, em, vocab = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                    cfg["vocab_size"])
    held = cfg["n_routed_experts"]
    experts = cfg.get("n_routed_experts_published", held)
    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"]
    n_dense = min(cfg["first_k_dense_replace"], layers)
    latent = [i for i in cfg["full_attention_layers"] if i < layers]
    n_gdn = layers - len(latent)
    width = (2 * hk + hv) * dk               # q | k | v
    # W_qkv, W_z, W_b | W_a, the taps, A_log, dt_bias, the head norm, W_out
    gdn = (d * width + d * hv * dv + 2 * d * hv + taps * width + 2 * hv + dv
           + hv * dv * d)
    # W_qa, its norm, W_qb, W_kva, the latent's norm, W_kvb, the gate, W_o
    mla = (d * ql + ql + ql * heads * (nope + rope) + d * (r + rope) + r
           + r * heads * (nope + vd) + 2 * d * heads * vd)
    dense, expert = 3 * d * m, 3 * d * em
    # router and its bias, the shared expert
    sparse = d * experts + experts + expert
    outside = (2 * vocab * d + d + 4 * d * layers + n_gdn * gdn
               + len(latent) * mla + n_dense * dense
               + (layers - n_dense) * sparse)
    n_sparse = layers - n_dense
    state_bytes = 4     # float32, which build_model holds the file's assumed to
    return {"layers": len(latent), "total_layers": layers, "hidden": d,
            "width": d, "heads": heads, "kv_heads": 1,
            "head_dim": nope + rope, "v_head_dim": vd,
            "cache_row_dim": r + rope, "latent": r, "rope_dim": rope,
            "q_latent": ql, "mlp": m, "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": outside + n_sparse * held * expert,
            "active_params": int(outside + n_sparse * k * held / experts
                                 * expert),
            "experts": experts, "experts_held": held,
            "experts_per_token": k, "expert_mlp": em,
            "sparse_layers": n_sparse, "dense_layers": n_dense,
            "gdn_layers": n_gdn, "gdn_key_heads": hk, "gdn_value_heads": hv,
            "gdn_key_dim": dk, "gdn_value_dim": dv, "gdn_conv": taps,
            "gdn_state_bytes": state_bytes,
            # a slot's state: the delta rule's, the tails (bf16), and the
            # latent rows (padded to whole lanes) of a request as long as the
            # allocation
            "state_bytes_per_slot": (
                n_gdn * (hv * dk * dv * state_bytes + (taps - 1) * width * 2)
                + len(latent) * cfg["max_position_embeddings"]
                * -(-(r + rope) // 128) * 128 * 2)}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``GigaChat35Config.tiny`` sizes, float32 weights: what
    a rehearsal in the sandbox runs; the dense delta-rule layer and one
    period, 2 of 16 experts held, as 16 of 256. Never a configuration of a
    cell."""
    return dict(cfg, num_hidden_layers=5, full_attention_layers=[1],
                first_k_dense_replace=1, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=4, q_lora_rank=16,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                qk_head_dim=24, v_head_dim=16, linear_num_key_heads=2,
                linear_num_value_heads=4, linear_key_head_dim=16,
                linear_value_head_dim=16, intermediate_size=128,
                moe_intermediate_size=32, n_routed_experts=2,
                n_routed_experts_published=16, experts_held_first=0,
                num_experts_per_tok=4, vocab_size=512,
                max_position_embeddings=64,
                rope_scaling=dict(cfg["rope_scaling"],
                                  original_max_position_embeddings=16),
                # the program's own block sizes, so that a prompt of 32 walks
                # two token blocks, several key blocks and several chunks
                program={"prompt_block": 16, "key_block": 8, "gdn_chunk": 8},
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has its
    own attention routes and no rematerialisation option here, so
    ``attn_impl`` other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.gigachat35 import GigaChat35Config, GigaChat35Model

    s = shapes(cfg)
    assumed = cfg.get("assumed", {})
    sc = cfg["rope_scaling"]
    only = {"attention_bias": False, "use_shared_expert_sigmoid": False,
            "nextn_is_sparse": False, "n_group": 1, "topk_group": 1,
            "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
            "n_shared_experts": 1, "hidden_act": "silu",
            "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
            "gated_attention": True, "rope_interleave": True,
            "use_mla_scaling_factor": True,
            "linear_attention_type": "GigaChat35GatedDeltaNet",
            "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered"}
    for key, want in only.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: GigaChat35Model computes "
                             f"{want!r} only")
    if sc.get("type") != "yarn":
        raise ValueError(f"rope_scaling.type={sc.get('type')!r}: the latent "
                         "layers rotate at YaRN's frequencies only")
    for key, want in (("scoring_func", "sigmoid"), ("conv_bias", False),
                      ("state_dtype", "float32")):
        if assumed.get(key, want) != want:
            raise ValueError(f"assumed.{key}={assumed[key]!r}: the program "
                             f"computes {want!r} only")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("GigaChat35Model has its own attention routes and no "
                         "rematerialisation option in a cell")
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    limit = cfg.get("swiglu_limit")
    config = GigaChat35Config(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"], num_layers=s["total_layers"],
        full_attention_layers=tuple(cfg["full_attention_layers"]),
        first_k_dense=cfg["first_k_dense_replace"], num_heads=s["heads"],
        q_lora_rank=s["q_latent"], kv_lora_rank=s["latent"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=s["rope_dim"], v_head_dim=s["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_factor=float(sc["factor"]),
        rope_original_max=sc["original_max_position_embeddings"],
        rope_beta_fast=float(sc["beta_fast"]),
        rope_beta_slow=float(sc["beta_slow"]),
        rope_mscale=float(sc["mscale"]),
        rope_mscale_all_dim=float(sc["mscale_all_dim"]),
        gdn_key_heads=s["gdn_key_heads"], gdn_value_heads=s["gdn_value_heads"],
        gdn_key_dim=s["gdn_key_dim"], gdn_value_dim=s["gdn_value_dim"],
        gdn_conv=s["gdn_conv"],
        gdn_gate_scale=float(cfg["linear_sigmoid_gate_scale"]),
        gdn_norm_eps=cfg["linear_attn_o_norm_eps"],
        intermediate_size=s["mlp"], moe_intermediate_size=s["expert_mlp"],
        num_experts=s["experts"], num_experts_per_tok=s["experts_per_token"],
        num_shared_experts=cfg["n_shared_experts"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"],
        swiglu_limit=None if limit is None else float(limit),
        norm_gate=float(cfg["layernorm_gating_weight"]),
        eps=cfg["rms_norm_eps"], **cfg.get("program", {}))
    model = GigaChat35Model(
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
