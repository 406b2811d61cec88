"""The Solar-Open2 family (HF ``solar_open2``; Solar-Open2-250B): from a
configuration file (the keys of that kind of published ``config.json``:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``linear_attn_config`` (``num_heads``, ``head_dim``,
``short_conv_kernel_size``, ``num_kv_heads``), ``gqa_layers``,
``gqa_interval``, ``use_rope``, ``use_gqa_gate``, ``kda_use_full_proj``,
``kda_allow_neg_eigval``, ``moe_intermediate_size``, ``n_routed_experts``,
``n_shared_experts``, ``num_experts_per_tok``, ``norm_topk_prob``,
``routed_scaling_factor``, ``first_k_dense_replace``, ``rms_norm_eps``,
``vocab_size``, ``tie_word_embeddings``, ``max_position_embeddings``) to the
program's ``SolarKdaConfig`` / ``SolarKdaModel``: gated delta-rule linear
attention layers and gated softmax grouped-query layers without positions in
the order ``gqa_layers`` gives, a sparse FFN behind every one.

A configuration may be ONE CHIP'S SHARE of an expert-parallel deployment:
``n_routed_experts`` then counts the experts held here,
``n_routed_experts_published`` the router's width and ``experts_held_first``
the first held expert (default 0); ``vocab_size`` the rows of the vocabulary
held here."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``heads``,
    ``kv_heads`` and ``head_dim`` are the SOFTMAX layers' queries and cached
    rows; only ``attn_layers`` of the ``layers`` hold rows, so
    ``cache_row_dim``, the elements one cached token holds in one layer, is
    the average over all layers (``flops.decode_attn_work`` multiplies it by
    ``layers``): 2 x 8 x 128 on one layer of four are 512. ``params`` is every
    parameter HELD HERE; ``active_params`` those a token passes through on
    average. ``width``, ``experts``, ``experts_held``, ``experts_per_token``,
    ``expert_mlp`` and ``sparse_layers`` are for ``work/moe_experts.py``;
    the ``kda_*`` sizes for ``work/kda_update.py``."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kv_heads, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    lin = cfg["linear_attn_config"]
    hk, dk, taps = (lin["num_heads"], lin["head_dim"],
                    lin["short_conv_kernel_size"])
    w, rank = hk * dk, lin["head_dim"]       # both gates low-rank through it
    em, vocab = cfg["moe_intermediate_size"], cfg["vocab_size"]
    held = cfg["n_routed_experts"]
    experts = cfg.get("n_routed_experts_published", held)
    k = cfg["num_experts_per_tok"]
    layers = cfg["num_hidden_layers"]
    n_gqa = sum(1 for i in cfg["gqa_layers"] if i < layers)
    n_kda = layers - n_gqa
    expert = 3 * d * em
    # the two norms over hidden, router and its bias, the shared expert
    common = 2 * d + d * experts + experts + expert
    # wq, wk, wv, the gate, wo
    gqa = d * dh * (3 * heads + 2 * kv_heads) + common
    # wq | wk | wv, wo, the two low-rank gates, w_beta, the three
    # convolutions, A_log, dt_bias, the head norm
    kda = (3 * d * w + w * d + 2 * (d * rank + rank * w) + d * hk
           + 3 * w * taps + hk + w + dk + common)
    outside = 2 * vocab * d + d + n_gqa * gqa + n_kda * kda
    state_bytes = 4     # float32, which build_model holds the file's assumed to
    return {"layers": layers, "hidden": d, "width": d, "heads": heads,
            "kv_heads": kv_heads, "head_dim": dh,
            "cache_row_dim": 2 * kv_heads * dh * n_gqa // layers,
            "mlp": cfg["intermediate_size"], "vocab": vocab,
            "positions": cfg["max_position_embeddings"],
            "params": outside + layers * held * expert,
            "active_params": int(outside + layers * k * held / experts
                                 * expert),
            "experts": experts, "experts_held": held,
            "experts_per_token": k, "expert_mlp": em, "sparse_layers": layers,
            "attn_layers": n_gqa, "kda_layers": n_kda, "kda_heads": hk,
            "kda_key_dim": dk, "kda_value_dim": dk, "kda_conv": taps,
            "kda_gate_rank": rank, "kda_state_bytes": state_bytes,
            # a slot's state: the delta rule's, the tails (bf16), and the
            # key-value rows of a request as long as the allocation
            "state_bytes_per_slot": (
                n_kda * (hk * dk * dk * state_bytes + (taps - 1) * 3 * w * 2)
                + n_gqa * cfg["max_position_embeddings"] * 2 * kv_heads * dh
                * 2)}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``SolarKdaConfig.tiny`` sizes, float32 weights: what a
    rehearsal in the sandbox runs; one period, 2 of 16 experts held, as 40 of
    320. Never a configuration of a cell."""
    return dict(cfg, num_hidden_layers=4, gqa_layers=[0], hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                linear_attn_config=dict(cfg["linear_attn_config"],
                                        num_heads=4, head_dim=16),
                intermediate_size=128, moe_intermediate_size=32,
                n_routed_experts=2, n_routed_experts_published=16,
                experts_held_first=0, num_experts_per_tok=4, vocab_size=512,
                max_position_embeddings=64,
                # the program's own block sizes, so that a prompt of 32 walks
                # two token blocks, several key blocks and several chunks
                program={"prompt_block": 16, "key_block": 8, "kda_chunk": 8},
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has its
    own attention routes and no rematerialisation option here, so
    ``attn_impl`` other than dense and ``remat`` are refused, not dropped."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.solar_kda import GQA, KDA, SolarKdaConfig, SolarKdaModel

    s = shapes(cfg)
    assumed = cfg.get("assumed", {})
    lin = cfg["linear_attn_config"]
    only = {"use_rope": False, "use_gqa_gate": True, "kda_use_full_proj": False,
            "kda_allow_neg_eigval": True, "tie_word_embeddings": False,
            "first_k_dense_replace": 0, "n_shared_experts": 1}
    for key, want in only.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: SolarKdaModel computes "
                             f"{want!r} only")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise ValueError("the delta rule's keys and values have its heads: "
                         f"num_kv_heads={lin['num_kv_heads']!r}")
    for key, want in (("scoring_func", "sigmoid"), ("n_group", 1),
                      ("topk_group", 1), ("state_dtype", "float32")):
        if assumed.get(key, want) != want:
            raise ValueError(f"assumed.{key}={assumed[key]!r}: the program "
                             f"computes {want!r} only")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("SolarKdaModel has its own attention routes and no "
                         "rematerialisation option in a cell")
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    config = SolarKdaConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        hidden_size=s["hidden"],
        layer_types=tuple(GQA if i in cfg["gqa_layers"] else KDA
                          for i in range(s["layers"])),
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], kda_heads=s["kda_heads"],
        kda_head_dim=s["kda_key_dim"], kda_conv=s["kda_conv"],
        kda_gate_rank=s["kda_gate_rank"],
        moe_intermediate_size=s["expert_mlp"], num_experts=s["experts"],
        num_experts_per_tok=s["experts_per_token"],
        num_shared_experts=cfg["n_shared_experts"],
        held=(cfg.get("experts_held_first", 0), s["experts_held"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=cfg["norm_topk_prob"], eps=cfg["rms_norm_eps"],
        **cfg.get("program", {}))
    model = SolarKdaModel(
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
