"""The EvaByte family (HF ``evabyte``, ``attention_class`` ``eva``): from a
configuration file (the keys of that published ``config.json``:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``intermediate_size``, ``num_hidden_layers``, ``vocab_size``,
``max_position_embeddings``, ``window_size``, ``chunk_size``, ``num_chunks``,
``num_pred_heads``, ``rope_theta``, ``rope_scaling``, ``rms_norm_eps``,
``norm_add_unit_offset``, ``attention_bias``, ``tie_word_embeddings``,
``hidden_act``, ``init_std``, ``fp32_skip_add``, ``fp32_logits``) to the
program's ``EvaByteConfig`` / ``EvaByteModel``.

A configuration may be ONE PIPELINE STAGE of a deployment:
``num_hidden_layers`` then counts the layers held here and
``num_hidden_layers_published`` all of them; the embedding and the head are
held here whatever the stage."""
from __future__ import annotations

from typing import Mapping


def _layer_params(cfg: Mapping) -> int:
    """One layer: the four attention matrices, the pooling's direction and
    the pooled key's offset (a head each), the gated MLP, the two norms."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return (4 * d * d + 2 * heads * (d // heads)
            + 3 * d * cfg["intermediate_size"] + 2 * d)


def _top_params(cfg: Mapping) -> int:
    """The embedding, the head of ``num_pred_heads`` x ``vocab_size``
    columns and the final norm."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return v * d + d * cfg["num_pred_heads"] * v + d


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys. ``params`` is every
    parameter HELD HERE (the held layers; embedding, head and final norm),
    ``active_params`` the same (a dense model), ``published_params`` the
    whole model's. ``window``, ``chunk`` and ``pred_heads`` are for
    ``work/eva_decode.py`` and ``work/eva_prefill.py``. ``cache_row_dim``
    keeps its default, a window row: no leaf of this cache holds a row a
    token."""
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    layers = cfg["num_hidden_layers"]
    held = layers * _layer_params(cfg) + _top_params(cfg)
    whole = (cfg.get("num_hidden_layers_published", layers)
             * _layer_params(cfg) + _top_params(cfg))
    return {"layers": layers, "hidden": d, "heads": heads,
            "kv_heads": cfg["num_key_value_heads"], "head_dim": d // heads,
            "mlp": cfg["intermediate_size"], "vocab": cfg["vocab_size"],
            "positions": cfg["max_position_embeddings"],
            "params": int(held), "active_params": int(held),
            "published_params": int(whole), "window": cfg["window_size"],
            "chunk": cfg["chunk_size"], "pred_heads": cfg["num_pred_heads"]}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``EvaByteConfig.tiny`` sizes, float32 weights: what a
    rehearsal in the sandbox runs: two layers of four, a window of 16 in
    chunks of 4, so that the rehearsal's buckets of 16 and 32 are whole
    windows and its 64 positions are four. Never a configuration of a
    cell."""
    return dict(cfg, num_hidden_layers=2, num_hidden_layers_published=4,
                hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                intermediate_size=128, window_size=16, chunk_size=4,
                max_position_embeddings=64, max_seq_length=64,
                # (matrices at the scale the published ones have on a stream
                # of 4096: attention and the summaries move the logits)
                init_std=0.1,
                assumed=dict(cfg.get("assumed", {}), weights_dtype="float32"))


def build_model(cfg: Mapping, options: Mapping):
    """``options`` (a traffic file's ``model_options``): this model has one
    attention route and no rematerialisation option here, so ``attn_impl``
    other than dense and ``remat`` are refused, not dropped; so is every
    published key the program computes one way only."""
    import jax.numpy as jnp

    from deepspeed_tpu.models.evabyte import EvaByteConfig, EvaByteModel

    s = shapes(cfg)
    only = {"attention_class": "eva", "attention_bias": False,
            "tie_word_embeddings": False, "num_chunks": None,
            "rope_scaling": None, "hidden_act": "silu",
            "norm_add_unit_offset": True, "fp32_skip_add": True,
            "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
            "num_key_value_heads": s["heads"]}
    for key, want in only.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{key}={cfg[key]!r}: EvaByteModel computes "
                             f"{want!r} only")
    if s["window"] % s["chunk"]:
        raise ValueError(f"chunk_size={s['chunk']} does not divide "
                         f"window_size={s['window']}")
    if options.get("attn_impl", "dense") != "dense" or options.get("remat"):
        raise ValueError("EvaByteModel has its own attention route and no "
                         "rematerialisation option in a cell")
    assumed = cfg.get("assumed", {})
    dtypes = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    config = EvaByteConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        num_layers=s["layers"], hidden_size=s["hidden"],
        num_heads=s["heads"], intermediate_size=s["mlp"],
        window_size=s["window"], chunk_size=s["chunk"],
        num_pred_heads=s["pred_heads"], rope_theta=float(cfg["rope_theta"]),
        eps=cfg["rms_norm_eps"], init_std=cfg["init_std"])
    return EvaByteModel(
        config, param_dtype=dtypes[assumed.get("weights_dtype", "float32")])


def engine_logits(model, params, input_ids):
    """Head 0's logits by the engine's own model object and route."""
    return model.logits(params, model.forward_hidden(params, input_ids))
