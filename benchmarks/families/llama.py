"""The LLaMA family: from a configuration file (the keys of that kind of
published ``config.json``: ``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``rms_norm_eps``, ``rope_theta``, ``max_position_embeddings``, ``vocab_size``,
``tie_word_embeddings``) to the program's ``LlamaConfig`` / ``LlamaModel``:
RMSNorm, rotary positions, SwiGLU, grouped-query heads, an untied head."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys: the sizes the kinds,
    ``benchmarks/flops.py`` and the ``work`` modules compute from. ``params``
    is every parameter (no biases, no learned positions); a dense model's
    are all active."""
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    heads = cfg["num_attention_heads"]
    kv_heads = cfg.get("num_key_value_heads") or heads
    head_dim = cfg.get("head_dim") or d // heads
    m, vocab = cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = (d * head_dim * (heads + 2 * kv_heads) + heads * head_dim * d
                 + 3 * d * m + 2 * d)
    params = vocab * d + layers * per_layer + d
    if not cfg.get("tie_word_embeddings", False):
        params += d * vocab
    return {"layers": layers, "hidden": d, "heads": heads,
            "kv_heads": kv_heads, "head_dim": head_dim, "mlp": m,
            "vocab": vocab, "positions": cfg["max_position_embeddings"],
            "params": params, "active_params": params}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``LlamaConfig.tiny`` sizes: what a rehearsal in the
    sandbox runs. Never a configuration of a cell."""
    return dict(cfg, num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, vocab_size=512,
                max_position_embeddings=128)


def build_model(cfg: Mapping, options: Mapping):
    """``options``: ``attn_impl``, ``remat``, ``remat_policy`` (a traffic
    file's ``model_options``)."""
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaModel

    s = shapes(cfg)
    if s["head_dim"] * s["heads"] != s["hidden"]:
        raise ValueError("LlamaConfig takes the head size as hidden / heads")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("LlamaModel has an untied head only")
    if cfg.get("attention_bias") or cfg.get("mlp_bias"):
        raise ValueError("LlamaModel has no biases")
    if cfg.get("rope_scaling"):
        raise ValueError("LlamaModel rotates by rope_theta alone")
    config = LlamaConfig(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        num_layers=s["layers"], hidden_size=s["hidden"],
        num_heads=s["heads"], num_kv_heads=s["kv_heads"],
        intermediate_size=s["mlp"], rope_theta=cfg["rope_theta"],
        eps=cfg["rms_norm_eps"])
    return LlamaModel(config, attn_impl=options.get("attn_impl", "dense"),
                      remat=options.get("remat", False),
                      remat_policy=options.get("remat_policy"))


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route (flash, bf16)."""
    return model.logits(params, model.forward_hidden(params, input_ids))
