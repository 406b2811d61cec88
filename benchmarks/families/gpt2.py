"""The GPT-2 family: from a configuration file (the keys of the published
``config.json``) to the program's ``GPT2Config`` / ``GPT2Model``."""
from __future__ import annotations

from typing import Mapping


def shapes(cfg: Mapping) -> dict:
    """The one place that translates the published keys: the sizes the kinds,
    ``benchmarks/flops.py`` and the ``work`` modules compute from. ``params``
    is every parameter of the published model (tied embedding, learned
    positions, biases and norms); a dense model's are all active."""
    d, layers = cfg["n_embd"], cfg["n_layer"]
    m = cfg.get("n_inner") or 4 * d
    vocab, positions = cfg["vocab_size"], cfg["n_positions"]
    per_layer = 4 * d * d + 2 * d * m + (3 * d + d + m + d) + 4 * d
    params = vocab * d + positions * d + layers * per_layer + 2 * d
    if not cfg.get("tie_word_embeddings", True):
        params += vocab * d
    return {"layers": layers, "hidden": d, "heads": cfg["n_head"],
            "kv_heads": cfg["n_head"], "head_dim": d // cfg["n_head"],
            "mlp": m, "vocab": vocab, "positions": positions,
            "params": params, "active_params": params}


def tiny(cfg: Mapping) -> dict:
    """The same keys at ``GPT2Config.tiny`` sizes: what a rehearsal in the
    sandbox runs. Never a configuration of a cell."""
    return dict(cfg, n_layer=2, n_embd=64, n_head=4, n_inner=None,
                vocab_size=512, n_positions=128)


def build_model(cfg: Mapping, options: Mapping):
    """``options``: ``attn_impl``, ``remat``, ``remat_policy``, ``loss_chunk``
    (a traffic file's ``model_options``)."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2Model

    s = shapes(cfg)
    if s["mlp"] % s["hidden"]:
        raise ValueError("GPT2Config takes the MLP width as a whole ratio")
    for key in ("attn_pdrop", "embd_pdrop", "resid_pdrop"):
        if cfg.get(key, 0.0):
            raise ValueError(f"{key}={cfg[key]}: the cells run without "
                             "dropout (the flash route implements none)")
    config = GPT2Config(
        vocab_size=s["vocab"], max_seq_len=s["positions"],
        num_layers=s["layers"], hidden_size=s["hidden"],
        num_heads=s["heads"], mlp_ratio=s["mlp"] // s["hidden"],
        tie_embeddings=cfg.get("tie_word_embeddings", True),
        eps=cfg["layer_norm_epsilon"],
        loss_chunk=options.get("loss_chunk", 0))
    return GPT2Model(config, attn_impl=options.get("attn_impl", "dense"),
                     remat=options.get("remat", False),
                     remat_policy=options.get("remat_policy"))


def engine_logits(model, params, input_ids):
    """Logits by the engine's own model object and route (flash, bf16)."""
    return model.logits(params, model.forward_hidden(params, input_ids))
