"""Operations and bytes from shapes. Every function here is arithmetic on the
sizes a configuration file and a traffic file state; nothing is measured.

``shapes`` is the dict a family's ``shapes()`` returns: layers, hidden, heads,
kv_heads, head_dim, mlp, vocab, positions, params, active_params. ``hidden``
is the residual stream's width and nothing here computes with it: attention is
``heads`` query heads of ``head_dim`` over ``kv_heads`` cached heads, and
``heads * head_dim`` need not be ``hidden``. A family whose value heads or
cache rows are of another size says so in two optional keys: ``v_head_dim``
(default ``head_dim``) and ``cache_row_dim``, the elements one cached token
holds in one layer (default ``2 * kv_heads * head_dim``: a key and a value a
key-value head). Nothing here reads a configuration's own keys.

``*_model_flops(observations)`` are the model FLOPs a traced window's work
needed, what ``readers/trace_program_mfu.py`` divides by the peak and a
program's device seconds: found by name from a metric file's ``flops``.
"""
from __future__ import annotations

from typing import Mapping, Sequence

BF16 = 2  # bytes


def train_flops_per_token(s: Mapping[str, int], seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per
    parameter a token passes through (the usual count; all parameters of a
    dense model, a family's ``active_params``) plus attention's
    6 * layers * heads * (head_dim + v_head_dim) * seq_len: scores over
    ``head_dim`` and values over ``v_head_dim``, 2 FLOPs each forward and 4
    backward, which is the usual 12 * layers * heads * head_dim * seq_len
    where the two sizes are one; the causal half is not discounted, the
    convention of the PaLM MFU. Recomputation (remat) is not counted."""
    dh = s["head_dim"]
    return (6.0 * s["active_params"] + 6.0 * s["layers"] * s["heads"]
            * (dh + s.get("v_head_dim", dh)) * seq_len)


def flash_train_work(s: Mapping[str, int], *, rows: int, seq_len: int):
    """(flops, bytes) that causal attention needs for ``rows`` sequences of
    ``seq_len`` through every layer, forward and backward, one pass each.

    Forward: QK^T and PV, 2 matmuls of 2*T*T*Dh per head, halved by the
    causal mask. Backward needs four such matmuls (dV, dP, dQ, dK); the
    kernel's recomputation of the scores is not needed work. Bytes: q, k, v
    read and o written forward; q, k, v, o, do read and dq, dk, dv written
    backward; each rows*T*Dh in bf16 for every query head (q, o, do, dq) or
    key-value head (k, v, dk, dv)."""
    h, hkv, dh, t = s["heads"], s["kv_heads"], s["head_dim"], seq_len
    one_matmul = 2.0 * rows * h * t * t * dh / 2.0
    flops = s["layers"] * (2 + 4) * one_matmul
    per_head = rows * t * dh * BF16
    nbytes = s["layers"] * (2 + 4) * (h + hkv) * per_head
    return flops, nbytes


def decode_attn_work(s: Mapping[str, int], *, context_lens: Sequence[int]):
    """(flops, bytes) of one decode step's attention over all layers: every
    active slot reads its context's cached rows once (bf16; a key and a value
    a key-value head, or the family's ``cache_row_dim``) and does 2*Dh FLOPs
    for the score and 2*Dv for the value per query head per cached token."""
    h, hkv, dh = s["heads"], s["kv_heads"], s["head_dim"]
    rows = float(sum(context_lens))
    flops = s["layers"] * rows * h * 2.0 * (dh + s.get("v_head_dim", dh))
    nbytes = s["layers"] * rows * s.get("cache_row_dim", 2 * hkv * dh) * BF16
    return flops, nbytes


def attended_rows(s: Mapping[str, int], upto: int) -> float:
    """Cached rows that the tokens at contexts 1..``upto`` of one sequence
    attended, summed over the tokens and over the attention layers
    (``attn_layers`` where a family's ``layers`` counts other mixers too).
    A token at context c attends c rows of a global layer. A family with a
    ``window`` states either ``sliding_layers`` and ``global_layers`` (the
    sliding ones attend min(c, window)) or, for every layer, an exact window
    with one pooled row per ``chunk`` tokens before it."""
    n, w = s.get("attn_layers", s["layers"]), s.get("window")
    full = upto * (upto + 1) / 2.0
    if w is None:
        return n * full
    past = max(upto - w, 0)
    near = full - past * (past + 1) / 2.0       # sum of min(c, w)
    if "sliding_layers" in s:
        return s["sliding_layers"] * near + s["global_layers"] * full
    pooled = past * (past + 1) / 2.0 / s["chunk"] if "chunk" in s else 0.0
    return n * (near + pooled)


def _serve_flops(s: Mapping[str, int], tokens: float, through_head: float,
                 rows: float) -> float:
    """2 FLOPs a parameter a token passes through (``active_params``, of
    which the LM head's ``vocab x hidden`` only where a position's logits
    were needed) plus attention's 2 * (head_dim + v_head_dim) a query head
    for every cached row attended. The embedding table of an untied model is
    counted though it is looked up (the usual count, as in training);
    recurrent mixers' state updates (under 1% of a layer's matmuls) are
    not, so the share can only read low by them."""
    dh = s["head_dim"]
    head = float(s["vocab"]) * s["hidden"]
    return (2.0 * (s["active_params"] - head) * tokens
            + 2.0 * head * through_head
            + 2.0 * s["heads"] * (dh + s.get("v_head_dim", dh)) * rows)


def decode_model_flops(obs: Mapping) -> float:
    """Model FLOPs of the tokens that decode steps committed inside the
    traced window of a ``serve_open_loop`` run: every token of a finished
    request but its first (the prefill's), by its commit stamp; the token at
    index i of a prompt of p attended p + i rows a layer."""
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    tokens, rows = 0, 0.0
    for r in obs["requests"]:
        inside = [i for i, t in enumerate(r["token_times"])
                  if i > 0 and lo <= t < hi]
        if inside:
            tokens += len(inside)
            rows += (attended_rows(s, r["prompt_len"] + inside[-1])
                     - attended_rows(s, r["prompt_len"] + inside[0] - 1))
    return _serve_flops(s, tokens, tokens, rows)


def prefill_model_flops(obs: Mapping) -> float:
    """Model FLOPs of the prompts admitted inside the traced window of a
    ``serve_open_loop`` run, each counted whole (as the prefill kernels'
    ``work`` files count it): causal attention over the prompt, the LM head
    at its last position alone."""
    lo, hi = obs["trace_span"]
    s = obs["shapes"]
    prompts = [r["prompt_len"] for r in obs["requests"]
               if lo <= r["admitted"] < hi]
    return _serve_flops(s, sum(prompts), len(prompts),
                        sum(attended_rows(s, p) for p in prompts))


def train_model_flops(obs: Mapping) -> float:
    """Model FLOPs one device's rows needed in the traced steps of a
    ``train_job`` run: ``train_flops_per_token`` for every token."""
    t = obs["train"]
    return (train_flops_per_token(obs["shapes"], t["seq_len"])
            * t["rows_per_device_step"] * t["seq_len"] * t["traced_steps"])


def roofline_seconds(flops: float, nbytes: float, peak: Mapping[str, float]):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / (peak["bf16_tflops"] * 1e12)
    t_m = nbytes / (peak["hbm_gbps"] * 1e9)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
