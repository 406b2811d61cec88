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


def roofline_seconds(flops: float, nbytes: float, peak: Mapping[str, float]):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / (peak["bf16_tflops"] * 1e12)
    t_m = nbytes / (peak["hbm_gbps"] * 1e9)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
