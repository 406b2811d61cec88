"""Operations and bytes from shapes. Every function here is arithmetic on the
sizes a configuration file and a traffic file state; nothing is measured.

``shapes`` is the dict a family's ``shapes()`` returns: layers, hidden, heads,
head_dim, mlp, vocab, positions.
"""
from __future__ import annotations

from typing import Mapping, Sequence

BF16 = 2  # bytes


def total_params(s: Mapping[str, int]) -> int:
    """All parameters of a GPT-2 style decoder with a tied embedding."""
    d, m = s["hidden"], s["mlp"]
    per_layer = 4 * d * d + 2 * d * m + (3 * d + d + m + d) + 4 * d
    return (s["vocab"] * d + s["positions"] * d + s["layers"] * per_layer
            + 2 * d)


def train_flops_per_token(s: Mapping[str, int], seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per
    parameter (the usual count, all parameters) plus attention's
    12 * layers * hidden * seq_len (scores and values, forward 4, backward 8;
    the causal half is not discounted, the convention of the PaLM MFU).
    Recomputation (remat) is not counted."""
    return 6.0 * total_params(s) + 12.0 * s["layers"] * s["hidden"] * seq_len


def flash_train_work(s: Mapping[str, int], *, rows: int, seq_len: int):
    """(flops, bytes) that causal attention needs for ``rows`` sequences of
    ``seq_len`` through every layer, forward and backward, one pass each.

    Forward: QK^T and PV, 2 matmuls of 2*T*T*Dh per head, halved by the
    causal mask. Backward needs four such matmuls (dV, dP, dQ, dK); the
    kernel's recomputation of the scores is not needed work. Bytes: q, k, v
    read and o written forward; q, k, v, o, do read and dq, dk, dv written
    backward; each rows*T*heads*Dh in bf16."""
    h, dh, t = s["heads"], s["head_dim"], seq_len
    one_matmul = 2.0 * rows * h * t * t * dh / 2.0
    flops = s["layers"] * (2 + 4) * one_matmul
    tensor = rows * t * h * dh * BF16
    nbytes = s["layers"] * (4 + 8) * tensor
    return flops, nbytes


def decode_attn_work(s: Mapping[str, int], *, context_lens: Sequence[int]):
    """(flops, bytes) of one decode step's attention over all layers: every
    active slot reads its context's keys and values once (bf16) and does
    2*2*Dh FLOPs per head per cached token."""
    h, dh = s["heads"], s["head_dim"]
    rows = float(sum(context_lens))
    flops = s["layers"] * rows * h * 4.0 * dh
    nbytes = s["layers"] * rows * h * dh * 2 * BF16
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peak: Mapping[str, float]):
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / (peak["bf16_tflops"] * 1e12)
    t_m = nbytes / (peak["hbm_gbps"] * 1e9)
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
