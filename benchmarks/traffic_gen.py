"""The one general traffic generator. A traffic mix is a data file of
parameters; this module turns it and ``--seed`` into requests or batches.

Copied in idea from ``deepspeed_tpu/serving/scheduler.py`` (``poisson_trace``,
``bursty_poisson_trace``, ``shared_prefix_trace``) and given length
distributions, so that a later PR to the program cannot move the traffic.

Steadiness rule: the *schedule* of a run (how many requests, each one's
arrival time, prompt length and output length) is drawn from the mix's own
``shape_seed`` and is the same for every ``--seed``. ``--seed`` draws the
token contents (and, in the kinds, the weights). Queueing tails follow which
long prompts meet: reordering the schedule by seed moved the 95th percentile
of time to first token by 12% to a factor of eight (PERF.md, PR 25).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Sequence

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    """One request as the generator plans it; a kind's runner turns it into
    the program's own request type."""

    rid: int
    arrival_time: float
    prompt: List[int]
    max_new_tokens: int


def fold_seed(seed: int, salt: int = 0) -> int:
    """Any whole number (the driver's seeds pass 2**31) to a 31-bit seed that
    ``numpy.random.RandomState`` and ``jax.random.PRNGKey`` both take."""
    state = np.random.SeedSequence([int(seed) & (2**64 - 1), salt])
    return int(state.generate_state(1)[0]) & 0x7FFFFFFF


def draw_lengths(rng: np.random.RandomState, spec: Mapping, n: int
                 ) -> np.ndarray:
    """``n`` whole lengths from a distribution spec, clipped to [min, max].

    ``{"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 16, "max": 768}``
    ``{"dist": "uniform", "min": 32, "max": 256}`` (inclusive)
    ``{"dist": "choice", "values": [24, 100, 200]}``
    ``{"dist": "fixed", "value": 384}``
    """
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(mean=np.log(spec["median"]), sigma=spec["sigma"],
                          size=n)
    elif dist == "uniform":
        x = rng.randint(spec["min"], spec["max"] + 1, size=n)
    elif dist == "choice":
        x = rng.choice(np.asarray(spec["values"]), size=n)
    elif dist == "fixed":
        x = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    x = np.rint(x).astype(np.int64)
    if "min" in spec:
        x = np.maximum(x, spec["min"])
    if "max" in spec:
        x = np.minimum(x, spec["max"])
    return x


def burst_gaps(rng: np.random.RandomState, n_bursts: int, seconds: float
               ) -> np.ndarray:
    """Gaps before each of ``n_bursts`` Poisson arrivals inside (0, seconds):
    exponential draws scaled so that the last arrival lands before the window
    closes, which keeps the count of requests due in the window fixed."""
    gaps = rng.exponential(1.0, size=n_bursts + 1)
    return gaps[:-1] / gaps.sum() * seconds


def open_loop_requests(params: Mapping, *, seed: int, seconds: float,
                       vocab_size: int) -> List[PlannedRequest]:
    """Requests due in a window of ``seconds`` under an open loop.

    ``params``: ``rate`` (requests per second), ``prompt`` and ``output``
    (length specs; with a shared prefix, ``prompt`` is the unique suffix),
    ``max_total`` (prompt + output may not pass it; the output is cut),
    optional ``burst_size``, optional ``shared_prefix``
    ``{"count": 4, "len": 384}``, ``shape_seed``.
    """
    n = max(1, int(round(params["rate"] * seconds)))
    shape = np.random.RandomState(fold_seed(params["shape_seed"], 1))
    prompts = draw_lengths(shape, params["prompt"], n)
    outputs = draw_lengths(shape, params["output"], n)
    burst = params.get("burst_size", 1)
    gaps = burst_gaps(shape, -(-n // burst), seconds)
    prefix = params.get("shared_prefix")
    plen = prefix["len"] if prefix else 0
    outputs = np.minimum(outputs, params["max_total"] - plen - prompts)
    if (outputs < 1).any():
        raise ValueError("max_total leaves a request no output token")

    # a burst's requests land together
    times = np.repeat(np.cumsum(gaps), burst)[:n]
    content = np.random.RandomState(fold_seed(seed, 3))
    prefixes = [content.randint(0, vocab_size, size=plen).tolist()
                for _ in range(prefix["count"])] if prefix else []
    out: List[PlannedRequest] = []
    for rid in range(n):
        body = content.randint(0, vocab_size, size=int(prompts[rid])).tolist()
        head = prefixes[rid % len(prefixes)] if prefix else []
        out.append(PlannedRequest(
            rid=rid, arrival_time=float(times[rid]), prompt=head + body,
            max_new_tokens=int(outputs[rid])))
    return out


def arith_rows(rng: np.random.RandomState, vocab_size: int,
               shape: Sequence[int]) -> Dict[str, np.ndarray]:
    """Learnable rows, as ``chip_smoke._arith_batch``: row r is
    ``start_r + stride_r * t`` mod the vocabulary. Random tokens would sit at
    ln(V) whatever the model does."""
    *lead, t = shape
    start = rng.randint(0, vocab_size, size=(*lead, 1))
    stride = rng.randint(1, 8, size=(*lead, 1))
    ids = ((start + stride * np.arange(t + 1)) % vocab_size).astype(np.int32)
    return {"input_ids": ids[..., :-1], "labels": ids[..., 1:]}
