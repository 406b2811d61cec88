"""A routed expert layer that is told which experts it holds.

The DeepSeek-V3 style router that today's large open models use (sigmoid
scores, the ``k`` largest after a selection bias, weights normalised over the
chosen, a scaling factor) and a dispatch with no capacity and no drop: the
(token, expert) pairs routed to the experts held HERE reach their experts
one of two ways. A PROMPT block's are sorted by expert into contiguous groups
and each group is multiplied by its expert (``jax.lax.ragged_dot``: on a TPU
a grouped-matmul kernel of XLA's own that visits only the row tiles the
groups fill). A DECODE step's, one token a slot, are not sorted at all: on
the chip the step is the one Pallas call ``dstpu_moe_experts_decode``
(ops/moe_experts.py), which streams each expert that got a pair once and
multiplies all the step's rows by it, weighed by the token's weight where it
chose the expert and 0 where it did not; off the chip, and for more rows than
ride free under a streamed matrix, it is the sorted buffer's worst case and
``ragged_dot`` (:func:`ops.moe_experts.default_route`). Either way an expert
no token chose is neither multiplied nor read.

``held=(first, count)`` is one chip's share of an expert-parallel layer: the
router, the choice of ``k`` and the normalisation run over ALL experts, the
sum runs over the chosen experts in ``[first, first + count)``, and what the
other chips' experts would add is left out. A pair whose expert lies outside
every share of the real experts (a ZERO-COMPUTE expert, numbered behind them:
:func:`softmax_topk_route`, models/moe_ffn.py) gets no row on any chip. The
exchange between chips is not
here (ROADMAP R2); summed over the shares ``(0, c), (c, c), ...`` the results
give the uncut layer (tests/unit/inference/test_exaone_moe.py).

A step's time follows what it touches: a decode step's 5 to 12 tokens leave
about half of 16 held experts without one and the step reads the others
only, at what their bytes cost (PERF.md, PR 67). With weights drawn from a
seed the share of pairs routed here is the seed's (10.5% to 14.1% over 12
seeds for the expected 12.5%), and the decode gap moves with it (PERF.md, PR
35, which also tried a decode step that multiplies every held expert: 4 ms a
step slower, and no steadier; the benchmark's cell serves one checkpoint for
that reason).

Shapes are static: a token's ``k`` experts are distinct, so at most
``min(k, count)`` of its pairs are held, and a sorted buffer of
``N * min(k, count)`` rows holds them whatever the routing; rows behind the
last group are masked. A prompt block is routed ``k * count / E`` pairs a
token on average, an eighth or a forty-eighth of the worst case, and everything
computed a row would run over the rest for nothing: its buffer is one of two
static sizes, chosen on the device by the pairs it holds
(:func:`held_experts`, :func:`compact_rows`): twice the expectation, or the
worst case when a router crowds this chip, so that no pair is ever dropped; a
block that is all padding passes the layer by. ``TopKGate`` / ``top1gating``
/ ``top2gating`` (sharded_moe.py) stay the capacity-einsum dispatch of
``gpt_moe``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import moe_experts


class Routing(NamedTuple):
    experts: jax.Array     # [N, k] int32: the chosen experts, of all of them
    weights: jax.Array     # [N, k] float32: normalised, scaled


def sigmoid_topk_route(x, w_router, select_bias, k: int, *,
                       scale: float = 1.0, normalize: bool = True) -> Routing:
    """``x [N, d]`` against ``w_router [d, E]``: ``sigma = sigmoid(x W)`` in
    float32 over all ``E``; the ``k`` experts with the largest ``sigma +
    select_bias`` (the bias picks and does not weigh); ``w = sigma_chosen /
    (their sum + 1e-20)`` if ``normalize``; times ``scale``."""
    sigma = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, experts = jax.lax.top_k(sigma + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(sigma, experts, axis=-1)
    if normalize:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return Routing(experts.astype(jnp.int32), w * scale)


def softmax_topk_route(x, w_router, select_bias, k: int, *,
                       scale: float = 1.0) -> Routing:
    """The router of a layer whose outputs are probabilities (LongCat-Flash):
    ``p = softmax(x W)`` in float32 over all ``E`` outputs, zero-compute
    experts included; the ``k`` with the largest ``p + select_bias`` (the bias
    picks and does not weigh); ``w = scale * p_chosen``, NOT normalised over
    the chosen."""
    p = jax.nn.softmax(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, experts = jax.lax.top_k(p + select_bias.astype(jnp.float32), k)
    w = jnp.take_along_axis(p, experts, axis=-1)
    return Routing(experts.astype(jnp.int32), w * scale)


class ExpertCounts(NamedTuple):
    """What a call did, counted on the device (int32 scalars)."""
    touched: jax.Array           # held experts that got at least one pair
    streamed: jax.Array          # held experts whose weights the call read:
    #                              the touched ones, by either route
    assignments_held: jax.Array  # pairs routed to held experts
    assignments: jax.Array       # all pairs of the valid tokens
    spilled: jax.Array           # 1: a prompt block's pairs outgrew the
    #                              compact buffer and the full one ran


# what XLA's grouped matmul makes its row tiles of (ROADMAP S11): a prompt
# block's compact buffer is whole tiles (a decode step on the chip has no
# buffer: ops/moe_experts.py)
ROW_TILE = 128


def swiglu_gate(gate, limit: Optional[float] = None):
    """A gated MLP's ``silu(gate)``; where a configuration states a
    ``swiglu_limit``, ``silu(min(gate, limit))``. The clamp is taken at trace
    time: without a limit the operations are the ones they were."""
    return jax.nn.silu(gate if limit is None else jnp.minimum(gate, limit))


def swiglu_up(up, limit: Optional[float] = None):
    """A gated MLP's linear half, clamped to ``[-limit, limit]`` where a
    configuration states a ``swiglu_limit`` (:func:`swiglu_gate`)."""
    return up if limit is None else jnp.clip(up, -limit, limit)


def relu2(x):
    """``relu(x) ** 2``: the activation of an ungated expert (Nemotron-H's
    ``relu2``)."""
    return jnp.square(jax.nn.relu(x))


def compact_rows(n: int, k: int, count: int, n_experts: int) -> int:
    """The rows of a prompt block's compact sorted buffer: twice the pairs
    ``n`` tokens send to ``count`` of ``n_experts`` experts when the router
    spreads them evenly (``n * k * count / n_experts``; about 48 standard
    deviations of a binomial at 2,048 tokens an eighth held), in whole row
    tiles. 4,096 of 16,384 rows at 2,048 tokens, ``k`` 8, an eighth held;
    1,024 of 24,576 at ``k`` 12, 16 of 768 held."""
    twice = -(-2 * n * k * count // n_experts)
    return -(-twice // ROW_TILE) * ROW_TILE


# the two ways an expert layer of each phase is traced
TRACED = {"prompt": ("full", "compact"), "decode": ("grouped", "fused")}


def count_traced(phase: str, second: bool) -> None:
    """Say in the program's registry how an expert layer was traced. A
    prompt's: ``moe/traced_prompt_compact`` (the three-way switch of
    :func:`held_experts`) or ``moe/traced_prompt_full`` (the worst-case
    buffer alone: a block so short that its compact buffer would be no
    smaller). A decode step's: ``moe/traced_decode_fused`` (the one call of
    ops/moe_experts.py) or ``moe/traced_decode_grouped`` (the sorted buffer
    and ``ragged_dot``). A phase's two exist from its first call on."""
    from deepspeed_tpu.telemetry.registry import get_registry

    reg = get_registry()
    counters = [reg.counter(f"moe/traced_{phase}_{n}") for n in TRACED[phase]]
    counters[bool(second)].inc()


def held_experts(x, routing: Routing, w_gate, w_up, w_down,
                 held: Tuple[int, int], *, valid: Optional[jax.Array] = None,
                 n_experts: Optional[int] = None,
                 limit: Optional[float] = None):
    """Sum over the chosen experts held here of ``w_e * Expert_e(x)``, each a
    gated MLP ``(silu(x Wg) * (x Wu)) Wd``; with ``limit`` (a configuration's
    ``swiglu_limit``) the gate's input clamped from above and the linear half
    to ``[-limit, limit]`` (:func:`swiglu_gate`, :func:`swiglu_up`). With
    ``w_gate`` ``None`` an expert is the two-matrix ``relu(x Wu)^2 Wd``
    (:func:`relu2`; the same routes, groups and counts; two matrices for
    three).

    ``x [N, d]``; ``w_gate, w_up [count, d, m]``, ``w_down [count, m, d]``:
    the weights of experts ``first .. first + count - 1``, or each a
    layer-stacked ``{"__whole__": [L, count, ..], "__layer__": i}``
    (models/base.layer_view): the stack is then the grouped matmul's
    operand as it lies, layer ``i``'s experts being groups ``i * count ..``
    of ``L * count`` with every other group empty: a slice of it would be a
    copy of the layer's experts (1.2 GB at 16 x 3 x 6144 x 2048) every step.
    ``valid [N]`` (bool): tokens that are real (bucket padding and slots that
    do not decode get no pair, touch no expert and come out zero).

    ``n_experts``: the router's width, which the caller states for a PROMPT
    block (``models/moe_ffn.ffn`` knows ``T``; this function sees ``N``
    alone, and a decode step of many slots is as long as a short prompt). It
    is what lets the sorted buffer follow the pairs held: their number is on
    the device before any row-sized work, and ``lax.switch`` picks by it

    - empty (no pair held: a token block that is all padding): zeros; no
      gather, no grouped matmul, no combine;
    - compact (at most :func:`compact_rows` pairs): the buffer, and all that
      is computed a row of it, has that many rows;
    - full (more: a router that crowds this chip; ``ExpertCounts.spilled``):
      the worst case, ``N * min(k, count)`` rows.

    A row's arithmetic, the order of the sum over a token's ``k`` and the
    three grouped matmuls against the whole stacks are the same in the
    compact and the full branch and the buffer holds every held pair in
    both, so the result is the same bit for bit whichever runs: no pair is
    dropped, there is no capacity. Where the compact buffer would be no
    smaller than the full one there is no switch and the full buffer alone.

    Without ``n_experts`` the call is a DECODE step and holds no conditional.
    Where :func:`ops.moe_experts.default_route` says ``"fused"`` (a TPU, rows
    that ride free under a streamed matrix, widths in whole rows of lanes:
    every cell's step) it is ONE Pallas call, ``dstpu_moe_experts_decode``:
    no sort, no buffer, the stacks as they lie with the layer in the DMA's
    index, each touched expert streamed once against all ``N`` rows into a
    float32 sum weighed by ``weights[n, e]``, selected where the token chose
    the expert. The sum then runs in expert order and an expert's result is
    not rounded before it is weighed: rounding alone tells it from the other
    route (``"grouped"``: the full buffer through ``ragged_dot``, weighed a
    row in float32), which runs off the chip and for more rows. The fused
    call serves only: it has no VJP and sits in no ``shard_map``, so a step
    on the chip that is differentiated, or sharded over a mesh, has no route
    here yet (no cell does either). Which way a step was traced:
    ``moe/traced_decode_fused`` / ``_grouped``.
    -> ``(y [N, d], ExpertCounts)``."""
    first, count = held
    n, k = routing.experts.shape
    full = n * min(k, count)
    local = routing.experts - first
    here = (local >= 0) & (local < count)
    if valid is not None:
        here &= valid[:, None]
    prompt = n_experts is not None
    whole = w_up["__whole__"] if isinstance(w_up, dict) else w_up
    fused = not prompt and moe_experts.default_route(
        n, *whole.shape[-2:]) == "fused"
    if not prompt:
        count_traced("decode", fused)
    # pairs of held experts first, by expert; the rest behind them (the
    # fused step sorts nothing)
    key = jnp.where(here, local, count).reshape(-1)
    by_expert = None if fused else jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0)
    total = sizes.sum()

    def streamed():
        """The one call a decode step is on the chip: every touched
        expert against all ``n`` rows, weighed by the token's weight where
        it chose the expert and 0 where it did not."""
        chose = here[:, :, None] & (
            local[:, :, None] == jnp.arange(count, dtype=local.dtype))
        weights = jnp.where(chose, routing.weights[:, :, None], 0.0).sum(1)
        # the three are stacked together or not at all
        layer = w_up["__layer__"] if isinstance(w_up, dict) else None
        gate, up, down = (w["__whole__"] if isinstance(w, dict) else w
                          for w in (w_gate, w_up, w_down))
        act = relu2 if w_gate is None else \
            lambda g, u: swiglu_gate(g, limit) * swiglu_up(u, limit)
        return moe_experts.experts_decode(x, weights, sizes, gate, up, down,
                                          layer, act=act)

    def grouped(lhs, w):
        groups = sizes
        if isinstance(w, dict):
            w, layer = w["__whole__"], w["__layer__"]
            groups = jax.lax.dynamic_update_slice(
                jnp.zeros((w.shape[0] * count,), sizes.dtype), sizes,
                (layer * count,))
            w = w.reshape((-1,) + w.shape[2:])
        assert w.shape[0] == groups.shape[0], (w.shape, held)
        return jax.lax.ragged_dot(lhs, w.astype(lhs.dtype), groups)

    def experts(rows: int, prompt: bool):
        """The layer through a sorted buffer of ``rows`` rows, which must
        hold every held pair. A decode step weighs the buffer's rows and
        gathers them in float32; a prompt block gathers what the last matmul
        wrote and weighs at the pair, in the reduction: the same products
        summed in the same order, and two float32 passes over the buffer and
        half the gather's bytes less."""
        order = by_expert[:rows]
        token = order // k
        with jax.named_scope("dstpu_moe_experts"):
            xs = x[token]
            h = relu2(grouped(xs, w_up)) if w_gate is None else \
                swiglu_gate(grouped(xs, w_gate), limit) \
                * swiglu_up(grouped(xs, w_up), limit)
            ys = grouped(h, w_down)
        if not prompt:
            w = routing.weights.reshape(-1)[order]
            # rows behind the last group hold whatever the kernel left there
            ys = jnp.where((jnp.arange(rows) < total)[:, None],
                           ys.astype(jnp.float32) * w[:, None], 0.0)
        # back to tokens: a pair's row in the sorted buffer, then the k of a
        # token summed (a gather; a scatter-add of the rows serialises on a
        # TPU)
        rank = jnp.zeros((n * k,), jnp.int32).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        picked = ys[jnp.minimum(rank, rows - 1)]
        if prompt:
            # a held pair's row lies before the last group's end
            picked = picked.astype(jnp.float32) \
                * routing.weights.reshape(-1)[:, None]
        picked = jnp.where(here.reshape(-1)[:, None], picked, 0.0)
        return picked.reshape(n, k, -1).sum(1).astype(x.dtype)

    compact = compact_rows(n, k, count, n_experts) if prompt else full
    if prompt:
        count_traced("prompt", compact < full)
    if fused:
        spilled = jnp.zeros((), jnp.int32)
        y = streamed()
    elif compact < full:
        spilled = (total > compact).astype(jnp.int32)
        y = jax.lax.switch(
            (total > 0).astype(jnp.int32) + spilled,
            (lambda: jnp.zeros_like(x), lambda: experts(compact, True),
             lambda: experts(full, True)))
    else:
        spilled = jnp.zeros((), jnp.int32)
        y = experts(full, prompt)

    n_valid = n if valid is None else valid.sum()
    counts = ExpertCounts(
        touched=(sizes > 0).sum().astype(jnp.int32),
        # the fused step walks the touched experts alone; XLA's TPU kernel
        # for ragged_dot makes row tiles for filled groups only, so an
        # expert without a pair is not read either (measured, PERF.md PR
        # 35); an implementation that multiplies every held expert counts
        # ``count`` here
        streamed=(sizes > 0).sum().astype(jnp.int32),
        assignments_held=total.astype(jnp.int32),
        assignments=jnp.asarray(n_valid * k, jnp.int32),
        spilled=spilled)
    return y, counts
