"""The feed-forward parts of a decoder with dense and sparse layers, as four
models here have them (``exaone_moe``, ``sarvam_mla``, ``solar_kda``,
``longcat_flash``): a dense SwiGLU, or the ``k`` routed experts a router
picks, of which the model HOLDS ``held = (first, count)`` (moe/grouped.py),
beside a shared expert where the layer has one. What is one model's
(attention, the cache, the stack's order) stays in its file; the leaves'
names, the layer's arithmetic and the counters a step returns are here, once.

    dense:  (silu(z Wg) * (z Wu)) Wd                      leaves ``w_*``
    sparse: Shared(z) + s * sum over the held of the chosen w_e Expert_e(z)
            leaves ``router``, ``select_bias``, ``shared_*``, ``expert_*``

What a configuration may state beyond the four keys every one has
(``num_experts_per_tok``, ``routed_scaling_factor``, ``norm_topk_prob``,
``held``): ``scoring_func`` "softmax" for moe/grouped.softmax_topk_route in
the sigmoid router's place; ``swiglu_limit``, a clamp on every gated MLP of
the layer, dense, shared and routed alike (``silu(min(z Wg, limit)) * clip(z
Wu, -limit, limit)``, moe/grouped.swiglu_gate: taken at trace time, so a
configuration without the key traces the operations it did); and
``zero_experts``, the router's last outputs
that are ZERO-COMPUTE identity experts: a chosen one returns the token itself,

    sparse += (s * sum over the chosen e >= E - zero_experts of w_e) z

computed where the token is, whatever share is held: like a shared expert it
is counted ONCE when the shares of a layer are added up. A layer without
``shared_*`` leaves has no shared expert.

A layer with ``latent_down`` and ``latent_up`` leaves is a LATENT expert layer
(Nemotron-H's LatentMoE): the router and the shared expert read the stream,
the routed experts work in a narrower latent,

    sparse: Shared(z) + Up(s * sum over the held of the chosen w_e Expert_e(Down(z)))

and ``Up`` is linear, so the shares of a layer still add up to the uncut one.
A layer without ``*_gate`` leaves has two-matrix experts ``relu(z W_up)^2
W_down`` (moe/grouped.relu2), the shared one included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import qdot
from deepspeed_tpu.moe.grouped import (held_experts, relu2, sigmoid_topk_route, softmax_topk_route, swiglu_gate,
                                       swiglu_up)

DENSE, SPARSE = "dense", "sparse"
# what a step counts on the device, in the order of the vector it returns
STEP_COUNTERS = ("moe_experts_touched", "moe_experts_streamed",
                 "moe_assignments_held", "moe_assignments",
                 "moe_assignments_zero")
# what a prompt block counts behind them (T > 1 alone: a decode step's vector
# is STEP_COUNTERS'): calls of a sparse layer, those whose pairs outgrew the
# compact sorted buffer, and those that held no pair (moe/grouped.held_experts)
PROMPT_COUNTERS = ("moe_prompt_blocks", "moe_prompt_blocks_spilled",
                   "moe_prompt_blocks_empty")
EXPERT_LEAVES = ("expert_gate", "expert_up", "expert_down")
# what a family may count behind STEP_COUNTERS in a step vector of its own
# (``ffn(..., buffer_counters=True)``): rows of the sorted buffer a decode step
# of the layer runs (its worst case, from shapes), and the experts it holds
BUFFER_COUNTERS = ("moe_buffer_rows", "moe_experts_held_steps")


def zero_counts(t: int, step: int = len(STEP_COUNTERS)):
    """The counters a walk over ``t`` positions a row starts from; ``step``:
    how many a decode step of the family counts."""
    return jnp.zeros((step + (len(PROMPT_COUNTERS) if t > 1 else 0),),
                     jnp.int32)


def carried_counts(cache, counts, step: int = len(STEP_COUNTERS)) -> dict:
    """A walk's counters as the cache it returns carries them:
    ``step_counters`` (the family's first ``step``: STEP_COUNTERS'), and for
    a caller that asked by handing ``cache["prompt_counts"]`` in (a serving
    prefill program; a cache that is a loop's carry keeps its keys) a
    prompt's PROMPT_COUNTERS added to those."""
    out = {"step_counters": counts[:step] if counts.shape[0] > step
           else counts}
    if "prompt_counts" in cache:
        out["prompt_counts"] = cache["prompt_counts"] + counts[step:]
    return out


def record_step_counters(telemetry, counts) -> None:
    """A decode step's ``step_counters`` vector, fetched with the tokens,
    into the registry: held experts that got a token and held experts whose
    weights were read, summed over the sparse layers; (token, expert) pairs
    routed here, all pairs of the step, and those of them that went to
    zero-compute experts."""
    touched, streamed, held, pairs, zero = (int(n) for n in counts)
    telemetry.counter("serving/moe_experts_touched").inc(touched)
    telemetry.counter("serving/moe_experts_streamed").inc(streamed)
    telemetry.counter("serving/moe_assignments_held").inc(held)
    telemetry.counter("serving/moe_assignments").inc(pairs)
    telemetry.counter("serving/moe_assignments_zero").inc(zero)


def record_prompt_counters(telemetry, counts) -> None:
    """The ``prompt_counts`` of the prefill programs a prompt took (``[
    programs, 3]``, fetched with its first token), into the registry: token
    blocks through a sparse layer, those whose pairs outgrew the compact
    sorted buffer, and those that held no pair."""
    blocks, spilled, empty = (int(n) for n in sum(counts))
    telemetry.counter("serving/moe_prompt_blocks").inc(blocks)
    telemetry.counter("serving/moe_prompt_blocks_spilled").inc(spilled)
    telemetry.counter("serving/moe_prompt_blocks_empty").inc(empty)


def gated_init(init, keys, lead, d: int, width: int, prefix: str, dtype,
               out_scale: float):
    """The three matrices of a gated MLP under ``prefix`` with leading
    dimensions ``lead`` (layers, or layers and held experts)."""
    return {prefix + "gate": init(keys[0], lead + (d, width), dtype),
            prefix + "up": init(keys[1], lead + (d, width), dtype),
            prefix + "down": init(keys[2], lead + (width, d), dtype)
            * out_scale}


def gated_axes(prefix: str, *lead):
    return {prefix + "gate": ("layer", *lead, "hidden", "mlp"),
            prefix + "up": ("layer", *lead, "hidden", "mlp"),
            prefix + "down": ("layer", *lead, "mlp", "hidden")}


def count_latent() -> None:
    """Say in the program's registry that a latent expert layer was traced
    (``moe/traced_latent``)."""
    from deepspeed_tpu.telemetry.registry import get_registry

    get_registry().counter("moe/traced_latent").inc()


def ffn(z, blk, kind: str, valid, c, buffer_counters: bool = False):
    """-> ``(FFN(z), counts int32 as zero_counts(T))``; ``z [B, T, d]``;
    ``valid [B, T]`` bool or None; ``c`` the model's configuration
    (``num_experts_per_tok``, ``routed_scaling_factor``, ``norm_topk_prob``,
    ``held``; optional ``scoring_func``, ``zero_experts``).
    ``buffer_counters``: BUFFER_COUNTERS follow STEP_COUNTERS in the vector
    (``zero_counts(T, 7)``).

    ``T`` says what is walked: a decode step (``T == 1``) hands no width on
    and is, on the chip, the one call that streams each touched expert once
    for all ``B`` rows (ops/moe_experts.py; elsewhere the sorted buffer's
    worst case, ``B * min(k, held)`` rows); a prompt block hands the router's
    width on, and its buffer follows the pairs held
    (moe/grouped.held_experts)."""

    limit = getattr(c, "swiglu_limit", None)

    def gated(prefix):
        if prefix + "gate" not in blk:
            return qdot("btm,md->btd", relu2(qdot("btd,dm->btm", z,
                                                  blk[prefix + "up"])),
                        blk[prefix + "down"])
        gate = swiglu_gate(qdot("btd,dm->btm", z, blk[prefix + "gate"]), limit)
        return qdot("btm,md->btd", gate * swiglu_up(
            qdot("btd,dm->btm", z, blk[prefix + "up"]), limit),
            blk[prefix + "down"])

    b, t, d = z.shape
    if kind == DENSE:
        return gated("w_"), zero_counts(t)
    flat = z.reshape(b * t, d)
    live = None if valid is None else valid.reshape(b * t)
    route = softmax_topk_route \
        if getattr(c, "scoring_func", "sigmoid") == "softmax" \
        else functools.partial(sigmoid_topk_route, normalize=c.norm_topk_prob)
    routing = route(flat, blk["router"], blk["select_bias"],
                    c.num_experts_per_tok, scale=c.routed_scaling_factor)
    latent = "latent_down" in blk
    inner = flat
    if latent:
        count_latent()
        with jax.named_scope("dstpu_moe_latent_down"):
            inner = qdot("nd,dl->nl", flat, blk["latent_down"])
    y, counts = held_experts(
        inner, routing, blk.get("expert_gate"), blk["expert_up"],
        blk["expert_down"], c.held, valid=live,
        n_experts=blk["router"].shape[-1] if t > 1 else None, limit=limit)
    if latent:
        with jax.named_scope("dstpu_moe_latent_up"):
            y = qdot("nl,ld->nd", y, blk["latent_up"])
    zero = jnp.zeros((), jnp.int32)
    if getattr(c, "zero_experts", 0):
        # the identity experts are the router's last outputs: a chosen one
        # adds its weight times the token, where the token is
        chosen = routing.experts >= blk["router"].shape[-1] - c.zero_experts
        if live is not None:
            chosen &= live[:, None]
        y = y + (jnp.where(chosen, routing.weights, 0.0).sum(-1, keepdims=True)
                 * flat.astype(jnp.float32)).astype(y.dtype)
        zero = chosen.sum().astype(jnp.int32)
    y = y.reshape(b, t, d)
    if "shared_up" in blk:
        y = gated("shared_") + y
    step = (counts.touched, counts.streamed, counts.assignments_held,
            counts.assignments, zero)
    if buffer_counters:
        rows = flat.shape[0] * min(c.num_experts_per_tok, c.held[1])
        step += (jnp.asarray(rows, jnp.int32),
                 jnp.asarray(c.held[1], jnp.int32))
    if t > 1:       # PROMPT_COUNTERS
        step += (jnp.ones((), jnp.int32), counts.spilled,
                 (counts.assignments_held == 0).astype(jnp.int32))
    return y, jnp.stack(step)
