"""The feed-forward half of a decoder with a leading dense layer and sparse
layers behind it, as two models here have it (``exaone_moe``, ``sarvam_mla``):
a dense SwiGLU, or a shared expert plus the ``k`` routed experts a sigmoid
router picks, of which the model HOLDS ``held = (first, count)``
(moe/grouped.py). What is one model's (attention, the cache, the stack's
order) stays in its file; the leaves' names, the layer's arithmetic and the
counters a step returns are here, once.

    dense:  (silu(z Wg) * (z Wu)) Wd                      leaves ``w_*``
    sparse: Shared(z) + s * sum over the held of the chosen w_e Expert_e(z)
            leaves ``router``, ``select_bias``, ``shared_*``, ``expert_*``
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.models.base import qdot
from deepspeed_tpu.moe.grouped import held_experts, sigmoid_topk_route

DENSE, SPARSE = "dense", "sparse"
# what a step counts on the device, in the order of the vector it returns
STEP_COUNTERS = ("moe_experts_touched", "moe_experts_streamed",
                 "moe_assignments_held", "moe_assignments")
EXPERT_LEAVES = ("expert_gate", "expert_up", "expert_down")


def record_step_counters(telemetry, counts) -> None:
    """A decode step's ``step_counters`` vector, fetched with the tokens,
    into the registry: held experts that got a token and held experts whose
    weights were read, summed over the sparse layers; (token, expert) pairs
    routed here and all pairs of the step."""
    touched, streamed, held, pairs = (int(n) for n in counts)
    telemetry.counter("serving/moe_experts_touched").inc(touched)
    telemetry.counter("serving/moe_experts_streamed").inc(streamed)
    telemetry.counter("serving/moe_assignments_held").inc(held)
    telemetry.counter("serving/moe_assignments").inc(pairs)


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


def ffn(z, blk, kind: str, valid, c):
    """-> ``(FFN(z), counts [4] int32)``; ``z [B, T, d]``; ``valid [B, T]``
    bool or None; ``c`` the model's configuration (``num_experts_per_tok``,
    ``routed_scaling_factor``, ``norm_topk_prob``, ``held``)."""

    def gated(prefix):
        gate = jax.nn.silu(qdot("btd,dm->btm", z, blk[prefix + "gate"]))
        return qdot("btm,md->btd", gate * qdot("btd,dm->btm", z,
                                               blk[prefix + "up"]),
                    blk[prefix + "down"])

    if kind == DENSE:
        return gated("w_"), jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    b, t, d = z.shape
    flat = z.reshape(b * t, d)
    routing = sigmoid_topk_route(
        flat, blk["router"], blk["select_bias"], c.num_experts_per_tok,
        scale=c.routed_scaling_factor, normalize=c.norm_topk_prob)
    routed, counts = held_experts(
        flat, routing, blk["expert_gate"], blk["expert_up"],
        blk["expert_down"], c.held,
        valid=None if valid is None else valid.reshape(b * t))
    return gated("shared_") + routed.reshape(b, t, d), jnp.stack(counts)
