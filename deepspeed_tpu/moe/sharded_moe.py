"""MoE gating + dispatch math.

TPU-native re-derivation of the reference's gating
(``deepspeed/moe/sharded_moe.py``: top1gating:179, top2gating:277,
TopKGate:343, MOELayer:473). Same semantics — softmax gate, capacity-factor
truncation, load-balancing aux loss, optional second expert — expressed as
static-shape einsums (SURVEY §7 hard-part #3: routing must stay static-shaped
to avoid recompiles; capacity padding + drop does that here exactly as in the
reference).

Dispatch/combine use the GShard formulation:
    dispatched[e,c,m] = Σ_s dispatch_mask[s,e,c] · x[s,m]
    out[s,m]         = Σ_{e,c} combine_weights[s,e,c] · expert_out[e,c,m]
With the token dim sharded over the batch axes and the expert dim sharded
over the 'expert' mesh axis, XLA lowers the dispatch einsum to the
all-to-all over ICI that the reference issues manually via its _AllToAll
autograd function (sharded_moe.py:90).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def _one_hot(x, num_classes, dtype=jnp.float32):
    return jax.nn.one_hot(x, num_classes, dtype=dtype)


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int) -> int:
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def top1gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None, drop_tokens: bool = True,
               used_capacity: int = 0):
    """Top-1 gating (reference top1gating, sharded_moe.py:179).

    logits: [S, E]. Returns (l_aux, combine_weights [S,E,C], dispatch_mask
    [S,E,C] bool, exp_counts [E]).
    """
    s, e = logits.shape
    c = _capacity(s, e, capacity_factor, min_capacity) if drop_tokens else s

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    if noisy_gate_policy == "RSample" and rng is not None:
        noisy = logits + jax.random.gumbel(rng, logits.shape, dtype=logits.dtype)
        indices1 = jnp.argmax(noisy, axis=-1)
    else:
        indices1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(indices1, e)  # [S, E]

    # load-balancing aux loss (Switch/GShard): E * Σ_e mean(gates_e)·mean(mask_e)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    # position of each token within its chosen expert's capacity buffer
    locations1 = jnp.cumsum(mask1, axis=0) - mask1  # [S, E]
    mask1 = mask1 * (locations1 < c)
    exp_counts = jnp.sum(mask1, axis=0).astype(jnp.int32)

    gates1 = jnp.sum(gates * mask1, axis=-1)  # [S] gate value of kept tokens
    locations1_s = jnp.sum(locations1 * mask1, axis=-1).astype(jnp.int32)  # [S]

    combine = (gates1[:, None, None] * mask1[:, :, None] *
               _one_hot(locations1_s, c)[:, None, :])  # [S, E, C]
    dispatch = combine.astype(bool)
    return l_aux, combine, dispatch, exp_counts


def top2gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, rng: Optional[jax.Array] = None,
               drop_tokens: bool = True):
    """Top-2 gating (reference top2gating, sharded_moe.py:277): second expert
    chosen after masking the first; weights renormalised over the kept pair."""
    s, e = logits.shape
    c = _capacity(s, e, capacity_factor * 2.0, min_capacity) if drop_tokens else s

    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    indices1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot(indices1, e)
    logits_w_noise = logits.astype(jnp.float32)
    if rng is not None:
        logits_w_noise = logits_w_noise + jax.random.gumbel(rng, logits.shape)
    logits2 = jnp.where(mask1.astype(bool), -jnp.inf, logits_w_noise)
    indices2 = jnp.argmax(logits2, axis=-1)
    mask2 = _one_hot(indices2, e)

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    l_aux = jnp.sum(me * ce) * e

    locations1 = jnp.cumsum(mask1, axis=0) - mask1
    # second-expert positions come after all first-expert tokens
    locations2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0, keepdims=True)
    mask1 = mask1 * (locations1 < c)
    mask2 = mask2 * (locations2 < c)
    exp_counts = jnp.sum(mask1 + mask2, axis=0).astype(jnp.int32)

    locations1_s = jnp.sum(locations1 * mask1, axis=-1).astype(jnp.int32)
    locations2_s = jnp.sum(locations2 * mask2, axis=-1).astype(jnp.int32)

    gates1 = jnp.sum(gates * mask1, axis=-1)
    gates2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.clip(gates1 + gates2, 1e-9, None)
    gates1, gates2 = gates1 / denom, gates2 / denom

    combine1 = (gates1[:, None, None] * mask1[:, :, None] *
                _one_hot(locations1_s, c)[:, None, :])
    combine2 = (gates2[:, None, None] * mask2[:, :, None] *
                _one_hot(locations2_s, c)[:, None, :])
    combine = combine1 + combine2
    dispatch = combine.astype(bool)
    return l_aux, combine, dispatch, exp_counts


class TopKGate:
    """Gate module (reference TopKGate, sharded_moe.py:343)."""

    def __init__(self, model_dim: int, num_experts: int, k: int = 1,
                 capacity_factor: float = 1.0, eval_capacity_factor: float = 1.0,
                 min_capacity: int = 4, noisy_gate_policy: Optional[str] = None,
                 drop_tokens: bool = True):
        assert k in (1, 2), (
            "TopKGate dispatches by a capacity einsum for top-1 and top-2 "
            "only; for k > 2 (sigmoid top-k, no capacity, no drop) use "
            "moe/grouped.py: sigmoid_topk_route and held_experts")
        self.model_dim = model_dim
        self.num_experts = num_experts
        self.k = k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.min_capacity = min_capacity
        self.noisy_gate_policy = noisy_gate_policy
        self.drop_tokens = drop_tokens

    def init(self, rng):
        w = jax.random.normal(rng, (self.model_dim, self.num_experts),
                              jnp.float32) * (self.model_dim ** -0.5)
        return {"wg": w}

    def __call__(self, params, x, *, train: bool = True, rng=None):
        """x: [S, M] flattened tokens. Returns (l_aux, combine, dispatch, counts)."""
        logits = x.astype(jnp.float32) @ params["wg"]
        cf = self.capacity_factor if train else self.eval_capacity_factor
        if self.k == 1:
            return top1gating(logits, cf, self.min_capacity,
                              self.noisy_gate_policy if train else None,
                              rng, self.drop_tokens)
        return top2gating(logits, cf, self.min_capacity,
                          rng if train else None, self.drop_tokens)
