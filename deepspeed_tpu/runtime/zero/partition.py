"""ZeRO partition planning — the TPU-native core of ZeRO stages 1/2/3.

This module replaces ~6,900 LoC of the reference's Python-driven machinery —
``runtime/zero/stage_1_and_2.py`` (DeepSpeedZeroOptimizer, :90),
``runtime/zero/stage3.py`` (DeepSpeedZeroOptimizer_Stage3, :65),
``runtime/zero/partition_parameters.py`` (zero.Init, :601) and
``runtime/zero/partitioned_param_coordinator.py`` (fetch/prefetch/release) —
with a declarative *partition plan*: a pytree of ``PartitionSpec``s per
parameter that tells XLA where every tensor lives, letting the compiler
schedule the collectives the reference drives by hand.

Mapping (see SURVEY.md §2.2):

  stage 0  master params replicated; grads all-reduced (``psum`` over the
           batch axes — the DP fallback path, engine.py:2251).
  stage 1  fp32 master params + optimizer moments sharded over 'data';
           grads replicated (all-reduce); the optimizer update runs on the
           local shard and XLA all-gathers updated params — exactly the
           reference's allgather-after-step (stage_1_and_2.py step:1636).
  stage 2  as stage 1, but the grad pytree carries a sharded constraint so
           the backward pass lowers to ``reduce_scatter`` instead of
           all-reduce (average_tensor, stage_1_and_2.py:894).
  stage 3  compute (bf16) params are *also* sharded, and the gather is
           stated where a parameter is used: the model brings a layer's
           slice to its *gathered* spec (``gathered_spec``: the TP/EP
           entries alone) inside the rematerialised block of its layer
           scan (``models/base.gathered``), so the compiler all-gathers one
           layer's weights, frees them after the block, gathers again in
           the backward pass, and reduce-scatters the cotangent to
           ``grad_spec`` — the PartitionedParameterCoordinator's
           fetch/release, scheduled by XLA. The sharded spec alone does
           NOT do this: ZeRO puts 'data' on a feature dimension of a weight
           and the batch is sharded over 'data' as well, so an unstated
           use reads to the SPMD partitioner as tensor parallelism over
           'data', and it moves the *activations* of every matmul
           (all-to-all) instead of the weights — "overlap for free" was
           56.8% of a GPT-2 XL step spent in exposed collectives on four
           chips (PERF.md, PR 25 and PR 28). Small params stay replicated
           below ``param_persistence_threshold`` (mirroring persistent
           params, partition_parameters.py) and need no gather.

Tensor parallelism composes orthogonally: logical-axis rules assign 'model'
to hidden dimensions first; ZeRO then shards the largest remaining dimension
over 'data'. Offload (ZeRO-Offload/Infinity host residency) is handled in
``offload.py`` by placing master/optimizer leaves in host memory.
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (
    DATA_AXIS,
    EXPERT_AXIS,
    MODEL_AXIS,
    PIPE_AXIS,
    SEQ_AXIS,
    MeshTopology,
)

# Default logical-axis → mesh-axis rules (model zoo annotates params with
# logical names; anything unmapped is replicated on that dim).
DEFAULT_LOGICAL_RULES: Dict[str, Optional[str]] = {
    "embed": None,            # vocab dim of embeddings — could map to 'model'
    "vocab": MODEL_AXIS,      # output head vocab dim is TP-sharded
    "hidden": None,
    "heads": MODEL_AXIS,      # attention heads / qkv fused dim
    "kv_heads": MODEL_AXIS,   # GQA kv projection dim (must divide by tp)
    "kv": None,
    "mlp": MODEL_AXIS,        # ffn intermediate dim
    "expert": EXPERT_AXIS,    # leading expert dim of MoE params
    "pipe_stage": PIPE_AXIS,  # leading stage dim of pipelined body params
    "seq": None,
    "norm": None,
}


@dataclasses.dataclass(frozen=True)
class ParamUse:
    """Spec trees (keyed like the params) for the gather at the point of
    use: a leaf whose ``compute`` spec differs from its ``gathered`` spec
    is brought to the latter, and its cotangent to ``grad``."""

    mesh: Any
    compute: Any
    gathered: Any
    grad: Any


_ACTIVE_USE: list = []


@contextlib.contextmanager
def stating_param_use(use: Optional[ParamUse]):
    """While a model's ``apply`` is traced under this, ``models/base.
    gathered`` states the gathers of ``use``; with None it stays the
    identity (as it is outside any engine)."""
    _ACTIVE_USE.append(use)
    try:
        yield
    finally:
        _ACTIVE_USE.pop()


def active_param_use() -> Optional[ParamUse]:
    return _ACTIVE_USE[-1] if _ACTIVE_USE else None


@dataclasses.dataclass
class PartitionPlan:
    """Computes master/compute/grad shardings for every parameter."""

    topology: MeshTopology
    zero_stage: int = 0
    param_persistence_threshold: int = int(1e5)
    logical_rules: Dict[str, Optional[str]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_LOGICAL_RULES))
    # shard expert params' data-parallel dim over 'data' only (their grads are
    # averaged over 'data', not ('data','expert') — groups._get_expert_data_parallel_group)
    zero_shard_axis: str = DATA_AXIS

    # ------------------------------------------------------------------ specs
    def _tp_spec(self, shape: Tuple[int, ...], logical_axes: Optional[Tuple[str, ...]]):
        """Mesh-axis assignment from logical names (TP/EP dims)."""
        entries: list = [None] * len(shape)
        if logical_axes is None:
            return entries
        assert len(logical_axes) == len(shape), (
            f"logical axes {logical_axes} rank != shape {shape}")
        mesh = self.topology
        for i, name in enumerate(logical_axes):
            axis = self.logical_rules.get(name)
            if axis and mesh.get_dim(axis) > 1 and shape[i] % mesh.get_dim(axis) == 0:
                entries[i] = axis
        return entries

    def _add_zero_axis(self, entries: list, shape: Tuple[int, ...]) -> list:
        """Shard the largest free dim over the data axis (ZeRO partitioning)."""
        dp = self.topology.get_dim(self.zero_shard_axis)
        if dp <= 1:
            return entries
        mesh = self.topology
        # candidate dims: unassigned, divisible by dp; pick the largest
        best, best_size = -1, 0
        for i, (e, s) in enumerate(zip(entries, shape)):
            if e is None and s % dp == 0 and s >= best_size and s > 1:
                best, best_size = i, s
        if best >= 0:
            entries = list(entries)
            entries[best] = self.zero_shard_axis
            return entries
        # try stacking onto an existing TP axis: (model, data) on one dim
        for i, (e, s) in enumerate(zip(entries, shape)):
            if e is not None and not isinstance(e, tuple):
                combined = mesh.get_dim(e) * dp
                if s % combined == 0:
                    entries = list(entries)
                    entries[i] = (e, self.zero_shard_axis)
                    return entries
        return entries  # small/odd-shaped params stay replicated

    def master_spec(self, shape: Tuple[int, ...],
                    logical_axes: Optional[Tuple[str, ...]] = None) -> P:
        """Sharding of fp32 master params and optimizer moments."""
        entries = self._tp_spec(shape, logical_axes)
        if self.zero_stage >= 1:
            entries = self._add_zero_axis(entries, shape)
        return P(*entries)

    def compute_spec(self, shape: Tuple[int, ...],
                     logical_axes: Optional[Tuple[str, ...]] = None) -> P:
        """Sharding of the compute-dtype (bf16) params used in fwd/bwd."""
        entries = self._tp_spec(shape, logical_axes)
        numel = int(np.prod(shape)) if shape else 1
        if self.zero_stage >= 3 and numel >= self.param_persistence_threshold:
            entries = self._add_zero_axis(entries, shape)
        return P(*entries)

    def gathered_spec(self, shape: Tuple[int, ...],
                      logical_axes: Optional[Tuple[str, ...]] = None) -> P:
        """Sharding of a compute param where it is used: ``compute_spec``
        with the ZeRO axis taken out (TP/EP entries stay)."""
        return P(*self._tp_spec(shape, logical_axes))

    def grad_spec(self, shape: Tuple[int, ...],
                  logical_axes: Optional[Tuple[str, ...]] = None) -> P:
        """Sharding constraint on gradients: sharded from stage 2 up so the
        backward pass lowers to reduce-scatter."""
        entries = self._tp_spec(shape, logical_axes)
        if self.zero_stage >= 2:
            entries = self._add_zero_axis(entries, shape)
        return P(*entries)

    # ------------------------------------------------------------------ trees
    def _tree_specs(self, params, logical_axes_tree, fn):
        if logical_axes_tree is None:
            return jax.tree_util.tree_map(lambda p: fn(tuple(p.shape), None), params)
        return jax.tree_util.tree_map(
            lambda p, ax: fn(tuple(p.shape), tuple(ax) if ax is not None else None),
            params, logical_axes_tree,
            is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x)))

    def master_specs(self, params, logical_axes_tree=None):
        return self._tree_specs(params, logical_axes_tree, self.master_spec)

    def compute_specs(self, params, logical_axes_tree=None):
        return self._tree_specs(params, logical_axes_tree, self.compute_spec)

    def grad_specs(self, params, logical_axes_tree=None):
        return self._tree_specs(params, logical_axes_tree, self.grad_spec)

    def param_use(self, params, logical_axes_tree=None) -> Optional["ParamUse"]:
        """What ``models/base.gathered`` needs to state stage 3's gathers,
        or None when no compute param is sharded over the ZeRO axis (stage
        below 3, a ZeRO axis of size 1, every leaf under the persistence
        threshold): then there is nothing to gather."""
        compute = self.compute_specs(params, logical_axes_tree)
        gathered = self._tree_specs(params, logical_axes_tree, self.gathered_spec)
        is_spec = lambda x: isinstance(x, P)
        if jax.tree_util.tree_leaves(compute, is_leaf=is_spec) == \
                jax.tree_util.tree_leaves(gathered, is_leaf=is_spec):
            return None
        return ParamUse(self.topology.mesh, compute, gathered,
                        self.grad_specs(params, logical_axes_tree))

    def shardings(self, specs, memory_kind: Optional[str] = None):
        mesh = self.topology.mesh
        def mk(spec):
            if memory_kind is not None:
                try:
                    return NamedSharding(mesh, spec, memory_kind=memory_kind)
                except (ValueError, TypeError):
                    pass  # backend without memory-kind support (CPU tests)
            return NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(mk, specs, is_leaf=lambda x: isinstance(x, P))

    # -------------------------------------------------------------- batch spec
    def batch_spec(self, ndim: int) -> P:
        """Batch arrays: dim0 over the dense batch axes, dim1 ('seq') when
        sequence parallelism is on."""
        entries: list = [None] * ndim
        entries[0] = (DATA_AXIS, EXPERT_AXIS)
        if ndim >= 2 and self.topology.get_dim(SEQ_AXIS) > 1:
            entries[1] = SEQ_AXIS
        return P(*entries)

    def batch_shardings(self, batch):
        mesh = self.topology.mesh
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(mesh, self.batch_spec(getattr(x, "ndim", 0))), batch)
