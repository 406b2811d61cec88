"""SPMD pipeline executor — the TPU-native pipeline-parallel core.

The reference drives pipelining imperatively: a per-rank instruction stream
(runtime/pipe/schedule.py TrainSchedule:189) interpreted by PipelineEngine
(runtime/pipe/engine.py:40) with NCCL p2p sends between stage processes
(runtime/pipe/p2p.py). On TPU the idiomatic equivalent compiles the WHOLE
schedule into one XLA program: stage weights live on their slice of the
'pipe' mesh axis, microbatches flow stage→stage via ``lax.ppermute`` over
ICI, and the tick loop is a ``lax.scan``. Because ``ppermute`` is
differentiable, ``jax.grad`` of the scanned forward replays the reverse
schedule — backward pipelining without a hand-written 1F1B interpreter
(the bubble profile matches GPipe; the fused scan keeps all stages busy in
steady state exactly like the reference's schedule ticks).

Occupancy semantics (tick t, stage s processes microbatch t-s) are shared
with — and tested against — ``runtime/pipe/schedule.InferenceSchedule``.

``shard_map`` is *manual* only over 'pipe' (``axis_names={'pipe'}``): data /
model / expert / seq axes stay in GSPMD auto mode, so ZeRO sharding and
tensor parallelism compose inside each stage unchanged.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import PIPE_AXIS


def spmd_pipeline(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    inputs: jax.Array,
    *,
    mesh,
    num_stages: int,
    num_microbatches: int,
    remat: bool = False,
    index_args: bool = False,
) -> jax.Array:
    """Run ``num_microbatches`` inputs through ``num_stages`` pipeline stages.

    stage_fn(stage_params_slice, x) -> y  — one stage's computation; input and
        output activations must share shape/dtype (stage boundaries of a
        transformer stack satisfy this).
    stage_params — pytree whose leaves have leading dim ``num_stages``,
        sharded ``P('pipe', ...)``.
    inputs — ``[M, ...]`` microbatch stream (replicated over 'pipe').
    index_args — when True, the stage fn is called as
        ``stage_fn(params_slice, x, stage, mb_id)`` with traced int32
        scalars: the stage index and the microbatch index that stage is
        processing this tick (``t - stage``; out-of-range on bubble ticks,
        whose outputs are discarded). Lets callers derive per-(stage,
        microbatch, layer) dropout keys that match the host-driven 1F1B
        interpreter exactly (reference threads CudaRNGStatesTracker state
        through its stages, activation_checkpointing/checkpointing.py:121).

    Returns ``[M, ...]`` last-stage outputs.
    """
    assert inputs.shape[0] == num_microbatches
    S, M = num_stages, num_microbatches
    if not index_args:
        base_fn = stage_fn
        stage_fn = lambda p, x, stage, mb: base_fn(p, x)  # noqa: E731
    if S == 1:
        def body(m, x):
            one = jax.tree_util.tree_map(lambda p: p[0], stage_params)
            return m + 1, stage_fn(one, x, jnp.int32(0), m)
        return jax.lax.scan(body, jnp.int32(0), inputs)[1]

    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    # XLA CPU workaround: the cotangent of an unvarying 16-bit shard_map input
    # lowers to an identity-reduction all-reduce that the CPU AllReducePromotion
    # pass cannot clone ("Invalid binary instruction opcode copy"); carry the
    # stream boundary in f32 there. TPU takes the 16-bit path untouched.
    compute_dtype = inputs.dtype
    f32_boundary = (jax.default_backend() == "cpu" and
                    compute_dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)))
    if f32_boundary:
        inputs = inputs.astype(jnp.float32)

    def run(params_local, xs):
        # per-device view: params leaves [1, ...]; xs is the full [M, ...] stream.
        # Make the stream varying over 'pipe' BEFORE the compute-dtype cast so
        # the transpose's boundary psum runs in the (f32) boundary dtype.
        xs = jax.lax.pcast(xs, (PIPE_AXIS,), to="varying").astype(
            compute_dtype)
        params_one = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(PIPE_AXIS)

        def tick(carry, t):
            state, outputs = carry
            x = jnp.where(stage == 0, xs[t % M], state)
            y = fn(params_one, x, stage, t - stage)
            outputs = outputs.at[(t - (S - 1)) % M].set(y)
            state = jax.lax.ppermute(
                y, PIPE_AXIS, [(i, (i + 1) % S) for i in range(S)])
            return (state, outputs), None

        # carries inherit xs's varying-over-'pipe' type (shard_map VMA typing)
        state0 = jnp.zeros_like(xs[0])
        out0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(
            tick, (state0, out0), jnp.arange(M + S - 1))
        # [1, M, ...] per device → global [S, M, ...] over 'pipe'
        return outputs[None]

    pipe_in = jax.tree_util.tree_map(lambda _: P(PIPE_AXIS), stage_params)
    outputs = shard_map(
        run, mesh=mesh,
        in_specs=(pipe_in, P()),
        out_specs=P(PIPE_AXIS),
        axis_names={PIPE_AXIS},
    )(stage_params, inputs)
    return outputs[-1]  # last stage's buffer


def stack_stage_params(per_stage_params: list) -> Any:
    """Stack S structurally-identical per-stage pytrees on a new leading dim
    (the 'pipe'-sharded dim). Analog of the reference's per-stage module
    partitioning (runtime/pipe/module.py _partition_layers)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)
