"""Pipeline-parallel training engine.

Parity target: reference ``runtime/pipe/engine.py`` (PipelineEngine:40,
train_batch:285, eval_batch:362, _exec_schedule:1287) — 1301 LoC of
instruction interpretation, p2p meta handshakes and buffer management.

TPU-native redesign: the whole 1F1B tick loop compiles into ONE XLA program
(parallel/pipeline.spmd_pipeline) — stage weights sharded over the 'pipe'
mesh axis, activations exchanged by ``ppermute`` over ICI, backward
pipelining by autodiff through the scanned schedule. The instruction
streams in ``schedule.py`` document/validate the tick semantics; this
engine never interprets them at runtime (no per-tick Python dispatch, no
meta handshake — shapes are static under jit).

Semantics parity notes:
  * micro_batches == gradient_accumulation_steps (reference engine.py:81).
  * forward()/backward()/step() are disabled exactly like the reference
    (:1175-1185) — ``train_batch``/``eval_batch`` are the only entries.
  * tied layers (TiedLayerSpec) hold ONE canonical param copy; both use
    sites read it, so autodiff *sums* their grads — the functional
    equivalent of the reference's ReduceTiedGrads allreduce over the tie
    group (:223).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.parallel.pipeline import spmd_pipeline
from deepspeed_tpu.parallel.topology import PIPE_AXIS
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState
from deepspeed_tpu.runtime.pipe.module import LayerSpec, PipelineModule, TiedLayerSpec
from deepspeed_tpu.telemetry.compile_log import at_work
from deepspeed_tpu.utils.logging import log_dist


class PipelineError(Exception):
    """Errors related to the use of deepspeed.PipelineModule (reference name)."""


def _layer_signature(layer) -> Tuple:
    """Stackability signature: same class + same param structure/shapes."""
    if not hasattr(layer, "init"):
        return (type(layer), None)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten(shapes)
    return (type(layer), str(treedef), tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


class PipelinedModelAdapter:
    """Restructures a PipelineModule into (prefix, body, suffix) segments.

    body — the longest run of structurally identical, untied layers, trimmed
    to a multiple of num_stages; its params stack to leading dims
    ``[num_stages, layers_per_stage, ...]`` and execute via spmd_pipeline.
    prefix/suffix — everything before/after (embeddings, final norm, lm head);
    computed on all pipe ranks (replicated over 'pipe'), scanned over the
    microbatch stream.
    """

    def __init__(self, module: PipelineModule, num_stages: int, mesh, remat: bool = False):
        self.module = module
        self.num_stages = num_stages
        self.mesh = mesh
        self.remat = remat
        self._plan_segments()

    # ------------------------------------------------------------- segmenting
    def _plan_segments(self):
        specs = self.module.layer_specs
        layers = self.module.layers
        S = self.num_stages
        sigs = []
        for spec, layer in zip(specs, layers):
            tied = isinstance(spec, TiedLayerSpec)
            sigs.append(("tied",) if tied else _layer_signature(layer))

        # longest homogeneous run of stackable (non-tied, param-bearing) layers
        best = (0, 0)  # (start, length)
        i = 0
        n = len(layers)
        while i < n:
            j = i
            while (j < n and sigs[j] == sigs[i] and sigs[i][0] != "tied"
                   and sigs[i][1] is not None):
                j += 1
            if j - i > best[1]:
                best = (i, j - i)
            i = max(j, i + 1)
        start, length = best
        K = length // S  # layers per stage
        if K == 0:
            raise PipelineError(
                f"cannot pipeline: longest homogeneous layer run ({length}) is "
                f"shorter than num_stages ({S})")
        extra = length - K * S
        # extras join the prefix so the run stays contiguous
        self.body_start = start + extra
        self.body_end = start + length
        self.layers_per_stage = K
        self.prefix_idx = list(range(0, self.body_start))
        self.suffix_idx = list(range(self.body_end, n))
        self.body_layer = layers[self.body_start]

        # tied groups: key -> owner layer index (first occurrence)
        self.tie_owner: Dict[str, int] = {}
        self.tied_of: Dict[int, str] = {}
        for i, spec in enumerate(specs):
            if isinstance(spec, TiedLayerSpec):
                self.tied_of[i] = spec.key
                self.tie_owner.setdefault(spec.key, i)

    # ------------------------------------------------------------------- init
    def init(self, rng):
        layers = self.module.layers
        pre: Dict[str, Any] = {}
        post: Dict[str, Any] = {}
        tied: Dict[str, Any] = {}
        body_per_layer: List[Any] = []
        for i, layer in enumerate(layers):
            rng, sub = jax.random.split(rng)
            if i in self.tied_of:
                key = self.tied_of[i]
                if self.tie_owner[key] == i:
                    tied[key] = layer.init(sub)
                continue
            if not hasattr(layer, "init"):
                continue
            p = layer.init(sub)
            if self.body_start <= i < self.body_end:
                body_per_layer.append(p)
            elif i < self.body_start:
                pre[str(i)] = p
            else:
                post[str(i)] = p
        S, K = self.num_stages, self.layers_per_stage
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *body_per_layer)
        body = jax.tree_util.tree_map(
            lambda x: x.reshape((S, K) + x.shape[1:]), stacked)
        return {"pre": pre, "body": body, "post": post, "tied": tied}

    def logical_axes(self):
        """TP/pipe logical names per param. Body leaves get
        ('pipe_stage', 'layer') + the block layer's own per-param axes, so
        tensor parallelism composes with the pipe sharding (closes the
        pipe>1 × tp>1 composition gap; ref runtime/pipe/topology.py:244
        PipeModelDataParallelTopology)."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        layers = self.module.layers

        def layer_axes(i, leaf_tree):
            layer = layers[i]
            if hasattr(layer, "logical_axes"):
                return layer.logical_axes()
            return jax.tree_util.tree_map(lambda l: (None,) * l.ndim, leaf_tree)

        if hasattr(self.body_layer, "logical_axes"):
            blk = self.body_layer.logical_axes()
            _is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
                isinstance(e, (str, type(None))) for e in x)
            body = jax.tree_util.tree_map(
                lambda ax: ("pipe_stage", "layer") + tuple(ax), blk,
                is_leaf=_is_axes)
        else:
            body = jax.tree_util.tree_map(
                lambda l: ("pipe_stage",) + (None,) * (l.ndim - 1), shapes["body"])

        tied_axes = {}
        for key, owner in self.tie_owner.items():
            tied_axes[key] = layer_axes(owner, shapes["tied"][key])
        return {
            "pre": {k: layer_axes(int(k), v) for k, v in shapes["pre"].items()},
            "body": body,
            "post": {k: layer_axes(int(k), v) for k, v in shapes["post"].items()},
            "tied": tied_axes,
        }

    # ------------------------------------------------------------------ apply
    def _layer_params(self, params, i: int):
        if i in self.tied_of:
            return params["tied"][self.tied_of[i]]
        if i < self.body_start:
            return params["pre"].get(str(i))
        return params["post"].get(str(i))

    @staticmethod
    def layer_key(base, mb_id, layer_idx):
        """Per-(microbatch, global-layer) dropout key. Both executors (SPMD
        scan and host 1F1B interpreter) derive keys through this one
        function, so pipelined dropout is numerics-identical across them —
        the functional analog of the reference's CudaRNGStatesTracker
        threading (activation_checkpointing/checkpointing.py:121)."""
        return jax.random.fold_in(jax.random.fold_in(base, mb_id), layer_idx)

    def _run_segment(self, params, idx_list, x, train: bool,
                     rng_base=None, mb_id=None):
        for i in idx_list:
            layer = self.module.layers[i]
            spec = self.module.layer_specs[i]
            if isinstance(spec, TiedLayerSpec) and spec.forward_fn is not None:
                # tied re-use site reinterpreting the owner's params (e.g. the
                # lm head projecting through the embedding table)
                x = spec.forward_fn(self._layer_params(params, i), x)
            elif hasattr(layer, "apply"):
                k = (self.layer_key(rng_base, mb_id, i)
                     if rng_base is not None else None)
                x = layer.apply(self._layer_params(params, i), x, rngs=k, train=train)
            else:
                x = layer(x)
        return x

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, dict):
            inputs = batch.get("inputs", batch.get("input_ids"))
            labels = batch.get("labels", batch.get("y"))
        else:
            inputs, labels = batch[0], batch[1]
        return inputs, labels

    def apply(self, params, batch, *, rngs=None, train: bool = False):
        """batch leaves carry a leading [M] microbatch dim (the pipeline
        stream == gradient-accumulation microbatches, reference engine.py:81).
        ``rngs`` (a key, or {'dropout': key}) threads per-(microbatch, layer)
        dropout keys through prefix/body/suffix via ``layer_key``."""
        M = jax.tree_util.tree_leaves(batch)[0].shape[0]
        base = rngs.get("dropout") if isinstance(rngs, dict) else rngs
        if not train:
            base = None
        K = self.layers_per_stage

        def pre_fn(args):
            mb, mb_id = args
            inputs, _ = self._split_batch(mb)
            return self._run_segment(params, self.prefix_idx, inputs, train,
                                     base, mb_id)

        xs = jax.lax.map(pre_fn, (batch, jnp.arange(M)))

        if base is None:
            def stage_fn(stage_params, x):
                def body(h, lp):
                    return self.body_layer.apply(
                        lp, h, rngs=None, train=train), None

                return jax.lax.scan(body, x, stage_params)[0]
        else:
            def stage_fn(stage_params, x, stage, mb_id):
                def body(h, lp_k):
                    lp, k = lp_k
                    key = self.layer_key(base, mb_id,
                                         self.body_start + stage * K + k)
                    return self.body_layer.apply(
                        lp, h, rngs=key, train=train), None

                return jax.lax.scan(body, x,
                                    (stage_params, jnp.arange(K)))[0]

        ys = spmd_pipeline(stage_fn, params["body"], xs, mesh=self.mesh,
                           num_stages=self.num_stages, num_microbatches=M,
                           remat=self.remat, index_args=base is not None)

        def post_fn(args):
            y, mb, mb_id = args
            _, labels = self._split_batch(mb)
            out = self._run_segment(params, self.suffix_idx, y, train,
                                    base, mb_id)
            if self.module.loss_fn is not None:
                return self.module.loss_fn(out, labels)
            return out

        losses = jax.lax.map(post_fn, (ys, batch, jnp.arange(M)))
        loss = jnp.mean(losses.astype(jnp.float32))
        return loss, {"loss": loss}


class PipelineEngine(DeepSpeedEngine):
    """Training engine for PipelineModule models (reference PipelineEngine:40)."""

    def __init__(self, module: PipelineModule, config, *, optimizer=None,
                 lr_scheduler=None, training_data=None, collate_fn=None,
                 topology=None, **kw):
        if not isinstance(module, PipelineModule):
            raise PipelineError("PipelineEngine requires a PipelineModule")
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.utils import groups as groups_mod

        if not isinstance(config, DeepSpeedConfig):
            config = DeepSpeedConfig(config)
        if topology is None:
            topology = groups_mod.initialize(
                tp_size=config.tensor_parallel.tp_size,
                pp_size=max(config.pipeline.stages, module.num_stages),
                ep_size=config.expert_parallel.ep_size,
                sp_size=config.sequence_parallel.sp_size,
            )
        num_stages = topology.pipe_parallel_size
        self.pipeline_module = module
        adapter = PipelinedModelAdapter(
            module, num_stages, topology.mesh,
            remat=module.activation_checkpoint_interval > 0)
        super().__init__(adapter, config, optimizer=optimizer,
                         lr_scheduler=lr_scheduler, training_data=training_data,
                         collate_fn=collate_fn, topology=topology, **kw)
        self.num_stages = num_stages
        self.micro_batches = self.gas
        self._exec_mode = self.config.pipeline.executor
        if self._exec_mode not in ("spmd", "host_1f1b"):
            raise PipelineError(
                f"pipeline.executor must be 'spmd' or 'host_1f1b', "
                f"got {self._exec_mode!r}")
        self._executor_1f1b = None
        self._executor_1f1b_eval = {}  # M → executor (eval_batch sizes)
        self._1f1b_cast = None
        self._1f1b_apply = None
        self.last_1f1b_stats = None
        if self._exec_mode == "host_1f1b":
            from deepspeed_tpu.runtime.pipe.executor import (
                Schedule1F1BExecutor)

            self._executor_1f1b = Schedule1F1BExecutor(adapter, self.gas)
        log_dist(
            f"PipelineEngine: stages={num_stages} "
            f"executor={self._exec_mode} "
            f"body_layers=[{adapter.body_start},{adapter.body_end}) "
            f"layers/stage={adapter.layers_per_stage} "
            f"tied_groups={list(adapter.tie_owner)}", ranks=[0])

    # ------------------------------------------------- fused pipelined step
    def _build_train_step(self, batch=None):
        def train_step(state: TrainState, batch, lr, rng):
            scale = state.scaler.cur_scale

            def loss_fn(master_params):
                cparams = self._cast_for_compute(master_params)
                loss, metrics = self.module.apply(cparams, batch, rngs={"dropout": rng},
                                                  train=True)
                return loss * scale, metrics

            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g.astype(jnp.float32),
                    jax.sharding.NamedSharding(self.mesh, s)),
                grads, self.grad_specs)
            new_state, overflow, norm = self._apply_grads(state, grads, lr)
            out = {"loss": metrics["loss"], "overflow": overflow, "grad_norm": norm,
                   "loss_scale": state.scaler.cur_scale}
            return new_state, out

        self._compiled_train_step = jax.jit(train_step, donate_argnums=(0,))
        return self._compiled_train_step

    # --------------------------------------------- host-driven 1F1B executor
    def _run_fused_step(self, batch):
        if self._exec_mode == "host_1f1b":
            return self._run_host_1f1b_step(batch)
        return super()._run_fused_step(batch)

    def _run_host_1f1b_step(self, batch):
        """One train_batch via the instruction-stream interpreter
        (reference _exec_schedule:1287): per-stage jitted fwd/bwd driven by
        TrainSchedule, activation memory bounded by num_pipe_buffers; the
        epilogue (unscale/clip/optimizer/scale-update) reuses the engine's
        compiled _apply_grads."""
        import jax.numpy as jnp  # noqa: F811
        from deepspeed_tpu.runtime.engine import TRAIN_BATCH_TIMER

        import time

        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        t_start = time.perf_counter()
        batch = self._apply_curriculum(batch)
        batch = jax.device_put(batch, self._gas_batch_shardings(batch))
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        if self._1f1b_cast is None:
            self._1f1b_cast = jax.jit(self._cast_for_compute)

            def apply(state, grads, lr):
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32), grads)
                # copy the used scale into an output: the input state is
                # donated, so its buffers must not be referenced afterwards
                used_scale = state.scaler.cur_scale * 1.0
                new_state, overflow, norm = self._apply_grads(state, grads, lr)
                return new_state, overflow, norm, used_scale

            # donate old state + grads: the epilogue must not double-buffer
            # params/opt state in the executor whose point is peak memory
            self._1f1b_apply = jax.jit(apply, donate_argnums=(0, 1))
        cparams = self._1f1b_cast(self.state.params)
        # keep the scale a device scalar — a host fetch here would fence
        # dispatch against the previous step's scaler update
        scale = self.state.scaler.cur_scale
        # same per-step base key as the SPMD path (_build_train_step passes
        # rngs={'dropout': fold_in(dropout_rng, step)}) — the executor folds
        # (mb_id, layer) on top via layer_key, so both executors drop the
        # same units
        rng = jax.random.fold_in(self._dropout_rng, self.global_steps)
        loss, grads, stats = self._executor_1f1b.train_batch(
            cparams, batch, loss_scale=scale, rngs=rng)
        self.last_1f1b_stats = stats
        self.state, overflow, norm, scale = self._1f1b_apply(
            self.state, grads, lr)
        self._global_grad_norm = norm
        self.micro_steps += self.gas
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        metrics = {"loss": loss, "overflow": overflow, "grad_norm": norm,
                   "loss_scale": scale}
        self._after_step(metrics)
        self.timers(TRAIN_BATCH_TIMER).stop(record=True)
        self.tput_timer.stop(global_step=True)
        if self.telemetry is not None:
            self._record_step_telemetry(
                metrics, batch, time.perf_counter() - t_start)
        if self._sync_each_step:
            # dstpu-lint: fence=opt-in per-step fence (config sync_each_step)
            jax.block_until_ready(self.state.params)
        return metrics["loss"]

    # --------------------------------------------------------------- user API
    @at_work
    def eval_batch(self, batch, compute_loss: bool = True):
        """reference eval_batch:362 — forward-only pipeline pass. In
        host_1f1b mode this interprets InferenceSchedule tick by tick (the
        path that still works when one XLA program cannot span the job)."""
        if self._exec_mode == "host_1f1b":
            leaves = jax.tree_util.tree_leaves(batch)
            if leaves and leaves[0].ndim >= 1 and not self._looks_stacked(batch):
                batch = jax.tree_util.tree_map(lambda x: x[None], batch)
            batch = jax.device_put(batch, self._gas_batch_shardings(batch))
            if self._1f1b_cast is None:
                self._1f1b_cast = jax.jit(self._cast_for_compute)
            M = jax.tree_util.tree_leaves(batch)[0].shape[0]
            ex = self._executor_1f1b
            if M != ex.M:
                # cache per-M executors: a fresh one per call would re-jit
                # its stage functions on every eval_batch
                if M not in self._executor_1f1b_eval:
                    from deepspeed_tpu.runtime.pipe.executor import (
                        Schedule1F1BExecutor)

                    self._executor_1f1b_eval[M] = Schedule1F1BExecutor(
                        self._executor_1f1b.adapter, M)
                ex = self._executor_1f1b_eval[M]
            return ex.eval_batch(self._1f1b_cast(self.state.params), batch)
        if self._compiled_eval is None:
            def ev(params, batch):
                cparams = self._cast_for_compute(params)
                loss, _ = self.module.apply(cparams, batch, rngs=None, train=False)
                return loss

            self._compiled_eval = jax.jit(ev)
        leaves = jax.tree_util.tree_leaves(batch)
        # accept both a single microbatch and a stacked [M, ...] stream
        if leaves and leaves[0].ndim >= 1 and not self._looks_stacked(batch):
            batch = jax.tree_util.tree_map(lambda x: x[None], batch)
        batch = jax.device_put(batch, self._gas_batch_shardings(batch))
        return self._compiled_eval(self.state.params, batch)

    def _looks_stacked(self, batch) -> bool:
        inputs, _ = PipelinedModelAdapter._split_batch(batch)
        return inputs.ndim >= 3

    # disabled entry points (reference engine.py:1175-1185)
    def forward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    __call__ = forward

    def backward(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    def step(self, *args, **kwargs):
        raise PipelineError("Only train_batch() is accessible in pipeline mode.")

    # ------------------------------------------------------------- stage info
    def is_first_stage(self) -> bool:
        return True  # single-controller SPMD: every process drives all stages

    def is_last_stage(self) -> bool:
        return True

    def is_pipe_parallel(self) -> bool:
        return self.num_stages > 1
