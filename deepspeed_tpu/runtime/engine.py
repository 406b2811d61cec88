"""Training engine (L4).

TPU-native re-design of the reference ``DeepSpeedEngine``
(runtime/engine.py:181, 3267 LoC). The reference wraps a torch nn.Module and
drives forward/backward/step imperatively with grad hooks firing collectives;
here the entire step — microbatch scan (grad accumulation), loss scaling,
mixed-precision casts, ZeRO collectives, overflow check, clip, optimizer
update, loss-scale adjustment — is ONE compiled XLA program built from the
PartitionPlan's shardings. XLA schedules the reduce-scatters/all-gathers the
reference hand-buckets (stage_1_and_2.py average_tensor:894, stage3.py
__reduce_and_partition_ipg_grads:1045).

API parity (reference names in parens):
    engine(batch) / engine.forward(batch)   — compute loss (+cache grads)
    engine.backward(loss)                   — accumulate grads (backward:1755)
    engine.step()                           — optimizer step at gas boundary
                                              (step:1951, _take_model_step:1886)
    engine.train_batch(data_iter)           — fused full step (PipelineEngine
                                              train_batch:285 shape, but valid
                                              for every topology here)
    engine.eval_batch(batch)                — no-grad loss
    engine.save_checkpoint / load_checkpoint
"""

from __future__ import annotations

import dataclasses
import os
import time
import weakref
from typing import Any, Callable, Dict, Iterator, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.ops.adam import build_optimizer
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.lr_schedules import build_lr_scheduler
from deepspeed_tpu.runtime.precision import (
    DynamicLossScaler,
    LossScalerState,
    StaticLossScaler,
    clip_grads_by_global_norm,
    create_loss_scaler,
    global_grad_norm,
    has_inf_or_nan,
)
from deepspeed_tpu.runtime.zero.partition import PartitionPlan, stating_param_use
from deepspeed_tpu.telemetry.compile_log import (SetupPhase, at_work,
                                                 compile_log)
from deepspeed_tpu.telemetry.host_watch import TrainWatch
from deepspeed_tpu.utils import groups as groups_mod
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import (
    BACKWARD_GLOBAL_TIMER,
    FORWARD_GLOBAL_TIMER,
    STEP_GLOBAL_TIMER,
    TRAIN_BATCH_TIMER,
    SynchronizedWallClockTimer,
    ThroughputTimer,
)


class TrainState(NamedTuple):
    params: Any            # fp32 master params (sharded per plan)
    opt_state: Any
    scaler: LossScalerState
    global_step: jax.Array


_STEP_ANNOTATIONS = ("dstpu/train_batch_put", "dstpu/train_step",
                     "dstpu/train_after_step")


class DeepSpeedEngine:
    # set by ``at_work`` for the length of the constructor and of every
    # step entry point: the compile events that arrive then are this
    # engine's (telemetry/compile_log.py)
    _at_work = False

    @at_work
    def __init__(self, model, config: Union[DeepSpeedConfig, dict, str], *,
                 optimizer=None, lr_scheduler=None, training_data=None,
                 collate_fn=None, topology=None, init_rng=None, dont_change_device=False):
        if not isinstance(config, DeepSpeedConfig):
            config = DeepSpeedConfig(config)
        self.config = config
        self._config = config  # reference attribute name
        self.module = model
        self.accelerator = get_accelerator()

        # ---- topology / groups (engine _configure_distributed_model analog)
        if topology is None:
            topology = groups_mod.initialize(
                tp_size=config.tensor_parallel.tp_size,
                pp_size=config.pipeline.stages,
                ep_size=config.expert_parallel.ep_size,
                sp_size=config.sequence_parallel.sp_size,
            )
        else:
            groups_mod.initialize(topology)
        self.topology = topology
        self.mesh = topology.mesh

        # ---- precision policy
        self.fp16_enabled = config.fp16_enabled
        self.bfloat16_enabled = config.bfloat16_enabled
        if self.fp16_enabled:
            self.compute_dtype = jnp.float16
            self.loss_scaler = create_loss_scaler(config.fp16_config)
        elif self.bfloat16_enabled:
            self.compute_dtype = jnp.bfloat16
            self.loss_scaler = StaticLossScaler(1.0)
        else:
            self.compute_dtype = jnp.float32
            self.loss_scaler = StaticLossScaler(1.0)
        self.dynamic_loss_scale = isinstance(self.loss_scaler, DynamicLossScaler)

        # ---- partition plan (ZeRO + TP declarative shardings)
        self.zero_stage = config.zero_optimization_stage
        self.plan = PartitionPlan(
            topology=topology,
            zero_stage=self.zero_stage,
            param_persistence_threshold=config.zero_config.param_persistence_threshold,
        )
        self.logical_axes = model.logical_axes() if hasattr(model, "logical_axes") else None

        # ---- offload: optimizer state / master params to host memory
        zc = config.zero_config
        self.offload_optimizer = bool(
            zc.offload_optimizer and zc.offload_optimizer.device != "none")

        # ---- optimizer (reference _configure_optimizer:1137)
        if optimizer is None and config.optimizer_name is not None:
            optimizer = build_optimizer(config.optimizer_name, config.optimizer_params)
        if optimizer is None:
            optimizer = build_optimizer("adam", {"lr": 1e-3})
        from deepspeed_tpu.ops.onebit import _OnebitBase

        self._onebit_compressed = False
        if isinstance(optimizer, _OnebitBase) and optimizer.with_compression:
            # true 1-bit comm needs LOCAL (unreduced) grads: the engine runs
            # the whole step under shard_map over the data axis so the
            # optimizer's compressed momentum sync REPLACES the grad
            # allreduce (reference disables backward allreduce for 1-bit
            # optimizers the same way). Only meaningful on a pure-DP stage-0
            # layout — other topologies fall back to exact math.
            pure_dp = (topology.data_parallel_size > 1 and
                       all(topology.get_dim(a) == 1
                           for a in ("model", "seq", "pipe", "expert")))
            if pure_dp and self.zero_stage == 0 and not \
                    self.offload_optimizer:
                self._onebit_compressed = True
            else:
                # replace, don't mutate: the caller may use the same
                # instance on the compressed path
                optimizer = dataclasses.replace(optimizer,
                                                with_compression=False)
                log_dist(
                    "1-bit optimizer: compressed comm needs pure-DP ZeRO-0 "
                    "without offload — falling back to exact communication "
                    "(no compression, no error-state memory)", ranks=[0])
        self.optimizer = optimizer

        # ---- host (ZeRO-Offload/Infinity) optimizer: fp32 master + moments in
        # host RAM or on NVMe, step on CPU via the native kernel
        # (reference stage_1_and_2.py:1031 cpu-offload, stage3.py:1735 + swap)
        self._host_opt = None
        if self.offload_optimizer:
            from deepspeed_tpu.runtime.zero.offload import HostOffloadOptimizer

            try:
                self._host_opt = HostOffloadOptimizer(
                    optimizer, zc.offload_optimizer, self.compute_dtype)
            except ValueError as e:
                log_dist(f"offload_optimizer: {e}; keeping device-state path",
                         ranks=[0])
        self.client_lr_scheduler = lr_scheduler
        if lr_scheduler is None and config.scheduler_name is not None:
            lr_scheduler = build_lr_scheduler(config.scheduler_name,
                                              config.scheduler_params, optimizer)
        self.lr_scheduler = lr_scheduler
        if self.lr_scheduler is not None and self.lr_scheduler.last_batch_iteration < 0:
            self.lr_scheduler.step(0)  # prime initial LR (warmup start)

        # ---- shardings
        self._build_shardings()

        # ---- state init (zero.Init analog: params born sharded on device)
        self._init_rng = init_rng if init_rng is not None else jax.random.PRNGKey(config.seed)
        # set-up's weights phase (parameters, master copy, optimizer state),
        # closed at a fence on the state it made; published where the
        # telemetry registry is made, further down
        compile_log()     # listening before the first program is traced
        setup_weights = SetupPhase("weights")
        self.state = self._init_state()
        setup_weights.close(fence=self.state)
        self._dropout_rng = jax.random.fold_in(self._init_rng, 0x5eed)

        # ---- debug/safe mode (SURVEY §5.2: the functional design makes
        # distributed invariants checkable as placements — DSTPU_DEBUG=1)
        from deepspeed_tpu.utils.debug import (
            check_sharding_invariants, debug_mode_enabled)

        self._debug_mode = debug_mode_enabled()
        if self._debug_mode:
            for p in check_sharding_invariants(self):
                logger.warning("sharding invariant (post-init): %s", p)

        # ---- progressive layer drop (reference engine.py pld wiring)
        self.progressive_layer_drop = None
        self._use_pld = False
        if config.pld_config.enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import (
                ProgressiveLayerDrop)

            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=config.pld_config.theta, gamma=config.pld_config.gamma)
            import inspect

            self._use_pld = "pld_theta" in inspect.signature(
                model.apply).parameters
            if not self._use_pld:
                log_dist("progressive_layer_drop: model.apply does not "
                         "accept pld_theta — schedule tracked but layers "
                         "are NOT dropped", ranks=[0])

        # ---- random-LTD token routing (reference data_routing wiring,
        # basic_layer.py RandomLayerTokenDrop): the scheduler's kept-token
        # count is passed to model.apply as a STATIC ``ltd_keep`` so the
        # gather->block->scatter shapes stay compile-time constants (one
        # compile per schedule granule, like the legacy curriculum).
        self.random_ltd_scheduler = None
        self._use_random_ltd = False
        if config.random_ltd_enabled:
            from deepspeed_tpu.runtime.data_pipeline.random_ltd import (
                RandomLTDScheduler)
            import inspect

            self.random_ltd_scheduler = RandomLTDScheduler(
                config.random_ltd_params)
            self._use_random_ltd = "ltd_keep" in inspect.signature(
                model.apply).parameters
            if not self._use_random_ltd:
                log_dist("random_ltd: model.apply does not accept "
                         "ltd_keep — schedule tracked but tokens are NOT "
                         "dropped", ranks=[0])
            elif self._use_pld:
                log_dist("random_ltd and progressive_layer_drop are "
                         "mutually exclusive; disabling random_ltd",
                         ranks=[0])
                self._use_random_ltd = False
            elif self._onebit_compressed:
                log_dist("random_ltd is not supported on the 1-bit "
                         "compressed path; disabling", ranks=[0])
                self._use_random_ltd = False

        # XLA:CPU's collective rendezvous keys executions by (run_id, op_id)
        # only; on a starved host a straggler async step can join the NEXT
        # step's rendezvous and deadlock both.  The CPU (test) backend
        # therefore synchronizes every step; TPU keeps async dispatch.
        self._sync_each_step = (self.accelerator.name() == "cpu" and
                                os.environ.get("DSTPU_SYNC_EACH_STEP") != "0")

        # ---- legacy curriculum learning (engine.py:1653 curriculum_seqlen
        # injection): batches are truncated host-side to the scheduled
        # seqlen. Each DISTINCT seqlen compiles once, so the difficulty
        # step should be a multiple of a reasonable tile (reference tells
        # users the same for attention kernels).
        self.curriculum_scheduler = None
        if config.curriculum_enabled_legacy:
            from deepspeed_tpu.runtime.data_pipeline import (
                CurriculumScheduler)

            self.curriculum_scheduler = CurriculumScheduler(
                config.curriculum_params_legacy)

        # ---- counters (reference engine attrs)
        self.micro_steps = 0
        self.global_steps = 0
        self.skipped_steps = 0
        self.gas = config.gradient_accumulation_steps
        self._grad_acc = None       # accumulated grads for fwd/bwd/step API
        self._acc_count = 0
        self._global_grad_norm = None

        # ---- compiled steps
        self._compiled_train_step = None
        # open from the fused step's build until its first result is fenced
        self._setup_first_step: Optional[SetupPhase] = None
        self._compiled_micro_grad = None
        self._compiled_apply_grads = None
        self._compiled_eval = None

        # ---- data / monitor / timers
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data, collate_fn=collate_fn)
        self.timers = SynchronizedWallClockTimer(
            # dstpu-lint: fence=timer sync_fn IS the declared wall-clock fence (utils/timer.py)
            sync_fn=lambda: jax.block_until_ready(self.state.params))
        self.tput_timer = ThroughputTimer(
            batch_size=config.train_batch_size,
            steps_per_output=config.steps_per_print or 50)
        if hasattr(model, "flops_per_token"):
            try:
                self.tput_timer.flops_per_sample = model.flops_per_token()
            except Exception:
                pass
        from deepspeed_tpu.monitor.monitor import MonitorMaster

        self.monitor = MonitorMaster(config.monitor_config)

        # ---- telemetry (ISSUE 3): in-process metrics registry + optional
        # JSONL sink. Per-step cost is a few dict ops and the host watch's
        # three clock reads (gpt2-large.train-1chip: 20,196.7 to 20,198.4
        # tokens/s with the watch, 20,196.9 to 20,197.2 without; PERF.md
        # section 6, PR 57); device-truth metrics (device
        # step time, MFU, grad-norm, fp16 skips, memory) are sampled at a
        # periodic block_until_ready fence so async dispatch survives.
        tcfg = config.telemetry_config
        self.telemetry = None
        self._telemetry_flops: Optional[float] = None  # None=unprobed, 0=n/a
        self._compile_sub = None
        self._watch: Optional[TrainWatch] = None   # the host, from inside
        self._fence_t: Optional[float] = None
        self._fence_step = 0
        self._fence_tokens = 0
        self._owned_sink = None
        # span-graph tracer (ISSUE 11): step windows, sentinel-check
        # fences, rewind recovery and checkpoint save/load — all stamped
        # host-side at fences that already exist (default off)
        self.tracer = None
        self._train_trace = None
        self._spans_sink = None
        if tcfg.enabled:
            from deepspeed_tpu import telemetry as _tele

            self.telemetry = _tele.get_registry()
            if tcfg.jsonl_path and jax.process_index() == 0 \
                    and self.telemetry.sink is None:
                try:
                    self._owned_sink = _tele.JsonlSink(tcfg.jsonl_path)
                    self.telemetry.attach_sink(self._owned_sink)
                except Exception as e:
                    logger.warning(f"telemetry jsonl sink disabled: {e}")
            if tcfg.spans:
                span_sink = None
                if tcfg.spans_path and jax.process_index() == 0:
                    try:
                        self._spans_sink = _tele.JsonlSink(tcfg.spans_path)
                        span_sink = self._spans_sink
                    except Exception as e:
                        logger.warning(f"telemetry spans sink disabled: {e}")
                if span_sink is None:
                    span_sink = self.telemetry.sink  # interleave, if any
                self.tracer = _tele.SpanTracer(sink=span_sink)
                self._train_trace = self.tracer.new_trace()
            # what this process has traced, lowered, compiled and loaded so
            # far, and what it will while this engine is at work: entry/*
            log = compile_log()
            engine = weakref.ref(self)

            def follows():
                # this engine's own: the event arrives inside its
                # constructor or one of its step entry points
                live = engine()
                return live is not None and live._at_work

            self._compile_sub = log.subscribe(self.telemetry,
                                              follows=follows)
            weakref.finalize(self, log.unsubscribe, self._compile_sub)
            setup_weights.publish(self.telemetry, self.tracer,
                                  trace_id=self._train_trace)

            def span(name, t0, t1, **attrs):
                live = engine()
                if live is not None and live.tracer is not None:
                    live.tracer.record(name, t0, t1,
                                       trace_id=live._train_trace, **attrs)
                return t1

            # stalls of the step loop and the collector's pauses, where the
            # compile log is followed and for as long (ISSUE 57); the
            # tracer's clock here is perf_counter, the watch's own
            self._watch = TrainWatch(self.telemetry, at_work=follows,
                                     span=span, gc_span=span)
            weakref.finalize(self, self._watch.close)
        # ---- flight recorder + SLO seam (ISSUE 13): the recorder tees
        # the telemetry/span streams into bounded rings and dumps one
        # postmortem JSON when the sentinel hits an actionable anomaly;
        # an SLOEngine attached via set_slo() is evaluated at the
        # sentinel's existing check fence (no extra device syncs).
        self.flight_recorder = None
        self.slo = None
        # the sink THIS engine attached to the (global) registry — the
        # owned JsonlSink itself, or the flight-recorder tee wrapping
        # it. _shutdown compares against this, not _owned_sink: with
        # the tee in place an identity check on the bare sink would
        # never match and the registry would keep a closed sink
        self._attached_sink = self._owned_sink
        if tcfg.enabled and tcfg.flight_recorder:
            from deepspeed_tpu import telemetry as _tele

            self.flight_recorder = _tele.FlightRecorder(
                dump_dir=tcfg.flight_dir or None, registry=self.telemetry)
            self._watch.recorder = self.flight_recorder
            self._attached_sink = self.flight_recorder.tee(
                self.telemetry.sink)
            self.telemetry.attach_sink(self._attached_sink)
            if self.tracer is not None:
                if self.tracer.sink is self._spans_sink \
                        and self._spans_sink is not None:
                    self.tracer.sink = self.flight_recorder.tee(
                        self._spans_sink)
                else:
                    # interleaved spans ride the registry sink, which is
                    # now the tee — point the tracer at the same tee so
                    # spans are recorded exactly once
                    self.tracer.sink = self.telemetry.sink
        # ---- training resilience (ISSUE 10): anomaly sentinel + finite-grad
        # guard + rewind-and-skip auto-recovery + SDC audits. The sentinel
        # consumes per-step device scalars lazily: they queue as jax arrays
        # and are fetched in ONE batch at the check fence, so detection adds
        # no per-step syncs.
        rcfg = config.resilience_config
        self.resilience_config = rcfg
        self._check_finite_grads = (rcfg.check_finite_grads
                                    if rcfg.check_finite_grads is not None
                                    else rcfg.enabled)
        self.sentinel = None
        self._pending_anomaly_reads: list = []
        self._rewind_budget = None
        self._rewinds_since_clean = 0
        self._resilience_baseline_saved = False
        self._sdc_quarantine_cb: Optional[Callable] = None
        self.sdc_suspect_devices: Tuple[int, ...] = ()
        self.rewind_log: list = []
        if rcfg.enabled:
            from deepspeed_tpu.elasticity.elastic_agent import (
                RollingWindowBudget)
            from deepspeed_tpu.runtime.sentinel import TrainingSentinel

            self.sentinel = TrainingSentinel(
                window=rcfg.window, min_history=rcfg.min_history,
                spike_zscore=rcfg.spike_zscore,
                divergence_patience=rcfg.divergence_patience,
                fp16=self.fp16_enabled)
            self._rewind_budget = RollingWindowBudget(
                rcfg.max_rewinds, rcfg.rewind_window_s)
        self._sentinel_interval = rcfg.check_interval or (
            tcfg.sync_interval if (self.telemetry is not None
                                   and tcfg.sync_interval) else 1)
        import deepspeed_tpu.comm as dist

        dist.configure(comms_config=None, enabled=config.comms_logger_config.enabled,
                       prof_all=config.comms_logger_config.prof_all,
                       prof_ops=config.comms_logger_config.prof_ops,
                       verbose=config.comms_logger_config.verbose)

        log_dist(
            f"DeepSpeedEngine: zero_stage={self.zero_stage} dtype={self.compute_dtype.__name__} "
            f"mesh={dict(zip(topology.get_axis_names(), topology.mesh_shape))} "
            f"batch triple=({config.train_batch_size},{config.train_micro_batch_size_per_gpu},"
            f"{config.gradient_accumulation_steps})", ranks=[0])

    # ------------------------------------------------------------------ specs
    def _build_shardings(self):
        mesh = self.mesh
        params_shape = jax.eval_shape(self.module.init, self._rng_placeholder())
        self._params_shape = params_shape
        self.master_specs = self.plan.master_specs(params_shape, self.logical_axes)
        self.compute_specs = self.plan.compute_specs(params_shape, self.logical_axes)
        self.grad_specs = self.plan.grad_specs(params_shape, self.logical_axes)
        # stage 3's gathers, stated by the model where it uses a parameter
        # (models/base.gathered); None when nothing is sharded for compute
        self._param_use = self.plan.param_use(params_shape, self.logical_axes)
        mem_kind = "pinned_host" if (self.offload_optimizer and
                                     self.accelerator.name() == "tpu") else None
        self.master_shardings = self.plan.shardings(self.master_specs)
        if self._onebit_compressed:
            # error-feedback tensors are PER-DEVICE state: leading [dp] dim
            # sharded over the data axis (never replicated)
            opt_state_shape = jax.eval_shape(self._onebit_opt_init, params_shape)
            specs = self._specs_like(opt_state_shape)
            err = lambda t: jax.tree_util.tree_map(lambda _: P("data"), t)
            self.opt_specs = specs._replace(
                worker_error=err(opt_state_shape.worker_error),
                server_error=err(opt_state_shape.server_error))
            self.opt_shardings = self.plan.shardings(self.opt_specs)
        elif self._host_opt is None:
            opt_state_shape = jax.eval_shape(self.optimizer.init, params_shape)
            self.opt_specs = self._specs_like(opt_state_shape)
            self.opt_shardings = self.plan.shardings(self.opt_specs, memory_kind=mem_kind)
        else:  # optimizer state lives host-side in self._host_opt
            self.opt_specs = None
            self.opt_shardings = {}
        self._replicated = NamedSharding(mesh, P())
        self.state_shardings = TrainState(
            params=self.master_shardings,
            opt_state=self.opt_shardings,
            scaler=jax.tree_util.tree_map(lambda _: self._replicated,
                                          self.loss_scaler.init()),
            global_step=self._replicated,
        )

    def _rng_placeholder(self):
        return jax.random.PRNGKey(0)

    def _specs_like(self, tree_shape):
        """Map arbitrary state trees (optimizer moments) to master specs by
        shape-matching against params; scalars/unknown shapes replicate."""
        shape_to_spec: Dict[Tuple, P] = {}

        def record(p, spec):
            shape_to_spec.setdefault(tuple(p.shape), spec)

        jax.tree_util.tree_map(record, self._params_shape, self.master_specs,
                               is_leaf=lambda x: isinstance(x, P))

        def assign(leaf):
            s = tuple(leaf.shape)
            if s in shape_to_spec:
                return shape_to_spec[s]
            if len(s) == 0:
                return P()
            return self.plan.master_spec(s, None)

        return jax.tree_util.tree_map(assign, tree_shape)

    # ------------------------------------------------------------------- init
    def _init_state(self) -> TrainState:
        init_params = jax.jit(self.module.init, out_shardings=self.master_shardings)
        params = init_params(self._init_rng)
        self._params_treedef = jax.tree_util.tree_structure(params)
        scaler_state = self.loss_scaler.init()
        if self._host_opt is not None:
            # masters go to host; device keeps only the compute-dtype image
            self._host_opt.init(params)
            cast = jax.jit(
                lambda p: jax.tree_util.tree_map(
                    lambda x: x.astype(self.compute_dtype)
                    if x.dtype == jnp.float32 else x, p),
                out_shardings=self.master_shardings, donate_argnums=0)
            return TrainState(params=cast(params), opt_state={},
                              scaler=scaler_state,
                              global_step=jnp.zeros((), jnp.int32))
        opt_init = self._onebit_opt_init if self._onebit_compressed \
            else self.optimizer.init
        # dstpu-lint: disable=recompile-hazard -- one-shot optimizer-state init at engine construction
        opt_state = jax.jit(opt_init, out_shardings=self.opt_shardings)(params)
        return TrainState(params=params, opt_state=opt_state, scaler=scaler_state,
                          global_step=jnp.zeros((), jnp.int32))

    def _onebit_opt_init(self, params):
        """Optimizer state for the compressed 1-bit path: worker/server
        error carriers get a leading [dp] device dim (per-device distinct,
        sharded over the data axis)."""
        base = self.optimizer.init(params)
        dp = self.topology.data_parallel_size
        stack = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.zeros((dp,) + a.shape, a.dtype), t)
        return base._replace(worker_error=stack(base.worker_error),
                             server_error=stack(base.server_error))

    # ---------------------------------------------------------- micro helpers
    def _cast_for_compute(self, params):
        specs = self.compute_specs

        def cast(p, spec):
            c = p.astype(self.compute_dtype) if p.dtype == jnp.float32 else p
            return jax.lax.with_sharding_constraint(c, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(cast, params, specs)

    def _micro_loss_and_grads(self, params, batch, scale, rng, pld_theta=None,
                              constrain=True, ltd_keep=None):
        """Single microbatch loss+grads in compute dtype; grads carry the
        stage-dependent sharding constraint (→ reduce-scatter from stage 2).
        ``constrain=False`` drops the NamedSharding constraints for callers
        already inside a shard_map manual context (the 1-bit path)."""
        kwargs = {"pld_theta": pld_theta} if pld_theta is not None else {}
        if ltd_keep is not None:
            kwargs["ltd_keep"] = ltd_keep

        def loss_fn(master_params):
            cparams = self._cast_for_compute(master_params) if constrain else \
                jax.tree_util.tree_map(
                    lambda x: x.astype(self.compute_dtype)
                    if x.dtype == jnp.float32 else x, master_params)
            with stating_param_use(self._param_use if constrain else None):
                loss, metrics = self.module.apply(cparams, batch,
                                                  rngs={"dropout": rng},
                                                  train=True, **kwargs)
            return loss * scale, metrics

        # scopes name the fused step's parts in a device profile; they
        # change op metadata only, never the program
        with jax.named_scope("dstpu_fwd_bwd"):
            (scaled_loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
        # grads accumulate in grad_accum_dtype (reference data_types.
        # grad_accum_dtype): bf16 halves the accumulation buffer
        acc_dt = jnp.bfloat16 if self.config.grad_accum_dtype == "bf16" \
            else jnp.float32
        if constrain:
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g.astype(acc_dt), NamedSharding(self.mesh, s)),
                grads, self.grad_specs)
        else:
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(acc_dt), grads)
        return scaled_loss, grads, metrics

    def _apply_grads(self, state: TrainState, grads, lr):
        """unscale → overflow check → clip → optimizer → scale update.
        (_take_model_step analog, engine.py:1886)."""
        inv = 1.0 / state.scaler.cur_scale
        grads = jax.tree_util.tree_map(lambda g: g * inv, grads)
        if self.fp16_enabled or self._check_finite_grads:
            # fp16: dynamic-loss-scale overflow. bf16/fp32 with the
            # finite-grad guard (ISSUE 10 satellite): a nonfinite grad —
            # poisoned batch, numeric blow-up — must not step into the
            # params; same skip-and-count semantics as the fp16 path
            # (global_step below advances only on applied updates).
            overflow = has_inf_or_nan(grads)
        else:
            overflow = jnp.zeros((), bool)
        norm = global_grad_norm(grads)
        if self.config.gradient_clipping > 0:
            grads, norm = clip_grads_by_global_norm(grads, self.config.gradient_clipping, norm)
        new_params, new_opt = self.optimizer.step(state.params, grads, state.opt_state, lr)
        # skip the update on overflow (dynamic loss scaling semantics)
        new_params = jax.tree_util.tree_map(
            lambda old, new: jnp.where(overflow, old, new), state.params, new_params)
        new_opt = jax.tree_util.tree_map(
            lambda old, new: jnp.where(overflow, old, new), state.opt_state, new_opt)
        new_scaler = self.loss_scaler.update(state.scaler, overflow)
        new_state = TrainState(params=new_params, opt_state=new_opt, scaler=new_scaler,
                               global_step=state.global_step + 1 - overflow.astype(jnp.int32))
        return new_state, overflow, norm

    # ---------------------------------------------------- shared step pieces
    def _scan_micro_grads(self, state: TrainState, batch, rng, pld_theta=None,
                          constrain=True, rng_fold=None, ltd_keep=None):
        """Grad-accumulation scan over the gas microbatches (shared by the
        fused device step, the host-offload grad step and the 1-bit
        shard_map step). ``rng_fold(rng, i)`` customizes the per-microbatch
        rng derivation (the 1-bit path folds in the device index)."""
        scale = state.scaler.cur_scale
        rng_fold = rng_fold or jax.random.fold_in

        def micro(carry, mb_and_i):
            grads_acc, loss_acc = carry
            mb, i = mb_and_i
            sub = rng_fold(rng, i)
            _, grads, metrics = self._micro_loss_and_grads(
                state.params, mb, scale, sub, pld_theta, constrain=constrain,
                ltd_keep=ltd_keep)
            with jax.named_scope("dstpu_accumulate"):
                grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc,
                                                   grads)
            return (grads_acc, loss_acc + metrics["loss"]), None

        acc_dt = jnp.bfloat16 if self.config.grad_accum_dtype == "bf16" \
            else jnp.float32
        if constrain:
            grads0 = jax.tree_util.tree_map(
                lambda p, s: jax.lax.with_sharding_constraint(
                    jnp.zeros(p.shape, acc_dt), NamedSharding(self.mesh, s)),
                state.params, self.grad_specs)
        else:
            grads0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dt), state.params)
        (grads, loss_sum), _ = jax.lax.scan(
            micro, (grads0, jnp.zeros((), jnp.float32)),
            (batch, jnp.arange(self.gas)))
        return grads, loss_sum

    def _unscale_epilogue(self, grads, scaler):
        """gas-mean + loss-scale unscale + overflow/norm (shared epilogue of
        both host-step entry points)."""
        inv = 1.0 / (self.gas * scaler.cur_scale)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32) * inv, grads)
        overflow = has_inf_or_nan(grads) \
            if (self.fp16_enabled or self._check_finite_grads) \
            else jnp.zeros((), bool)
        return grads, overflow, global_grad_norm(grads)

    # ---------------------------------------------------- host (offload) step
    def _build_grad_step(self):
        """Compiled grad-accumulation-only step for the host-optimizer path:
        returns mean unscaled grads + metrics; the optimizer update happens
        on the CPU (ZeRO-Offload semantics)."""

        def grad_step(state: TrainState, batch, rng, ltd_keep=None):
            grads, loss_sum = self._scan_micro_grads(state, batch, rng,
                                                     ltd_keep=ltd_keep)
            grads, overflow, norm = self._unscale_epilogue(grads, state.scaler)
            # host optimizer consumes grads in the MASTER layout: each
            # process updates exactly the master shards it owns (multi-host
            # offload partitioning; single-host this is a no-op reshard)
            grads = jax.tree_util.tree_map(
                lambda g, s: jax.lax.with_sharding_constraint(
                    g, NamedSharding(self.mesh, s)), grads, self.master_specs)
            metrics = {"loss": loss_sum / self.gas, "overflow": overflow,
                       "grad_norm": norm, "loss_scale": state.scaler.cur_scale}
            return grads, metrics

        # ltd_keep static: shapes depend on it (same contract as the
        # fused train step)
        self._compiled_grad_step = jax.jit(grad_step, static_argnums=(3,))
        return self._compiled_grad_step

    def _host_apply(self, grads, overflow: bool, norm: float, lr):
        """CPU optimizer update on host masters; push compute-dtype params
        back (reference cpu-offload step: grads→CPU, Adam, params→device)."""
        new_scaler = jax.device_put(
            self.loss_scaler.update(self.state.scaler, jnp.asarray(overflow)),
            jax.tree_util.tree_map(lambda _: self._replicated, self.state.scaler))
        if overflow:
            self.skipped_steps += 1
            self.state = self.state._replace(scaler=new_scaler)
            return
        clip = self.config.gradient_clipping
        factor = min(1.0, clip / (norm + 1e-6)) if clip and clip > 0 else 1.0
        # align grads to the MASTER layout (no-op when already aligned; the
        # fused grad_step constrains in-program, but the manual
        # forward/backward/step path reaches here with grad-spec placement)
        grads = jax.device_put(grads, self.master_shardings)
        grads_host = self._host_opt.grads_to_host(grads)
        out = self._host_opt.step(grads_host, lr=float(np.asarray(lr)),
                                  grad_scale=factor)
        new_params = self._host_opt.images_to_device(
            out, self._params_treedef, self.master_shardings)
        self.state = TrainState(
            params=new_params, opt_state={}, scaler=new_scaler,
            global_step=self.state.global_step + 1)

    # -------------------------------------------------------- fused train step
    def _build_train_step(self, batch=None):
        if self._onebit_compressed:
            return self._build_onebit_train_step(batch)
        gas = self.gas

        def train_step(state: TrainState, batch, lr, rng, pld_theta=None,
                       ltd_keep=None):
            grads, loss_sum = self._scan_micro_grads(state, batch, rng,
                                                     pld_theta,
                                                     ltd_keep=ltd_keep)
            with jax.named_scope("dstpu_optimizer"):
                # back to f32 for unscale/clip/optimizer regardless of the
                # accumulation dtype
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) / gas, grads)
                new_state, overflow, norm = self._apply_grads(state, grads,
                                                              lr)
            metrics = {"loss": loss_sum / gas, "overflow": overflow, "grad_norm": norm,
                       "loss_scale": state.scaler.cur_scale}
            return new_state, metrics

        batch_sharding_fn = self._gas_batch_shardings
        # ltd_keep is STATIC (it sets gather/scatter shapes): one compile
        # per schedule granule, bounded by the scheduler's seq_per_step
        self._compiled_train_step = jax.jit(train_step, donate_argnums=(0,),
                                            static_argnums=(5,))
        # subclass step builders (pipeline engine) and the 1-bit path keep
        # the 4-arg signature; _run_fused_step checks this flag
        self._step_takes_extra_args = True
        return self._compiled_train_step

    def _build_onebit_train_step(self, batch):
        """Compressed-comm train step (reference: engine disables backward
        allreduce for 1-bit optimizers and lets compressed_allreduce carry
        the sync — runtime/comm/nccl.py:54). shard_map over the data axis
        keeps grads LOCAL; the optimizer's error-compensated momentum sync
        is the only cross-device traffic (int8 signs over ICI)."""
        if self._use_pld:
            log_dist("progressive_layer_drop is not supported on the 1-bit "
                     "compressed path; disabling", ranks=[0])
            self._use_pld = False
        if self.config.gradient_clipping and self.config.gradient_clipping > 0:
            # the global grad norm is undefined when grads never leave the
            # device (only the momentum is synced) — same limitation as the
            # reference's 1-bit optimizers; grad_norm stays a diagnostic
            # (norm of the concatenated local grads)
            log_dist("gradient_clipping is not supported with compressed "
                     "1-bit communication; ignoring (reference 1-bit Adam "
                     "has the same limitation)", ranks=[0])

        mesh, gas, opt = self.mesh, self.gas, self.optimizer
        fp16 = self.fp16_enabled
        loss_scaler = self.loss_scaler

        rep = lambda t: jax.tree_util.tree_map(lambda _: P(), t)
        err_specs = jax.tree_util.tree_map(
            lambda _: P("data"), self.state.opt_state.worker_error)
        state_specs = TrainState(
            params=rep(self.state.params),
            opt_state=rep(self.state.opt_state)._replace(
                worker_error=err_specs, server_error=err_specs),
            scaler=rep(self.state.scaler),
            global_step=P())
        batch_specs = jax.tree_util.tree_map(
            lambda x: P(None, *self.plan.batch_spec(x.ndim - 1)), batch)
        metric_specs = {"loss": P(), "overflow": P(), "grad_norm": P(),
                        "loss_scale": P()}

        def step(state: TrainState, batch, lr, rng):
            params = state.params
            drop0 = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            add0 = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            my = jax.lax.axis_index("data")

            grads, loss_sum = self._scan_micro_grads(
                state, batch, rng, constrain=False,
                rng_fold=lambda r, i: jax.random.fold_in(
                    jax.random.fold_in(r, i), my))
            grads, overflow, _ = self._unscale_epilogue(grads, state.scaler)
            if fp16:
                overflow = jax.lax.psum(
                    overflow.astype(jnp.int32), "data") > 0
            # diagnostic only — NOT used for clipping (see builder note):
            # norm of the concatenated per-device local grads
            # (fp16/fused_optimizer get_grad_norm over local groups)
            sumsq = sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree_util.tree_leaves(grads))
            norm = jnp.sqrt(jax.lax.psum(sumsq, "data"))
            inner = state.opt_state._replace(
                worker_error=drop0(state.opt_state.worker_error),
                server_error=drop0(state.opt_state.server_error))
            new_p, new_opt = opt.step(params, grads, inner, lr,
                                      axis_name="data")
            skip = lambda old, new: jax.tree_util.tree_map(
                lambda o, n: jnp.where(overflow, o, n), old, new)
            new_p = skip(params, new_p)
            new_opt = skip(inner, new_opt)
            new_state = TrainState(
                params=new_p,
                opt_state=new_opt._replace(
                    worker_error=add0(new_opt.worker_error),
                    server_error=add0(new_opt.server_error)),
                scaler=loss_scaler.update(state.scaler, overflow),
                global_step=state.global_step + 1 - overflow.astype(jnp.int32))
            metrics = {"loss": jax.lax.pmean(loss_sum / gas, "data"),
                       "overflow": overflow, "grad_norm": norm,
                       "loss_scale": state.scaler.cur_scale}
            return new_state, metrics

        sharded = shard_map(
            step, mesh=mesh,
            in_specs=(state_specs, batch_specs, P(), P()),
            out_specs=(state_specs, metric_specs),
            # params/moments stay consensus by construction (compressed sync
            # ends in an allgather reconstruction identical on every device)
            # — vma typing cannot prove that statically
            check_vma=False)
        def train_step(state, batch, lr, rng):   # the fused step's one name
            return sharded(state, batch, lr, rng)

        self._compiled_train_step = jax.jit(train_step, donate_argnums=(0,))
        return self._compiled_train_step

    def _gas_batch_shardings(self, batch):
        def shard(x):
            spec = self.plan.batch_spec(x.ndim - 1)
            return NamedSharding(self.mesh, P(None, *spec))
        return jax.tree_util.tree_map(shard, batch)

    def _batch_shardings(self, batch):
        return jax.tree_util.tree_map(
            lambda x: NamedSharding(self.mesh, self.plan.batch_spec(x.ndim)), batch)

    # --------------------------------------------------------------- user API
    def _ensure_train_iter(self):
        """Engine-owned repeating iterator over ``training_dataloader``
        (rebuilt after a checkpoint load / anomaly rewind invalidates it)."""
        assert self.training_dataloader is not None, \
            "train_batch needs a data_iter or training_data at init"
        if not hasattr(self, "_train_iter") or self._train_iter is None:
            from deepspeed_tpu.runtime.dataloader import RepeatingLoader

            self._train_iter = iter(RepeatingLoader(self.training_dataloader))
        return self._train_iter

    def train_batch(self, data_iter: Optional[Iterator] = None):
        """Pull ``gas`` microbatches, run ONE fused compiled step.
        Microbatch leaves are stacked on a leading [gas] dim."""
        # anomaly rewind can only fast-forward a stream the ENGINE owns;
        # track which source fed the step so recovery never rewinds the
        # engine loader while a caller-supplied iterator keeps advancing
        self._engine_owned_stream = data_iter is None
        if data_iter is None:
            # baseline checkpoint BEFORE the first pull: the rewind target
            # of an anomaly in the first interval must pair step-0 params
            # with dataloader offset 0, or the resumed stream desyncs
            if (self.sentinel is not None
                    and self.resilience_config.checkpoint_dir
                    and not self._resilience_baseline_saved):
                self._resilience_baseline_saved = True
                self.save_checkpoint(self.resilience_config.checkpoint_dir)
            data_iter = self._ensure_train_iter()
        micro_batches = [next(data_iter) for _ in range(self.gas)]
        batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *micro_batches)
        return self._run_fused_step(batch)

    def train_batch_from_stacked(self, batch):
        """As train_batch, but the caller supplies the [gas, ...] stacked batch."""
        self._engine_owned_stream = False  # caller owns the data stream
        return self._run_fused_step(batch)

    @at_work
    def _run_fused_step(self, batch):
        h = getattr(self, "_preemption_handler", None)
        if h is not None:
            h.poll()  # deferred preemption: final save at the step boundary
        if self._host_opt is not None:
            return self._run_host_step(batch)
        # the step that builds its program is set-up (``entry/*`` holds it):
        # the host watch joins at the next
        watch = self._watch
        if self._compiled_train_step is None:
            watch = None
            if self.telemetry is not None:
                self._setup_first_step = SetupPhase("first_step")
            self._build_train_step(batch)
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        t_start = time.perf_counter()
        # host annotations name the device's idle gaps around the step on
        # the profiler's clock; no fence. With a registry the host watch
        # holds them (a collection can then take the open one's place) and
        # stamps the edge behind each: three clock reads a step
        if watch is None:
            put, run, after = (jax.profiler.TraceAnnotation(name)
                               for name in _STEP_ANNOTATIONS)
        else:
            put, run, after = watch.phases
            watch.enter(t_start)
        with put:
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            rng = jax.random.fold_in(self._dropout_rng, self.global_steps)
            batch = self._apply_curriculum(batch)
            batch = jax.device_put(batch, self._gas_batch_shardings(batch))
        ltd_keep = None
        if self._use_random_ltd:
            seq_len = int(batch["input_ids"].shape[-1]) \
                if isinstance(batch, dict) and "input_ids" in batch else None
            keep = self.random_ltd_scheduler.update_seq(self.global_steps)
            if seq_len is None or keep < seq_len:
                ltd_keep = keep
        with run:
            if self._use_pld:
                theta = jnp.asarray(self.progressive_layer_drop.get_theta(),
                                    jnp.float32)
                self.state, metrics = self._compiled_train_step(
                    self.state, batch, lr, rng, theta)
            elif not getattr(self, "_step_takes_extra_args", False):
                # 1-bit shard_map step and subclass (pipeline) step builders
                # keep the 4-arg signature
                if ltd_keep is not None and not getattr(self, "_ltd_warned",
                                                        False):
                    log_dist("random_ltd: this engine's train step does not "
                             "route tokens — schedule tracked but NOT applied",
                             ranks=[0])
                    self._ltd_warned = True
                self.state, metrics = self._compiled_train_step(
                    self.state, batch, lr, rng)
            else:
                self.state, metrics = self._compiled_train_step(
                    self.state, batch, lr, rng, None, ltd_keep)
        with after:
            self._global_grad_norm = metrics["grad_norm"]
            self.micro_steps += self.gas
            self.global_steps += 1
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            self._after_step(metrics)
            self.timers(TRAIN_BATCH_TIMER).stop(record=True)
            self.tput_timer.stop(global_step=True)
            if self.telemetry is not None:
                self._record_step_telemetry(
                    metrics, batch, time.perf_counter() - t_start,
                    ltd_keep=ltd_keep)
            if self.sentinel is not None:
                self._resilience_step(metrics, batch)
        if self._sync_each_step:
            # dstpu-lint: fence=opt-in per-step fence (config sync_each_step)
            jax.block_until_ready(self.state.params)
        if watch is not None:
            watch.leave()
        return metrics["loss"]

    def _run_host_step(self, batch):
        if getattr(self, "_compiled_grad_step", None) is None:
            self._build_grad_step()
        self.tput_timer.start()
        self.timers(TRAIN_BATCH_TIMER).start()
        t_start = time.perf_counter()
        lr = self.get_lr()[0]
        rng = jax.random.fold_in(self._dropout_rng, self.global_steps)
        batch = self._apply_curriculum(batch)
        batch = jax.device_put(batch, self._gas_batch_shardings(batch))
        ltd_keep = None
        if self._use_random_ltd:
            seq_len = int(batch["input_ids"].shape[-1]) \
                if isinstance(batch, dict) and "input_ids" in batch else None
            keep = self.random_ltd_scheduler.update_seq(self.global_steps)
            if seq_len is None or keep < seq_len:
                ltd_keep = keep
        grads, metrics = self._compiled_grad_step(self.state, batch, rng,
                                                  ltd_keep)
        overflow = bool(jax.device_get(metrics["overflow"]))  # dstpu-lint: fence=host-optimizer path: overflow/norm gate the host apply
        norm = float(jax.device_get(metrics["grad_norm"]))  # dstpu-lint: fence=host-optimizer path: overflow/norm gate the host apply
        self._host_apply(grads, overflow, norm, lr)
        self._global_grad_norm = metrics["grad_norm"]
        self.micro_steps += self.gas
        self.global_steps += 1
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        self._after_step(metrics)
        self.timers(TRAIN_BATCH_TIMER).stop(record=True)
        self.tput_timer.stop(global_step=True)
        if self.telemetry is not None:
            # host-optimizer path: the update already synchronized on the
            # grads, so wall time here IS device time
            self._record_step_telemetry(
                metrics, batch, time.perf_counter() - t_start)
        if self.sentinel is not None:
            self._resilience_step(metrics, batch)
        if self._sync_each_step:
            # dstpu-lint: fence=opt-in per-step fence (config sync_each_step)
            jax.block_until_ready(self.state.params)
        return metrics["loss"]

    def _apply_curriculum(self, batch):
        """Legacy curriculum: truncate sequences to the scheduled difficulty
        (reference engine.py:1653-1656 curriculum_seqlen). Host-side slicing
        — each distinct seqlen is one compile."""
        if self.curriculum_scheduler is None:
            return batch
        seqlen = self.curriculum_scheduler.update_difficulty(
            self.global_steps + 1)
        seq_keys = {"input_ids", "labels", "attention_mask",
                    "token_type_ids", "position_ids"}

        def trunc(node):
            if isinstance(node, dict):
                return {k: (v[..., :seqlen]
                            if k in seq_keys and hasattr(v, "ndim") and
                            v.ndim >= 2 else trunc(v))
                        for k, v in node.items()}
            return node

        return trunc(batch)

    def _after_step(self, metrics):
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        self._after_step_impl(metrics)

    def _after_step_impl(self, metrics):
        cfg = self.config
        if self._debug_mode and cfg.steps_per_print and \
                self.global_steps % cfg.steps_per_print == 0:
            from deepspeed_tpu.utils.debug import check_sharding_invariants

            for p in check_sharding_invariants(self):
                logger.warning("sharding invariant (step %d): %s",
                               self.global_steps, p)
        # autotuning experiment: report throughput after warmup then exit
        # (reference exits inside engine.forward:1687-1691 once profiled)
        result_path = os.environ.get("DSTPU_AUTOTUNING_RESULT")
        if result_path:
            # fence EVERY armed step before tput_timer.stop(): under async
            # dispatch the timer otherwise brackets only the dispatch and
            # self-reports physically impossible rates (36M tokens/sec
            # observed in round 4)
            float(jax.device_get(metrics["loss"]))  # dstpu-lint: fence=autotune armed-step fence: honest rates
        if result_path and self.global_steps >= 5:
            import json as _json

            samples_per_sec = self.tput_timer.avg_samples_per_sec() or 0.0
            with open(result_path, "w") as f:
                _json.dump({"metric": samples_per_sec,
                            "unit": "samples/sec"}, f)
            log_dist(f"autotuning: wrote metric {samples_per_sec:.2f} "
                     f"samples/sec, exiting", ranks=[0])
            raise SystemExit(0)
        if self.fp16_enabled:
            # host round-trip only when someone asks; keep async by default
            pass
        # monitor cadence decoupled from print cadence (monitor_interval
        # config key; 0 = legacy coupling to steps_per_print)
        mon_interval = cfg.monitor_interval or max(cfg.steps_per_print or 0, 1)
        if self.monitor.enabled and self.global_steps % mon_interval == 0:
            self._step_fenced()
            # dstpu-lint: fence=monitor cadence read (mon_interval-gated)
            loss = float(jax.device_get(metrics["loss"]))
            events = [("Train/Samples/train_loss", loss, self.global_steps),
                      ("Train/Samples/lr", self.get_lr()[0], self.global_steps)]
            if self.fp16_enabled:
                events.append(("Train/Samples/loss_scale",
                               float(jax.device_get(metrics["loss_scale"])), self.global_steps))  # dstpu-lint: fence=monitor cadence read
            self.monitor.write_events(events)
        if cfg.steps_per_print and self.global_steps % cfg.steps_per_print == 0:
            self._step_fenced()
            # dstpu-lint: fence=steps_per_print cadence read
            loss = float(jax.device_get(metrics["loss"]))
            log_dist(f"step={self.global_steps} loss={loss:.4f} lr={self.get_lr()[0]:.3e}",
                     ranks=[0])
            if cfg.wall_clock_breakdown:
                self.timers.log([TRAIN_BATCH_TIMER, FORWARD_GLOBAL_TIMER,
                                 BACKWARD_GLOBAL_TIMER, STEP_GLOBAL_TIMER],
                                memory_breakdown=cfg.memory_breakdown)

    # -------------------------------------------------------------- telemetry
    @staticmethod
    def _batch_token_count(batch) -> int:
        """Tokens in one engine step (LM batches); sample count otherwise."""
        if isinstance(batch, dict) and "input_ids" in batch:
            try:
                return int(np.prod(np.shape(batch["input_ids"])))
            except Exception:
                pass
        return 0

    def _record_step_telemetry(self, metrics, batch, wall_dt: float,
                               ltd_keep=None):
        """Hot-path accounting: a histogram observe + two counter incs per
        step. Everything that would force a device sync (grad-norm, fp16
        skips, memory, device-time MFU) waits for the periodic fence."""
        reg = self.telemetry
        tokens = self._batch_token_count(batch)
        reg.counter("train/steps").inc()
        if tokens:
            self._fence_tokens += tokens
            reg.counter("train/tokens").inc(tokens)
        # dispatch-bounded under async dispatch (TPU); device truth comes
        # from the fence-to-fence gauge below
        reg.histogram("train/step_wall_ms").observe(wall_dt * 1e3)
        interval = self.config.telemetry_config.sync_interval
        if interval and (self.global_steps % interval == 0
                         or self.global_steps == 1):
            self._telemetry_fence(metrics, batch, ltd_keep)
        elif self._setup_first_step is not None:
            # this step brings no fence of the engine's own (sync_interval
            # is 0, or a resumed run built its step off the interval, where
            # the next fence lies whole steps away): the reading ends here,
            # at the dispatch, as an unfenced prefill_chunk's does
            self._close_first_step(fenced=False)

    def _close_first_step(self, fenced: bool) -> None:
        """``entry/setup_first_step_ms``: the fused step's build (trace,
        lowering, compile or cache load) and its first run, closed at the
        first telemetry fence behind it."""
        phase, self._setup_first_step = self._setup_first_step, None
        phase.close().publish(self.telemetry, self.tracer,
                              trace_id=self._train_trace, fenced=fenced)

    def _step_fenced(self) -> None:
        """This step ends at a wait for the device that the engine itself
        makes at a cadence (the telemetry fence, a print or monitor read,
        the sentinel's drain): its length is the device's, and the host
        watch judges neither it nor the gap it belongs to."""
        if self._watch is not None:
            self._watch.fenced()

    def _reset_telemetry_window(self):
        """Invalidate the fence-to-fence device-rate baseline. Called
        around work that is NOT training steps (checkpoint save/load) so
        a multi-second blocking save between fences is never charged to
        train/device_step_time_ms or train/mfu, nor read as a stall of
        the step loop."""
        if self._watch is not None:
            self._watch.forget()
        self._fence_t = None
        self._fence_step = self.global_steps
        self._fence_tokens = 0

    def _telemetry_fence(self, metrics, batch, ltd_keep=None):
        """Periodic block_until_ready fence: honest device-time step
        latency + MFU from fence-to-fence elapsed, plus the scalars whose
        read would otherwise break async dispatch. Assumes fence-to-fence
        wall time is training; engine-visible non-training work
        (checkpoint save/load) resets the window via
        _reset_telemetry_window. A caller-side stall between steps is
        still charged to the device rate here; the host watch names it
        (``host_stall``, phase ``caller``: telemetry/host_watch.py)."""
        reg = self.telemetry
        self._step_fenced()
        # dstpu-lint: fence=THE periodic telemetry fence (sync_interval): device-truth metrics
        jax.block_until_ready(self.state.params)
        if self._setup_first_step is not None:
            self._close_first_step(fenced=True)
        now = time.perf_counter()
        steps = self.global_steps - self._fence_step
        if self._fence_t is not None and steps > 0:
            if self.tracer is not None:
                # fence-to-fence window as one span: both instants were
                # observed at fences the untraced engine already paid
                self.tracer.record(
                    "step_window", self._fence_t, now,
                    trace_id=self._train_trace, steps=steps,
                    tokens=self._fence_tokens,
                    end_step=self.global_steps)
            dev_step_s = (now - self._fence_t) / steps
            reg.gauge("train/device_step_time_ms").set(dev_step_s * 1e3)
            if self._fence_tokens:
                reg.gauge("train/tokens_per_sec").set(
                    self._fence_tokens / (now - self._fence_t))
            flops = self._telemetry_flops  # probed at the previous fence
            if flops:
                reg.gauge("train/model_tflops").set(flops / dev_step_s / 1e12)
                from deepspeed_tpu.telemetry.mfu import mfu as _mfu

                u = _mfu(flops, dev_step_s)
                if u is not None:
                    reg.gauge("train/mfu").set(u)
        # probe flops AFTER reading the window so the probe's one-time
        # lower+compile never pollutes a device-rate sample; the first
        # fence is step 1, so the compile lands in warmup
        self._train_step_flops(batch, ltd_keep)
        self._fence_step = self.global_steps
        self._fence_tokens = 0
        # device-truth scalars: the fence already drained the pipeline, so
        # these fetches are free of extra sync
        try:
            reg.gauge("train/grad_norm").set(
                float(jax.device_get(metrics["grad_norm"])))  # dstpu-lint: fence=post-fence read: pipeline already drained
            reg.gauge("train/loss").set(
                float(jax.device_get(metrics["loss"])))  # dstpu-lint: fence=post-fence read: pipeline already drained
            if self.fp16_enabled:
                reg.gauge("train/loss_scale").set(
                    float(jax.device_get(metrics["loss_scale"])))  # dstpu-lint: fence=post-fence read: pipeline already drained
                # device global_step counts only successful steps; the host
                # counter counts all — the difference IS the skip count
                device_gs = int(jax.device_get(self.state.global_step))  # dstpu-lint: fence=post-fence read: pipeline already drained
                reg.gauge("train/fp16_skipped_steps").set(
                    max(self.global_steps - device_gs, 0))
            elif self._check_finite_grads:
                # same accounting for the bf16/fp32 finite-grad guard
                device_gs = int(jax.device_get(self.state.global_step))  # dstpu-lint: fence=post-fence read: pipeline already drained
                reg.gauge("train/nonfinite_skipped_steps").set(
                    max(self.global_steps - device_gs, 0))
        except Exception:
            pass
        stats = self.accelerator.memory_stats()
        if stats:
            reg.gauge("device/mem_in_use_bytes").set(
                stats.get("bytes_in_use", 0))
            reg.gauge("device/mem_peak_bytes").set(
                stats.get("peak_bytes_in_use", 0))
        reg.flush(step=self.global_steps)
        # window baseline AFTER the probe + fetches, so only training
        # steps are charged to the next fence-to-fence device rate
        self._fence_t = time.perf_counter()

    def _train_step_flops(self, batch, ltd_keep=None) -> Optional[float]:
        """Model flops of ONE fused train step, cached after first probe.
        Primary: XLA's own cost_analysis of the compiled step (post-fusion,
        includes remat recompute — the PaLM MFU numerator). Costs one extra
        lower+compile at the first fence (disable via
        telemetry.cost_analysis). Fallback: analytic 6*N*tokens."""
        if self._telemetry_flops is not None:
            return self._telemetry_flops or None
        flops = 0.0
        # the probe costs one extra lower+compile of the train step, so it
        # runs only where the result is actually consumed: a JSONL sink is
        # attached, or the accelerator has a peak entry (MFU computable —
        # real TPU, or DSTPU_PEAK_TFLOPS set). CPU unit tests take the
        # free analytic fallback.
        worth_probing = (self.telemetry.sink is not None
                         or self.accelerator.peak_tflops() is not None)
        if (self.config.telemetry_config.cost_analysis and worth_probing
                and self._compiled_train_step is not None
                and getattr(self, "_step_takes_extra_args", False)
                and not self._use_pld):
            try:
                lowered = self._compiled_train_step.lower(
                    self.state, batch,
                    jnp.zeros((), jnp.float32),
                    jax.random.PRNGKey(0), None, ltd_keep)
                ca = lowered.compile().cost_analysis()
                if isinstance(ca, list):
                    ca = ca[0] if ca else {}
                flops = float((ca or {}).get("flops", 0.0) or 0.0)
                # cost_analysis sees the PER-DEVICE partitioned module;
                # scale to global so both flops sources and the aggregate
                # peak denominator (mfu.peak_flops_per_sec over all chips)
                # agree. Replicated compute makes this a slight
                # overcount — acceptable for an MFU estimate.
                flops *= jax.device_count()
            except Exception as e:
                logger.warning("telemetry: cost_analysis of the train step "
                               "failed (%s: %s); using analytic flops",
                               type(e).__name__, e)
        if not flops:
            tokens = self._batch_token_count(batch)
            if tokens:
                n_params = sum(int(np.prod(l.shape)) for l in
                               jax.tree_util.tree_leaves(self._params_shape))
                flops = 6.0 * n_params * tokens
        self._telemetry_flops = flops
        return flops or None

    # ------------------------------------------------- resilience (ISSUE 10)
    def _resilience_step(self, metrics, batch):
        """Per-step sentinel bookkeeping. The scalars queue as device
        arrays; classification happens at the check fence (one batched
        device_get — free right after a telemetry fence, which shares the
        cadence by default). Auto-checkpoints are screened: the sentinel
        drains BEFORE a save so a detected-late anomaly can never be
        published as a rewind target."""
        rcfg = self.resilience_config
        self._pending_anomaly_reads.append(
            (self.global_steps, metrics.get("loss"),
             metrics.get("grad_norm"), metrics.get("overflow")))
        save_due = (rcfg.checkpoint_dir is not None and rcfg.checkpoint_interval
                    and self.global_steps % rcfg.checkpoint_interval == 0)
        # an SDC-armed run audits BEFORE every save too: a bit flipped
        # between audits must never be published into a rewind target,
        # where the recovery reload would re-replicate it to every device
        # and the corruption would pass all future audits
        audit_due = bool(rcfg.sdc_audit_interval) and (
            save_due or self.global_steps % rcfg.sdc_audit_interval == 0)
        replay_due = (rcfg.step_replay_interval
                      and self.global_steps % rcfg.step_replay_interval == 0)
        if not (save_due or audit_due or replay_due
                or self.global_steps % self._sentinel_interval == 0):
            return
        anomaly = self._sentinel_drain()
        if anomaly is None and audit_due:
            anomaly = self._sdc_audit_check()
        if anomaly is None and replay_due:
            anomaly = self._sdc_step_replay_check(batch)
        if self.slo is not None:
            # SLO judgment at the sentinel's existing fence (ISSUE 13):
            # the training SLIs (MFU floor, anomaly rate) read gauges/
            # counters the fence just refreshed — host-only, on the SLO
            # engine's own clock
            self.slo.maybe_evaluate()
        if anomaly is not None:
            self._recover_or_raise(anomaly)
            return
        # de-escalate the skip width only once training has cleanly passed
        # the last anomaly's region — a clean check while still replaying
        # toward it must not shrink the next escalation
        if self.global_steps > getattr(self, "_last_anomaly_step", -1):
            self._rewinds_since_clean = 0
        if save_due:
            self.save_checkpoint(rcfg.checkpoint_dir)

    def _sentinel_drain(self):
        """Classify every queued step; returns the first *actionable*
        anomaly (overflows are counted but the loss scaler already handled
        them). Entries after an actionable anomaly are dropped — they ran
        on suspect params and the rewind re-executes them anyway."""
        from deepspeed_tpu.runtime.sentinel import AnomalyClass

        if not self._pending_anomaly_reads:
            return None
        t0 = time.perf_counter() if self.tracer is not None else 0.0
        pending, self._pending_anomaly_reads = \
            self._pending_anomaly_reads, []
        self._step_fenced()
        # dstpu-lint: fence=sentinel drain: ONE batched fetch at the declared cadence
        vals = jax.device_get([(l, n, o) for _, l, n, o in pending])
        reg = self.telemetry
        found = None
        for (step, *_), (loss, norm, ovf) in zip(pending, vals):
            a = self.sentinel.observe(
                step,
                float(loss) if loss is not None else 0.0,
                float(norm) if norm is not None else 0.0,
                bool(ovf) if ovf is not None else False)
            if a is None:
                continue
            if reg is not None:
                reg.counter(f"resilience/anomalies_{a.cls}").inc()
            if a.cls != AnomalyClass.OVERFLOW:
                found = a
                break
        if self.tracer is not None:
            # the batched fetch above is the sentinel's existing fence —
            # the span just names it
            self.tracer.record(
                "sentinel_check", t0, time.perf_counter(),
                trace_id=self._train_trace, observations=len(pending),
                step=self.global_steps,
                anomaly=(found.cls if found is not None else None))
        return found

    def _sdc_audit_check(self):
        """Cross-data-parallel-replica checksum agreement over params +
        optimizer state (replicas are bit-identical by construction; see
        sentinel.sdc_audit). A mismatch quarantines the suspect device —
        counted, evented, and surfaced to the elastic agent via
        ``set_sdc_quarantine_callback`` — and returns an SDC anomaly so
        recovery rewinds (the reload re-replicates clean bytes)."""
        from deepspeed_tpu import telemetry as _tele
        from deepspeed_tpu.runtime.sentinel import (
            AnomalyClass, TrainingAnomaly, sdc_audit)

        res = sdc_audit({"params": self.state.params,
                         "opt_state": self.state.opt_state})
        reg = self.telemetry
        if reg is not None:
            reg.counter("resilience/sdc_audits").inc()
        if res.ok:
            self.sdc_suspect_devices = ()  # healed / transient: un-flag
            return None
        self.sdc_suspect_devices = res.suspects
        if reg is not None:
            reg.counter("resilience/sdc_mismatches").inc()
        _tele.record_event("resilience/sdc_quarantine",
                           step=self.global_steps,
                           suspect_devices=list(res.suspects),
                           mismatched_groups=res.mismatched_groups)
        logger.error(
            "SDC audit: %d/%d replica groups disagree; suspect device(s) "
            "%s quarantined", res.mismatched_groups, res.n_groups,
            list(res.suspects))
        if self._sdc_quarantine_cb is not None:
            try:
                self._sdc_quarantine_cb(res)
            except Exception as e:
                logger.warning("sdc quarantine callback failed: %s", e)
        detail = (f"{res.mismatched_groups}/{res.n_groups} replica groups "
                  f"disagree; suspects {list(res.suspects)}")
        return TrainingAnomaly(AnomalyClass.SDC, self.global_steps,
                               float(res.mismatched_groups), 0.0, detail)

    def set_sdc_quarantine_callback(self, cb):
        """Hook for the elastic agent / launcher: called with the
        :class:`~deepspeed_tpu.runtime.sentinel.SDCAuditResult` when an
        audit finds a deviating replica, so the supervisor can exclude the
        host from the next worker group."""
        self._sdc_quarantine_cb = cb

    def set_slo(self, slo) -> None:
        """Attach an :class:`~deepspeed_tpu.telemetry.slo.SLOEngine`
        (ISSUE 13): the training SLIs (``train_mfu`` floor,
        ``train_anomaly_rate``) are evaluated at the sentinel's check
        fence, where the gauges/counters they read were just refreshed.
        Requires the resilience sentinel to be armed (the fence is the
        evaluation site); raises otherwise so a misconfigured job fails
        loudly instead of silently never judging."""
        if slo is not None and self.sentinel is None:
            raise ValueError(
                "set_slo needs the resilience sentinel armed "
                "(resilience.enabled): SLO evaluation rides the "
                "sentinel's check fence")
        self.slo = slo

    def _sdc_step_replay_check(self, batch):
        """Single-host determinism probe: the compiled step run twice from
        bit-identical state copies must agree bit-exactly; a mismatch is
        flaky hardware (counted + evented, recovered like SDC)."""
        from deepspeed_tpu import telemetry as _tele
        from deepspeed_tpu.runtime.sentinel import (
            AnomalyClass, TrainingAnomaly, step_replay_probe)

        if (self._compiled_train_step is None or self._host_opt is not None
                or not getattr(self, "_step_takes_extra_args", False)
                or self._use_pld or self._use_random_ltd):
            return None
        lr = jnp.asarray(self.get_lr()[0], jnp.float32)
        rng = jax.random.fold_in(self._dropout_rng, self.global_steps)
        ok, detail = step_replay_probe(
            self._compiled_train_step, self.state, self.state_shardings,
            args=(batch, lr, rng, None, None))
        reg = self.telemetry
        if reg is not None:
            reg.counter("resilience/step_replays").inc()
        if ok:
            return None
        if reg is not None:
            reg.counter("resilience/step_replay_mismatches").inc()
        _tele.record_event("resilience/step_replay_mismatch",
                           step=self.global_steps, detail=detail)
        logger.error("step-replay probe: %s", detail)
        return TrainingAnomaly(AnomalyClass.REPLAY, self.global_steps,
                               0.0, 0.0, detail)

    def _recover_or_raise(self, anomaly):
        """PaLM-style rewind-and-skip: reload the newest *valid* checkpoint
        (PR 1's walk-back survives a tag corrupted mid-recovery), restore
        the dataloader position from its ``__meta__``, then fast-forward
        past the offending batch window — the batches between the rewind
        target and the anomaly, plus an extra width that escalates across
        back-to-back rewinds. SDC/replay anomalies skip nothing (the data
        was fine): they rewind and deterministically replay. Bounded by
        the rolling rewind budget so a poisoned shard cannot livelock."""
        from deepspeed_tpu import telemetry as _tele
        from deepspeed_tpu.runtime.sentinel import (
            AnomalyClass, RewindBudgetExceededError, TrainingAnomalyError)

        rcfg = self.resilience_config
        _tele.record_event("resilience/anomaly", cls=anomaly.cls,
                           step=anomaly.step, value=anomaly.value,
                           zscore=round(anomaly.zscore, 2),
                           detail=anomaly.detail)
        if self.flight_recorder is not None:
            # freeze the pre-incident window BEFORE recovery rewinds
            # state — the dump is the postmortem of what training saw
            # at detection, not of the already-healed timeline
            self.flight_recorder.trigger(
                "training_anomaly", cls=anomaly.cls, step=anomaly.step,
                value=anomaly.value, zscore=round(anomaly.zscore, 2),
                detail=anomaly.detail)
        logger.warning("training anomaly: %s at step %d (%s)",
                       anomaly.cls, anomaly.step, anomaly.detail)
        dl = self.training_dataloader
        recoverable = (rcfg.on_anomaly == "recover"
                       and rcfg.checkpoint_dir is not None
                       # the engine-owned loader must be the LIVE source:
                       # rewinding it while a caller-supplied iterator
                       # keeps advancing would silently desync data from
                       # params — raise instead
                       and getattr(self, "_engine_owned_stream", False)
                       and dl is not None
                       and hasattr(dl, "load_state_dict")
                       and getattr(dl, "supports_deterministic_resume",
                                   lambda: True)())
        if not recoverable:
            raise TrainingAnomalyError(anomaly)
        t0 = time.perf_counter()
        spent = self._rewind_budget.record()
        if spent > rcfg.max_rewinds:
            _tele.record_event("resilience/rewind_budget_exhausted",
                               spent=spent, budget=rcfg.max_rewinds)
            raise RewindBudgetExceededError(
                anomaly, f"rewind budget exhausted: {spent} rewinds "
                         f"(budget {rcfg.max_rewinds}"
                         + (f" in {rcfg.rewind_window_s}s"
                            if rcfg.rewind_window_s else "")
                         + f"); last anomaly: {anomaly.cls} at step "
                           f"{anomaly.step}")
        # rewind: auto-resume walk-back to the newest valid tag; raises the
        # typed CheckpointCorruptionError loudly if every tag is invalid
        it_before = getattr(self, "_train_iter", None)
        path, _ = self.load_checkpoint(rcfg.checkpoint_dir)
        if path is None:
            raise TrainingAnomalyError(
                anomaly, f"anomaly at step {anomaly.step} but no checkpoint "
                         f"under {rcfg.checkpoint_dir} to rewind to")
        if it_before is not None and \
                getattr(self, "_train_iter", None) is it_before:
            # the loaded tag carried no restorable dataloader state (saved
            # pre-ISSUE-10, or before the loader was attached): params are
            # rewound but the data stream is NOT — fast-forwarding the
            # stale iterator would silently desync data from params
            raise TrainingAnomalyError(
                anomaly, f"rewound params to {path}, but that checkpoint "
                         f"has no dataloader state — cannot rewind the "
                         f"data stream deterministically; re-save "
                         f"checkpoints with this engine to enable "
                         f"auto-recovery")
        rewound_to = self.global_steps
        self._rewinds_since_clean += 1
        self._last_anomaly_step = anomaly.step
        if anomaly.cls in AnomalyClass.DATA_CLASSES:
            extra = min(rcfg.skip_width_base * rcfg.skip_width_factor
                        ** (self._rewinds_since_clean - 1),
                        rcfg.skip_width_max)
            skip_steps = max(anomaly.step - rewound_to, 0) + extra
        else:  # sdc/replay: the data was fine — replay it
            skip_steps = 0
        n_batches = skip_steps * self.gas
        it = self._ensure_train_iter()  # load invalidated the old iterator
        for _ in range(n_batches):
            next(it)
        # sentinel history is kept: the rewind RESTORES the pre-anomaly
        # regime, so that history is the correct baseline for the replayed
        # steps — resetting would open a min_history blind spot right
        # where a widened second skip may be needed. (The anomalous value
        # itself was never pushed.)
        self._pending_anomaly_reads.clear()
        dt_ms = (time.perf_counter() - t0) * 1e3
        rec = {"class": anomaly.cls, "anomaly_step": anomaly.step,
               "rewound_to": rewound_to, "skipped_steps": skip_steps,
               "skipped_batches": n_batches, "checkpoint": path,
               "recovery_ms": round(dt_ms, 2)}
        self.rewind_log.append(rec)
        reg = self.telemetry
        if reg is not None:
            reg.counter("resilience/rewinds").inc()
            if n_batches:
                reg.counter("resilience/skipped_batches").inc(n_batches)
            reg.histogram("resilience/recovery_latency_ms").observe(dt_ms)
        if self.tracer is not None:
            self.tracer.record(
                "recovery", t0, time.perf_counter(),
                trace_id=self._train_trace, anomaly=anomaly.cls,
                anomaly_step=anomaly.step, rewound_to=rewound_to,
                skipped_batches=n_batches)
        _tele.record_event("resilience/rewind", **rec)
        log_dist(
            f"anomaly recovery: {anomaly.cls} at step {anomaly.step} -> "
            f"rewound to step {rewound_to} ({path}), skipping "
            f"{n_batches} batch(es) ({skip_steps} step(s)), "
            f"{dt_ms:.0f} ms", ranks=[0])

    def destroy(self):
        """Engine shutdown (reference engine.destroy): emit the comms
        summary when comms logging is enabled, flush telemetry, close the
        engine-owned JSONL sink."""
        import deepspeed_tpu.comm as dist

        if self.config.comms_logger_config.enabled:
            dist.log_summary()
        if self.telemetry is not None:
            self.telemetry.flush(step=self.global_steps)
        if self._compile_sub is not None:
            compile_log().unsubscribe(self._compile_sub)
            self._compile_sub = None
        if self._watch is not None:
            self._watch.close()
        if self._spans_sink is not None:
            self._spans_sink.close()
            self._spans_sink = None
        if self._owned_sink is not None:
            self._owned_sink.close()
            self._owned_sink = None
        if self._attached_sink is not None:
            if self.telemetry is not None and \
                    self.telemetry.sink is self._attached_sink:
                # detach whatever THIS engine attached — the bare owned
                # sink, or the flight-recorder tee wrapping it — so the
                # process-global registry never keeps writing through a
                # closed sink (or a dead engine's recorder) afterwards
                self.telemetry.attach_sink(None)
            self._attached_sink = None

    # ------------------------------------------ forward/backward/step parity
    @at_work
    def forward(self, batch):
        """Compute loss for one microbatch; grads are computed in the same
        compiled program and cached for backward() (JAX has no separate
        autograd pass — doc'd divergence from reference forward:1614)."""
        if self._compiled_micro_grad is None:
            def micro(state_params, scaler, batch, rng):
                return self._micro_loss_and_grads(state_params, batch, scaler.cur_scale, rng)
            self._compiled_micro_grad = jax.jit(micro)
        self.timers(FORWARD_GLOBAL_TIMER).start()
        rng = jax.random.fold_in(self._dropout_rng, self.micro_steps)
        batch = jax.device_put(batch, self._batch_shardings(batch))
        with jax.profiler.TraceAnnotation("dstpu/forward"):
            scaled_loss, grads, metrics = self._compiled_micro_grad(
                self.state.params, self.state.scaler, batch, rng)
        self._pending = (scaled_loss, grads)
        self.timers(FORWARD_GLOBAL_TIMER).stop()
        return metrics["loss"]

    __call__ = forward

    @at_work
    def backward(self, loss=None, allreduce_gradients: bool = True):
        """Accumulate the cached grads (reference backward:1755 + grad hooks)."""
        assert getattr(self, "_pending", None) is not None, \
            "backward() must follow forward()"
        self.timers(BACKWARD_GLOBAL_TIMER).start()
        _, grads = self._pending
        self._pending = None
        with jax.profiler.TraceAnnotation("dstpu/backward"):
            if self._grad_acc is None:
                self._grad_acc = grads
            else:
                add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
                self._grad_acc = add(self._grad_acc, grads)
        self._acc_count += 1
        self.timers(BACKWARD_GLOBAL_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return (self.micro_steps + 1) % self.gas == 0

    @at_work
    def step(self):
        """Apply optimizer at gas boundary (reference step:1951)."""
        self.timers(STEP_GLOBAL_TIMER).start()
        at_boundary = self.is_gradient_accumulation_boundary()
        if at_boundary and self._host_opt is not None:
            assert self._acc_count == self.gas, (
                f"step() at boundary needs {self.gas} backward() calls, "
                f"got {self._acc_count}")
            if getattr(self, "_compiled_prep_grads", None) is None:
                self._compiled_prep_grads = jax.jit(
                    self._unscale_epilogue, donate_argnums=(0,))
            grads, overflow, norm = self._compiled_prep_grads(
                self._grad_acc, self.state.scaler)
            self._host_apply(grads, bool(jax.device_get(overflow)),  # dstpu-lint: fence=host-optimizer path: boundary apply is host-side
                             float(jax.device_get(norm)), self.get_lr()[0])
            self._grad_acc = None
            self._acc_count = 0
            self._global_grad_norm = norm
            self.global_steps += 1
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            if self.progressive_layer_drop is not None:
                self.progressive_layer_drop.update_state(self.global_steps)
            self.micro_steps += 1
            self.timers(STEP_GLOBAL_TIMER).stop()
            return
        if at_boundary:
            assert self._acc_count == self.gas, (
                f"step() at boundary needs {self.gas} backward() calls, "
                f"got {self._acc_count}")
            if self._compiled_apply_grads is None:
                def apply_fn(state, grads, lr):
                    grads = jax.tree_util.tree_map(lambda g: g / self.gas, grads)
                    new_state, overflow, norm = self._apply_grads(state, grads, lr)
                    return new_state, overflow, norm
                self._compiled_apply_grads = jax.jit(apply_fn, donate_argnums=(0, 1))
            lr = jnp.asarray(self.get_lr()[0], jnp.float32)
            with jax.profiler.TraceAnnotation("dstpu/optimizer_step"):
                self.state, overflow, norm = self._compiled_apply_grads(
                    self.state, self._grad_acc, lr)
            self._grad_acc = None
            self._acc_count = 0
            self._global_grad_norm = norm
            self.global_steps += 1
            if self.lr_scheduler is not None:
                self.lr_scheduler.step()
            if self.progressive_layer_drop is not None:
                self.progressive_layer_drop.update_state(self.global_steps)
        self.micro_steps += 1
        self.timers(STEP_GLOBAL_TIMER).stop()

    # -------------------------------------------------------------- eval path
    @at_work
    def eval_batch(self, batch):
        if self._compiled_eval is None:
            def ev(params, batch):
                cparams = self._cast_for_compute(params)
                with stating_param_use(self._param_use):
                    loss, metrics = self.module.apply(cparams, batch, rngs=None, train=False)
                return loss
            self._compiled_eval = jax.jit(ev)
        batch = jax.device_put(batch, self._batch_shardings(batch))
        return self._compiled_eval(self.state.params, batch)

    # ------------------------------------------------------------- accessors
    def get_lr(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler.get_last_lr()
        return [getattr(self.optimizer, "lr", 1e-3)]

    def get_global_grad_norm(self):
        return None if self._global_grad_norm is None else float(
            # dstpu-lint: fence=user-facing accessor, not on the step path
            jax.device_get(self._global_grad_norm))

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def gradient_accumulation_steps(self) -> int:
        return self.gas

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    @property
    def params(self):
        return self.state.params

    def get_loss_scale(self):
        # dstpu-lint: fence=user-facing accessor, not on the step path
        return float(jax.device_get(self.state.scaler.cur_scale))

    # --------------------------------------------------------------- data io
    def deepspeed_io(self, dataset, batch_size=None, collate_fn=None, **kw):
        from deepspeed_tpu.runtime.dataloader import build_dataloader

        if batch_size is None:
            # per-process batch: micro_batch * local share of the dense batch axes
            batch_size = self.config.train_micro_batch_size_per_gpu * (
                self.topology.data_parallel_size // max(jax.process_count(), 1))
        return build_dataloader(dataset, batch_size, config=self.config,
                                collate_fn=collate_fn, **kw)

    # ----------------------------------------------------------- checkpoints
    def _checkpoint_engine(self):
        """Engine-lifetime checkpoint backend; async when configured
        (reference Nebula engine selection)."""
        if getattr(self, "_ckpt_engine", None) is None:
            if self.config.checkpoint_config.async_save:
                from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import (
                    AsyncCheckpointEngine)

                self._ckpt_engine = AsyncCheckpointEngine()
            else:
                self._ckpt_engine = None  # default NativeCheckpointEngine
        return self._ckpt_engine

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import save_engine_checkpoint

        t0 = time.perf_counter()
        try:
            return save_engine_checkpoint(self, save_dir, tag=tag,
                                          client_state=client_state,
                                          save_latest=save_latest,
                                          checkpoint_engine=self._checkpoint_engine())
        finally:
            if self.tracer is not None:
                self.tracer.record("checkpoint_save", t0,
                                   time.perf_counter(),
                                   trace_id=self._train_trace,
                                   step=self.global_steps)
            if self.telemetry is not None:
                self._reset_telemetry_window()

    def load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                        load_lr_scheduler_states=True, load_module_only=False):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import load_engine_checkpoint

        t0 = time.perf_counter()
        try:
            return load_engine_checkpoint(self, load_dir, tag=tag,
                                          load_optimizer_states=load_optimizer_states,
                                          load_lr_scheduler_states=load_lr_scheduler_states,
                                          load_module_only=load_module_only,
                                          checkpoint_engine=self._checkpoint_engine())
        finally:
            if self.tracer is not None:
                self.tracer.record("checkpoint_load", t0,
                                   time.perf_counter(),
                                   trace_id=self._train_trace,
                                   step=self.global_steps)
            if self.telemetry is not None:
                self._reset_telemetry_window()

    def save_16bit_model(self, save_dir, save_filename="model_weights.npz"):
        from deepspeed_tpu.runtime.checkpoint_engine.engine import save_16bit_model

        return save_16bit_model(self, save_dir, save_filename)

    def install_preemption_handler(self, save_dir, tag=None, defer=None,
                                   **handler_kw):
        """SIGTERM (TPU maintenance/preemption notice) → final synchronous
        checkpoint to ``save_dir`` → exit with the restartable preemption
        code, which the elastic agent restarts without burning budget.
        Returns the installed handler (also usable as a maintenance-event
        callback via ``handler.trigger()``).

        On multi-host meshes the final save is deferred to the next step
        boundary (the engine polls the handler each train step): the save's
        gather collectives must not launch from an arbitrary
        signal-interrupt point where they could interleave with in-flight
        step collectives differently on each host. Single-host defaults to
        immediate. Override via ``defer``."""
        from deepspeed_tpu.elasticity.preemption import PreemptionHandler

        def final_save():
            self.save_checkpoint(save_dir, tag=tag)
            ck = self._checkpoint_engine()
            if ck is not None and hasattr(ck, "wait"):
                ck.wait()  # async engine: durable before the process dies

        if defer is None:
            defer = jax.process_count() > 1
        if defer and jax.process_count() > 1 and \
                "consensus_fn" not in handler_kw:
            # per-step scalar allgather: hosts agree who saw a notice, so
            # the save's collectives start on every host at the SAME step
            # boundary — the cost is opt-in (handler installed) and tiny
            def consensus(local_flag):
                from jax.experimental import multihost_utils

                votes = multihost_utils.process_allgather(
                    np.int32(bool(local_flag)))
                return bool(np.max(votes))

            handler_kw["consensus_fn"] = consensus
        self._preemption_handler = PreemptionHandler(
            final_save, defer=defer, **handler_kw).install()
        return self._preemption_handler
