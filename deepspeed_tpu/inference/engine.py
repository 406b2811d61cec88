"""Inference engine (L5).

Parity target: reference ``deepspeed/inference/engine.py`` (InferenceEngine:89,
610 LoC) + the CUDA kernel set behind it (ds_attention.py, ds_mlp.py,
softmax_context w/ KV cache). TPU-native redesign:

  * kernel injection (`replace_transformer_layer`, module_inject) becomes
    *weight mapping*: HF torch modules are converted once into this
    framework's own model implementations via per-arch policies
    (inference/policies.py) — the containers/policies concept survives, the
    nn.Module surgery does not (SURVEY §7.12).
  * CUDA-graph capture/replay (engine.py:500,:519) is replaced by jit: the
    prefill and the decode step are each ONE compiled XLA program with a
    static-shape KV cache.
  * TP for serving (`_create_model_parallel_group`, :261) is the 'model'
    mesh axis; per-layer output allreduces are XLA collectives over ICI.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.models.base import recurrent_state_keys, row_state_keys, slot_state_keys
from deepspeed_tpu.ops.attention import extract_slot_row, insert_slot_row, write_slot_rows
from deepspeed_tpu.runtime.zero.partition import PartitionPlan
from deepspeed_tpu.telemetry.compile_log import SetupPhase, compile_log
from deepspeed_tpu.utils import groups as groups_mod
from deepspeed_tpu.utils.logging import log_dist, logger


def _named_jit(name: str, fn, **jit_kw):
    """``jax.jit(fn)`` under ``name``: the name the program has in
    ``ServingEngine.program_cache_sizes()``, which JAX then gives its
    trace, lowering and compile events (telemetry/compile_log.py), the
    compiled module and the profile. ``fn`` is renamed: pass a closure of
    the builder's own, never a function other code imports."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


def filter_logits(logits, *, top_k: int = 0, top_p: float = 1.0):
    """Sampling-filter parity with HF's TopKLogitsWarper + TopPLogitsWarper
    (the path the reference's serving takes through HF ``generate``,
    reference inference/engine.py:588): top-k first, then nucleus — keep the
    smallest prefix of the descending-sorted distribution whose cumulative
    probability reaches ``top_p`` (always >= 1 token), mask the rest to
    -inf. Value-ties at the nucleus boundary are all kept (HF cuts by
    sorted position; with distinct logits the support sets are identical).
    """
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sort = jnp.sort(logits, axis=-1)[..., ::-1]  # descending
        probs = jax.nn.softmax(sort, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p  # exclusive cumsum: keeps the crosser
        kth = jnp.min(jnp.where(keep, sort, jnp.inf), axis=-1, keepdims=True)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


class InferenceEngine:
    """Serve a ModelSpec (or a converted HF torch model) with a compiled
    prefill + decode loop (reference InferenceEngine:89)."""

    def __init__(self, model, config: DeepSpeedInferenceConfig, *,
                 params=None, topology=None):
        if not isinstance(config, DeepSpeedInferenceConfig):
            config = DeepSpeedInferenceConfig(**(config or {}))
        self._config = config
        # listening before the first program of this engine is traced: a
        # registry that subscribes later is brought up to these too
        compile_log()
        self.dtype = config.jax_dtype()
        # int8 = weight-only quantization (reference GroupQuantizer path,
        # module_inject/replace_module.py:140): HBM holds int8 weights +
        # per-column scales, compute runs in bf16 on per-layer dequantized
        # tiles (see models/base.qdot)
        self.weight_quant = bool(config.quant.enabled)
        if self.dtype == jnp.int8:
            self.weight_quant = True
            self.dtype = jnp.bfloat16
        if self.weight_quant:
            if config.quant.bits != 8:
                raise ValueError(
                    f"weight quantization supports bits=8 only "
                    f"(got {config.quant.bits})")
            log_dist("weight quantization uses per-layer per-output-column "
                     "scales; quant.group_size is ignored", ranks=[0])
        elif getattr(config.quant, "quantize_embedding", False):
            # same fail-loudly contract as the int8 weight check below:
            # silently leaving the ~77 MB tied table full-precision when
            # its quantization was explicitly requested would defeat the
            # sizing the flag exists for
            raise ValueError(
                "quant.quantize_embedding requires weight quantization "
                "(quant.enabled=true or dtype='int8'): the tied-embedding "
                "int8 path rides the weight-quant initialization")

        # HF torch module → (ModelSpec, params) via policy (module_inject analog)
        if _is_torch_module(model):
            from deepspeed_tpu.inference.policies import convert_hf_model

            model, hf_params = convert_hf_model(model, compute_dtype=self.dtype)
            if params is None:
                params = hf_params
        # align the model's compute dtype with the serving dtype — a bf16
        # model served with dtype="fp32" would otherwise mix dtypes in the
        # decode-loop carry (scan carries are dtype-strict)
        if hasattr(model, "compute_dtype") and model.compute_dtype != self.dtype:
            model.compute_dtype = self.dtype
        self.module = model

        # ---- topology: model axis = tp (reference _create_model_parallel_group)
        if topology is None:
            topology = groups_mod.initialize(tp_size=config.tp_size,
                                             ep_size=config.ep_size)
        else:
            groups_mod.initialize(topology)
        self.topology = topology
        self.mesh = topology.mesh
        self.plan = PartitionPlan(topology=topology, zero_stage=0)
        self.logical_axes = model.logical_axes() if hasattr(model, "logical_axes") else None

        # ---- parameters: explicit > checkpoint > fresh init
        if self.weight_quant and not getattr(self.module,
                                             "supports_weight_quant", False):
            # an explicit int8 request that cannot be honored must fail
            # loudly — silently serving bf16 would use ~4x the HBM the
            # deployment was sized for
            raise ValueError(
                f"int8 weight quantization requested but "
                f"{type(self.module).__name__} does not support dequant "
                "blocks (models must route weight matmuls through models/base.qdot in "
                "their block scan and set supports_weight_quant = True)")
        # set-up's weights phase, closed at a fence on the tree it made. No
        # registry here: the reading stays on the engine, and a
        # ServingEngine publishes it into the registry it was given
        self.setup_weights = SetupPhase("weights")
        if params is None and config.checkpoint is not None:
            params = self._load_checkpoint_params(config.checkpoint)
        if (params is None and self.weight_quant
                and config.tp_size == 1 and config.ep_size == 1):
            # stream-init: each quantizable block leaf is initialized AND
            # quantized in its own fused program (XLA DCE reduces the jitted
            # init to just that leaf), so the full serving-dtype tree never
            # materializes — HBM peak is the int8 tree + ONE bf16 leaf
            # (~9.4 GB at 6.7B vs ~20 GB init-then-quantize). Values are
            # bit-identical to the one-shot init.
            self.params, n_q = self._stream_init_quantized(
                jax.random.PRNGKey(config.seed))
            log_dist(f"weight-only int8: stream-initialized {n_q} block "
                     "weight tensors (per-layer, per-output-column scales)",
                     ranks=[0])
            self.params = self._maybe_quantize_embedding(self.params)
        else:
            if params is None:
                # cast fused INTO the jitted init: XLA folds the astype into
                # the elementwise RNG sampling, so only serving-dtype params
                # ever materialize — initializing a 7B model in f32 and
                # casting after would transiently need 2x the weight HBM
                # (27 GB at 6.7B)
                # dstpu-lint: disable=recompile-hazard -- one-shot fused init+cast at engine construction
                params = jax.jit(self._init_cast)(
                    jax.random.PRNGKey(config.seed))
            self.params = self._shard_and_cast(params)
            params = None  # drop the caller-scope tree: the quantize walk
            # below frees each bf16 leaf as its int8 replacement is built
            if self.weight_quant:
                self.params, n_q = self._quantize_block_weights(self.params)
                log_dist(f"weight-only int8: quantized {n_q} block weight "
                         "tensors (per-layer, per-output-column scales)",
                         ranks=[0])
                self.params = self._maybe_quantize_embedding(self.params)

        self.setup_weights.close(fence=self.params)
        self._compiled: Dict[Tuple, Any] = {}
        self._gen_rng = jax.random.PRNGKey(config.seed)
        log_dist(
            f"InferenceEngine: dtype={self.dtype.__name__} tp={config.tp_size} "
            f"ep={config.ep_size} max_tokens={config.max_tokens}", ranks=[0])

    # ----------------------------------------------------------------- params
    def _init_cast(self, key):
        """Fresh init with the serving-dtype cast fused into the jitted
        program (XLA folds the astype into the RNG sampling)."""
        return jax.tree_util.tree_map(
            lambda x: x.astype(self.dtype)
            if x.dtype == jnp.float32 else x, self.module.init(key))

    @staticmethod
    def _is_quantizable(leaf, in_blocks: bool) -> bool:
        """Same predicate as _quantize_block_weights: stacked [L, in, out]
        float matmul weights under a 'blocks' subtree."""
        return (in_blocks and hasattr(leaf, "ndim") and leaf.ndim == 3
                and leaf.dtype in (jnp.float32, jnp.bfloat16, jnp.float16)
                and min(leaf.shape[1:]) >= 16)

    def _stream_init_quantized(self, key):
        """Random-init int8 serving without ever materializing the full
        serving-dtype tree: each quantizable block leaf gets its own fused
        jitted program (init -> take leaf -> quantize) — XLA dead-code
        eliminates every other leaf's sampling, so the program's footprint
        is ONE bf16 leaf + its int8 image. Peak HBM = int8 tree + largest
        bf16 leaf (~9.4 GB at 6.7B) instead of full-bf16 + int8 (~20 GB),
        which is the difference between fitting and OOMing a 16 GB chip.
        Values are bit-identical to the one-shot init + quantize path
        (single-mesh tp=1/ep=1 only; larger meshes take the sharded
        two-phase path). Reference sizing analog: the deployment-sized
        GroupQuantizer load in module_inject/replace_module.py:140."""
        from deepspeed_tpu.compression.quantize import quantize_int8

        shapes = jax.eval_shape(self._init_cast, key)

        def find_qpaths(tree, in_blocks=False, prefix=()):
            out = []
            if isinstance(tree, dict):
                for k, v in tree.items():
                    if self._is_quantizable(v, in_blocks):
                        out.append(prefix + (k,))
                    else:
                        out.extend(find_qpaths(v, in_blocks or k == "blocks",
                                               prefix + (k,)))
            return out

        def get(tree, path):
            for k in path:
                tree = tree[k]
            return tree

        qpaths = find_qpaths(shapes)
        quantized = {}
        for path in qpaths:
            def leaf_q(key, _path=path):
                leaf = get(self._init_cast(key), _path)
                qv, scale = jax.vmap(
                    lambda w: quantize_int8(w, per_channel_axis=1))(leaf)
                return {"__q__": qv, "__scale__": scale}

            # block per leaf: overlapping two leaf programs would double the
            # transient bf16 footprint this path exists to avoid
            # dstpu-lint: disable=recompile-hazard -- init-time weight quantize: serial per-leaf programs bound the transient bf16 footprint
            quantized[path] = jax.block_until_ready(jax.jit(leaf_q)(key))

        def rest(key):
            tree = self._init_cast(key)
            for path in qpaths:
                del get(tree, path[:-1])[path[-1]]
            return tree

        # the non-quantized remainder honors the same placement/cast
        # contract as the init-then-quantize path (_shard_and_cast:
        # serving-dtype recast + device_put under the plan's
        # NamedSharding) — this path is gated to tp=1/ep=1, where the
        # specs are replicated, but the contract should not silently
        # diverge between init paths
        # dstpu-lint: disable=recompile-hazard -- one-shot init-time quantize of the non-block leaves
        params = self._shard_and_cast(jax.jit(rest)(key))
        for path, qleaf in quantized.items():
            get(params, path[:-1])[path[-1]] = qleaf
        return params, len(qpaths)

    def _shard_and_cast(self, params):
        axes = self.logical_axes

        missing = []

        def prune(ax, tree, path=""):
            """Logical-axes subtree matching ``tree`` (the stream-init
            path shards a PARTIAL tree whose quantized leaves were
            carved out). Param keys ABSENT from logical_axes are kept
            with None (replicated) specs — silently dropping them used
            to surface as an opaque tree-structure mismatch deep in
            compute_specs instead of naming the unannotated param."""
            if isinstance(ax, dict) and isinstance(tree, dict):
                out = {}
                for k, v in tree.items():
                    if k in ax:
                        out[k] = prune(ax[k], v, f"{path}/{k}")
                    else:
                        missing.append(f"{path}/{k}")
                        out[k] = jax.tree_util.tree_map(lambda _: None, v)
                return out
            return ax

        if axes is not None:
            axes = prune(axes, params)
            if missing:
                logger.warning(
                    "logical_axes is missing entries for %s — treating "
                    "them as replicated (no TP/ZeRO sharding); annotate "
                    "them in the model's logical_axes() to shard them",
                    ", ".join(missing))
        specs = self.plan.compute_specs(
            jax.eval_shape(lambda: params), axes)

        def put(p, spec):
            arr = jnp.asarray(p)
            if arr.dtype in (jnp.float32, jnp.float16, jnp.bfloat16):
                arr = arr.astype(self.dtype)
            return jax.device_put(arr, NamedSharding(self.mesh, spec))

        return jax.tree_util.tree_map(put, params, specs)

    def _quantize_block_weights(self, params):
        """Quantize scanned-block matmul weights ([L, in, out] float leaves
        under a 'blocks' subtree) to int8 with [L, 1, out] fp32 scales."""
        from deepspeed_tpu.compression.quantize import quantize_int8

        count = 0

        @jax.jit
        def q(leaf):
            # per-layer (vmap over L), per-output-column scales
            qv, scale = jax.vmap(
                lambda w: quantize_int8(w, per_channel_axis=1))(leaf)
            return {"__q__": qv, "__scale__": scale}

        def walk(tree, in_blocks=False):
            nonlocal count
            if isinstance(tree, dict):
                out = {}
                for k, v in list(tree.items()):
                    if self._is_quantizable(v, in_blocks):
                        # consume the source leaf BEFORE quantizing: at 7B
                        # scale holding the full bf16 tree alongside the
                        # int8 one would peak at ~3x the quantized
                        # footprint. Mutating `tree` is safe only because
                        # _shard_and_cast always returns fresh dict
                        # containers (never caller-owned ones); a failure
                        # mid-walk leaves the source tree with popped keys,
                        # and the caller must not reuse it.
                        leaf = tree.pop(k)
                        out[k] = q(leaf)
                        del leaf
                        count += 1
                    else:
                        out[k] = walk(v, in_blocks or k == "blocks")
                return out
            return tree

        return walk(params), count

    def _maybe_quantize_embedding(self, params):
        """int8 tied-embedding quantization (ISSUE 12 satellite,
        ``quant.quantize_embedding``): ONE per-vocab-row scale serves
        both consumers of the tied table — the embedding gather (exact
        per-row dequant, models/base.embed_tokens) and the lm-head
        matmul (scale on the output logit column, base.tied_logits).
        At 125M the tied table is ~77 MB of the 249 MB int8 weight
        stream — the last unquantized resident.
        Requires the model to route wte through the quant-aware helpers
        (``supports_embedding_quant``); fails loudly otherwise, exactly
        like the block-weight support check."""
        if not getattr(self._config.quant, "quantize_embedding", False):
            return params
        if not getattr(self.module, "supports_embedding_quant", False):
            raise ValueError(
                f"quant.quantize_embedding requested but "
                f"{type(self.module).__name__} does not route its tied "
                "embedding through models/base.embed_tokens/tied_logits "
                "(set supports_embedding_quant = True once it does)")
        mcfg = getattr(self.module, "config", None)
        if not getattr(mcfg, "tie_embeddings", True):
            raise ValueError(
                "quant.quantize_embedding targets the TIED embedding; "
                "this model unties wte from its lm_head")
        from deepspeed_tpu.compression.quantize import quantize_int8

        @jax.jit
        def q(leaf):
            qv, scale = quantize_int8(leaf, per_channel_axis=0)  # [V, 1]
            return {"__q__": qv, "__scale__": scale}

        leaf = params.pop("wte")
        params["wte"] = jax.block_until_ready(q(leaf))
        del leaf
        log_dist("weight-only int8: quantized tied embedding/lm-head "
                 "(per-vocab-row scales)", ranks=[0])
        return params

    def _load_checkpoint_params(self, checkpoint):
        """Load from this framework's sharding-agnostic engine checkpoint
        (reference loads mp-rank/meta-tensor checkpoints, load_checkpoint.py;
        here one global npz serves any mesh)."""
        from deepspeed_tpu.runtime.checkpoint_engine.engine import load_params_for_inference

        if isinstance(checkpoint, str):
            path = checkpoint
        else:
            path = checkpoint.get("checkpoint_dir") or checkpoint.get("base_dir")
            if path is None:
                raise ValueError(
                    "inference checkpoint dict must carry 'checkpoint_dir' (or "
                    f"'base_dir') pointing at an engine checkpoint; got keys "
                    f"{sorted(checkpoint)}")
        template = jax.eval_shape(self.module.init, jax.random.PRNGKey(0))
        return load_params_for_inference(path, template)

    # ---------------------------------------------------------------- forward
    def forward(self, input_ids):
        """Full no-cache forward → logits (reference forward:560)."""
        key = ("fwd", tuple(np.shape(input_ids)))
        if key not in self._compiled:
            def fwd(params, ids):
                hidden = self.module.forward_hidden(params, ids, train=False)
                return self.module.logits(params, hidden)

            self._compiled[key] = jax.jit(fwd)
        return self._compiled[key](self.params, jnp.asarray(input_ids))

    __call__ = forward

    # --------------------------------------------------------------- generate
    def generate(self, input_ids, max_new_tokens: int = 32, *,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: int = 0, seed: Optional[int] = None):
        """Autoregressive generation: one jitted prefill + one jitted decode
        step iterated ``max_new_tokens`` times (reference _generate:588 via HF
        model.generate over injected modules). Sampling supports greedy,
        top-k, and top-p/nucleus (HF TopPLogitsWarper semantics); with
        ``eos_token_id`` set, the decode loop is a ``while_loop`` that exits
        as soon as every batch row has emitted EOS (HF early-stopping analog)
        — remaining positions are ``pad_token_id``.

        input_ids: [B, T] — uniform prompt length per call (static shapes).
        Returns np.ndarray [B, T + max_new_tokens].
        """
        input_ids = np.asarray(input_ids)
        assert input_ids.ndim == 2, "generate expects [batch, seq]"
        if max_new_tokens < 1:
            raise ValueError(f"generate: max_new_tokens must be >= 1, got {max_new_tokens}")
        if max_new_tokens < self._config.min_out_tokens:
            raise RuntimeError(
                f"generate: max_new_tokens {max_new_tokens} below min_out_tokens "
                f"{self._config.min_out_tokens} (reference min_tokens semantics)")
        b, t = input_ids.shape
        total = t + max_new_tokens
        # token budget guard (reference engine.py:588 blocks > max_out_tokens)
        if total > self._config.max_tokens:
            raise RuntimeError(
                f"generate: input+new tokens {total} exceeds max_tokens "
                f"{self._config.max_tokens} (reference max_out_tokens semantics); "
                f"raise it in the inference config")
        # position-table guard: past max_seq_len the wpe/RoPE gathers clamp and
        # silently produce garbage — fail loudly instead
        mcfg = getattr(self.module, "config", None)
        model_max = getattr(mcfg, "max_seq_len", None)
        if not getattr(mcfg, "has_position_table", True):
            model_max = None  # pure-ALiBi models extrapolate freely
        if model_max is not None and total > model_max:
            raise RuntimeError(
                f"generate: input+new tokens {total} exceeds the model's "
                f"max_seq_len {model_max} (position table size)")
        vocab = getattr(getattr(self.module, "config", None), "vocab_size", None)
        if top_k and vocab is not None and top_k > vocab:
            raise ValueError(f"generate: top_k {top_k} > vocab_size {vocab}")
        if not (0.0 < top_p <= 1.0):
            raise ValueError(f"generate: top_p must be in (0, 1], got {top_p}")

        key = ("gen", b, t, max_new_tokens, do_sample, top_k, float(top_p),
               eos_token_id, pad_token_id)
        if key not in self._compiled:
            self._compiled[key] = self._build_generate(
                b, t, max_new_tokens, do_sample=do_sample, top_k=top_k,
                top_p=float(top_p), eos_token_id=eos_token_id,
                pad_token_id=pad_token_id)
        if seed is not None:
            rng = jax.random.PRNGKey(seed)
        else:
            self._gen_rng, rng = jax.random.split(self._gen_rng)
        temp = jnp.asarray(max(temperature, 1e-6), jnp.float32)
        out_tokens = self._compiled[key](self.params, jnp.asarray(input_ids), temp, rng)
        return np.concatenate([input_ids, np.asarray(jax.device_get(out_tokens))], axis=1)

    def _build_generate(self, b, t, max_new, *, do_sample, top_k, top_p,
                        eos_token_id, pad_token_id):
        """Two compiled programs — prefill (builds the cache, picks token 0)
        and decode (the token loop) — composed by a host-side driver.

        Why not one fused program: a single XLA program carrying BOTH the
        prefill graph and the decode loop over the full weight tree fails
        with ResourceExhausted on large models on this backend even though
        its compiled peak memory fits (measured at 6.7B int8: prefill-only
        and decode-only each run fine; the fusion of the two does not).
        Both programs still recompile per prompt length (the KV cache is
        shaped [*, t + max_new, *], so `total` is in both cache keys) —
        the split's benefit is the ResourceExhausted fix plus smaller
        individual executables. It mirrors the split the reference's
        inference engine makes between its prompt and token phases
        (csrc/transformer/inference pt_binding.cpp allocate_workspace
        prompt/token paths)."""
        model = self.module
        total = t + max_new
        pick = self._make_pick(do_sample, top_k, top_p)

        # pad the KV allocation to a multiple of 128 so the flash-decode
        # kernel's sequence blocks tile (ops/attention.decode_attention
        # routing); masking by cache_index keeps padded positions inert
        cache_len = (total + 127) // 128 * 128

        pf_key = ("pf", b, t, total, do_sample, top_k, top_p)
        if pf_key not in self._compiled:
            def prefill(params, ids, temp, rng):
                cache = model.init_cache(b, cache_len, dtype=self.dtype)
                logits, cache = model.forward_with_cache(params, ids, cache)
                rng, sub = jax.random.split(rng)
                return pick(logits[:, -1], temp, sub), cache, rng

            self._compiled[pf_key] = _named_jit("generate_prefill", prefill)
        prefill_fn = self._compiled[pf_key]

        if eos_token_id is None:
            dec_key = ("dec", b, total, max_new, do_sample, top_k, top_p)
            if dec_key not in self._compiled:
                def decode(params, tok, cache, temp, rng):
                    def step(carry, _):
                        tok, cache, rng = carry
                        logits, cache = model.forward_with_cache(
                            params, tok[:, None], cache)
                        rng, sub = jax.random.split(rng)
                        nxt = pick(logits[:, -1], temp, sub)
                        return (nxt, cache, rng), tok

                    (last, _, _), toks = jax.lax.scan(
                        step, (tok, cache, rng), None, length=max_new - 1)
                    return jnp.concatenate([toks.T, last[:, None]], axis=1)

                # donate the cache: the decode loop must not double-buffer
                # the [L,B,H,S,Dh] KV tensors at 7B scale
                self._compiled[dec_key] = _named_jit(
                    "generate_decode", decode, donate_argnums=(2,))
            decode_fn = self._compiled[dec_key]

            def gen(params, ids, temp, rng):
                tok, cache, rng = prefill_fn(params, ids, temp, rng)
                if max_new == 1:
                    return tok[:, None]
                return decode_fn(params, tok, cache, temp, rng)

            return gen

        # EOS path: while_loop exits once every row has EMITTED its eos
        # (prev_done); pending-but-unwritten eos keeps the loop alive one
        # more tick so it lands in the buffer.
        dec_key = ("dec_eos", b, total, max_new, do_sample, top_k, top_p,
                   eos_token_id, pad_token_id)
        if dec_key not in self._compiled:
            def decode_eos(params, tok, cache, temp, rng):
                done = tok == eos_token_id
                buf = jnp.full((max_new, b), pad_token_id, jnp.int32)

                def cond(carry):
                    i, *_rest, prev_done, _buf = carry
                    return (i < max_new) & ~jnp.all(prev_done)

                def body(carry):
                    i, tok, cache, rng, done, prev_done, buf = carry
                    buf = buf.at[i].set(tok)

                    def do_step(args):
                        tok, cache, rng = args
                        logits, cache = model.forward_with_cache(
                            params, tok[:, None], cache)
                        rng, sub = jax.random.split(rng)
                        nxt = pick(logits[:, -1], temp, sub)
                        return jnp.where(done, pad_token_id, nxt), cache, rng

                    # skip the decode forward when this was the last token
                    # to emit (parity with the scan path's max_new - 1
                    # forwards)
                    need = (i + 1 < max_new) & ~jnp.all(done)
                    nxt, cache, rng = jax.lax.cond(
                        need, do_step, lambda args: args, (tok, cache, rng))
                    return (i + 1, nxt, cache, rng,
                            done | (nxt == eos_token_id), done, buf)

                prev_done = jnp.zeros((b,), bool)
                *_state, buf = jax.lax.while_loop(
                    cond, body, (0, tok, cache, rng, done, prev_done, buf))
                return buf.T

            self._compiled[dec_key] = _named_jit(
                "generate_decode_eos", decode_eos, donate_argnums=(2,))
        decode_eos_fn = self._compiled[dec_key]

        def gen(params, ids, temp, rng):
            tok, cache, rng = prefill_fn(params, ids, temp, rng)
            return decode_eos_fn(params, tok, cache, temp, rng)

        return gen

    def _make_pick(self, do_sample, top_k, top_p):
        """Token-selection closure shared by generate() and the serving
        programs: greedy argmax, or top-k/top-p filtered sampling."""
        def pick(logits, temp, rng):
            logits = logits.astype(jnp.float32)
            if not do_sample:
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            logits = filter_logits(logits / temp, top_k=top_k, top_p=top_p)
            return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)

        return pick

    # ------------------------------------------- continuous-batching programs
    @staticmethod
    def _carry_donation(n_state: int):
        """Operands 1 .. n_state + 1 of a slot program are the per-slot
        state's leaves and the lengths: donated on TPU."""
        return tuple(range(1, n_state + 2)) \
            if jax.default_backend() == "tpu" else ()

    def _with_first_token(self, carry: tuple, token, slot, behind,
                          cache) -> tuple:
        """A slot prefill program's outputs: the carry, the picked token,
        where the serving loop passed the decode step's previous tokens
        (``behind``), those with the token written at ``slot``, and for a
        model that counts what a prompt block did (:meth:`_count_prompt`)
        the returned ``cache``'s counts last, so that the host fetches them
        with the token."""
        out = (*carry, token)
        if behind:
            out += (jax.lax.dynamic_update_index_in_dim(
                behind[0], token, slot, 0),)
        if "prompt_counts" in cache:
            out += (cache["prompt_counts"],)
        return out

    def _count_prompt(self, cache) -> None:
        """Ask a model that counts what a prompt block did
        (``model.prompt_counters``) for those counts: it returns
        ``cache["prompt_counts"]`` with them added."""
        counted = len(getattr(self.module, "prompt_counters", ()))
        if counted:
            cache["prompt_counts"] = jnp.zeros((counted,), jnp.int32)

    @staticmethod
    def _last_logits(logits, length):
        """``[B, V]`` at a prompt block's last real position. A model told
        the block's true length (``cache["valid_len"]``) may compute its head
        there alone and hand back ``[B, 1, V]``: at 16,384 positions and
        32,768 vocabulary rows the other logits are a gigabyte that nothing
        reads (models/sarvam_mla.py)."""
        if logits.shape[1] == 1:
            return logits[:, 0]
        return jax.lax.dynamic_index_in_dim(logits, length - 1, 1,
                                            keepdims=False)

    def slot_prefill_program(self, bucket_len: int, num_slots: int,
                             max_len: int, *, do_sample: bool = False,
                             top_k: int = 0, top_p: float = 1.0):
        """Jitted slot-insert prefill for the continuous-batching serving
        runtime (serving/engine.py): run ONE request's bucket-padded
        prompt through a fresh bucket-sized cache, copy the prefix KV
        into slot ``slot`` of the persistent slot-paged cache
        (ops/attention.write_slot_rows), set the slot's valid length,
        and pick the first generated token from the logits at the TRUE
        last prompt position (pad tokens behind it are causally
        invisible, so bucket padding cannot change the pick). Slot index
        and true length are traced scalars — ONE compiled program per
        bucket serves every slot, length, and arrival pattern.

        Per-slot state is a tree (serving/kv_slots.py): key-value rows, and
        for a model with recurrent layers their state beside them. It is
        passed as its leaves in the model's ``slot_state_keys`` order
        (``k, v`` where the model declares none), so for a key-value-only
        model the operands are what they always were. Recurrent state is not
        addressed by rows and padding is NOT invisible to it: the model gets
        the true length as ``cache["valid_len"]`` and stops its state there;
        the slot's row of every other leaf is replaced whole.

        Signature of the returned program:
        ``(params, *state, lengths, ids[1, bucket], slot, length, temp,
        rng[, previous[B]]) -> (*state, lengths, first_token[, previous][,
        prompt_counts])`` (cache operands donated on TPU;
        :meth:`_with_first_token`). The serving loop passes
        ``previous``, the decode step's next tokens on the device (see
        :meth:`slot_decode_program`), and gets it back with the first token
        written at ``slot``: the next decode step can then be launched
        before the host has fetched that token."""
        key = ("slot_pf", bucket_len, num_slots, max_len, do_sample,
               top_k, float(top_p))
        if key not in self._compiled:
            model = self.module
            pick = self._make_pick(do_sample, top_k, float(top_p))
            names = slot_state_keys(model)
            rows = row_state_keys(model)

            def prefill(params, *ops):
                leaves = ops[:len(names)]
                lengths, ids, slot, length, temp, rng, *behind = \
                    ops[len(names):]
                state = dict(zip(names, leaves))
                cache = model.init_cache(1, bucket_len, dtype=self.dtype)
                cache["valid_len"] = length
                self._count_prompt(cache)
                with jax.named_scope("dstpu_prefill"):
                    logits, cache = model.forward_with_cache(params, ids,
                                                             cache)
                for name in rows:
                    state[name] = write_slot_rows(state[name], cache[name],
                                                  slot)
                for name in recurrent_state_keys(names, rows):
                    state[name] = insert_slot_row(state[name], cache[name],
                                                  slot)
                lengths = jax.lax.dynamic_update_index_in_dim(
                    lengths, length, slot, 0)
                last = self._last_logits(logits, length)         # [1, V]
                return self._with_first_token(
                    (*(state[n] for n in names), lengths),
                    pick(last, temp, rng)[0], slot, behind, cache)

            self._compiled[key] = _named_jit(
                f"prefill_{bucket_len}", prefill,
                donate_argnums=self._carry_donation(len(names)))
        return self._compiled[key]

    def slot_decode_program(self, num_slots: int, max_len: int, *,
                            do_sample: bool = False, top_k: int = 0,
                            top_p: float = 1.0, pad_token_id: int = 0):
        """Jitted persistent-cache decode step for the continuous-batching
        serving runtime: ONE token for every slot against the slot-paged
        KV cache with a per-slot valid-length vector
        (models/base.cache_positions + ops/attention per-slot masking).
        Inactive slots (``active`` false) keep their length, which goes
        stale once the slot is freed, and emit ``pad_token_id``. A slot
        mid-way through a chunked prefill is inactive here while its state
        is live, so ``active`` reaches the model twice over and an inactive
        slot's state is neither read nor written: as ``cache["valid_len"]``
        (1 or 0 real positions) for recurrent state, which has no rows to
        hide a write behind, and as ``cache["slot_walk"]`` for key-value
        rows, the order the fused decode step walks the slots in (active
        ones by descending length, ops/decode_step.slot_walk), made here
        once a step for every layer. The fused step fetches an active
        slot's own prefix and nothing of an inactive one, whatever its
        stale length; on the einsum path (a CPU, a bias, a window) an
        inactive slot's masked write still lands at the row behind its
        length, dead until the next prefill or chunk overwrites it. Fixed
        slot count + fixed cache shape = exactly one compiled program for
        the entire decode side of the serving loop, regardless of arrival
        pattern.

        Signature: ``(params, *state, lengths[B], tokens[B], active[B]
        bool, temp, rng[, previous[B], from_host[B] bool]) -> (*state,
        lengths, next_tokens[B])`` with the state's leaves as in
        :meth:`slot_prefill_program` (cache operands donated on TPU). The
        serving loop passes the two trailing operands: ``previous`` is the
        ``next_tokens`` of the step launched before this one, still on the
        device, and a slot's input is ``tokens`` where ``from_host`` says
        the host has it (a prefill just finished, a resume) and
        ``previous`` elsewhere, so a step can be launched before the last
        one's tokens have come back. ``previous`` is read only: never
        donated. A model that counts on the device what a step did
        (``model.step_counters``; an expert layer's touched experts) returns
        the counts behind the tokens, an int32 vector in that order, so the
        host fetches both at once and hands the vector to the model's
        ``record_step_counters(telemetry, counts)``."""
        from deepspeed_tpu.ops.decode_step import slot_walk

        key = ("slot_dec", num_slots, max_len, do_sample, top_k,
               float(top_p), pad_token_id)
        if key not in self._compiled:
            model = self.module
            pick = self._make_pick(do_sample, top_k, float(top_p))
            names = slot_state_keys(model)

            def decode(params, *ops):
                leaves = ops[:len(names)]
                lengths, tokens, active, temp, rng, *behind = ops[len(names):]
                if behind:
                    previous, from_host = behind
                    tokens = jnp.where(from_host, tokens, previous)
                cache = dict(zip(names, leaves), index=lengths,
                             valid_len=active.astype(jnp.int32),
                             slot_walk=slot_walk(lengths, active))
                with jax.named_scope("dstpu_decode"):
                    logits, cache = model.forward_with_cache(
                        params, tokens[:, None], cache)
                nxt = jnp.where(active, pick(logits[:, -1], temp, rng),
                                pad_token_id)
                lengths = jnp.where(active, lengths + 1, lengths)
                out = (*(cache[n] for n in names), lengths, nxt)
                if getattr(model, "step_counters", ()):
                    out += (cache["step_counters"],)
                return out

            self._compiled[key] = _named_jit(
                "decode", decode,
                donate_argnums=self._carry_donation(len(names)))
        return self._compiled[key]

    def slot_verify_program(self, num_slots: int, max_len: int, k: int, *,
                            do_sample: bool = False, top_k: int = 0,
                            top_p: float = 1.0, pad_token_id: int = 0):
        """Jitted speculative-decoding verify step (ISSUE 4,
        serving/speculative.py): score ``k`` drafted tokens per slot in
        ONE target-model forward over the slot-paged cache and emit each
        slot's accepted prefix plus one bonus/correction token.

        The [B, k+1] block (last committed token + k drafts) runs through
        the SAME ``forward_with_cache`` the decode step uses: per-slot
        positions from the length vector (models/base.cache_positions),
        per-slot-prefix + intra-block-causal masks in
        ops/attention.decode_attention, and a vector-idx block scatter
        writing all k+1 candidate K/V entries
        (ops/attention.write_kv_cache). Rollback of rejected drafts is
        free: the returned length vector advances only over the accepted
        prefix, so rejected cache entries stay dead behind the mask and
        the NEXT verify block overwrites them in place. One compiled
        program per k-bucket — k comes from the engine's fixed bucket
        set, so the jit cache stays pinned after warmup.

        Signature: ``(params, k_slots, v_slots, lengths[B], tokens[B,k+1],
        draft_len[B], active[B] bool, temp, rng) -> (k_slots, v_slots,
        lengths, out_tokens[B,k+1], n_emit[B])``; row b emits
        ``out_tokens[b, :n_emit[b]]`` (cache operands donated on TPU)."""
        from deepspeed_tpu.serving.speculative import speculative_acceptance

        key = ("slot_ver", num_slots, max_len, k, do_sample, top_k,
               float(top_p), pad_token_id)
        if key not in self._compiled:
            model = self.module

            def verify(params, k_slots, v_slots, lengths, tokens,
                       draft_len, active, temp, rng):
                cache = {"k": k_slots, "v": v_slots, "index": lengths}
                logits, cache = model.forward_with_cache(
                    params, tokens, cache)
                out_tokens, n_emit = speculative_acceptance(
                    logits, tokens, draft_len, temp, rng,
                    do_sample=do_sample, top_k=top_k, top_p=float(top_p),
                    pad_token_id=pad_token_id)
                n_emit = jnp.where(active, n_emit, 0)
                out_tokens = jnp.where(active[:, None], out_tokens,
                                       pad_token_id)
                lengths = lengths + n_emit      # n_emit is 0 when inactive
                return (cache["k"], cache["v"], lengths, out_tokens,
                        n_emit)

            donate = (1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit(f"verify_{k}", verify,
                                             donate_argnums=donate)
        return self._compiled[key]

    def slot_draft_program(self, window_len: int, num_slots: int, k: int):
        """Jitted greedy drafting for the DRAFT model of a speculative-
        decoding pair (serving/speculative.DraftModelDrafter): re-prefill
        each slot's trailing ``window_len`` history tokens into a fresh
        in-program cache, then roll ``k`` greedy tokens forward —
        returning [B, k] draft proposals in one compiled program.

        Stateless by design: the draft cache lives and dies inside the
        program, so there is no persistent draft KV to roll back when the
        target rejects, and the program's shapes never vary (one program
        per (window, k) pair, both from fixed bucket sets). Right-padded
        windows with a per-slot true length reuse the slot machinery:
        positions/masks come from the per-slot index vector, and each
        decode write lands at ``wlen + j``, overwriting window padding
        before the mask ever exposes it.

        Signature: ``(params, window[B, window_len] int32, wlen[B] int32
        >= 1) -> drafts[B, k] int32`` (greedy argmax; point-mass
        proposals stay lossless under both acceptance modes)."""
        key = ("slot_draft", window_len, num_slots, k)
        if key not in self._compiled:
            model = self.module
            cache_len = window_len + k

            def draft(params, window, wlen):
                cache = model.init_cache(num_slots, cache_len,
                                         dtype=self.dtype)
                zeros = jnp.zeros((num_slots,), jnp.int32)
                logits, cache = model.forward_with_cache(
                    params, window, {"k": cache["k"], "v": cache["v"],
                                     "index": zeros})
                # first draft from each row's TRUE last window position
                tok = jnp.argmax(jnp.take_along_axis(
                    logits, (wlen - 1)[:, None, None], axis=1
                )[:, 0].astype(jnp.float32), axis=-1).astype(jnp.int32)
                out = [tok]
                idx = wlen
                for _ in range(k - 1):
                    logits, cache = model.forward_with_cache(
                        params, tok[:, None],
                        {"k": cache["k"], "v": cache["v"], "index": idx})
                    tok = jnp.argmax(logits[:, -1].astype(jnp.float32),
                                     axis=-1).astype(jnp.int32)
                    out.append(tok)
                    idx = idx + 1
                return jnp.stack(out, axis=1)

            self._compiled[key] = _named_jit(f"draft_{k}", draft)
        return self._compiled[key]

    # --------------------------------------------- block-paged programs
    # (ISSUE 6, serving/kv_blocks.py + serving/radix.py): the slot
    # programs' prefix-sharing analogs. Same zero-recompile contract —
    # the block table is a TRACED int32 operand, never a shape, so one
    # compiled program per (bucket | k-bucket | step kind) serves every
    # block assignment the radix index produces.

    def block_prefill_program(self, bucket_len: int, num_slots: int,
                              max_blocks: int, *, do_sample: bool = False,
                              top_k: int = 0, top_p: float = 1.0,
                              kv_dtype: str = "compute"):
        """Jitted SUFFIX prefill against the block pool: run ONE
        request's bucket-padded UNMATCHED suffix through the pool with
        the slot's [1, MB] table row — the suffix tokens attend over the
        radix-matched prefix blocks already in the pool (start = matched
        length), and their K/V scatter through the table
        (ops/attention.write_kv_blocks). This is where the prefix-cache
        win lands: a matched prefix is never recomputed, and the bucket
        is picked by SUFFIX length, so a 2k-token shared system prompt
        with a 30-token user suffix prefills in the smallest bucket.

        Signature: ``(params, k_pool, v_pool, lengths, ids[1, bucket],
        table_row[1, MB], slot, start, suffix_len, temp, rng) ->
        (k_pool, v_pool, lengths, first_token)`` (pool operands donated
        on TPU). ``start`` is the matched prefix length; the slot's
        length becomes ``start + suffix_len``."""
        key = ("blk_pf", bucket_len, num_slots, max_blocks, do_sample,
               top_k, float(top_p), kv_dtype)
        if key not in self._compiled:
            model = self.module
            pick = self._make_pick(do_sample, top_k, float(top_p))

            def prefill(params, k_pool, v_pool, lengths, ids, table_row,
                        slot, start, length, temp, rng):
                idx = jnp.reshape(jnp.asarray(start, jnp.int32), (1,))
                cache = {"k": k_pool, "v": v_pool, "index": idx,
                         "block_table": table_row}
                logits, cache = model.forward_with_cache(params, ids, cache)
                lengths = jax.lax.dynamic_update_index_in_dim(
                    lengths, start + length, slot, 0)
                last = self._last_logits(logits, length)         # [1, V]
                return (cache["k"], cache["v"], lengths,
                        pick(last, temp, rng)[0])

            donate = (1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit(f"prefill_{bucket_len}", prefill,
                                             donate_argnums=donate)
        return self._compiled[key]

    def block_decode_program(self, num_slots: int, max_blocks: int, *,
                             do_sample: bool = False, top_k: int = 0,
                             top_p: float = 1.0, pad_token_id: int = 0,
                             kv_dtype: str = "compute"):
        """Jitted block-paged decode step: one token for every slot,
        KV addressed through the full [B, MB] block table (single-token
        decode on TPU routes to the fused Pallas block kernel,
        ops/decode_step.fused_block_decode_step). Inactive slots carry
        sentinel tables — their writes land in the pool's garbage row.

        Signature: ``(params, k_pool, v_pool, lengths[B], tables[B, MB],
        tokens[B], active[B] bool, temp, rng[, previous[B], from_host[B]
        bool]) -> (k_pool, v_pool, lengths, next_tokens[B])`` (pool
        operands donated on TPU); the trailing pair as in
        :meth:`slot_decode_program`."""
        key = ("blk_dec", num_slots, max_blocks, do_sample, top_k,
               float(top_p), pad_token_id, kv_dtype)
        if key not in self._compiled:
            model = self.module
            pick = self._make_pick(do_sample, top_k, float(top_p))

            def decode(params, k_pool, v_pool, lengths, tables, tokens,
                       active, temp, rng, *behind):
                if behind:
                    previous, from_host = behind
                    tokens = jnp.where(from_host, tokens, previous)
                cache = {"k": k_pool, "v": v_pool, "index": lengths,
                         "block_table": tables}
                logits, cache = model.forward_with_cache(
                    params, tokens[:, None], cache)
                nxt = jnp.where(active, pick(logits[:, -1], temp, rng),
                                pad_token_id)
                lengths = jnp.where(active, lengths + 1, lengths)
                return cache["k"], cache["v"], lengths, nxt

            donate = (1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit("decode", decode,
                                             donate_argnums=donate)
        return self._compiled[key]

    def block_verify_program(self, num_slots: int, max_blocks: int, k: int,
                             *, do_sample: bool = False, top_k: int = 0,
                             top_p: float = 1.0, pad_token_id: int = 0,
                             kv_dtype: str = "compute"):
        """Jitted speculative verify step over the block pool — the
        block-table analog of :meth:`slot_verify_program`. Rollback
        stays free: rejected candidates' K/V stay dead behind the
        per-slot length in the slot's PRIVATE decode blocks (a shared
        prefix block is never written after admit — the radix COW fork
        happens at admit time, before any decode write could touch a
        shared block), and the next verify block overwrites them in
        place through the same table.

        Signature: ``(params, k_pool, v_pool, lengths[B], tables[B, MB],
        tokens[B, k+1], draft_len[B], active[B] bool, temp, rng) ->
        (k_pool, v_pool, lengths, out_tokens[B, k+1], n_emit[B])``."""
        from deepspeed_tpu.serving.speculative import speculative_acceptance

        key = ("blk_ver", num_slots, max_blocks, k, do_sample, top_k,
               float(top_p), pad_token_id, kv_dtype)
        if key not in self._compiled:
            model = self.module

            def verify(params, k_pool, v_pool, lengths, tables, tokens,
                       draft_len, active, temp, rng):
                cache = {"k": k_pool, "v": v_pool, "index": lengths,
                         "block_table": tables}
                logits, cache = model.forward_with_cache(
                    params, tokens, cache)
                out_tokens, n_emit = speculative_acceptance(
                    logits, tokens, draft_len, temp, rng,
                    do_sample=do_sample, top_k=top_k, top_p=float(top_p),
                    pad_token_id=pad_token_id)
                n_emit = jnp.where(active, n_emit, 0)
                out_tokens = jnp.where(active[:, None], out_tokens,
                                       pad_token_id)
                lengths = lengths + n_emit
                return (cache["k"], cache["v"], lengths, out_tokens,
                        n_emit)

            donate = (1, 2, 3) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit(f"verify_{k}", verify,
                                             donate_argnums=donate)
        return self._compiled[key]

    def block_copy_program(self, num_blocks: int, block_size: int, *,
                           kv_dtype: str = "compute"):
        """Jitted one-block COW copy: duplicate pool block ``src`` into
        ``dst`` across both pools and every layer (the device half of a
        radix copy-on-write fork, serving/radix.PrefixCache.admit —
        issued BEFORE the suffix prefill that partially overwrites the
        fork). ``src``/``dst`` are traced scalars: one compiled program
        serves every fork. Quantized ``{"q", "s"}`` pools (ISSUE 12)
        copy leaf-wise — a fork carries the source block's payload AND
        its per-token scales, so the forked block dequantizes
        bit-identically to the shared original (pinned by tests).

        Signature: ``(k_pool, v_pool, src, dst) -> (k_pool, v_pool)``
        (pool operands donated on TPU)."""
        key = ("blk_copy", num_blocks, block_size, kv_dtype)
        if key not in self._compiled:
            def copy(k_pool, v_pool, src, dst):
                def copy_one(pool):
                    def f(leaf):
                        blk = jax.lax.dynamic_slice_in_dim(leaf, src, 1, 1)
                        return jax.lax.dynamic_update_slice_in_dim(
                            leaf, blk, dst, 1)

                    return jax.tree_util.tree_map(f, pool)

                return copy_one(k_pool), copy_one(v_pool)

            donate = (0, 1) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit("block_copy", copy,
                                             donate_argnums=donate)
        return self._compiled[key]

    # ----------------------------------------- SLO-aware serving programs
    # (ISSUE 8, serving/engine.py): chunked prefill against the
    # slot-paged cache, and the device halves of preemption KV
    # swap-out/in for both cache modes. Same zero-recompile contract as
    # every serving program: slot / start / length / block lists are
    # traced DATA, so chunk counts and preemption patterns are invisible
    # to the jit cache.

    def slot_chunk_prefill_program(self, bucket_len: int, num_slots: int,
                                   max_len: int, *, do_sample: bool = False,
                                   top_k: int = 0, top_p: float = 1.0):
        """Jitted mid-prompt CHUNK prefill against the slot-paged cache
        (ISSUE 8): run ONE request's bucket-padded prompt chunk with the
        slot's own cache row — the chunk's queries attend over the
        slot's already-prefilled prefix (``start`` tokens, a traced
        scalar) plus the chunk's own causal block, and its K/V scatter
        in at ``start .. start+length`` through the per-slot vector
        write path (ops/attention.write_kv_cache). The slot's row pair
        is sliced out (ops/attention.extract_slot_kv), stepped as a
        batch-1 cache, and written back. Slot/start/length are all
        traced, so ONE compiled program per bucket serves every chunk of
        every prompt — chunk COUNT is data, which is what lets long
        prompts prefill in fixed-bucket-sized pieces interleaved with
        decode steps without a single recompile (the block-paged mode
        needs no new program: block_prefill_program's ``start`` operand
        already is the chunk offset).

        The returned token is the pick at the chunk's TRUE last
        position — meaningful only on the FINAL chunk (the engine
        discards it for intermediate chunks; the first generated token
        of a chunked prompt exists only after the last chunk, which is
        also when TTFT is stamped).

        Recurrent state rides the same way: the slot's row of every leaf
        is sliced out, the chunk continues from it (a chunk that starts at
        0 starts from zeros, whatever the slot held), and the state at the
        chunk's true length is written back.

        Signature: ``(params, *state, lengths, ids[1, bucket], slot, start,
        length, temp, rng[, previous[B]]) -> (*state, lengths, token[,
        previous][, prompt_counts])`` with the state's leaves and the
        trailing operand as in :meth:`slot_prefill_program` (cache operands
        donated on TPU)."""
        key = ("slot_chunk_pf", bucket_len, num_slots, max_len, do_sample,
               top_k, float(top_p))
        if key not in self._compiled:
            model = self.module
            pick = self._make_pick(do_sample, top_k, float(top_p))
            names = slot_state_keys(model)

            def chunk(params, *ops):
                leaves = ops[:len(names)]
                lengths, ids, slot, start, length, temp, rng, *behind = \
                    ops[len(names):]
                state = dict(zip(names, leaves))
                idx = jnp.reshape(jnp.asarray(start, jnp.int32), (1,))
                cache = {n: extract_slot_row(state[n], slot) for n in names}
                cache.update(index=idx, valid_len=length)
                self._count_prompt(cache)
                logits, cache = model.forward_with_cache(params, ids, cache)
                for name in names:
                    state[name] = insert_slot_row(state[name], cache[name],
                                                  slot)
                lengths = jax.lax.dynamic_update_index_in_dim(
                    lengths, start + length, slot, 0)
                last = self._last_logits(logits, length)         # [1, V]
                return self._with_first_token(
                    (*(state[n] for n in names), lengths),
                    pick(last, temp, rng)[0], slot, behind, cache)

            self._compiled[key] = _named_jit(
                f"chunk_prefill_{bucket_len}", chunk,
                donate_argnums=self._carry_donation(len(names)))
        return self._compiled[key]

    def slot_swap_out_program(self, num_slots: int, max_len: int):
        """Jitted preemption swap-OUT for the slot-paged cache: slice
        slot ``slot``'s full row pair out (the engine device_gets it
        into the host swap buffer). Read-only — the cache operands are
        NOT donated, the caller keeps using them.

        Signature: ``(k_slots, v_slots, slot) -> (k_row, v_row)`` with
        rows ``[L, 1, Hkv, S(/pair), Dh(*pair)]``."""
        from deepspeed_tpu.ops.attention import extract_slot_kv

        key = ("slot_swap_out", num_slots, max_len)
        if key not in self._compiled:
            self._compiled[key] = _named_jit(
                "swap_out", lambda k, v, slot: extract_slot_kv(k, v, slot))
        return self._compiled[key]

    def slot_swap_in_program(self, num_slots: int, max_len: int):
        """Jitted preemption swap-IN for the slot-paged cache: write a
        host-uploaded row pair back into slot ``slot`` and restore its
        valid length — after this the slot decodes exactly as if it had
        never been preempted (bit-identical, pinned by tests).

        Signature: ``(k_slots, v_slots, k_row, v_row, lengths, slot,
        length) -> (k_slots, v_slots, lengths)`` (cache operands donated
        on TPU)."""
        from deepspeed_tpu.ops.attention import insert_slot_kv

        key = ("slot_swap_in", num_slots, max_len)
        if key not in self._compiled:
            def swap_in(k_slots, v_slots, k_row, v_row, lengths, slot,
                        length):
                k_slots, v_slots = insert_slot_kv(
                    k_slots, v_slots, k_row, v_row, slot)
                lengths = jax.lax.dynamic_update_index_in_dim(
                    lengths, jnp.asarray(length, jnp.int32), slot, 0)
                return k_slots, v_slots, lengths

            donate = (0, 1, 4) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit("swap_in", swap_in,
                                             donate_argnums=donate)
        return self._compiled[key]

    def block_swap_out_program(self, num_blocks: int, max_blocks: int, *,
                               kv_dtype: str = "compute"):
        """Jitted preemption swap-OUT for the block pool: gather the
        contents of one slot's table-named blocks (sentinel entries
        gather the garbage row — the engine trims to the blocks the
        request actually used before parking them on host). Read-only.

        Signature: ``(k_pool, v_pool, table[MB]) -> (k_blocks, v_blocks)``
        with blocks ``[L, MB, Hkv, bs(/pair), Dh(*pair)]``."""
        from deepspeed_tpu.ops.attention import gather_pool_blocks

        key = ("blk_swap_out", num_blocks, max_blocks, kv_dtype)
        if key not in self._compiled:
            self._compiled[key] = _named_jit(
                "swap_out", lambda k, v, table: gather_pool_blocks(k, v, table))
        return self._compiled[key]

    def block_swap_in_program(self, num_blocks: int, max_blocks: int, *,
                              kv_dtype: str = "compute"):
        """Jitted preemption swap-IN for the block pool: scatter
        host-uploaded block contents into the pool rows named by
        ``dst`` and restore the slot's valid length. Entries the
        restore skips (radix re-matched shared prefix blocks, allocated
        but never-written tail blocks) name the garbage row, so the
        program's shapes never vary with how much actually uploads.

        Signature: ``(k_pool, v_pool, k_blocks, v_blocks, dst[MB],
        lengths, slot, length) -> (k_pool, v_pool, lengths)`` (pool
        operands donated on TPU)."""
        from deepspeed_tpu.ops.attention import scatter_pool_blocks

        key = ("blk_swap_in", num_blocks, max_blocks, kv_dtype)
        if key not in self._compiled:
            def swap_in(k_pool, v_pool, k_blocks, v_blocks, dst, lengths,
                        slot, length):
                k_pool, v_pool = scatter_pool_blocks(
                    k_pool, v_pool, k_blocks, v_blocks, dst)
                lengths = jax.lax.dynamic_update_index_in_dim(
                    lengths, jnp.asarray(length, jnp.int32), slot, 0)
                return k_pool, v_pool, lengths

            donate = (0, 1, 5) if jax.default_backend() == "tpu" else ()
            self._compiled[key] = _named_jit("swap_in", swap_in,
                                             donate_argnums=donate)
        return self._compiled[key]

    # ------------------------------------------------------------- utilities
    def compiled_programs(self, batch: int, prompt_len: int, max_new: int,
                          *, do_sample: bool = False, top_k: int = 0,
                          top_p: float = 1.0):
        """The (prefill, decode) jitted programs generate() uses for this
        shape — built on demand, for a caller that times or lowers the
        programs directly without reconstructing the private cache keys. Greedy/eos-free only (decode is the scan
        program; the eos path's while-loop program is not exposed).

        NOTE: the decode program DONATES its cache argument
        (donate_argnums=(2,)) — a second dec() call on the same cache
        hits a deleted-buffer error; run the prefill program again per
        decode invocation."""
        self._build_generate(batch, prompt_len, max_new,
                             do_sample=do_sample, top_k=top_k,
                             top_p=float(top_p), eos_token_id=None,
                             pad_token_id=0)
        total = prompt_len + max_new
        pf = self._compiled[("pf", batch, prompt_len, total, do_sample,
                             top_k, float(top_p))]
        dec = self._compiled.get(("dec", batch, total, max_new, do_sample,
                                  top_k, float(top_p)))
        return pf, dec

    def jit_cache_sizes(self) -> Dict[str, int]:
        """Compiled-entry count per jitted program this engine has built
        (recompile accounting for telemetry: a program whose count keeps
        growing is recompiling — some argument's shape/dtype varies).
        Composed host-side drivers (``generate``'s gen closures) carry no
        cache and are skipped."""
        out: Dict[str, int] = {}
        for key, fn in self._compiled.items():
            size_fn = getattr(fn, "_cache_size", None)
            if size_fn is None:
                continue
            try:
                out[str(key)] = int(size_fn())
            except Exception:
                continue
        return out

    @property
    def config(self):
        return self._config

    def eval(self):  # torch-API compat no-op
        return self

    def to(self, *a, **k):  # torch-API compat no-op
        return self


def _is_torch_module(model) -> bool:
    try:
        import torch.nn as nn

        return isinstance(model, nn.Module)
    except Exception:
        return False
