"""deepspeed_tpu — a TPU-native distributed training & inference framework
with the capability surface of DeepSpeed (reference v0.9.2), re-designed for
JAX/XLA/pjit/Pallas. See SURVEY.md at the repo root for the capability map.

Public API parity with reference ``deepspeed/__init__.py``:
    initialize()            (:58)  — engine selection + wiring
    init_inference()        (:260) — inference engine
    init_distributed        — comm facade init
    add_config_arguments    (:237) — argparse bootstrap
    comm, zero, ops, moe, PipelineModule re-exports
"""

from __future__ import annotations

import argparse
import re
from typing import Any, Optional, Union

import jax

# written against this jax's own names (``jax.shard_map`` with ``check_vma``
# and ``axis_names``, ``lax.pcast``, avals that carry ``vma``) with no layer
# that translates to an older one's: the version the tests and the chip run
MIN_JAX = (0, 9, 0)


class UnsupportedJaxError(ImportError):
    """The installed jax is older than the one this package is written for."""


def require_jax(installed: str) -> None:
    """Refuse a jax older than :data:`MIN_JAX`, here and by name, before an
    import deeper in the package fails on a name that jax does not have."""
    if tuple(map(int, re.findall(r"\d+", installed)[:3])) < MIN_JAX:
        raise UnsupportedJaxError(
            f"deepspeed_tpu needs jax >= {'.'.join(map(str, MIN_JAX))}, "
            f"found {installed}")


require_jax(jax.__version__)

from deepspeed_tpu.accelerator import get_accelerator, set_accelerator
from deepspeed_tpu import comm
from deepspeed_tpu import ops
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.utils.logging import log_dist, logger

__version__ = "0.1.0"
__git_branch__ = "main"


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, topology=None, mpu=None,
               dist_init_required: Optional[bool] = None, collate_fn=None,
               config: Union[dict, str, None] = None, config_params=None):
    """Initialize the engine (reference deepspeed/__init__.py:58).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` exactly
    like the reference. ``model`` is a ModelSpec (see models/base.py) or a
    flax module wrapped in FlaxModelAdapter. Engine selection mirrors the
    reference (:150-190): PipelineModule → PipelineEngine, hybrid_engine
    section → HybridEngine, else DeepSpeedEngine.
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    if config is None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") \
            and args.deepspeed_config is not None:
        config = args.deepspeed_config
    assert config is not None, "a config dict/path is required"

    log_dist(f"deepspeed_tpu info: version={__version__}", ranks=[0])
    init_distributed(dist_init_required=dist_init_required)

    from deepspeed_tpu.runtime.pipe.module import PipelineModule

    ds_config = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(config)
    if isinstance(model, PipelineModule):
        from deepspeed_tpu.runtime.pipe.engine import PipelineEngine

        engine = PipelineEngine(model, ds_config, optimizer=optimizer,
                                lr_scheduler=lr_scheduler, training_data=training_data,
                                collate_fn=collate_fn, topology=topology)
    elif ds_config.hybrid_engine.enabled:
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

        engine = DeepSpeedHybridEngine(model, ds_config, optimizer=optimizer,
                                       lr_scheduler=lr_scheduler,
                                       training_data=training_data,
                                       collate_fn=collate_fn, topology=topology)
    else:
        engine = DeepSpeedEngine(model, ds_config, optimizer=optimizer,
                                 lr_scheduler=lr_scheduler, training_data=training_data,
                                 collate_fn=collate_fn, topology=topology)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Inference engine factory (reference deepspeed/__init__.py:260)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    if config is None:
        config = kwargs
    elif kwargs:
        config = {**(config if isinstance(config, dict) else {}), **kwargs}
    if not isinstance(config, DeepSpeedInferenceConfig):
        config = DeepSpeedInferenceConfig(**config)
    return InferenceEngine(model, config)


def add_config_arguments(parser: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """CLI bootstrap flags (reference deepspeed/__init__.py:237)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed-TPU json configuration file")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse.SUPPRESS)
    group.add_argument("--local_rank", default=-1, type=int,
                       help="Reserved for launcher compatibility")
    return parser


def _lazy(name: str):
    import importlib

    return importlib.import_module(name)


# convenience namespaces (populated lazily to keep import light)
def __getattr__(name: str):
    if name == "zero":
        return _lazy("deepspeed_tpu.runtime.zero")
    if name == "serving":
        return _lazy("deepspeed_tpu.serving")
    if name == "telemetry":
        return _lazy("deepspeed_tpu.telemetry")
    if name == "PipelineModule":
        return _lazy("deepspeed_tpu.runtime.pipe.module").PipelineModule
    if name == "moe":
        return _lazy("deepspeed_tpu.moe")
    if name == "checkpointing":
        return _lazy("deepspeed_tpu.runtime.activation_checkpointing")
    raise AttributeError(f"module 'deepspeed_tpu' has no attribute '{name}'")
