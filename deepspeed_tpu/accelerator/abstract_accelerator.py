"""Accelerator abstraction (L0).

TPU-native re-design of the reference's ``accelerator/abstract_accelerator.py``
(DeepSpeedAccelerator, ~70 methods). Every other layer asks ``get_accelerator()``
for device facts instead of touching ``jax`` backends directly, which is what
makes the whole stack runnable on the CPU-emulated multi-device mesh used by the
test harness.

Differences from the reference surface, by design:
  * no streams/events — XLA owns scheduling; ``synchronize`` maps to
    ``block_until_ready`` on request.
  * tensor factory methods return jnp dtypes/arrays, not torch tensors.
  * ``communication_backend_name`` names the collective lowering ("xla-ici"),
    consumed by :mod:`deepspeed_tpu.comm`.
"""

from __future__ import annotations

import abc
from typing import Any, Dict, List, Optional


class DeepSpeedAccelerator(abc.ABC):
    _name: str = "abstract"
    _communication_backend_name: str = "undefined"

    # ------------------------------------------------------------------ device
    @abc.abstractmethod
    def device_name(self, device_index: Optional[int] = None) -> str:
        ...

    @abc.abstractmethod
    def device_count(self) -> int:
        """Global device count visible to this process group."""

    @abc.abstractmethod
    def local_device_count(self) -> int:
        ...

    @abc.abstractmethod
    def devices(self) -> List[Any]:
        ...

    def current_device(self) -> int:
        return 0

    def current_device_name(self) -> str:
        return self.device_name(self.current_device())

    def set_device(self, device_index: int) -> None:  # no-op: XLA places arrays
        pass

    @abc.abstractmethod
    def is_available(self) -> bool:
        ...

    def process_count(self) -> int:
        import jax

        return jax.process_count()

    def process_index(self) -> int:
        import jax

        return jax.process_index()

    # --------------------------------------------------------------- execution
    def synchronize(self, obj: Any = None) -> None:
        """Block until device work completes (analog of torch.cuda.synchronize)."""
        import jax

        if obj is not None:
            jax.block_until_ready(obj)
        else:
            # Barrier against all pending local computations.
            jax.effects_barrier()

    # ---------------------------------------------------------------------- RNG
    def default_rng(self, seed: int):
        import jax

        return jax.random.PRNGKey(seed)

    # ------------------------------------------------------------------- memory
    def memory_stats(self, device_index: int = 0) -> Dict[str, int]:
        try:
            d = self.devices()[device_index]
            return dict(d.memory_stats() or {})
        except Exception:
            return {}

    def memory_allocated(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("peak_bytes_in_use", 0)

    def total_memory(self, device_index: int = 0) -> int:
        return self.memory_stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index: int = 0) -> int:
        stats = self.memory_stats(device_index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def reset_peak_memory_stats(self, device_index: int = 0) -> None:
        pass  # not supported by all backends; peak stats are advisory

    def empty_cache(self) -> None:
        pass

    # -------------------------------------------------------------------- dtype
    @abc.abstractmethod
    def preferred_dtype(self):
        """The fast matmul dtype on this accelerator (bf16 on TPU)."""

    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True

    def supported_dtypes(self):
        import jax.numpy as jnp

        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # ------------------------------------------------------------- peak flops
    def peak_tflops(self) -> Optional[float]:
        """Dense peak TFLOPs per chip in the fast matmul dtype
        (:meth:`preferred_dtype`) — the MFU denominator
        (telemetry/mfu.py). Concrete accelerators consult their
        device-kind table; ``DSTPU_PEAK_TFLOPS`` overrides everywhere
        (new silicon, derated quotas, CPU test runs). None = unknown, and
        MFU-vs-peak is simply not reported."""
        import os

        env = os.environ.get("DSTPU_PEAK_TFLOPS")
        if env:
            try:
                return float(env)
            except ValueError:
                pass
        return None

    def peak_hbm_gbps(self) -> Optional[float]:
        """Peak HBM bandwidth per chip in GB/s: the memory roof a roofline
        is judged against (``chip_smoke.py`` reports it beside
        ``peak_tflops()`` and refuses a chip with no entry). Concrete
        accelerators consult their device-kind table;
        ``DSTPU_PEAK_HBM_GBPS`` overrides everywhere. None = unknown."""
        import os

        env = os.environ.get("DSTPU_PEAK_HBM_GBPS")
        if env:
            try:
                return float(env)
            except ValueError:
                pass
        return None

    # ------------------------------------------------------------ profiler hooks
    def range_push(self, msg: str):
        """NVTX analog: jax profiler trace annotation (used by instrument_w_scope)."""
        import jax

        ctx = jax.profiler.TraceAnnotation(msg)
        ctx.__enter__()
        self._range_stack = getattr(self, "_range_stack", [])
        self._range_stack.append(ctx)

    def range_pop(self):
        stack = getattr(self, "_range_stack", [])
        if stack:
            stack.pop().__exit__(None, None, None)

    # ------------------------------------------------------------- communication
    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    # --------------------------------------------------------------- op builders
    def create_op_builder(self, op_name: str):
        from deepspeed_tpu.ops.registry import get_op_builder

        return get_op_builder(op_name)(accelerator=self)

    def get_op_builder(self, op_name: str):
        from deepspeed_tpu.ops.registry import get_op_builder

        return get_op_builder(op_name)

    # -------------------------------------------------------------------- naming
    def name(self) -> str:
        return self._name

    def platform(self) -> str:
        return self._name

    def is_synchronized_device(self) -> bool:
        return False

    def device_kind(self) -> str:
        try:
            return self.devices()[0].device_kind
        except Exception:
            return "unknown"
