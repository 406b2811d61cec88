"""Concrete accelerators: TPU and CPU-emulated.

Replaces the reference's ``accelerator/cuda_accelerator.py`` with a JAX-backed
implementation. The CPU accelerator exists so the entire framework (ZeRO, MoE,
PP meshes) runs on ``--xla_force_host_platform_device_count=N`` virtual devices
— something the reference's test harness could not do without GPUs
(tests/unit/common.py in the reference always needs real NCCL).
"""

from __future__ import annotations

from typing import List, Optional

from .abstract_accelerator import DeepSpeedAccelerator


# Dense bf16 peak TFLOPs per CHIP (not per core) by device-kind substring,
# from the published TPU system specs. The MFU denominator
# (telemetry/mfu.py); lookup is case-insensitive longest-match so
# "TPU v5 lite"/"TPU v5e" both hit the v5e entry. DSTPU_PEAK_TFLOPS
# (abstract_accelerator.peak_tflops) overrides for unlisted silicon.
TPU_PEAK_TFLOPS = {
    "v2": 45.0,
    "v3": 123.0,
    "v4": 275.0,
    "v5 lite": 197.0,
    "v5litepod": 197.0,
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
}

# HBM bandwidth per CHIP in GB/s, same published specs + lookup rules:
# the memory roof beside the compute roof above (chip_smoke.py reports
# both); DSTPU_PEAK_HBM_GBPS overrides.
TPU_PEAK_HBM_GBPS = {
    "v2": 700.0,
    "v3": 900.0,
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5litepod": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}


class TPU_Accelerator(DeepSpeedAccelerator):
    _name = "tpu"
    _communication_backend_name = "xla-ici"

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def devices(self) -> List:
        import jax

        return jax.devices()

    def device_count(self) -> int:
        import jax

        return jax.device_count()

    def local_device_count(self) -> int:
        import jax

        return jax.local_device_count()

    def is_available(self) -> bool:
        import jax

        try:
            return any(d.platform == "tpu" for d in jax.devices())
        except RuntimeError:
            return False

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16

    def is_fp16_supported(self) -> bool:
        # fp16 compute works but bf16 is native; DynamicLossScaler stays optional.
        return True

    def peak_tflops(self):
        env = super().peak_tflops()
        if env is not None:
            return env
        return self._kind_lookup(TPU_PEAK_TFLOPS)

    def peak_hbm_gbps(self):
        env = super().peak_hbm_gbps()
        if env is not None:
            return env
        return self._kind_lookup(TPU_PEAK_HBM_GBPS)

    def _kind_lookup(self, table):
        kind = self.device_kind().lower()
        best = None
        for sub, v in table.items():
            if sub in kind and (best is None or len(sub) > best[0]):
                best = (len(sub), v)
        return best[1] if best else None


class CPU_Accelerator(DeepSpeedAccelerator):
    """Host-platform accelerator for tests and CI (virtual multi-device mesh)."""

    _name = "cpu"
    _communication_backend_name = "xla-host"

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return "cpu"
        return f"cpu:{device_index}"

    def devices(self) -> List:
        import jax

        return jax.devices("cpu")

    def device_count(self) -> int:
        return len(self.devices())

    def local_device_count(self) -> int:
        return len(self.devices())

    def is_available(self) -> bool:
        return True

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.float32
