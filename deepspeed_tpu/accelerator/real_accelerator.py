"""Accelerator selection — analog of the reference's
``accelerator/real_accelerator.py`` (get_accelerator/set_accelerator).

Selection order:
  1. explicit ``set_accelerator()``
  2. ``DSTPU_ACCELERATOR`` env var ("tpu" | "cpu"); "tpu" with no TPU
     present raises :class:`AcceleratorUnavailableError` — an explicit
     request is never answered with the CPU
  3. auto-detect: TPU if the default jax backend exposes TPU devices,
     else CPU.
"""

from __future__ import annotations

import os
from typing import Optional

from .abstract_accelerator import DeepSpeedAccelerator

_accelerator: Optional[DeepSpeedAccelerator] = None


class AcceleratorUnavailableError(RuntimeError):
    """The accelerator that was asked for by name is not present."""


def _detect() -> DeepSpeedAccelerator:
    from .tpu_accelerator import CPU_Accelerator, TPU_Accelerator

    env = os.environ.get("DSTPU_ACCELERATOR")
    if env == "cpu":
        return CPU_Accelerator()
    tpu = TPU_Accelerator()
    if tpu.is_available():
        return tpu
    if env == "tpu":
        raise AcceleratorUnavailableError(
            "DSTPU_ACCELERATOR=tpu but jax.devices() holds no TPU "
            "(unset the variable to auto-detect, or set it to 'cpu')")
    return CPU_Accelerator()


def get_accelerator() -> DeepSpeedAccelerator:
    global _accelerator
    if _accelerator is None:
        _accelerator = _detect()
    return _accelerator


def set_accelerator(accel: DeepSpeedAccelerator) -> None:
    global _accelerator
    _accelerator = accel


def is_current_accelerator_supported() -> bool:
    return get_accelerator().is_available()
