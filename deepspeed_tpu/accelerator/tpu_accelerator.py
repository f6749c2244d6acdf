"""TPU (and CPU-mesh fallback) accelerator implementations.

Analog of ``accelerator/cuda_accelerator.py`` — the concrete device layer
behind :func:`deepspeed_tpu.accelerator.get_accelerator`.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from deepspeed_tpu.accelerator.abstract_accelerator import (
    DeepSpeedAccelerator)


class TPU_Accelerator(DeepSpeedAccelerator):
    _name = "tpu"
    _communication_backend_name = "xla"   # ICI/DCN collectives via XLA

    def device_name(self, device_index: Optional[int] = None) -> str:
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def device(self, device_index: int = 0):
        return jax.devices()[device_index]

    def device_count(self) -> int:
        return jax.device_count()

    def current_device(self) -> int:
        # single-controller SPMD: "current" = the default device
        return 0

    def is_available(self) -> bool:
        try:
            return any(d.platform == "tpu" for d in jax.devices())
        except RuntimeError:
            return False

    def synchronize(self, device_index: Optional[int] = None) -> None:
        # force a host transfer: fetching a value enqueued after all
        # prior work is a barrier on every backend
        float(jnp.zeros(()).block_until_ready() + 0.0)

    def manual_seed(self, seed: int):
        return jax.random.PRNGKey(seed)

    def memory_stats(self, device_index: Optional[int] = None) -> dict:
        d = self.device(device_index or 0)
        try:
            return dict(d.memory_stats() or {})
        except Exception:
            return {}

    def pin_memory(self, array):
        try:
            from jax.sharding import SingleDeviceSharding
            return jax.device_put(array, SingleDeviceSharding(
                self.device(0), memory_kind="pinned_host"))
        except Exception:
            return array


class CPU_Accelerator(TPU_Accelerator):
    """Virtual-mesh / test backend: same surface over XLA:CPU devices."""
    _name = "cpu"
    _communication_backend_name = "xla"

    def is_available(self) -> bool:
        return True

    def pin_memory(self, array):
        return array  # XLA:CPU has no distinct host memory space
