"""DeepSpeedCPUAdam / DeepSpeedCPUAdagrad — host-offload optimizers.

Analog of ``deepspeed/ops/adam/cpu_adam.py:13`` (+ ``adagrad/cpu_adagrad.py``):
the fp32 master weights and moments live in host RAM as numpy arrays; the
fused SIMD step (csrc/cpu_adam.cpp) updates them in place and emits the
bf16 copy-back buffer that is pushed to the TPU — the ``fp16_param_groups``
overlapped-copy path of the reference (``cpu_adam.py:117``).

``use_native=True`` (the default) builds the C++ op on first use and
raises if it cannot — a missing compiler is an error, never a quiet
numpy run two orders of magnitude slower. ``use_native=False`` asks for
the pure-numpy definition (the tests' oracle).
"""
from __future__ import annotations

import ctypes
from typing import Any, Dict, Optional

import numpy as np

from deepspeed_tpu.ops.op_builder import CPUAdamBuilder


def _as_f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _as_u16p(a: Optional[np.ndarray]):
    if a is None:
        return ctypes.POINTER(ctypes.c_uint16)()
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint16))


class DeepSpeedCPUAdam:
    """Per-leaf host Adam over a pytree of flat fp32 numpy arrays."""

    def __init__(self, lr=1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, adamw_mode=True, use_native=True):
        self.lr = lr
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adamw_mode = adamw_mode
        self.step_count = 0
        self._lib = CPUAdamBuilder().load() if use_native else None

    @property
    def native(self) -> bool:
        return self._lib is not None

    def init_state(self, master: Dict[str, np.ndarray]):
        return {k: {"m": np.zeros_like(v), "v": np.zeros_like(v)}
                for k, v in master.items()}

    def step(self, master: Dict[str, np.ndarray],
             grads: Dict[str, np.ndarray], state: Dict[str, Any],
             lr: Optional[float] = None,
             bf16_out: Optional[Dict[str, np.ndarray]] = None,
             step: Optional[int] = None) -> None:
        """In-place update of every leaf. ``bf16_out[k]`` (uint16 view)
        receives the bf16 copy in the same pass when provided. ``step``
        pins the bias-correction step for leaf-at-a-time callers (NVMe
        swap loop) — default auto-increments once per call."""
        if step is None:
            self.step_count += 1
        else:
            self.step_count = int(step)
        lr = self.lr if lr is None else float(lr)
        for k, w in master.items():
            g = grads[k]
            st = state[k]
            out = None if bf16_out is None else bf16_out.get(k)
            if self._lib is not None:
                assert w.dtype == np.float32 and w.flags["C_CONTIGUOUS"]
                self._lib.dstpu_adam_update(
                    _as_f32p(w), _as_f32p(g), _as_f32p(st["m"]),
                    _as_f32p(st["v"]), w.size, self.step_count, lr,
                    self.beta1, self.beta2, self.eps, self.weight_decay,
                    1 if self.adamw_mode else 0, _as_u16p(out))
            else:
                self._numpy_step(w, g, st, lr, out)

    def _numpy_step(self, w, g, st, lr, out):
        if not self.adamw_mode and self.weight_decay > 0:
            g = g + self.weight_decay * w
        st["m"][:] = self.beta1 * st["m"] + (1 - self.beta1) * g
        st["v"][:] = self.beta2 * st["v"] + (1 - self.beta2) * g * g
        bc1 = 1 - self.beta1 ** self.step_count
        bc2 = 1 - self.beta2 ** self.step_count
        denom = np.sqrt(st["v"]) / np.sqrt(bc2) + self.eps
        if self.adamw_mode and self.weight_decay > 0:
            w *= 1 - lr * self.weight_decay
        w -= (lr / bc1) * st["m"] / denom
        if out is not None:
            out[:] = _f32_to_bf16_np(w)


class DeepSpeedCPUAdagrad:
    """Host Adagrad (reference ops/adagrad/cpu_adagrad.py)."""

    def __init__(self, lr=1e-2, eps=1e-10, weight_decay=0.0,
                 use_native=True):
        self.lr = lr
        self.eps = eps
        self.weight_decay = weight_decay
        self._lib = CPUAdamBuilder().load() if use_native else None

    def init_state(self, master):
        return {k: {"h": np.zeros_like(v)} for k, v in master.items()}

    def step(self, master, grads, state, lr=None, bf16_out=None):
        lr = self.lr if lr is None else float(lr)
        for k, w in master.items():
            g = grads[k]
            st = state[k]
            out = None if bf16_out is None else bf16_out.get(k)
            if self._lib is not None:
                self._lib.dstpu_adagrad_update(
                    _as_f32p(w), _as_f32p(g), _as_f32p(st["h"]), w.size,
                    lr, self.eps, self.weight_decay, _as_u16p(out))
            else:
                gg = g + self.weight_decay * w if self.weight_decay else g
                st["h"] += gg * gg
                w -= lr * gg / (np.sqrt(st["h"]) + self.eps)
                if out is not None:
                    out[:] = _f32_to_bf16_np(w)


def _f32_to_bf16_np(w: np.ndarray) -> np.ndarray:
    """Round-to-nearest-even fp32→bf16 (uint16 payload); NaN stays NaN
    (RNE carry would overflow a NaN mantissa into the Inf pattern)."""
    x = w.view(np.uint32)
    lsb = (x >> 16) & 1
    rounded = ((x + 0x7FFF + lsb) >> 16).astype(np.uint16)
    nan = (x & 0x7FFFFFFF) > 0x7F800000
    return np.where(nan, ((x >> 16) | 0x0040).astype(np.uint16), rounded)
