"""Test-harness utilities for users of the framework.

Analog of the reference's distributed unit-test harness
(``tests/unit/common.py``: ``DistributedTest`` classes declare
``world_size`` and the harness spawns that many NCCL processes;
``DistributedFixture`` for cross-world-size fixtures). On TPU/XLA a
single process owns all devices, so "distribution" in tests is a mesh
over local (or CPU-simulated) devices — no forkserver, no rendezvous:

* ``DistributedTest``: subclass with ``world_size = N``; each test
  method receives ``self.mesh``, an N-device mesh over the axes in
  ``mesh_axes``. Skips (like the reference's pytest skip translation)
  when fewer than N devices exist.
* ``virtual_mesh(n, axes)``: build a mesh from the first ``n`` devices.
* ``requires_devices(n)``: pytest skip marker helper.

For N virtual devices on CPU set, before jax initializes (conftest):
``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def virtual_mesh(n: Optional[int] = None,
                 axes: Dict[str, int] | Sequence[Tuple[str, int]] = None
                 ) -> Mesh:
    """Mesh over the first ``n`` local devices. ``axes``: {name: size}
    whose product must be ``n`` (one 'data' axis by default)."""
    devices = jax.devices()
    if n is None:
        n = len(devices)
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    if axes is None:
        axes = {"data": n}
    items = list(axes.items()) if isinstance(axes, dict) else list(axes)
    names = tuple(k for k, _ in items)
    shape = tuple(v for _, v in items)
    total = int(np.prod(shape))
    if total != n:
        raise ValueError(f"axes {dict(items)} product {total} != {n}")
    return Mesh(np.array(devices[:n]).reshape(shape), names)


def requires_devices(n: int):
    """``@requires_devices(8)`` — skip when the backend has fewer
    devices (the harness analog of the reference's world-size skips).
    The device count is read at CALL time, not decoration time: touching
    ``jax.device_count()`` during collection would freeze the platform
    before a fixture/pytest_configure could set the virtual mesh up."""
    import functools

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import pytest
            if jax.device_count() < n:
                pytest.skip(f"needs {n} devices, have "
                            f"{jax.device_count()}")
            return fn(*args, **kwargs)
        return wrapper
    return deco


class DistributedTest:
    """Subclass with ``world_size`` (and optionally ``mesh_axes``); test
    methods read ``self.mesh``. Mirrors the reference's class-level
    declaration (tests/unit/common.py:244) without process spawning —
    the mesh IS the world."""

    world_size: int = 2
    mesh_axes: Optional[Dict[str, int]] = None

    @property
    def mesh(self) -> Mesh:
        import pytest
        if jax.device_count() < self.world_size:
            pytest.skip(f"needs {self.world_size} devices, have "
                        f"{jax.device_count()}")
        return virtual_mesh(self.world_size, self.mesh_axes)
