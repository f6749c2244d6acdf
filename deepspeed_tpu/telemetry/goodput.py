"""Goodput accounting: where does a train step's wall time go?

"As fast as the hardware allows" (ROADMAP) is only meaningful as a
fraction: of each ``train_step`` wall interval, how much was the device
actually computing, versus the host waiting on data or running python?
:class:`GoodputMeter` splits every step's wall time into three buckets
that sum to it **by construction**:

* ``data_wait`` — time spent fetching the batch (the dataloader
  ``next()``); zero when the caller hands the batch in.
* ``device``   — dispatch → ``block_until_ready`` of the step's outputs:
  the device-side compute (plus its launch latency).
* ``host``     — the remainder: host-side sync, python overhead, monitor
  writes, host-offload optimizer work.

``host = wall − data_wait − device``, so the histograms' sums reconcile
exactly (tests/test_numerics_goodput.py pins it). The meter is
config-gated (``telemetry.goodput``) because the device bucket requires
one ``block_until_ready`` per step — it trades async step pipelining
for an honest split, the same trade ``wall_clock_breakdown`` makes at
print cadence.

Host-pure: no jax import (the *caller* measures the device interval).
"""
from __future__ import annotations

import threading
from typing import Optional

from deepspeed_tpu.telemetry.registry import MetricRegistry, get_registry


class GoodputMeter:
    """Per-step wall-time bucket accounting over the registry.

    ``source`` labels every instrument (``engine="train"`` /
    ``"pipeline"``) so two engines in one process stay separable on the
    scrape surface. A disabled meter records nothing — ``record_step``
    is a single attribute read.
    """

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 enabled: bool = False, source: str = "train"):
        self.registry = registry if registry is not None else get_registry()
        self.enabled = bool(enabled)
        self.source = source
        self._labels = {"engine": source}
        self._lock = threading.Lock()
        self.steps = 0
        self.wall_total = 0.0
        self.data_wait_total = 0.0
        self.device_total = 0.0
        self.host_total = 0.0

    def record_step(self, wall_s: float, data_wait_s: float = 0.0,
                    device_s: float = 0.0) -> None:
        """Record one step's split. ``host`` is derived, so the three
        buckets always sum to ``wall_s`` (clock jitter clamps at 0)."""
        if not self.enabled:
            return
        wall = max(float(wall_s), 0.0)
        data = min(max(float(data_wait_s), 0.0), wall)
        device = min(max(float(device_s), 0.0), wall - data)
        host = wall - data - device
        with self._lock:
            self.steps += 1
            self.wall_total += wall
            self.data_wait_total += data
            self.device_total += device
            self.host_total += host
            fraction = (self.device_total / self.wall_total
                        if self.wall_total > 0 else 0.0)
        self.registry.histogram(
            "train_goodput_step_wall_seconds",
            help="train_batch wall interval (entry to exit)",
            labels=self._labels).observe(wall)
        self.registry.histogram(
            "train_goodput_data_wait_seconds",
            help="per-step time fetching the batch from the dataloader",
            labels=self._labels).observe(data)
        self.registry.histogram(
            "train_goodput_device_seconds",
            help="per-step dispatch-to-ready device interval",
            labels=self._labels).observe(device)
        self.registry.histogram(
            "train_goodput_host_seconds",
            help="per-step host remainder: sync, python, monitors, "
                 "host-offload optimizer (= wall - data_wait - device)",
            labels=self._labels).observe(host)
        self.registry.gauge(
            "train_goodput_fraction",
            help="cumulative device-compute share of train-step wall "
                 "time (1.0 = as fast as the hardware allows)",
            labels=self._labels).set(fraction)

    def snapshot(self) -> dict:
        """JSON-able totals."""
        with self._lock:
            return {
                "enabled": self.enabled,
                "source": self.source,
                "steps": self.steps,
                "wall_s": self.wall_total,
                "data_wait_s": self.data_wait_total,
                "device_s": self.device_total,
                "host_s": self.host_total,
                "fraction": (self.device_total / self.wall_total
                             if self.wall_total > 0 else 0.0),
            }
