"""Unified telemetry: metrics registry + spans + exposition + capture.

The observability layer under both engines (ROADMAP: you cannot make the
hot path faster than the hardware without measuring it first):

* ``registry`` — process-wide counters/gauges/histograms (fixed
  exponential buckets → p50/p90/p99 without stored samples)
* ``spans`` — host spans that record into histograms AND the jax
  profiler timeline via ``profiling/trace.py``
* ``exporter`` — Prometheus-text / JSON scrape endpoint (stdlib
  ``http.server``, config-gated, off by default)
* ``capture`` — on-demand ``jax.profiler`` capture scoped in steps
  ("trace the next N decode steps to this logdir")

Everything here is host-pure except ``capture``'s default hooks; no
module imports jax at import time, so the registry is usable from config
parsing and test collection alike.
"""
from deepspeed_tpu.telemetry.accounting import (RequestLedger, TenantMeter,
                                                merge_cost_legs,
                                                new_cost_record,
                                                register_cost_histograms)
from deepspeed_tpu.telemetry.alerts import AlertEngine
from deepspeed_tpu.telemetry.canary import CANARY_TENANT, CanaryProber
from deepspeed_tpu.telemetry.capacity import CapacityModel, rollup_capacity
from deepspeed_tpu.telemetry.capture import ProfilerCapture
from deepspeed_tpu.telemetry.compile_watch import (WatchedFunction,
                                                   all_watched,
                                                   compile_report,
                                                   executable_cost,
                                                   kernel_table,
                                                   movement_table,
                                                   pass_table,
                                                   phase_totals,
                                                   scope_table,
                                                   watched_jit)
from deepspeed_tpu.telemetry.config import (AccountingConfig,
                                            CanaryConfig,
                                            FaultInjectionConfig,
                                            IncidentConfig, SLOConfig,
                                            SLOObjectiveConfig,
                                            TelemetryConfig)
from deepspeed_tpu.telemetry.events import (EventRing, dump_ring,
                                            get_event_ring,
                                            install_fault_dump,
                                            record_event, set_event_ring)
from deepspeed_tpu.telemetry.faultinject import (CkptWriteFault, DataStall,
                                                 FaultInjector,
                                                 PrefillFault,
                                                 ReplicaKilled, StepCrash,
                                                 TrainingPreempted)
from deepspeed_tpu.telemetry.goodput import GoodputMeter
from deepspeed_tpu.telemetry.incident import (IncidentRecorder,
                                              config_fingerprint,
                                              last_incident_path)
from deepspeed_tpu.telemetry.exporter import (TelemetryHTTPServer,
                                              start_http_server)
from deepspeed_tpu.telemetry.memory import (KVPoolAccountant,
                                            MemoryMonitor,
                                            get_memory_monitor,
                                            set_memory_monitor)
from deepspeed_tpu.telemetry.numerics import (BlockSpec, NumericsWatch,
                                              block_nonfinite_counts,
                                              block_spec, block_sq_norms,
                                              numerics_snapshot,
                                              register_numerics_watch,
                                              unregister_numerics_watch)
from deepspeed_tpu.telemetry.registry import (DEFAULT_TIME_BUCKETS, Counter,
                                              Gauge, Histogram,
                                              MetricRegistry,
                                              exponential_buckets,
                                              get_registry,
                                              sanitize_metric_name,
                                              set_registry)
from deepspeed_tpu.telemetry.slo import SLOMonitor
from deepspeed_tpu.telemetry.spans import (SpanLog, get_span_log,
                                            set_span_log, span, timed)
from deepspeed_tpu.telemetry.step_profile import (NULL_STEP_HANDLE,
                                                  StepProfiler)
from deepspeed_tpu.telemetry.tracing import (Trace, Tracer, TraceSpan,
                                             current_span, get_tracer,
                                             set_tracer)
from deepspeed_tpu.telemetry.watchdog import Watchdog

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricRegistry",
    "DEFAULT_TIME_BUCKETS", "exponential_buckets", "get_registry",
    "set_registry", "sanitize_metric_name", "span", "timed",
    "SpanLog", "get_span_log", "set_span_log",
    "TelemetryHTTPServer", "start_http_server", "ProfilerCapture",
    "TelemetryConfig", "SLOConfig",
    # flight recorder (events ring / compile watch / memory / watchdog)
    "EventRing", "get_event_ring", "set_event_ring", "record_event",
    "install_fault_dump", "WatchedFunction", "watched_jit",
    "compile_report", "all_watched", "executable_cost",
    "phase_totals", "scope_table", "kernel_table", "movement_table",
    "pass_table",
    "MemoryMonitor", "get_memory_monitor", "set_memory_monitor",
    "Watchdog",
    # training numerics observatory + goodput accounting
    "BlockSpec", "NumericsWatch", "block_spec", "block_sq_norms",
    "block_nonfinite_counts", "numerics_snapshot",
    "register_numerics_watch", "unregister_numerics_watch",
    "GoodputMeter", "dump_ring",
    # request-scoped tracing + SLO gates
    "Trace", "Tracer", "TraceSpan", "current_span", "get_tracer",
    "set_tracer", "SLOMonitor",
    # fault injection (chaos hooks for the serving lifecycle layer)
    "FaultInjector", "FaultInjectionConfig", "PrefillFault",
    "ReplicaKilled",
    # serving step observatory + KV-pool accounting
    "StepProfiler", "NULL_STEP_HANDLE", "KVPoolAccountant",
    # request-level cost accounting + tenant metering + capacity model
    "RequestLedger", "TenantMeter", "merge_cost_legs",
    "new_cost_record", "register_cost_histograms",
    "CapacityModel", "rollup_capacity", "AccountingConfig",
    # SLO alerting + canary probes + incident bundles (the closed loop)
    "AlertEngine", "CanaryProber", "CANARY_TENANT", "IncidentRecorder",
    "config_fingerprint", "last_incident_path",
    "SLOObjectiveConfig", "CanaryConfig", "IncidentConfig",
]
