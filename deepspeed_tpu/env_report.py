"""``dstpu_report`` — environment & op compatibility report.

Analog of ``deepspeed/env_report.py`` (``ds_report`` CLI, 143 LoC): prints
the framework/runtime version matrix and an op-availability table. On TPU
"op installed" means the Pallas kernel imports and traces (no JIT C++
builds), plus the native host-side ops (C++ CPU-Adam / AIO) when built.
"""
from __future__ import annotations

import importlib
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


OPS = {
    "flash_attention": "deepspeed_tpu.ops.pallas.flash_attention",
    "decode_attention": "deepspeed_tpu.ops.pallas.decode_attention",
    "fused_layer_norm": "deepspeed_tpu.ops.pallas.layer_norm",
    "quantizer": "deepspeed_tpu.ops.quantizer",
    "random_ltd": "deepspeed_tpu.ops.random_ltd",
    "ring_attention": "deepspeed_tpu.ops.ring_attention",
    "optimizers": "deepspeed_tpu.ops.adam",
}


def op_report():
    rows = []
    for name, mod in sorted(OPS.items()):
        try:
            importlib.import_module(mod)
            rows.append((name, True, ""))
        except Exception as e:  # pragma: no cover - env specific
            rows.append((name, False, str(e)[:60]))
    return rows


def versions():
    out = {}
    import deepspeed_tpu
    out["deepspeed_tpu"] = deepspeed_tpu.__version__
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint",
                "numpy"):
        try:
            m = importlib.import_module(mod)
            out[mod] = getattr(m, "__version__", "?")
        except Exception:
            out[mod] = "not installed"
    return out


def device_info():
    try:
        import jax
        devs = jax.devices()
        return {"backend": jax.default_backend(),
                "device_count": len(devs),
                "devices": [str(d) for d in devs[:8]]}
    except Exception as e:  # pragma: no cover
        return {"backend": f"unavailable: {e}", "device_count": 0,
                "devices": []}


def telemetry_info():
    """Telemetry/flight-recorder status: registry + event ring state,
    which config-gated surfaces the defaults arm, and per-device HBM
    totals when the backend reports them (docs/observability.md)."""
    out = {}
    try:
        from deepspeed_tpu.telemetry import (TelemetryConfig,
                                             get_event_ring, get_registry)
        cfg = TelemetryConfig()
        reg = get_registry()
        ring = get_event_ring()
        out["telemetry"] = ("on (registry default; "
                            f"{len(reg.snapshot())} metric families)"
                            if cfg.enabled else "off")
        out["scrape_endpoint"] = (
            f"port {cfg.http_port}" if cfg.http_port is not None
            else "off (set telemetry.http_port)")
        out["event_ring"] = f"{len(ring)}/{ring.capacity} events"
        out["hang_watchdog"] = (
            f"{cfg.watchdog_deadline_s}s deadline"
            if cfg.watchdog_deadline_s is not None
            else "off (set telemetry.watchdog_deadline_s)")
        from deepspeed_tpu.telemetry import numerics_snapshot
        watches = numerics_snapshot()
        # registration is the /debug/numerics reporting hook and happens
        # even with numerics off — report the enabled state separately
        state = ("enabled by default config" if cfg.numerics_enabled
                 else "off (set telemetry.numerics_enabled)")
        if watches:
            state += (f"; {len(watches)} watch(es) registered: "
                      f"{sorted(watches)}")
        out["numerics_watch"] = state
        out["goodput"] = ("on by default config" if cfg.goodput
                          else "off (set telemetry.goodput)")
        out["step_profile"] = (
            "on by default config (serve step phase decomposition + "
            "goodput fraction + dispatch-gap detector; /debug/goodput; "
            "every step's phase spans in the span log)"
            if cfg.step_profile
            else "off (set telemetry.step_profile)")
        out["kv_pool_accounting"] = (
            "on by default config (block lifetime / age-at-eviction "
            "histograms, free-list fragmentation gauge, per-request "
            "peak blocks, famine ring snapshots)"
            if cfg.step_profile
            else "off (rides telemetry.step_profile)")
        out["request_tracing"] = (
            f"sample rate {cfg.trace_sample_rate}, ring "
            f"{cfg.trace_ring_capacity}, slow-keep "
            f"{cfg.trace_slow_threshold_s}s"
            if cfg.trace_sample_rate > 0
            else "off (set telemetry.trace_sample_rate)")
        slo_targets = [k for k in ("ttft_p90_s", "token_p50_s",
                                   "queue_wait_p90_s", "error_rate")
                       if getattr(cfg.slo, k) is not None]
        out["slo_gates"] = (
            f"on ({len(slo_targets)} objective(s): "
            f"{', '.join(slo_targets)}; window {cfg.slo.window_s}s)"
            if cfg.slo.enabled and slo_targets
            else "off (set telemetry.slo.enabled + objectives)")
        # the SLO closed loop (docs/observability.md "SLOs, alerting &
        # incidents"): declared burn-rate rules + canary/incident arm
        # state from the default config, live firing count from the
        # process registry, and the newest bundle path any recorder in
        # this process wrote
        from deepspeed_tpu.telemetry import last_incident_path
        rules = sorted(cfg.slo.objectives)
        firing = 0
        fam = reg.snapshot().get("serve_alert_firing")
        if fam:
            firing = sum(1 for s in fam["series"] if s["value"] >= 1.0)
        parts = [
            (f"{len(rules)} alert rule(s): {', '.join(rules)}"
             if cfg.slo.enabled and rules else
             "no alert rules (set telemetry.slo.enabled + "
             "slo.objectives)"),
            (f"canary every {cfg.canary.interval_s}s"
             if cfg.canary.enabled else
             "canary off (set telemetry.canary.enabled)"),
            (f"incident bundles -> {cfg.incident.dir or 'in-memory'}"
             if cfg.incident.enabled else
             "incident bundles off (set telemetry.incident.enabled)"),
            f"{firing} rule(s) firing now",
        ]
        last = last_incident_path()
        if last:
            parts.append(f"last incident {last}")
        out["serve_slo"] = "; ".join(parts)
        from deepspeed_tpu.inference.config import \
            DeepSpeedInferenceConfig
        icfg = DeepSpeedInferenceConfig()
        k = icfg.speculation_tokens
        out["serve_speculation"] = (
            f"on by default config (speculation_tokens={k}, "
            "prompt-lookup or draft-model proposals "
            "(speculation_draft), batched paged verify)"
            if k else
            "off (set DeepSpeedInferenceConfig.speculation_tokens>=2 — "
            "docs/serving.md 'Per-slot speculative decoding')")
        if icfg.async_loop:
            # configured vs OBSERVED lag: the step profiler's
            # serve_commit_lag_depth histogram records the chain depth
            # at every dispatch in this process — report its deepest
            # bucket beside the config knob when any server has run
            blurb = (f"on by default config (per-step commit lag: 0 "
                     f"when the host has a state change to make, "
                     f"{icfg.max_commit_lag} (max_commit_lag) otherwise, "
                     f"with worker-thread publish — docs/serving.md "
                     f"'Async dispatch loop')")
            fam = reg.snapshot().get("serve_commit_lag_depth")
            if fam:
                # buckets are [upper_bound, count] pairs; the deepest
                # non-empty finite bucket's bound IS the observed depth
                # (integer-valued observations on integer bounds)
                depths = [b for s in fam["series"]
                          for b, n in s.get("buckets", [])
                          if n and b != float("inf")]
                if depths:
                    blurb += (f"; observed chain depth up to "
                              f"{max(depths):g} this process")
            out["serve_async_loop"] = blurb
        else:
            out["serve_async_loop"] = (
                "off: every step commits at lag 0 (set "
                "DeepSpeedInferenceConfig.async_loop=true)")
        out["serve_kv_dtype"] = (
            "int8 by default config (per-block-per-head scales, VMEM "
            "dequant in the paged kernels)"
            if icfg.kv_cache_dtype == "int8" else
            "fp by default config (set kv_cache_dtype='int8' for ~2x "
            "KV capacity — docs/serving.md 'KV quantization & host "
            "tiering')")
        out["serve_kv_host_offload"] = (
            f"on by default config (cold prefix blocks demote to host "
            f"RAM, cap {icfg.kv_host_blocks or 'unbounded'} blocks)"
            if icfg.kv_host_offload else
            "off (set kv_host_offload=true + enable_prefix_caching — "
            "demotion replaces eviction, swap-in restores on prefix "
            "hits)")
        rc = icfg.replication
        out["serve_replication"] = (
            f"{rc.replicas} replicas by default config (health-checked "
            f"routing, failover after {rc.heartbeat_dead_s}s heartbeat "
            f"silence, {rc.max_failovers} retries)"
            if rc.replicas > 1 else
            "single replica (set replication.replicas > 1 for the "
            "supervised pool — health-checked routing, mid-flight "
            "failover, rolling drain; docs/serving.md 'Replicated "
            "serving & failover')")
        out["serve_disaggregation"] = (
            f"role topology {rc.roles} by default config (chain-hash "
            f"KV handoff, telemetry-routed decode admission, handoff "
            f"tier cap {rc.handoff_blocks or 'unbounded'} blocks)"
            if rc.disaggregated else
            "colocated (set replication.roles, e.g. "
            "['prefill','decode'] — prefill replicas chunk-prefill "
            "only and hand KV off by chain hash to telemetry-picked "
            "decode replicas; docs/serving.md 'Disaggregated "
            "prefill/decode')")
        out["serve_fleet_obs"] = (
            f"{rc.replicas} replicas federated into one /metrics "
            f"scrape (replica-labeled merge, staleness-marked "
            f"snapshots), trace stitching "
            f"{'on' if cfg.trace_sample_rate > 0 else 'off'} "
            f"(sample rate {cfg.trace_sample_rate})"
            if rc.replicas > 1 else
            "single replica — fleet plane idle (with "
            "replication.replicas > 1 the frontend merges every "
            "replica's instruments under replica labels, stitches "
            "cross-replica request legs into one trace, and serves "
            "/debug/fleet + a merged timeline; docs/observability.md "
            "'Fleet observability')")
        out["serve_accounting"] = (
            f"on by default config (per-request device-second ledger "
            f"closing against the step profiler, KV block-seconds, "
            f"tenant metering top-{cfg.accounting.max_tenants}, live "
            f"capacity model window {cfg.accounting.window_s}s at "
            f"/debug/capacity)"
            if cfg.accounting.enabled and cfg.step_profile else
            "off (needs telemetry.step_profile + "
            "telemetry.accounting.enabled — docs/observability.md "
            "'Cost accounting & capacity')")
        fic = cfg.fault_injection
        out["fault_injection"] = (
            f"ARMED (seed {fic.seed}; step latency "
            f"{fic.step_latency_s}s, prefill failure rate "
            f"{fic.prefill_failure_rate}, famine {fic.famine_blocks} "
            f"blocks, wedge every {fic.wedge_nth_request})"
            if fic.enabled
            else "off (chaos hooks; telemetry.fault_injection — "
                 "training kinds: step_crash / nan_burst / data_stall / "
                 "preempt_step / ckpt_write_failure / ckpt_corrupt)")
        from deepspeed_tpu.config.config import (CheckpointConfig,
                                                 ResilienceConfig)
        ckpt = CheckpointConfig()
        out["ckpt_integrity"] = (
            f"verified atomic commit by default config (per-file sha256 "
            f"manifest, 'latest' advances post-verify, load fallback "
            f"ladder; retention keep_last="
            f"{ckpt.keep_last or 'unbounded'})"
            if ckpt.verify else
            "off (set checkpoint.verify — docs/training.md "
            "'Fault-tolerant training & verified checkpoints')")
        res = ResilienceConfig()
        from deepspeed_tpu.runtime.resilience import resilience_snapshot
        live = resilience_snapshot()
        state = (
            f"defaults: checkpoint every {res.checkpoint_every} steps, "
            f"{res.max_restarts} restarts, backoff "
            f"{res.backoff_base_s}-{res.backoff_max_s}s "
            "(wrap the loop with runtime/resilience.py "
            "TrainingSupervisor; GET /debug/resilience)")
        if live.get("enabled"):
            sups = live["supervisors"]
            state = (f"{len(sups)} supervisor(s) live: " + "; ".join(
                f"{s.get('status')} step {s.get('step')} "
                f"restarts {s.get('restarts')}" for s in sups))
        out["train_resilience"] = state
    except Exception as e:  # pragma: no cover - env specific
        out["telemetry"] = f"unavailable: {e}"
        return out
    try:
        import jax
        hbm = []
        for d in jax.local_devices():
            stats = dict(d.memory_stats() or {})
            limit = int(stats.get("bytes_limit", 0))
            used = int(stats.get("bytes_in_use", 0))
            if limit:
                hbm.append(f"{d.id}: {used / 2**30:.2f}/"
                           f"{limit / 2**30:.2f} GiB")
        out["device_hbm"] = "; ".join(hbm) if hbm \
            else "no allocator stats (CPU backend?)"
    except Exception:  # pragma: no cover - env specific
        out["device_hbm"] = "unavailable"
    return out


def main(hide_operator_status=False, hide_errors_and_warnings=False):
    print("-" * 64)
    print("DeepSpeed-TPU C++/Pallas op report")
    print("-" * 64)
    if not hide_operator_status:
        print(f"{'op name':<24}{'status':<12}")
        print("-" * 64)
        for name, ok, err in op_report():
            status = GREEN_OK if ok else RED_NO
            line = f"{name:<24}{status:<12}"
            if err and not hide_errors_and_warnings:
                line += f"  {err}"
            print(line)
    print("-" * 64)
    print("DeepSpeed-TPU general environment info:")
    for k, v in versions().items():
        print(f"{k:<24}{v}")
    for k, v in device_info().items():
        print(f"{k:<24}{v}")
    print("-" * 64)
    print("DeepSpeed-TPU telemetry / flight recorder:")
    for k, v in telemetry_info().items():
        print(f"{k:<24}{v}")
    print("-" * 64)
    return 0


def cli_main():  # console entry
    sys.exit(main())


if __name__ == "__main__":
    cli_main()
