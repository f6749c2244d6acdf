"""Generate docs/config.md — the complete JSON config-key reference.

Introspects the pydantic section models in deepspeed_tpu/config/config.py
(plus MeshConfig and the optimizer/scheduler registries) so the doc cannot
drift from the code: tests/test_docs_consistency.py regenerates it and
asserts byte-identity.

Usage: python scripts/gen_config_reference.py [--check]
"""
from __future__ import annotations

import argparse
import dataclasses
import io
import os
import sys
import typing

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "docs", "config.md")

# root keys -> one-line description + where it's consumed. Every member of
# DeepSpeedConfig.KNOWN_KEYS must appear here (asserted at generation).
ROOT_KEYS = {
    "train_batch_size": "global batch = micro x gas x dp (triad resolution: config/config.py resolve_batch_config)",
    "train_micro_batch_size_per_gpu": "per-device micro-batch size",
    "gradient_accumulation_steps": "micro-steps accumulated per optimizer step (fused lax.scan in the engine)",
    "steps_per_print": "engine log cadence",
    "wall_clock_breakdown": "per-phase step timing logs (engine timers)",
    "memory_breakdown": "device-memory logging (runtime/utils.py see_memory_usage)",
    "prescale_gradients": "divide gradients before the DP reduction instead of after",
    "gradient_predivide_factor": "pre-division factor for the DP gradient reduction",
    "gradient_clipping": "global-norm clip applied in the fused step (runtime/utils.py clip_grad_norm_)",
    "dump_state": "print the resolved engine state after init",
    "seed": "base PRNG seed (per-step keys fold in the step counter)",
    "fp16": "section — see below",
    "bf16": "section — see below (alias: bfloat16)",
    "bfloat16": "alias of bf16",
    "zero_optimization": "section — see below",
    "optimizer": "section — see below",
    "scheduler": "section — see below",
    "comms_logger": "section — see below",
    "tensorboard": "section — see below",
    "wandb": "section — see below",
    "csv_monitor": "section — see below",
    "activation_checkpointing": "section — see below",
    "checkpoint": "section — see below",
    "mesh": "section — see below (TPU-specific: parallel axis degrees)",
    "compile_cache_dir": "persistent XLA compile-cache directory (jax_compilation_cache_dir)",
    "flops_profiler": "section — see below",
    "monitor": "accepted for reference parity; the tensorboard/wandb/csv_monitor sections drive MonitorMaster",
    "elasticity": "elastic batch/world-size config (elasticity/elasticity.py compute_elastic_config)",
    "autotuning": "autotuner config (autotuning/autotuner.py; launched via dstpu --autotuning)",
    "compression_training": "compression/QAT/pruning config (compression/compress.py init_compression; MoQ reads quantization.weight_quantization)",
    "data_efficiency": "curriculum + data-sampling + random-ltd config (runtime/data_pipeline/)",
    "curriculum_learning": "legacy top-level curriculum section (reference engine.py:1807)",
    "aio": "async-IO tuning for NVMe swap (ops/aio.py; swap_tensor/)",
    "sparse_attention": "sparse-attention mode+config (ops/sparse_attention/sparsity_config.py family)",
    "zero_allow_untested_optimizer": "allow non-Adam-family optimizers under ZeRO",
    "communication_data_type": "DP gradient-reduction dtype (maps onto the GAS accumulation buffer under GSPMD)",
    "sparse_gradients": "sparse embedding-gradient DP exchange (runtime/sparse_tensor.py)",
    "amp": "section — see below (Apex-AMP compat; maps to native bf16 mixed precision)",
    "pipeline": "pipeline-engine knobs (parallel/pipe/executor.py train_batch facade)",
    "inference": "accepted for reference parity; inference uses DeepSpeedInferenceConfig (inference/config.py)",
    "data_types": "section — see below",
    "eigenvalue": "section — see below",
    "progressive_layer_drop": "PLD schedule (runtime/progressive_layer_drop.py)",
    "nebula": "async checkpoint-engine alias (checkpoint.engine='async')",
    "telemetry": "section — see below (metrics registry + scrape endpoint, docs/observability.md)",
    "resilience": "section — see below (fault-tolerant training supervisor, docs/training.md)",
}


def _type_name(ann) -> str:
    origin = typing.get_origin(ann)
    if origin is typing.Union:
        args = [a for a in typing.get_args(ann) if a is not type(None)]
        inner = ", ".join(_type_name(a) for a in args)
        return f"Optional[{inner}]" if len(typing.get_args(ann)) > len(args) \
            else f"Union[{inner}]"
    if origin is typing.Literal:
        return " \\| ".join(repr(a) for a in typing.get_args(ann))
    if origin is not None:
        name = getattr(origin, "__name__", str(origin))
        args = ", ".join(_type_name(a) for a in typing.get_args(ann))
        return f"{name}[{args}]"
    return getattr(ann, "__name__", str(ann))


def _default_repr(f) -> str:
    try:
        from pydantic_core import PydanticUndefined
        if f.default is PydanticUndefined:
            if f.default_factory is not None:
                return repr(f.default_factory())
            return "required"
    except ImportError:
        pass
    return repr(f.default)


def _doc_to_md(doc: str) -> str:
    """Docstring → markdown: keep paragraph/line structure, turn RST-style
    ``x`` literals into `x` code spans."""
    import re
    import textwrap
    lines = doc.strip().splitlines()
    if len(lines) > 1:
        body = textwrap.dedent("\n".join(lines[1:]))
        doc = lines[0] + "\n" + body
    return re.sub(r"``([^`]+)``", r"`\1`", doc)


def emit_model(buf, title: str, model, note: str = "") -> None:
    buf.write(f"### `{title}`\n\n")
    doc = (model.__doc__ or "").strip()
    if doc:
        buf.write(_doc_to_md(doc))
        buf.write("\n\n")
    if note:
        buf.write(note + "\n\n")
    buf.write("| key | type | default |\n|---|---|---|\n")
    for name, f in model.model_fields.items():
        buf.write(f"| `{name}` | {_type_name(f.annotation)} "
                  f"| `{_default_repr(f)}` |\n")
    buf.write("\n")


def emit_dataclass(buf, title: str, dc, note: str = "") -> None:
    buf.write(f"### `{title}`\n\n")
    doc = (dc.__doc__ or "").strip()
    if doc:
        buf.write(_doc_to_md(doc))
        buf.write("\n\n")
    if note:
        buf.write(note + "\n\n")
    buf.write("| key | type | default |\n|---|---|---|\n")
    for f in dataclasses.fields(dc):
        buf.write(f"| `{f.name}` | {_type_name(f.type)} "
                  f"| `{f.default!r}` |\n")
    buf.write("\n")


def generate() -> str:
    from deepspeed_tpu.comm.mesh import MeshConfig
    from deepspeed_tpu.config import config as C
    from deepspeed_tpu.ops.adam import OPTIMIZER_REGISTRY
    from deepspeed_tpu.runtime.lr_schedules import SCHEDULE_REGISTRY

    missing = set(C.DeepSpeedConfig.KNOWN_KEYS) - set(ROOT_KEYS)
    extra = set(ROOT_KEYS) - set(C.DeepSpeedConfig.KNOWN_KEYS)
    if missing or extra:
        raise SystemExit(
            f"gen_config_reference.py ROOT_KEYS out of date: "
            f"missing={sorted(missing)} extra={sorted(extra)}")

    buf = io.StringIO()
    buf.write(
        "# Config JSON reference\n\n"
        "<!-- GENERATED by scripts/gen_config_reference.py — edit that "
        "script, not this file. tests/test_docs_consistency.py enforces "
        "byte-identity. -->\n\n"
        "Every key accepted by `deepspeed_tpu.initialize(config=...)`. "
        "The schema mirrors the reference's `DeepSpeedConfig` "
        "(runtime/config.py:702) plus the TPU-specific `mesh` section; "
        "unknown top-level keys are rejected with a did-you-mean error "
        "(config/config.py `_validate_keys`).\n\n"
        "## Top-level keys\n\n| key | meaning |\n|---|---|\n")
    for key in sorted(ROOT_KEYS):
        buf.write(f"| `{key}` | {ROOT_KEYS[key]} |\n")
    buf.write("\n## Sections\n\n")

    emit_model(buf, "fp16", C.FP16Config)
    emit_model(buf, "bf16", C.BF16Config)
    emit_model(buf, "zero_optimization", C.ZeroConfig)
    emit_model(buf, "zero_optimization.offload_optimizer",
               C.OffloadOptimizerConfig)
    emit_model(buf, "zero_optimization.offload_param", C.OffloadParamConfig)
    emit_model(
        buf, "optimizer", C.OptimizerConfig,
        note=("Supported `type` values (ops/adam.py OPTIMIZER_REGISTRY): "
              + ", ".join(f"`{k}`" for k in sorted(OPTIMIZER_REGISTRY))
              + ". `params` passes lr/betas/eps/weight_decay through."))
    emit_model(
        buf, "scheduler", C.SchedulerConfig,
        note=("Supported `type` values (runtime/lr_schedules.py "
              "SCHEDULE_REGISTRY): "
              + ", ".join(f"`{k}`" for k in sorted(SCHEDULE_REGISTRY))
              + "."))
    emit_model(buf, "activation_checkpointing",
               C.ActivationCheckpointingConfig)
    emit_model(
        buf, "checkpoint", C.CheckpointConfig,
        note=("`verify`/`keep_last` drive the verified atomic-commit "
              "protocol and bounded retention (runtime/checkpointing.py, "
              "checkpoint/integrity.py) — see docs/training.md "
              "\"Fault-tolerant training & verified checkpoints\"."))
    emit_model(
        buf, "resilience", C.ResilienceConfig,
        note=("Consumed by `runtime/resilience.py` `TrainingSupervisor` "
              "— see docs/training.md \"Fault-tolerant training & "
              "verified checkpoints\" for the recovery semantics, fault "
              "kinds, and the bit-identical resume oracle these knobs "
              "drive."))
    emit_dataclass(
        buf, "mesh", MeshConfig,
        note=("TPU-specific: explicit parallel-axis degrees replace the "
              "reference's implicit world-size/process-group wiring. "
              "`data=-1` absorbs all remaining devices."))
    emit_model(buf, "amp", C.AMPConfig)
    emit_model(buf, "data_types", C.DataTypesConfig)
    emit_model(buf, "eigenvalue", C.EigenvalueConfig)
    emit_model(
        buf, "flops_profiler", C.FlopsProfilerConfig,
        note=("With `detailed: true` (the default) the profile step also "
              "prints the reference-style **per-module table** (forward "
              "FLOPs, share of total, params per module). The TPU-native "
              "module boundary is the flax `named_scope` path in the "
              "jaxpr — `module_flops_breakdown()` walks the jaxpr "
              "(recursing through `pjit`/`remat`/`scan`, scaling scan "
              "bodies by trip count) and groups analytic per-equation "
              "FLOPs by module path; rows sum exactly to the printed "
              "TOTAL. The same breakdown is available standalone via "
              "`get_model_profile(..., per_module_depth=N)` → "
              "`prof[\"module_breakdown\"]` / `prof[\"module_table\"]` "
              "(`profiling/flops_profiler.py`; reference "
              "`flops_profiler/profiler.py`'s torch-hook module tree)."))
    emit_model(buf, "comms_logger", C.CommsLoggerConfig,
               note=("`enabled`: after the first step the engine logs what "
                     "each compiled program moves a step, by kind of "
                     "transfer (host-link copies by direction, collectives "
                     "by opcode; `verbose`: also by pass and scope), read "
                     "off the compiled program, so partitioner-inserted "
                     "collectives and the offload stream are in it "
                     "(docs/observability.md \"Training step: what "
                     "moves\"). `prof_all` / `debug` are accepted for "
                     "reference parity."))
    emit_model(buf, "tensorboard", C.TensorBoardConfig)
    emit_model(buf, "wandb", C.WandbConfig)
    emit_model(buf, "csv_monitor", C.CSVConfig)
    emit_model(buf, "telemetry", C.TelemetryConfig,
               note=("Shared with `DeepSpeedInferenceConfig.telemetry` "
                     "(telemetry/config.py). The registry records "
                     "regardless of any monitor backend; the scrape "
                     "endpoint opens only when `http_port` is set. Full "
                     "metric catalog: docs/observability.md."))
    from deepspeed_tpu.telemetry.config import SLOConfig
    emit_model(buf, "telemetry.slo", SLOConfig,
               note=("See docs/observability.md \"Request tracing & "
                     "SLOs\" for the evaluation semantics and metric "
                     "names."))
    from deepspeed_tpu.telemetry.config import SLOObjectiveConfig
    emit_model(buf, "telemetry.slo.objectives.<rule>", SLOObjectiveConfig,
               note=("One named burn-rate alert rule "
                     "(telemetry/alerts.py) — see docs/observability.md "
                     "\"SLOs, alerting & incidents\". Rules ride under "
                     "the `slo.enabled` master switch; an empty "
                     "`objectives` dict (the default) arms no alert "
                     "engine and registers no `serve_alert*` "
                     "instruments."))
    from deepspeed_tpu.telemetry.config import CanaryConfig
    emit_model(buf, "telemetry.canary", CanaryConfig,
               note=("Synthetic end-to-end probe through the real "
                     "submit/step/result path, `tenant=\"__canary\"`, "
                     "excluded byte-identically from bills, tenant "
                     "metering, and capacity rates — see "
                     "docs/observability.md \"SLOs, alerting & "
                     "incidents\"."))
    from deepspeed_tpu.telemetry.config import IncidentConfig
    emit_model(buf, "telemetry.incident", IncidentConfig,
               note=("One-shot incident bundles captured when an alert "
                     "fires or the hang watchdog dumps, rate-limited "
                     "per episode and re-armed on resolve; listed at "
                     "`GET /debug/incidents` — see docs/observability.md "
                     "\"SLOs, alerting & incidents\"."))
    from deepspeed_tpu.telemetry.config import AccountingConfig
    emit_model(buf, "telemetry.accounting", AccountingConfig,
               note=("Request-level cost accounting, tenant metering, "
                     "and the live capacity model — see "
                     "docs/observability.md \"Cost accounting & "
                     "capacity\". The ledger arms only when the step "
                     "profiler is on (`telemetry.step_profile`); "
                     "disabled accounting is byte-identical and "
                     "registers no `serve_request_*`/`serve_tenant_*` "
                     "families."))

    from deepspeed_tpu.inference.config import (DeepSpeedInferenceConfig,
                                                ReplicationConfig)
    buf.write("## Inference config (`init_inference`)\n\n")
    emit_model(
        buf, "DeepSpeedInferenceConfig", DeepSpeedInferenceConfig,
        note=("Top-level keys accepted by `deepspeed_tpu.init_inference"
              "(...)` / `config=` (inference/config.py). The `tp`/`moe`/"
              "`quant` sections and the serving knobs (`block_size`, "
              "`num_slots`, `enable_prefix_caching`, "
              "`prefill_chunk_tokens`, ...) are documented in "
              "docs/serving.md; `telemetry` shares the schema above."))
    emit_model(
        buf, "replication", ReplicationConfig,
        note=("Consumed by `inference/frontend.py` `ServingFrontend` — "
              "see docs/serving.md \"Replicated serving & failover\" "
              "for the health state machine, failover semantics, and "
              "drain protocol these knobs drive."))

    buf.write(
        "## Subsystem configs documented elsewhere\n\n"
        "- `autotuning` — autotuning/autotuner.py (`dstpu --autotuning "
        "run`, `bin/dstpu_autotune`)\n"
        "- `elasticity` — elasticity/config.py (v0.1/v0.2 semantics, "
        "`bin/dstpu_elastic`)\n"
        "- `compression_training` — compression/compress.py (QAT, pruning, "
        "SLR, KD; MoQ via quantization.weight_quantization)\n"
        "- `data_efficiency` — runtime/data_pipeline/ (curriculum, data "
        "sampling, random-ltd)\n"
        "- `sparse_attention` — ops/sparse_attention/sparsity_config.py "
        "(dense/fixed/variable/bigbird/bslongformer)\n")
    return buf.getvalue()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs/config.md is out of date")
    args = ap.parse_args()
    text = generate()
    if args.check:
        on_disk = open(OUT_PATH).read() if os.path.exists(OUT_PATH) else ""
        if on_disk != text:
            raise SystemExit("docs/config.md is stale — run "
                             "scripts/gen_config_reference.py")
        print("docs/config.md up to date")
        return
    with open(OUT_PATH, "w") as fh:
        fh.write(text)
    print(f"wrote {OUT_PATH} ({len(text)} bytes)")


if __name__ == "__main__":
    main()
