"""The training engine.

TPU-native analog of ``DeepSpeedEngine`` (``deepspeed/runtime/engine.py:193``).
The reference wraps a torch module and orchestrates eager
forward/backward/step with hook-driven ZeRO machinery; here the entire
micro-step — gradient accumulation loop, ZeRO reduce-scatter, precision
casts, loss-scale bookkeeping, optimizer update, weight re-gather — is ONE
jitted SPMD program over the device mesh, and the "engine" is the host-side
object that owns the compiled step, the sharded state, and the DS-style API:

* ``train_batch(batch)``           — fused step (forward+backward+step),
  the analog of the engine.forward/backward/step sequence in §3.2 of SURVEY.
* ``forward`` / ``backward`` / ``step`` — DS-shaped micro-batch API for
  users porting loops 1:1 (backward takes the micro-batch, not a loss
  tensor: autodiff needs the function, not the value).
* ``save_checkpoint`` / ``load_checkpoint`` — runtime/checkpoint parity.

ZeRO stages are sharding policies (runtime/zero/partition.py); XLA emits and
overlaps the collectives the reference hand-schedules (stage_1_and_2.py:937,
:1743; stage3.py:1146).
"""
from __future__ import annotations

import time
import weakref
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu import comm
from deepspeed_tpu.comm.mesh import (build_mesh, get_data_parallel_world_size,
                                     set_global_mesh)
from deepspeed_tpu.config.config import DeepSpeedConfig
from deepspeed_tpu.ops.adam import Optimizer, build_optimizer
from deepspeed_tpu.runtime.activation_checkpointing import (
    REMAT_LIMIT_OPTION, remat_limit_percent)
from deepspeed_tpu.runtime.lr_schedules import Schedule, build_schedule
from deepspeed_tpu.runtime.precision import (PRECISION_DTYPES, LossScaleState,
                                             cast_tree, grads_finite,
                                             make_loss_scale,
                                             update_loss_scale)
from deepspeed_tpu.runtime.utils import clip_coef
from deepspeed_tpu.runtime.zero.offload_stream import (COPY_BUDGET_OPTION,
                                                       copy_budget,
                                                       moment_fields,
                                                       streamed_update)
from deepspeed_tpu.runtime.zero.partition import ZeroShardingPolicy
from deepspeed_tpu.telemetry.spans import annotation as span_annotation
from deepspeed_tpu.telemetry.spans import get_span_log
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import ThroughputTimer

from deepspeed_tpu.comm.mesh import DATA_AXES  # noqa: F401


def _close(ann) -> None:
    """Close a profiler range ``span_annotation`` may have opened."""
    if ann is not None:
        ann.__exit__(None, None, None)


@struct.dataclass
class TrainState:
    """Everything a training step consumes and produces.

    ``master`` holds fp32 master weights when training in bf16/fp16
    (BF16_Optimizer / FP16_Optimizer semantics); ``None`` in pure-fp32 mode,
    in which case ``params`` is the master copy.
    """
    step: jnp.ndarray
    params: Any
    master: Any
    opt_state: Any
    loss_scale: LossScaleState


def _split_loss_out(out):
    """loss_fn may return a bare scalar or ``(loss, aux_dict)`` (the
    reference's multi-output models: extra per-step scalars ride into the
    step metrics). Reserved metric names stay the engine's."""
    if not isinstance(out, tuple):
        return out, {}
    loss, aux = out
    if not isinstance(aux, dict):
        raise TypeError(
            "loss_fn returning a tuple must be (loss, aux_dict); "
            f"got aux of type {type(aux).__name__}")
    reserved = {"loss", "grad_norm", "lr", "loss_scale", "skipped",
                "finite", "_numerics"}
    bad = reserved & set(aux)
    if bad:
        raise ValueError(
            f"aux metric names {sorted(bad)} collide with engine "
            "metrics — rename them")
    aux = {k: jnp.asarray(v, jnp.float32) for k, v in aux.items()}
    nonscalar = [k for k, v in aux.items() if v.shape != ()]
    if nonscalar:
        raise ValueError(
            f"aux metrics must be scalars, got non-scalar "
            f"{sorted(nonscalar)} (reduce them in loss_fn)")
    return loss, aux


class DeepSpeedEngine:
    def __init__(self,
                 loss_fn: Callable,
                 params: Any,
                 config: DeepSpeedConfig,
                 mesh=None,
                 optimizer: Optional[Optimizer] = None,
                 lr_scheduler: Optional[Schedule] = None,
                 tp_specs=None,
                 training_data=None,
                 collate_fn=None,
                 rng: Optional[jax.Array] = None,
                 model_handles_param_offload: bool = False,
                 sparse_grad_paths: Optional[Any] = None):
        if config.compile_cache_dir:
            # persistent XLA executable cache (the TORCH_EXTENSIONS_DIR
            # JIT-cache analog, SURVEY §5.6): step recompiles across
            # process restarts become disk hits. The JSON key never
            # overrides JAX_COMPILATION_CACHE_DIR or a cache already in
            # force (utils/compile_cache.py).
            from deepspeed_tpu.utils.compile_cache import (
                enable_compile_cache)
            enable_compile_cache(config.compile_cache_dir)
        self.mesh = mesh if mesh is not None else build_mesh(config.mesh)
        set_global_mesh(self.mesh)
        self.config = config
        config.resolve_batch_config(get_data_parallel_world_size(self.mesh))
        comm.configure(deepspeed_config=config)

        self.loss_fn = loss_fn
        self.compute_dtype = PRECISION_DTYPES[config.precision_dtype]
        self.mixed_precision = config.precision_dtype != "float32"
        self.gas = config.gradient_accumulation_steps
        self.micro_batch_size = config.train_micro_batch_size_per_gpu
        self.train_batch_size = config.train_batch_size

        opt_cfg = config.optimizer
        # 1-bit optimizer family: when the mesh has a real data-parallel
        # extent, the whole train step drops into shard_map over the DP
        # axes so the optimizer's error-feedback sign compression runs on
        # the actual gradient exchange (reference runtime/comm/nccl.py:51
        # over DCN) — not just in unit tests. GSPMD would otherwise insert
        # an exact allreduce before the optimizer ever saw the grads.
        self._onebit_axes: tuple = ()
        if optimizer is None:
            from deepspeed_tpu.ops.adam import (ONEBIT_OPTIMIZER_KEYS,
                                                normalize_optimizer_key)
            opt_type = (opt_cfg.type if opt_cfg else "AdamW")
            opt_params = dict(opt_cfg.params) if opt_cfg else {}
            if normalize_optimizer_key(opt_type) in ONEBIT_OPTIMIZER_KEYS:
                axes = tuple(a for a in ("data", "fsdp")
                             if self.mesh.shape[a] > 1)
                if axes:
                    if config.zero_config.stage != 0:
                        raise ValueError(
                            "1-bit optimizers need replicated parameters "
                            "(zero_optimization.stage=0) for the "
                            "compressed DP exchange — the reference has "
                            "the same restriction")
                    if config.fp16.enabled:
                        raise NotImplementedError(
                            "fp16 dynamic loss scaling is not wired into "
                            "the compressed-DP step; use bf16")
                    for ax in ("tensor", "seq", "pipe"):
                        if self.mesh.shape[ax] > 1:
                            raise NotImplementedError(
                                f"compressed-DP step composes only with "
                                f"pure data parallelism (mesh {ax}="
                                f"{self.mesh.shape[ax]})")
                    opt_params["axis_name"] = axes
                    self._onebit_axes = axes
            optimizer = build_optimizer(opt_type, opt_params)
        self.optimizer = optimizer
        # sparse_gradients (reference constants.py:107, engine sparse
        # allreduce :2459-2541): embedding-shaped leaves exchange (ids,
        # rows) instead of the dense [vocab, dim] gradient. Engages in the
        # explicit shard_map DP step; needs replicated params like the
        # reference (ZeRO rejects sparse grads, stage_1_and_2 asserts).
        self._sparse_grad_axes: tuple = ()
        if config.sparse_gradients:
            if self._onebit_axes:
                raise NotImplementedError(
                    "sparse_gradients cannot combine with the 1-bit "
                    "optimizer family (its error-feedback compression "
                    "assumes dense tensors — same as the reference)")
            if config.fp16.enabled:
                raise NotImplementedError(
                    "sparse_gradients + fp16 loss scaling is not wired "
                    "into the explicit-exchange step; use bf16")
            axes = tuple(a for a in ("data", "fsdp")
                         if self.mesh.shape[a] > 1)
            if not sparse_grad_paths:
                # like the reference, only *declared* sparse embeddings
                # ride the sparse exchange (torch needs Embedding(
                # sparse=True); name-guessing would silently corrupt
                # tied embeddings, whose grads are dense through the
                # softmax). No declaration → nothing to do.
                logger.warning(
                    "sparse_gradients enabled but no sparse_grad_paths "
                    "declared (model attribute or initialize kwarg) — "
                    "falling back to the dense exchange. NOTE: tied "
                    "input/output embeddings must NOT be declared (their "
                    "gradient is dense through the logits)")
            elif axes:
                if config.zero_config.stage != 0:
                    raise ValueError(
                        "sparse_gradients requires replicated parameters "
                        "(zero_optimization.stage=0); the reference ZeRO "
                        "optimizer rejects sparse gradients too")
                for ax in ("tensor", "seq", "pipe"):
                    if self.mesh.shape[ax] > 1:
                        raise NotImplementedError(
                            "sparse_gradients composes only with pure "
                            f"data parallelism (mesh {ax}="
                            f"{self.mesh.shape[ax]})")
                self._sparse_grad_axes = axes
            else:
                log_dist("sparse_gradients: no data-parallel extent, "
                         "nothing to exchange — using the fused step",
                         ranks=[0])
        self._sparse_grad_patterns = tuple(sparse_grad_paths or ())
        self.lr_scheduler = lr_scheduler or build_schedule(
            config.scheduler, opt_cfg.params if opt_cfg else None)

        # Activation checkpointing (reference engine _configure_checkpointing
        # → deepspeed.checkpointing.configure): install the JSON section so
        # model code using deepspeed_tpu.checkpointing.checkpoint() sees it;
        # configure() itself rejects the fields XLA cannot honor.
        ac = config.activation_checkpointing
        from deepspeed_tpu.runtime import activation_checkpointing
        if ac != type(ac)():
            activation_checkpointing.configure(ac, _by_engine=True)
        else:
            # a previous ENGINE's config must not leak into this engine's
            # models; a user's direct configure() call is preserved
            activation_checkpointing.reset(only_engine_installed=True)

        # ---- sharding policy & state materialization ----
        self.zero_stage = config.zero_config.stage
        self.policy = ZeroShardingPolicy(
            self.zero_stage, self.mesh, tp_specs=tp_specs,
            param_persistence_threshold=(
                config.zero_config.stage3_param_persistence_threshold
                if self.zero_stage >= 3 else 0))
        oc = config.zero_config.offload_optimizer
        self._offload_cfg = oc if (oc is not None and
                                   oc.device != "none") else None
        # Streamed offload (config.py OffloadOptimizerConfig.implementation):
        # fp32 master+moments live in TPU-host pinned memory and the update
        # runs on device inside the fused step — the role cpu_adam + PCIe
        # copy streams play in the reference, kept inside one XLA program.
        # The per-leaf host<->HBM DMAs are NOT left to XLA's scheduler: on
        # the chip it ran the two directions in turn (both in flight 7 % of
        # the time); runtime/zero/offload_stream.py writes them as a
        # pipeline over the leaves, a store leaving while the next fetches
        # arrive. The NVMe tier and non-TPU backends (XLA:CPU has no
        # memory-space shardings) use the C++ host path.
        self._offload_stream = False
        if self._offload_cfg is not None:
            impl = self._offload_cfg.implementation
            if impl == "auto":
                # fp16 stays on the host path (its loss-scale skip cond
                # cannot wrap memory-space transfers); explicit 'stream'
                # + fp16 is refused below
                impl = ("stream" if (jax.default_backend() == "tpu" and
                                     self._offload_cfg.device == "cpu" and
                                     not config.fp16.enabled)
                        else "host")
            if impl == "stream":
                # backend-independent refusals first (testable everywhere)
                if self._offload_cfg.device == "nvme":
                    raise ValueError(
                        "offload_optimizer.implementation='stream' holds "
                        "state in TPU-host pinned memory; the nvme tier "
                        "needs implementation='host' (aio swap files)")
                if config.fp16.enabled:
                    raise ValueError(
                        "streamed offload supports bf16/fp32 training; "
                        "fp16's overflow-skip cond cannot wrap "
                        "memory-space transfers — use "
                        "implementation='host' for fp16")
                if jax.default_backend() != "tpu":
                    raise ValueError(
                        "offload_optimizer.implementation='stream' needs "
                        "a TPU backend (XLA:CPU lacks memory-space "
                        "shardings); use 'host' or 'auto'")
            self._offload_stream = impl == "stream"
        # ZeRO-3 parameter offload (stage3.py:448; partitioned_param_swapper)
        pc = config.zero_config.offload_param
        self._param_offload_cfg = pc if (pc is not None and
                                         pc.device != "none") else None
        if self._param_offload_cfg is not None and self.zero_stage < 3:
            raise ValueError(
                "offload_param requires ZeRO stage 3 (reference "
                "stage3.py:448 — parameter offload is a stage-3 feature)")
        self._model_fetches_params = bool(model_handles_param_offload)
        # In-jit host→HBM streaming (per-layer fetch inside the step) needs
        # SPMD support for memory-space annotations — present on TPU, absent
        # in XLA:CPU. Non-TPU backends stage the whole tree eagerly around
        # the step instead (eviction between steps is identical).
        self._param_offload_in_jit = (
            self._param_offload_cfg is not None and
            jax.default_backend() == "tpu")
        self._param_swapper = None
        if self._param_offload_cfg is not None and \
                self._param_offload_cfg.device == "nvme":
            if not self._param_offload_cfg.nvme_path:
                raise ValueError("offload_param.device=nvme requires "
                                 "nvme_path")
            from deepspeed_tpu.runtime.zero.param_offload import ParamSwapper
            self._param_swapper = ParamSwapper(
                self._param_offload_cfg.nvme_path)
        self.state = self._init_state(params)
        self.host_opt = None
        if self._offload_cfg is not None and not self._offload_stream:
            opt_type = (opt_cfg.type if opt_cfg else "AdamW").lower()
            if opt_type not in ("adam", "adamw", "fusedadam", "cpuadam"):
                raise ValueError(
                    f"offload_optimizer supports Adam-family only, got "
                    f"{opt_type} (reference pairs cpu_offload with "
                    "DeepSpeedCPUAdam, engine.py:1314)")
            from deepspeed_tpu.runtime.zero.offload import (
                HostOffloadOptimizer)
            self.host_opt = HostOffloadOptimizer(
                params, opt_cfg.params if opt_cfg else {},
                device=self._offload_cfg.device,
                nvme_path=self._offload_cfg.nvme_path)
            self._host_loss_scale = make_loss_scale(
                config.fp16 if config.fp16.enabled else None)
            self._offload_grad_fn = None
        self.training_dataloader = self._build_dataloader(training_data,
                                                          collate_fn)

        self._step_fn = None  # compiled lazily (first train_batch)
        self._step_spans: list = []   # this step's timed intervals
        self._grad_fn = None
        self._pending_grads = None
        self._pending_losses = []
        self._pending_aux = []
        self._last_micro_batch = None
        self._micro_steps = 0
        self.global_steps = 0
        self.skipped_steps = 0
        self._train_mode = True
        self._last_skipped = None
        self._warned_aux_dropped = False
        self._rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
        # telemetry registry (docs/observability.md): process-global, or
        # — with telemetry.enabled=false — a private one, so recording
        # cost stays identical while nothing reaches the scrape surface
        from deepspeed_tpu.telemetry import MetricRegistry, get_registry
        tcfg = getattr(config, "telemetry", None)
        telemetry_on = tcfg is None or tcfg.enabled
        self.telemetry = get_registry() if telemetry_on \
            else MetricRegistry()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size,
            steps_per_output=config.steps_per_print,
            registry=self.telemetry)
        # what a step moves (train_moved_bytes_* / train_movement_calls_*):
        # derived from the compiled step's text when the registry is READ,
        # never here or in a step (the parse costs tenths of a second)
        publish = weakref.WeakMethod(self._publish_movement)
        self._movement_collector = lambda: (publish() or (lambda: None))()
        self.telemetry.add_collector(self._movement_collector)
        self._comms_logged = False
        self.monitor = self._build_monitor()
        # step metrics route through the telemetry registry FIRST —
        # MonitorMaster (tb/wandb/csv) is one sink of several, and the
        # registry one is backend-free
        from deepspeed_tpu.monitor.monitor import RegistryMonitor
        self._registry_sink = RegistryMonitor(self.telemetry)
        # request-scoped tracing (telemetry/tracing.py): every sampled
        # train step becomes a root span with data-wait/device/host
        # children synthesized from the goodput splits — the same
        # timeline surface the serving loop exports
        self.tracer = None
        if telemetry_on and tcfg is not None and \
                tcfg.trace_sample_rate > 0:
            from deepspeed_tpu.telemetry import Tracer
            self.tracer = Tracer(
                sample_rate=tcfg.trace_sample_rate,
                ring_capacity=tcfg.trace_ring_capacity,
                seed=tcfg.trace_seed,
                slow_threshold_s=tcfg.trace_slow_threshold_s,
                registry=self.telemetry)
        self._telemetry_http = None
        if telemetry_on and tcfg is not None and \
                tcfg.http_port is not None:
            from deepspeed_tpu.telemetry import start_http_server
            try:
                self._telemetry_http = start_http_server(
                    tcfg.http_port, host=tcfg.http_host,
                    registry=self.telemetry, tracer=self.tracer)
            except OSError as e:   # port taken must not kill training
                logger.warning(f"telemetry endpoint unavailable: {e}")
        self._init_flight_recorder(tcfg)   # helper honors tcfg.enabled
        # ---- numerics observatory + goodput accounting ----
        # (telemetry/numerics.py, telemetry/goodput.py — the divergence
        # and wall-time-split layer, docs/observability.md "Training
        # numerics & goodput"). The block spec is built ONCE from the
        # materialized param tree; the in-graph statistics ride the
        # jitted step behind a static flag, so toggling at runtime
        # (set_numerics_enabled) costs exactly one attributed retrace.
        from deepspeed_tpu.telemetry.goodput import GoodputMeter
        from deepspeed_tpu.telemetry.numerics import (
            NumericsWatch, block_spec, register_numerics_watch)
        self._telemetry_on = telemetry_on
        self._numerics_spec = block_spec(
            self.state.params,
            depth=(tcfg.numerics_block_depth if tcfg is not None else 1))
        self._numerics_on = bool(telemetry_on and tcfg is not None and
                                 tcfg.numerics_enabled)
        self.numerics = NumericsWatch(
            self._numerics_spec.names, registry=self.telemetry,
            window=(tcfg.numerics_spike_window if tcfg is not None
                    else 64),
            threshold=(tcfg.numerics_spike_threshold if tcfg is not None
                       else 6.0),
            source="train",
            dump_path=(tcfg.events_dump_path if tcfg is not None
                       else None))
        if telemetry_on:
            register_numerics_watch("train", self.numerics)
        self.goodput = GoodputMeter(
            registry=self.telemetry,
            enabled=bool(telemetry_on and tcfg is not None and
                         tcfg.goodput),
            source="train")
        if self._numerics_on and (self._onebit_axes or
                                  self._sparse_grad_axes):
            logger.warning(
                "telemetry.numerics_enabled is not supported on the "
                "explicit-DP (1-bit/sparse) shard_map steps — numerics "
                "disabled for this engine")
            self._numerics_on = False
        self._last_grad_norm = None
        self.curriculum_scheduler = None
        if config.curriculum_learning.get("enabled", False):
            from deepspeed_tpu.runtime.data_pipeline import (
                CurriculumScheduler)
            self.curriculum_scheduler = CurriculumScheduler(
                config.curriculum_learning)
        self.flops_profiler = None
        if config.flops_profiler.enabled:
            from deepspeed_tpu.profiling import FlopsProfiler
            self.flops_profiler = FlopsProfiler(
                self, profile_step=config.flops_profiler.profile_step,
                detailed=config.flops_profiler.detailed,
                output_file=config.flops_profiler.output_file)
        # MoQ: quantize-in-step (reference engine.py:1400 _configure_
        # quantization + :2078 quantizer.quantize in _take_model_step)
        self.quantizer = None
        self.eigenvalue = None
        from deepspeed_tpu.runtime.quantize import MoQConfig, MoQuantizer
        moq_cfg = MoQConfig.from_compression_config(config.compression_config)
        if moq_cfg.enabled:
            if not self.mixed_precision:
                raise ValueError(
                    "MoQ (quantize in optimizer step) requires fp16 or "
                    "bf16 master-weight training — the quantized compute "
                    "params are re-derived from the unquantized fp32 "
                    "master each step (reference engine.py:1412 asserts "
                    "fp16)")
            if self.host_opt is not None or self._offload_stream:
                raise NotImplementedError(
                    "MoQ is not wired into the ZeRO-Offload host step; "
                    "disable offload_optimizer or in-forward quantize "
                    "via compression instead")
            self.quantizer = MoQuantizer(moq_cfg, self.state.params,
                                         self.compute_dtype)
        if config.eigenvalue.enabled:
            from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
            ev = config.eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=ev.verbose, max_iter=ev.max_iter, tol=ev.tol,
                stability=ev.stability)
        self._gas_boundary_ctr = 0
        self.block_eigenvalue: Optional[Dict[str, float]] = None
        if config.prescale_gradients or \
                config.gradient_predivide_factor != 1.0:
            # no-op BY DESIGN, not silently: the reference pre-divides
            # fp16 grads to dodge overflow in large-DP ring reductions
            # (engine.py:2339); here grads accumulate/reduce in fp32 (or
            # the configured dtype) inside XLA, so the range concern the
            # knob exists for does not arise and the final grads are
            # identical either way.
            if comm.get_rank() == 0:
                logger.warning(
                    "prescale_gradients/gradient_predivide_factor have "
                    "no effect: gradient reduction runs at the "
                    "accumulation dtype inside XLA (fp32 by default) — "
                    "the fp16-range motivation does not apply")
        if config.dump_state:
            # reference dump_state: print the full engine configuration
            # (rank-0 only — N hosts must not dump N copies)
            if comm.get_rank() == 0:
                config.print_config()
            n_params = sum(int(np.prod(p.shape))
                           for p in jax.tree.leaves(self.state.params))
            log_dist(
                f"engine state: {n_params / 1e6:.1f}M params, "
                f"zero_stage={self.zero_stage} "
                f"mixed_precision={self.mixed_precision} "
                f"offload_optimizer={self._offload_cfg is not None} "
                f"offload_param={self._param_offload_cfg is not None}",
                ranks=[0])
        log_dist(
            f"engine ready: zero_stage={self.zero_stage} "
            f"dtype={config.precision_dtype} mesh="
            f"{dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
            f"micro={self.micro_batch_size} gas={self.gas} "
            f"global_batch={self.train_batch_size}", ranks=[0])

    # ------------------------------------------------------------------
    # state init
    # ------------------------------------------------------------------
    def _init_state(self, params) -> TrainState:
        """Materialize params/master/opt-state directly with their target
        shardings — the analog of ``zero.Init`` constructing parameters
        already partitioned (partition_parameters.py:537), minus the
        __init__ hijack: jit's out_shardings places each leaf where it
        lives, so no full replica ever exists on any chip."""
        param_sh = self.policy.param_sharding(params)
        master_sh = self.policy.master_sharding(params)
        compute_dtype = self.compute_dtype
        mixed = self.mixed_precision
        opt_init = self.optimizer.init
        # host offload (C++ path): fp32 master + moments live in process
        # RAM/NVMe (runtime/zero/offload.py) — nothing optimizer-shaped on
        # device. Streamed offload instead keeps them as jax arrays in
        # pinned_host memory, handled below.
        offload = self._offload_cfg is not None and not self._offload_stream
        if self._offload_stream:
            host_kind = lambda s: s.with_memory_kind("pinned_host")  # noqa: E731
            master_sh = jax.tree.map(host_kind, master_sh)

        def init_fn(p):
            p32 = cast_tree(p, jnp.float32)
            master = p32 if (mixed and not offload) else None
            compute = cast_tree(p32, compute_dtype)
            opt = () if offload else opt_init(p32)
            return compute, master, opt

        if offload:
            opt_sh = ()
        else:
            # opt-state mirrors params per-leaf (moments) plus scalar
            # counters; shard moments like the master, replicate scalars.
            opt_shape = jax.eval_shape(opt_init, jax.eval_shape(
                lambda q: cast_tree(q, jnp.float32), params))

            def opt_leaf_sharding(leaf):
                return NamedSharding(self.mesh, P())
            opt_sh = jax.tree.map(opt_leaf_sharding, opt_shape)
            # moments live under .mu/.nu (or .accum), follow master spec
            for field in moment_fields(opt_shape):
                opt_sh = opt_sh.replace(**{field: master_sh})
            if self._offload_stream:
                # the whole optimizer tree (moments + scalar counters)
                # lives in TPU-host pinned memory between steps
                opt_sh = jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"), opt_sh)

        mixed = mixed and not offload
        shardings = (param_sh, master_sh if mixed else None, opt_sh)
        compute, master, opt_state = jax.jit(
            init_fn, out_shardings=shardings)(params)
        self._device_param_shardings = param_sh
        if self._param_offload_cfg is not None:
            # bf16 params live in TPU-host memory between (and during)
            # steps — the jitted step fetches per-layer into HBM at use
            # sites (stage3.py:448 offload_param; coordinator prefetch ≈
            # XLA latency-hiding DMA scheduling). Eager placement: the CPU
            # test backend lacks host-memory out_shardings.
            param_sh = jax.tree.map(
                lambda s: s.with_memory_kind("pinned_host"), param_sh)
            compute = jax.device_put(compute, param_sh)
            log_dist(
                f"offload_param: bf16 params placed in host memory "
                f"(device={self._param_offload_cfg.device})", ranks=[0])
        loss_scale = make_loss_scale(
            self.config.fp16 if self.config.fp16.enabled else None)
        state = TrainState(step=jnp.zeros((), jnp.int32), params=compute,
                           master=master, opt_state=opt_state,
                           loss_scale=loss_scale)
        self._state_shardings = TrainState(
            step=NamedSharding(self.mesh, P()),
            params=param_sh,
            master=master_sh if mixed else None,
            opt_state=opt_sh,
            loss_scale=jax.tree.map(lambda _: NamedSharding(self.mesh, P()),
                                    loss_scale))
        # Commit EVERY leaf — including the step/loss_scale scalars built
        # eagerly above — to its sharding. Uncommitted scalars enter the
        # first step with empty-sharding avals while the step's outputs are
        # mesh-committed, so the second train_batch would retrace and
        # recompile the entire program: ~double compile time before any
        # steady-state step runs.
        state = jax.device_put(state, self._state_shardings)
        return state

    # ------------------------------------------------------------------
    # the compiled step
    # ------------------------------------------------------------------
    def _batch_sharding(self, batch):
        return jax.tree.map(
            lambda x: NamedSharding(self.mesh, P(DATA_AXES)), batch)

    def _make_grad_core(self, native_acc_out: bool = False):
        """The shared gradient producer: gas-scan accumulation, fp16
        unscale, finite check, global-norm clip. Used by both the fused
        in-HBM step and the host-offload step so the two paths cannot
        drift (they share bias/clip/epsilon semantics by construction).

        ``native_acc_out``: return grads in data_types.grad_accum_dtype
        instead of upcasting to fp32 at scan exit. With bf16 accumulation
        this halves both the device-resident grad footprint (the fp32
        materialization of a 1.2B-param tree costs 4.8 GB HBM on top of
        the carry) and the device→host grad stream of the ZeRO-Offload
        path — the host optimizer upcasts per-leaf as it consumes them
        (offload.py step_streamed). fp16 keeps the fp32 exit: its
        unscale/overflow contract is defined on fp32 grads."""
        gas = self.gas
        loss_fn = self.loss_fn
        fp16 = self.config.fp16.enabled
        clip = self.config.gradient_clipping
        acc_dtype = self._grad_accum_dtype()
        # bf16 only: an fp16 accumulation dtype must still exit fp32 —
        # clipping in fp16 flushes near-subnormal grads to zero
        native_out = (native_acc_out and not fp16
                      and acc_dtype == jnp.bfloat16)
        grad_spec = self.policy.spec_of(
            self.policy.grad_sharding(self.state.params))
        mesh = self.mesh
        # offload_param with a model that doesn't fetch its own layers:
        # bring the whole tree into device memory at step start (coarse —
        # params live in HBM for the step, host between steps). Models that
        # declare handles_param_offload fetch per-layer inside their remat
        # regions instead, bounding HBM to a few layers (stage3.py:448).
        param_offload = self._param_offload_in_jit
        coarse_fetch = param_offload and not self._model_fetches_params

        def constrain(tree):
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)), tree, grad_spec)

        split_loss_out = _split_loss_out

        def micro_grads(params, scale, mb, rng):
            def scaled_loss(p):
                loss, aux = split_loss_out(loss_fn(p, mb, rng))
                return (loss * scale / gas).astype(jnp.float32), (loss, aux)
            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled_loss, has_aux=True)(params)
            if param_offload:
                # cotangents of host-resident params may inherit the host
                # memory space; the update pipeline runs in device memory.
                # Explicit NamedShardings: bare memory-space transfers leave
                # the SPMD partitioner's placement annotations unsharded.
                grads = jax.tree.map(
                    lambda g, s: jax.device_put(
                        g, NamedSharding(mesh, s, memory_kind="device")),
                    grads, grad_spec)
            return loss, aux, grads

        fetch_sh = jax.tree.map(
            lambda s: s.with_memory_kind("device"),
            self._device_param_shardings) if coarse_fetch else None

        aux_keys_cache: dict = {"keys": None}
        numerics_spec = self._numerics_spec

        def grad_core(params, scale, batch, rng, want_numerics=False):
            """→ (grads fp32 clipped+unscaled, mean_loss, aux_mean dict,
            gnorm, finite, block_stats). ``block_stats`` is None unless
            ``want_numerics`` (a trace-time python bool): then a dict of
            per-layer-block arrays — ``grad_sq`` (unscaled, PRE-clip sum
            of squares; the clip would smear one block's NaN over all of
            them) and ``nonfinite`` counts (telemetry/numerics.py)."""
            from deepspeed_tpu.telemetry.numerics import (
                block_nonfinite_counts, block_sq_norms)
            if coarse_fetch:
                params = jax.tree.map(jax.device_put, params, fetch_sh)
            if gas > 1:
                def mb_body(carry, mb_rng):
                    acc, loss_sum, aux_sum = carry
                    mb, r = mb_rng
                    loss, aux, grads = micro_grads(params, scale, mb, r)
                    grads = cast_tree(grads, acc_dtype)
                    acc = constrain(jax.tree.map(jnp.add, acc, grads))
                    aux_sum = jax.tree.map(jnp.add, aux_sum, aux)
                    return (acc, loss_sum + loss, aux_sum), None

                zero_grads = constrain(jax.tree.map(
                    lambda p: jnp.zeros(p.shape, acc_dtype), params))
                mbs = jax.tree.map(
                    lambda x: x.reshape((gas, x.shape[0] // gas)
                                        + x.shape[1:]), batch)
                rngs = jax.random.split(rng, gas)
                # learn the aux KEY SET without spending FLOPs so the
                # scan carry can be initialized to matching zeros; the
                # structure is batch-shape-independent, so one abstract
                # trace per engine suffices (cached across recompiles)
                if aux_keys_cache["keys"] is None:
                    first_mb = jax.tree.map(lambda x: x[0], mbs)
                    aux_keys_cache["keys"] = tuple(jax.eval_shape(
                        lambda p: split_loss_out(loss_fn(
                            p, first_mb, rngs[0]))[1], params))
                aux_zero = {k: jnp.zeros((), jnp.float32)
                            for k in aux_keys_cache["keys"]}
                (grads, loss_sum, aux_sum), _ = jax.lax.scan(
                    mb_body, (zero_grads, jnp.float32(0.0), aux_zero),
                    (mbs, rngs))
                if not native_out:
                    grads = cast_tree(grads, jnp.float32)
                mean_loss = loss_sum / gas
                aux_mean = jax.tree.map(lambda a: a / gas, aux_sum)
            else:
                mean_loss, aux_mean, grads = micro_grads(
                    params, scale, batch, rng)
                grads = constrain(cast_tree(
                    grads, acc_dtype if native_out else jnp.float32))

            if native_out:
                # Fused unscale+clip, dtype-preserving: one elementwise
                # pass (XLA fuses the fp32 upcast/downcast into it), so
                # no fp32 copy of the grad tree is ever materialized.
                gnorm_raw = jnp.sqrt(sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in jax.tree.leaves(grads)))
                inv = jnp.float32(1.0) / scale
                gnorm = gnorm_raw * inv
                block_stats = None
                if want_numerics:
                    # unscaled squares: (g*inv)² = g²·inv² — one scalar
                    # multiply instead of a second grad-tree pass
                    block_stats = {
                        "grad_sq": block_sq_norms(grads, numerics_spec)
                        * (inv * inv),
                        "nonfinite": block_nonfinite_counts(
                            grads, numerics_spec)}
                factor = inv
                if clip > 0.0:
                    factor = inv * clip_coef(clip, gnorm)
                grads = jax.tree.map(
                    lambda g: (g * factor).astype(g.dtype), grads)
                return (grads, mean_loss, aux_mean, gnorm,
                        jnp.bool_(True), block_stats)

            # unscale (fp16) — gas scaling already folded into the loss
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g * inv, grads)
            finite = grads_finite(grads) if fp16 else jnp.bool_(True)

            block_stats = None
            if want_numerics:
                # pre-clip on purpose: the global-norm clip multiplies
                # EVERY leaf by a factor derived from the global norm,
                # so one block's NaN would smear into all of them and
                # destroy provenance
                block_stats = {
                    "grad_sq": block_sq_norms(grads, numerics_spec),
                    "nonfinite": block_nonfinite_counts(
                        grads, numerics_spec)}

            # global grad-norm clip (runtime/utils.py clip_grad_norm_ —
            # MP-awareness is free: grads are global arrays)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
            if clip > 0.0:
                coef = clip_coef(clip, gnorm)
                grads = jax.tree.map(lambda g: g * coef, grads)
            return grads, mean_loss, aux_mean, gnorm, finite, block_stats

        return grad_core

    def _make_step_fn(self):
        optimizer = self.optimizer
        schedule = self.lr_scheduler
        mixed = self.mixed_precision
        fp16 = self.config.fp16.enabled
        grad_core = self._make_grad_core()
        stream = self._offload_stream
        numerics_spec = self._numerics_spec
        if stream:
            # streamed offload: master/moments enter in pinned_host and
            # go through the device leaf by leaf, in an order this
            # program fixes itself (the overlap the reference builds by
            # hand with copy streams, stage_1_and_2.py:1069; XLA's
            # latency-hiding scheduler, left alone, ran fetches and
            # stores in turn). The stream has its own update path,
            # runtime/zero/offload_stream.py; every other configuration
            # keeps do_update below.
            master_host_sh = self._state_shardings.master
            opt_host_sh = self._state_shardings.opt_state

        def step_fn(state: TrainState, batch, rng, numerics_on=False):
            # ``numerics_on`` is STATIC (jit static_argnums): off, the
            # program is byte-identical to the un-instrumented step;
            # toggling is one retrace the compile watch attributes as
            # ``numerics_on: static:False -> static:True``.
            from deepspeed_tpu.telemetry.numerics import block_sq_norms
            scale = state.loss_scale.scale if fp16 else jnp.float32(1.0)
            # the step's two scopes (docs/observability.md "Spans"):
            # metadata the compile watch's scope table reads, so that
            # device time splits into forward+backward and optimizer
            # (the offload stream's transfers included)
            with jax.named_scope("fwd_bwd"):
                grads, mean_loss, aux, gnorm, finite, bstats = grad_core(
                    state.params, scale, batch, rng,
                    want_numerics=numerics_on)
            with jax.named_scope("optimizer"):
                lr = schedule(state.step)
                master = state.master if mixed else state.params

                def do_update(operand):
                    grads_, master_, opt_state_ = operand
                    updates, new_opt = optimizer.update(
                        grads_, opt_state_, master_, lr)
                    new_master = jax.tree.map(jnp.add, master_, updates)
                    upd_sq = (block_sq_norms(updates, numerics_spec)
                              if numerics_on else ())
                    return new_master, new_opt, upd_sq

                def skip_update(operand):
                    _, master_, opt_state_ = operand
                    upd_sq = (jnp.zeros((len(numerics_spec.names),),
                                        jnp.float32) if numerics_on else ())
                    return master_, opt_state_, upd_sq

                if stream:
                    # (fp16's skip cond cannot wrap memory-space
                    # transfers: refused at construction). The bf16
                    # cast happens inside, while each fresh master leaf
                    # is still in device space.
                    new_master, new_opt, new_params, upd_sq = \
                        streamed_update(
                            optimizer, grads, master, state.opt_state, lr,
                            master_sh=master_host_sh, opt_sh=opt_host_sh,
                            compute_dtype=(self.compute_dtype if mixed
                                           else None),
                            upd_sq_spec=(numerics_spec if numerics_on
                                         else None))
                elif fp16:
                    new_master, new_opt, upd_sq = jax.lax.cond(
                        finite, do_update, skip_update,
                        (grads, master, state.opt_state))
                else:
                    new_master, new_opt, upd_sq = do_update(
                        (grads, master, state.opt_state))

                if mixed:
                    if not stream:
                        new_params = cast_tree(new_master,
                                               self.compute_dtype)
                    new_state = state.replace(
                        step=state.step + 1, params=new_params,
                        master=new_master, opt_state=new_opt,
                        loss_scale=update_loss_scale(state.loss_scale,
                                                     finite))
                else:
                    new_state = state.replace(
                        step=state.step + 1, params=new_master,
                        opt_state=new_opt,
                        loss_scale=update_loss_scale(state.loss_scale,
                                                     finite))

            metrics = {"loss": mean_loss, "grad_norm": gnorm, "lr": lr,
                       "loss_scale": scale,
                       "skipped": jnp.logical_not(finite)}
            metrics.update(aux)   # user aux scalars (multi-output models)
            if numerics_on:
                # per-block observatory payload, popped by train_batch
                # before metrics reach the caller. Param norms use the
                # PRE-update master (fp32) — except under streamed
                # offload, where the master lives in host memory and
                # the bf16 compute params are the device-resident copy.
                param_src = state.params if stream else master
                metrics["_numerics"] = {
                    "grad_norm": jnp.sqrt(bstats["grad_sq"]),
                    "param_norm": jnp.sqrt(
                        block_sq_norms(param_src, numerics_spec)),
                    "update_norm": jnp.sqrt(upd_sq),
                    "nonfinite": bstats["nonfinite"],
                }
            return new_state, metrics

        return step_fn

    def _make_compressed_step_fn(self, batch):
        """Whole-step shard_map over the DP axes for the 1-bit optimizer
        family: each worker computes LOCAL gradients from its batch shard
        (no GSPMD allreduce — the batch never crosses workers), and the
        optimizer's own pmean / error-feedback sign-compressed exchange is
        the only gradient communication (reference onebit design: engine
        backward-allreduce disabled, optimizer owns comm).

        Semantics notes vs the exact path: gradient clipping acts on the
        per-worker local gradient (a global norm cannot be formed without
        the exact exchange the algorithm exists to avoid) and the reported
        grad_norm is the worker mean. Model code must not place sharding
        constraints over the DP axes (they are manual inside this region).
        """
        axes = self._onebit_axes
        local_grads = self._make_local_grads_fn(axes)
        clip = self.config.gradient_clipping
        apply_update = self._make_replicated_update()

        def local_step(state: TrainState, batch, rng):
            with jax.named_scope("fwd_bwd"):
                grads, mean_loss = local_grads(state.params, batch, rng)
            # clip acts on the per-worker LOCAL gradient: a global norm
            # cannot be formed without the exact exchange this algorithm
            # exists to avoid; reported grad_norm is the worker mean
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)))
            if clip > 0.0:
                coef = clip_coef(clip, gnorm)
                grads = jax.tree.map(lambda g: g * coef, grads)
            with jax.named_scope("optimizer"):
                # the 1-bit optimizer owns the gradient exchange
                new_state, lr = apply_update(state, grads)
            metrics = {"loss": jax.lax.pmean(mean_loss, axes),
                       "grad_norm": jax.lax.pmean(gnorm, axes),
                       "lr": lr,
                       "loss_scale": jnp.float32(1.0),
                       "skipped": jnp.bool_(False)}
            return new_state, metrics

        return self._wrap_explicit_dp(local_step, batch)

    def _grad_accum_dtype(self):
        """GAS accumulation-buffer dtype, shared by the fused GSPMD step
        and the explicit-exchange shard_map steps (1-bit/sparse) so the
        two paths cannot drift. data_types.grad_accum_dtype
        (constants.py:389-394) wins; else communication_data_type
        (constants.py:119) — under GSPMD the DP reduction happens at the
        accumulated grads' dtype, so the comm-bytes knob IS the
        accumulator dtype (conflict validated at config construction);
        else the reference's safe default, fp32."""
        return {"fp32": jnp.float32, "fp16": jnp.float16,
                "bf16": jnp.bfloat16, None: jnp.float32}[
                    self.config.data_types.grad_accum_dtype or
                    self.config.communication_data_type]

    def _make_local_grads_fn(self, axes):
        """Per-worker gradient producer shared by the explicit-exchange
        shard_map steps (1-bit compressed, sparse): distinct rng per
        worker, GAS scan accumulation in ``data_types.grad_accum_dtype``,
        mean over micro-batches. Returns fp32 grads + local mean loss."""
        gas = self.gas
        loss_fn = self.loss_fn
        axis_sizes = {a: self.mesh.shape[a] for a in axes}
        acc_dtype = self._grad_accum_dtype()

        def local_grads(params, batch, rng):
            # distinct dropout/randomness per worker: the exact GSPMD path
            # draws one mask over the global batch, so the local shard must
            # not repeat the same rng stream on every worker
            widx = jnp.int32(0)
            for a in axes:
                widx = widx * axis_sizes[a] + jax.lax.axis_index(a)
            rng = jax.random.fold_in(rng, widx)

            def micro(mb, r):
                def scalar_loss(p):
                    out = loss_fn(p, mb, r)
                    if isinstance(out, tuple):
                        # aux metrics are a standard-step feature; here
                        # they would ride the explicit all-gather — drop
                        # them (once, loudly) instead of refusing so a
                        # docs/training.md-style loss_fn still trains
                        # with the 1-bit/sparse optimizers
                        if not self._warned_aux_dropped:
                            self._warned_aux_dropped = True
                            logger.warning(
                                "loss_fn aux metrics are ignored on the "
                                "1-bit/sparse explicit-DP step (reported "
                                "metrics carry loss/grad_norm/lr only)")
                        out = _split_loss_out(out)[0]
                    return out.astype(jnp.float32)
                loss, grads = jax.value_and_grad(scalar_loss)(params)
                return loss, grads

            if gas > 1:
                mbs = jax.tree.map(
                    lambda x: x.reshape((gas, x.shape[0] // gas)
                                        + x.shape[1:]), batch)
                rngs = jax.random.split(rng, gas)

                def body(carry, mb_r):
                    acc, lsum = carry
                    loss, grads = micro(*mb_r)
                    grads = cast_tree(grads, acc_dtype)
                    return (jax.tree.map(jnp.add, acc, grads),
                            lsum + loss), None
                zero = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, acc_dtype), params)
                (grads, lsum), _ = jax.lax.scan(
                    body, (zero, jnp.float32(0.0)), (mbs, rngs))
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / gas, grads)
                mean_loss = lsum / gas
            else:
                mean_loss, grads = micro(batch, rng)
                grads = cast_tree(grads, jnp.float32)
            return grads, mean_loss

        return local_grads

    def _make_replicated_update(self):
        """Optimizer/master update on replicated (post-exchange) grads —
        the tail both explicit-DP steps share."""
        optimizer = self.optimizer
        schedule = self.lr_scheduler
        mixed = self.mixed_precision
        dtype = self.compute_dtype

        def apply_update(state: TrainState, grads):
            lr = schedule(state.step)
            master = state.master if mixed else state.params
            updates, new_opt = optimizer.update(
                grads, state.opt_state, master, lr)
            new_master = jax.tree.map(jnp.add, master, updates)
            new_params = (cast_tree(new_master, dtype) if mixed
                          else new_master)
            new_state = state.replace(
                step=state.step + 1, params=new_params,
                master=new_master if mixed else None,
                opt_state=new_opt, loss_scale=state.loss_scale)
            return new_state, lr

        return apply_update

    def _wrap_explicit_dp(self, local_step, batch):
        state_specs = jax.tree.map(lambda _: P(), self.state)
        batch_specs = jax.tree.map(lambda _: P(DATA_AXES), batch)
        metric_specs = {k: P() for k in ("loss", "grad_norm", "lr",
                                         "loss_scale", "skipped")}
        return jax.shard_map(
            local_step, mesh=self.mesh,
            in_specs=(state_specs, batch_specs, P()),
            out_specs=(state_specs, metric_specs),
            check_vma=False)

    def _make_sparse_step_fn(self, batch):
        """Whole-step shard_map over the DP axes with a row-sparse
        exchange for embedding-shaped leaves (reference sparse allreduce,
        engine.py:2459: all_gather indices+values instead of dense
        allreduce). Numerically identical to the GSPMD fused step: local
        grads are mean-exchanged (pmean for dense leaves, (ids,rows)
        gather-scatter for sparse ones), then clip/optimizer run
        replicated."""
        from deepspeed_tpu.runtime.quantize import _leaf_paths
        from deepspeed_tpu.runtime.sparse_tensor import (sparse_all_mean,
                                                         sparse_capacity)
        import fnmatch
        clip = self.config.gradient_clipping
        axes = self._sparse_grad_axes
        dp = 1
        for a in axes:
            dp *= self.mesh.shape[a]

        # leaf selection + per-leaf capacity, resolved at trace time
        paths = _leaf_paths(self.state.params)
        caps = []
        n_sparse = 0
        for path, leaf in zip(paths, jax.tree.leaves(self.state.params)):
            cap = None
            if leaf.ndim == 2 and any(fnmatch.fnmatch(path, p)
                                      for p in self._sparse_grad_patterns):
                c = sparse_capacity(batch, dp, leaf.shape[0])
                # only exchange sparsely when it actually saves bandwidth
                # (ids+rows from every worker vs one dense reduce)
                if 2 * c * dp < leaf.shape[0]:
                    cap = c
                    n_sparse += 1
            caps.append(cap)
        log_dist(f"sparse_gradients: {n_sparse} leaf(s) on the sparse "
                 f"exchange, dp={dp}", ranks=[0])
        cap_by_path = dict(zip(paths, caps))

        def exchange(grads):
            flat, treedef = jax.tree_util.tree_flatten(grads)
            out = []
            for cap, g in zip(caps, flat):
                if cap is None:
                    out.append(jax.lax.pmean(g, axes))
                else:
                    out.append(sparse_all_mean(g, cap, axes))
            return jax.tree_util.tree_unflatten(treedef, out)

        local_grads = self._make_local_grads_fn(axes)
        apply_update = self._make_replicated_update()

        def local_step(state: TrainState, batch, rng):
            with jax.named_scope("fwd_bwd"):
                grads, mean_loss = local_grads(state.params, batch, rng)
            # the DP exchange — the one piece that differs from pmean;
            # clip/update then run on replicated (global) grads, exactly
            # like the fused GSPMD step
            with jax.named_scope("grad_exchange"):
                grads = exchange(grads)
                mean_loss = jax.lax.pmean(mean_loss, axes)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                 for g in jax.tree.leaves(grads)))
            if clip > 0.0:
                coef = clip_coef(clip, gnorm)
                grads = jax.tree.map(lambda g: g * coef, grads)
            with jax.named_scope("optimizer"):
                new_state, lr = apply_update(state, grads)
            metrics = {"loss": mean_loss, "grad_norm": gnorm, "lr": lr,
                       "loss_scale": jnp.float32(1.0),
                       "skipped": jnp.bool_(False)}
            return new_state, metrics

        self._sparse_grad_caps = cap_by_path  # introspection + tests
        # capacities are baked into this executable from THIS batch's
        # shapes; train_batch rebuilds the step when batch shapes change
        self._sparse_batch_shapes = tuple(
            tuple(x.shape) for x in jax.tree.leaves(batch))
        return self._wrap_explicit_dp(local_step, batch)

    def _init_flight_recorder(self, tcfg) -> None:
        """Config-gated flight-recorder surfaces (docs/observability.md
        "Flight recorder") via the shared telemetry helper; the
        training HBM residents are params and optimizer state (fp32
        master included). Weak self-reference so a dropped engine never
        pins its arrays through the process-wide monitor."""
        import weakref

        from deepspeed_tpu.telemetry.flight import arm_flight_recorder
        ref = weakref.ref(self)

        def _params():
            eng = ref()
            return None if eng is None else eng.state.params

        def _opt_state():
            eng = ref()
            if eng is None:
                return None
            # fp32 master weights are optimizer-owned memory too
            return (eng.state.opt_state,
                    getattr(eng.state, "master", None))

        self._flight = arm_flight_recorder(
            tcfg, self.telemetry, "train_watchdog",
            [("params", _params), ("optimizer_state", _opt_state)])
        self.watchdog = self._flight.watchdog
        # training-scoped chaos hooks (telemetry/faultinject.py):
        # consulted by the TrainingSupervisor (runtime/resilience.py)
        # and the checkpoint write path; None when the config section is
        # off — the train loop never branches on it then
        from deepspeed_tpu.telemetry import FaultInjector
        self.fault_injector = FaultInjector.from_config(
            tcfg.fault_injection if tcfg is not None else None,
            registry=self.telemetry)

    @staticmethod
    def _accept_numerics_flag(step3):
        """Give a 3-arg step the fused step's 4-arg signature. The
        explicit-DP (1-bit/sparse) steps do not support in-graph
        numerics (their gradients are per-worker inside shard_map);
        the flag is accepted — so every path shares one calling
        convention — and ignored."""
        def step_fn(state, batch, rng, numerics_on=False):
            return step3(state, batch, rng)
        return step_fn

    def _compile_step(self, batch, bytes_limit: Optional[int] = None):
        """Build ``self._step_fn`` for ``batch``'s shapes. ``bytes_limit``:
        a chip's memory where the caller knows it and the device cannot
        say (a described device: ``scripts/aot_train_step.py``); None
        asks the device."""
        from deepspeed_tpu.telemetry import watched_jit
        if self._onebit_axes:
            self._eager_param_staging = False
            self._step_fn = watched_jit(
                self._accept_numerics_flag(
                    self._make_compressed_step_fn(batch)),
                name="train_step", registry=self.telemetry,
                static_argnums=(3,), donate_argnums=(0,))
            return
        if self._sparse_grad_axes:
            self._eager_param_staging = False
            self._step_fn = watched_jit(
                self._accept_numerics_flag(
                    self._make_sparse_step_fn(batch)),
                name="train_step", registry=self.telemetry,
                static_argnums=(3,), donate_argnums=(0,))
            return
        batch_sh = self._batch_sharding(batch)
        in_sh = self._state_shardings
        out_sh = self._state_shardings
        self._eager_param_staging = False
        if self._param_offload_cfg is not None and \
                not self._param_offload_in_jit:
            # non-TPU backends: the compiled step sees device-resident
            # params; train_batch stages host→device before and device→host
            # after each step (between-step eviction preserved).
            in_sh = in_sh.replace(params=self._device_param_shardings)
            out_sh = out_sh.replace(params=self._device_param_shardings)
            self._eager_param_staging = True
        compiler_options = {}
        if self._offload_stream:
            # the stream's pipeline needs its transfers in flight
            # together; the compiler's own budget of outstanding host
            # copies (5) would serialize them again
            compiler_options[COPY_BUDGET_OPTION] = copy_budget(
                self.state.opt_state, self.state.master is not None)
        remat_percent = self._remat_limit_percent(bytes_limit)
        if remat_percent is not None:
            compiler_options[REMAT_LIMIT_OPTION] = remat_percent
        options = {"compiler_options": compiler_options} \
            if compiler_options else {}
        # numerics_on is static (one retrace per toggle); in_shardings
        # cover the three dynamic args only
        self._step_fn = watched_jit(
            self._make_step_fn(),
            name="train_step", registry=self.telemetry,
            in_shardings=(in_sh, batch_sh, None),
            out_shardings=(out_sh, None),
            static_argnums=(3,),
            donate_argnums=(0,), **options)

    def _remat_limit_percent(self, bytes_limit: Optional[int]
                             ) -> Optional[int]:
        """The share of a chip's memory to give the compiler's
        rematerialisation pass for ``train_step``
        (``activation_checkpointing.remat_limit_percent``): the pass
        counts the state this engine placed in pinned host memory
        against the chip, so that share is given back. None where no
        state lives there (the option is not set and the step's text is
        what it was) or the chip's limit is unknown. Published as gauge
        ``train_remat_limit_percent`` (0: left alone) and one log line."""
        host = 0
        for x, s in zip(jax.tree.leaves(self.state),
                        jax.tree.leaves(self._state_shardings)):
            if s.memory_kind == "pinned_host":
                host += int(np.prod(s.shard_shape(x.shape))) \
                    * jnp.dtype(x.dtype).itemsize
        if host and bytes_limit is None:
            # None: a backend that keeps no account (XLA:CPU)
            stats = self.mesh.local_devices[0].memory_stats() or {}
            bytes_limit = int(stats.get("bytes_limit", 0))
        percent = remat_limit_percent(bytes_limit or 0, host)
        self.telemetry.gauge(
            "train_remat_limit_percent",
            help="the rematerialisation pass's share of a chip's memory "
                 "as train_step was compiled: the default plus the state "
                 "in pinned host memory, which the pass counts against "
                 "the chip (0: no state on the host or limit unknown, "
                 "option not set)").set(float(percent or 0))
        if percent is not None:
            log_dist("train_step: {:.2f} GB of state a chip in pinned host "
                     "memory, bytes_limit {:.2f} GB -> {} = {}".format(
                         host / 1e9, bytes_limit / 1e9, REMAT_LIMIT_OPTION,
                         percent), ranks=[0])
        elif host and self.mesh.devices.flat[0].platform == "tpu":
            logger.warning(
                "train_step: state lives in pinned host memory and the "
                "chip reports no bytes_limit: %s is not set, and the "
                "compiler's rematerialisation pass will count that state "
                "against the chip (recomputed matmuls)", REMAT_LIMIT_OPTION)
        return percent

    # ------------------------------------------------------------------
    # ZeRO-Offload step: device grads → host SIMD Adam → device params
    # (runtime/zero/offload.py; reference stage_1_and_2.py:1069-1219)
    # ------------------------------------------------------------------
    def _compile_offload_grad_fn(self, batch):
        # native_acc_out: with grad_accum_dtype=bf16 the grads leave the
        # device in bf16 — halves grad HBM and the per-step D2H stream
        # (the host Adam upcasts per-leaf). No-op at the fp32 default.
        grad_core = self._make_grad_core(native_acc_out=True)
        # numerics on this path is a closure constant, not a static arg
        # (the grad program is plain jit); set_numerics_enabled drops
        # the executable so the toggle rebuilds it. update_norm is not
        # available here — the update happens in the host optimizer.
        numerics_on = self._numerics_on
        numerics_spec = self._numerics_spec
        from deepspeed_tpu.telemetry.numerics import block_sq_norms

        def grad_fn(params, scale, batch, rng):
            with jax.named_scope("fwd_bwd"):
                grads, loss, aux, gnorm, finite, bstats = grad_core(
                    params, scale, batch, rng, want_numerics=numerics_on)
            out = {"loss": loss, "grad_norm": gnorm,
                   "finite": finite, **aux}
            if numerics_on:
                out["_numerics"] = {
                    "grad_norm": jnp.sqrt(bstats["grad_sq"]),
                    "param_norm": jnp.sqrt(
                        block_sq_norms(params, numerics_spec)),
                    "nonfinite": bstats["nonfinite"],
                }
            return grads, out

        batch_sh = self._batch_sharding(batch)
        param_in_sh = self._state_shardings.params
        self._offload_grad_stage = False
        if self._param_offload_cfg is not None and \
                not self._param_offload_in_jit:
            param_in_sh = self._device_param_shardings
            self._offload_grad_stage = True
        # Donate the incoming param buffers: they are replaced wholesale by
        # the host update, so holding both copies through the step doubles
        # param HBM for nothing. Exception: fp16 with un-staged params —
        # an overflow-skipped step must keep the old params alive.
        donate = ((0,) if (self._offload_grad_stage or
                           not self.config.fp16.enabled) else ())
        grad_fn.__name__ = grad_fn.__qualname__ = "train_offload_grads"
        self._offload_grad_fn = jax.jit(
            grad_fn,
            in_shardings=(param_in_sh, None, batch_sh, None),
            donate_argnums=donate)

    def _offload_train_batch(self, batch) -> Dict[str, Any]:
        if self._offload_grad_fn is None:
            self._compile_offload_grad_fn(batch)
        self.tput_timer.start()
        self._rng, rng = jax.random.split(self._rng)
        fp16 = self.config.fp16.enabled
        scale = float(self._host_loss_scale.scale) if fp16 else 1.0
        params_in = self.state.params
        if self._offload_grad_stage:
            params_in = jax.device_put(params_in,
                                       self._device_param_shardings)
        phase = span_annotation("train:dispatch")
        t_disp = time.perf_counter()
        grads, metrics = self._offload_grad_fn(
            params_in, jnp.float32(scale), batch, rng)
        t_sent = time.perf_counter()
        _close(phase)
        phase = span_annotation("train:wait")
        finite = bool(metrics["finite"])   # host sync — grads are ready
        t_ready = time.perf_counter()
        _close(phase)
        self._offload_device_s = t_ready - t_disp
        self._step_spans += [("train:dispatch", t_disp, t_sent),
                             ("train:wait", t_sent, t_ready)]
        numer = metrics.pop("_numerics", None)
        lr = float(self.lr_scheduler(self.state.step))
        skipped = fp16 and not finite
        if not skipped:
            from deepspeed_tpu.runtime.zero.offload import (
                _flatten_with_names)
            if self.host_opt.swapper is None:
                # leaf-pipelined: D2H ∥ host Adam ∥ async H2D per leaf
                # (reference stage_1_and_2.py:1069-1219 overlap machinery)
                leaf_sh = _flatten_with_names(self._state_shardings.params)
                new_params = self.host_opt.step_streamed(
                    _flatten_with_names(grads), lr, self.compute_dtype,
                    put=lambda k, payload: jax.device_put(
                        payload, leaf_sh[k]))
            else:
                # NVMe moments: whole-tree step (pipelined through the aio
                # double buffer instead)
                grads_host = {k: np.asarray(v, np.float32).reshape(-1)
                              for k, v in _flatten_with_names(grads).items()}
                new_params = jax.device_put(
                    self.host_opt.step(grads_host, lr, self.compute_dtype),
                    self._state_shardings.params)
            self.state = self.state.replace(params=new_params)
        self._step_spans.append(
            ("train:host_optimizer", t_ready, time.perf_counter()))
        # step advances even when skipped — matches the in-HBM step_fn so
        # the lr schedule is identical across both paths
        self.state = self.state.replace(step=self.state.step + 1)
        if fp16:
            # exact same dynamics as the device path: reuse precision.py
            self._host_loss_scale = update_loss_scale(
                self._host_loss_scale, jnp.bool_(finite))
            if skipped:
                self._count_overflow_skip()
        self.global_steps += 1
        self._micro_steps += self.gas
        self._last_grad_norm = metrics.get("grad_norm")
        if numer is not None:
            self._observe_numerics(numer, metrics["loss"])
        self.tput_timer.stop(global_step=self.global_steps,
                             report_speed=True)
        self._record_step_progress()
        out = {"loss": metrics["loss"], "grad_norm": metrics["grad_norm"],
               "lr": lr, "loss_scale": scale, "skipped": skipped}
        # user aux scalars computed by grad_fn ride through here too
        out.update({k: v for k, v in metrics.items()
                    if k not in ("loss", "grad_norm", "finite")})
        if self.global_steps % self.config.steps_per_print == 0:
            self._write_monitor_events(out)
        return out

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def train_batch(self, batch=None) -> Dict[str, Any]:
        """Run one full optimizer step over a global batch of
        ``train_batch_size`` (= micro * gas * dp). Returns metrics with the
        mean loss — the analog of forward/backward/step over ``gas``
        micro-batches (SURVEY §3.2)."""
        t_wall = time.perf_counter()   # goodput: the step wall interval
        data_wait = 0.0
        ann = span_annotation("train:step", step=self.global_steps + 1)
        self._step_spans = []
        if batch is None:
            phase = span_annotation("train:data")
            batch = next(self.training_dataloader)
            data_wait = time.perf_counter() - t_wall
            _close(phase)
            self._step_spans.append(
                ("train:data", t_wall, t_wall + data_wait))
        batch = self._global_micro_batch(batch)
        leading = jax.tree.leaves(batch)[0].shape[0]
        expected = self.micro_batch_size * self.gas * \
            get_data_parallel_world_size(self.mesh)
        if leading != expected:
            raise ValueError(
                f"global batch leading dim {leading} != "
                f"micro*gas*dp = {expected}")
        if self.curriculum_scheduler is not None:
            self.curriculum_scheduler.update_difficulty(self.global_steps)
        # NVMe tier: params spent the inter-step window in swap files
        # (partitioned_param_swapper.py semantics); restore for the step
        self._ensure_params_resident()
        if self.host_opt is not None:
            out = self._offload_train_batch(batch)
            self._maybe_swap_params_out()
            self._last_skipped = out.get("skipped")
            self.goodput.record_step(
                time.perf_counter() - t_wall, data_wait,
                getattr(self, "_offload_device_s", 0.0))
            self._record_step_trace(
                time.perf_counter() - t_wall, data_wait,
                getattr(self, "_offload_device_s", 0.0))
            self._record_step_spans(t_wall, ann)
            return out
        if (self._sparse_grad_axes and self._step_fn is not None and
                tuple(tuple(x.shape) for x in jax.tree.leaves(batch))
                != self._sparse_batch_shapes):
            # sparse-exchange capacities are shape-derived compile-time
            # constants — a different batch shape would retrace with STALE
            # capacities and silently drop embedding-grad rows. Rebuild
            # (the retrace was unavoidable anyway).
            self._step_fn = None
        if self._step_fn is None:
            self._compile_step(batch)
        profiling = (self.flops_profiler is not None and
                     self.global_steps + 1 ==
                     self.flops_profiler.profile_step)
        if self.quantizer is not None and self.global_steps == 0 and \
                not getattr(self, "_moq_step0_done", False):
            # "quantization happens at step 0" (reference engine.py:1786):
            # the initial weights are quantized before the first update
            self._moq_boundary(batch, overflow=False, step_zero=True)
        self.tput_timer.start()
        self._rng, rng = jax.random.split(self._rng)
        if self._eager_param_staging:
            self.state = self.state.replace(params=jax.device_put(
                self.state.params, self._device_param_shardings))
        if profiling:
            if self.global_steps == 0:
                # the timed region would include the XLA compile of the
                # first dispatch — latency/FLOPS would be compile-dominated
                # and wildly misleading. Pre-compile (AOT, no execution,
                # same avals/shardings as the dispatch below — hence after
                # staging — and no extra rng split: lowering only reads
                # avals, and splitting would perturb the training
                # trajectory of profiled vs unprofiled runs).
                logger.warning(
                    "flops_profiler.profile_step coincides with the first "
                    "(compiling) step; pre-compiling so reported latency "
                    "excludes compilation")
                # warm() lands the executable in the compile watch's
                # cache, so the dispatch below reuses it (one compile
                # total) and cost analysis later is free
                self._step_fn.warm(self.state, batch, rng,
                                   self._numerics_on)
            self.flops_profiler.start_profile()
        t_step = (time.perf_counter()
                  if self.config.wall_clock_breakdown else None)
        # the step's phases are profiler ranges too (one is_enabled()
        # read each with no profiler): a device trace then lays an idle
        # gap to dispatch or wait, not to the whole train:step
        phase = span_annotation("train:dispatch")
        t_disp = time.perf_counter()
        self.state, metrics = self._step_fn(self.state, batch, rng,
                                            self._numerics_on)
        t_sent = time.perf_counter()
        _close(phase)
        self._step_spans.append(("train:dispatch", t_disp, t_sent))
        device_s = 0.0
        if self.goodput.enabled:
            # the goodput device bucket IS this sync: dispatch → outputs
            # ready (the documented cost of telemetry.goodput); without
            # the meter the step has no train:wait span — nothing here
            # adds a sync
            phase = span_annotation("train:wait")
            jax.block_until_ready(metrics)
            t_ready = time.perf_counter()
            _close(phase)
            device_s = t_ready - t_disp
            self._step_spans.append(("train:wait", t_sent, t_ready))
        if t_step is not None and self.global_steps > 0 and \
                (self.global_steps + 1) % self.config.steps_per_print == 0:
            # wall_clock_breakdown (reference EngineTimers): the fused
            # step has no fwd/bwd/step phases to split — one synced step
            # time on print steps is the honest breakdown. Step 1 is
            # skipped (it would report XLA compile time). The host
            # transfer is deliberate: dispatch is asynchronous, and
            # fetching a value the step produced is the barrier that
            # cannot be satisfied early.
            jax.block_until_ready(metrics["loss"])
            float(metrics["loss"])
            log_dist(f"step {self.global_steps + 1}: "
                     f"{(time.perf_counter() - t_step) * 1e3:.1f} ms "
                     "(fused fwd+bwd+step, incl. one host sync)",
                     ranks=[0])
        if self._eager_param_staging:
            self.state = self.state.replace(params=jax.device_put(
                self.state.params, self._state_shardings.params))
        if self.quantizer is not None:
            # GAS boundary: every train_batch is one (the gas scan is
            # inside the step). NOTE: the fp16 overflow gate reads
            # metrics["skipped"] — a host sync per step, same cadence the
            # reference pays reading optimizer.overflow.
            overflow = self.config.fp16.enabled and bool(metrics["skipped"])
            self._moq_boundary(batch, overflow=overflow)
        self._maybe_swap_params_out()
        if profiling:
            jax.block_until_ready(metrics["loss"])
            float(metrics["loss"])   # host sync: the fetch is the barrier
            self.flops_profiler.mark_step_done()  # latency frozen here
            # the compile watch already holds this signature's
            # executable (the step that just ran) — its normalized
            # cost comes back without a second compile, and is BY
            # CONSTRUCTION the same number compile_report() shows
            cost = self._step_fn.cost(self.state, batch, rng,
                                      self._numerics_on)
            n_params = sum(int(np.prod(p.shape))
                           for p in jax.tree.leaves(self.state.params))
            breakdown = None
            if self.flops_profiler.detailed:
                # reference per-module tree (forward attribution via
                # flax named_scope paths in the jaxpr); profiling must
                # never kill a training step, hence the broad guard
                try:
                    from deepspeed_tpu.profiling.flops_profiler import (
                        module_flops_breakdown)
                    md = self.config.flops_profiler.module_depth
                    breakdown = module_flops_breakdown(
                        lambda p_: self.loss_fn(p_, batch, rng),
                        self.state.params,
                        depth=None if md < 0 else md)
                except Exception as e:  # noqa: BLE001
                    logger.warning(f"per-module breakdown failed: {e}")
            self.flops_profiler.stop_profile(
                flops=float(cost.get("flops", 0.0)), params=n_params,
                module_breakdown=breakdown)
            self.flops_profiler.print_model_profile()
        numer = metrics.pop("_numerics", None)
        self.global_steps += 1
        self._micro_steps += self.gas
        self._last_skipped = metrics.get("skipped")
        self._last_grad_norm = metrics.get("grad_norm")
        if self.config.fp16.enabled and bool(metrics["skipped"]):
            self._count_overflow_skip()
        if numer is not None:
            self._observe_numerics(numer, metrics["loss"])
        self.tput_timer.stop(global_step=self.global_steps,
                             report_speed=True)
        self._record_step_progress()
        if self.global_steps % self.config.steps_per_print == 0:
            self._write_monitor_events(metrics)
        self.goodput.record_step(time.perf_counter() - t_wall,
                                 data_wait, device_s)
        self._record_step_trace(time.perf_counter() - t_wall,
                                data_wait, device_s)
        self._record_step_spans(t_wall, ann, program=self._step_fn.name,
                                executable=self._step_fn.last_index)
        if comm.comms_logger.enabled and not self._comms_logged:
            # the comms_logger block: what the compiled step moves, once
            self._comms_logged = True
            comm.comms_logger.log_all()
        return metrics

    def _record_step_spans(self, t_wall: float, ann,
                           program: Optional[str] = None,
                           executable: Optional[int] = None) -> None:
        """This step in the span log (telemetry/spans.py): ``train:step``
        from the call to its return, and under it the intervals the step
        already timed (``train:data`` / ``train:dispatch`` /
        ``train:wait``, on the host-offload path ``train:host_optimizer``).
        Dispatch is asynchronous: without a wait span the step's end is
        the host's, not the device's. ``program`` and ``executable`` (the
        watched program the step ran and which of its executables, by
        index) become the span's attributes: whose movement-table rows
        the step ran, where a program was compiled more than once."""
        log = get_span_log()
        sid = log.next_id()
        for name, t0, t1 in self._step_spans:
            log.record(name, t0, t1, parent=sid, key=self.global_steps)
        log.record("train:step", t_wall, time.perf_counter(),
                   key=self.global_steps, span_id=sid,
                   attrs=None if program is None else
                   {"program": program, "executable": executable})
        _close(ann)

    def _publish_movement(self) -> None:
        """The registry's read-time collector: what ONE execution of the
        compiled step moves, by kind (host-link copies by direction,
        collectives by opcode), from the movement table of the
        executable the last step ran, and the bytes all steps moved."""
        fn = self._step_fn
        if fn is None or fn.last_index is None:
            return
        from deepspeed_tpu.telemetry.compile_watch import (
            executable_tables, movement_per_step)
        per_step = {rec.index: movement_per_step(
            executable_tables(rec).movement) for rec in fn.executables}
        for kind, moved in per_step[fn.last_index].items():
            labels = {"kind": kind}
            self.telemetry.gauge(
                "train_moved_bytes_per_step",
                help="bytes one train step moves over the host link or "
                     "the interconnect, per chip, by kind of transfer "
                     "(from the compiled step's movement table)",
                labels=labels).set(moved["bytes"])
            self.telemetry.gauge(
                "train_movement_calls_per_step",
                help="transfers (copy pairs, collectives) one train step "
                     "makes, by kind", labels=labels).set(moved["calls"])
            total = self.telemetry.counter(
                "train_moved_bytes_total",
                help="steps taken x train_moved_bytes_per_step, by kind",
                labels=labels)
            moved_so_far = sum(
                rec.calls * per_step[rec.index].get(kind, {"bytes": 0.0})[
                    "bytes"] for rec in fn.executables)
            total.inc(max(moved_so_far - total.value, 0.0))

    def _record_step_trace(self, wall: float, data_wait: float,
                           device_s: float) -> None:
        """One trace per train step (head-sampled like serving): a root
        ``train_step`` span whose data-wait/device/host children are
        synthesized from the goodput splits — intervals laid out in the
        data→device→host order the step logically runs, summing to the
        root by construction. With ``telemetry.goodput`` off the device
        interval is unmeasured (no extra sync is ever added for
        tracing), so the host child absorbs it."""
        if self.tracer is None:
            return
        now = self.tracer.clock()
        wall = max(float(wall), 0.0)
        data = min(max(float(data_wait), 0.0), wall)
        device = min(max(float(device_s), 0.0), wall - data)
        t0 = now - wall
        tr = self.tracer.start_trace(
            "train_step", trace_id=self.global_steps, start=t0,
            step=self.global_steps,
            goodput_measured=self.goodput.enabled)
        if data:
            tr.add_span("data_wait", t0, t0 + data)
        if device:
            tr.add_span("device", t0 + data, t0 + data + device)
        tr.add_span("host", t0 + data + device, now)
        self.tracer.finish(tr, end=now)

    def _record_step_progress(self) -> None:
        """Flight-recorder step event + watchdog heartbeat — one host
        append per optimizer step (training steps run at seconds
        cadence, so unlike serving decode this is not sampled)."""
        from deepspeed_tpu.telemetry import events as _ev
        _ev.record_event(_ev.STEP_END, source="train",
                         step=self.global_steps)
        if self.watchdog is not None:
            self.watchdog.notify_progress()

    def _count_overflow_skip(self) -> None:
        """The one registration site for the overflow-skip counter —
        all three skip paths (fused, offload, micro-batch step) share
        it so name/help cannot drift."""
        self.skipped_steps += 1
        self.telemetry.counter(
            "train_overflow_skips_total",
            help="fp16 overflow-skipped optimizer steps (dynamic loss "
                 "scale backed off)").inc()

    def _observe_numerics(self, numer, loss) -> None:
        """Feed one step's in-graph block arrays to the numerics watch —
        the single device→host transfer numerics costs per step (the
        loss float doubles as the spike-detector sample). Guarded:
        observability must never kill a training step."""
        try:
            self.numerics.observe(
                step=self.global_steps, loss=float(loss),
                grad_norms=numer.get("grad_norm"),
                param_norms=numer.get("param_norm"),
                update_norms=numer.get("update_norm"),
                nonfinite=numer.get("nonfinite"))
        except Exception as e:  # noqa: BLE001
            logger.warning(f"numerics observe failed: {e}")

    # ------------------------------------------------------------------
    # MoQ (runtime/quantize.py; reference _take_model_step engine.py:2078)
    # ------------------------------------------------------------------
    def _moq_boundary(self, batch, overflow: bool,
                      step_zero: bool = False) -> None:
        """Advance the MoQ schedule and quantize the compute params.
        Mirrors the reference boundary block (engine.py:2146-2166):
        eigenvalue recompute every ``gas_boundary_resolution`` boundaries
        while a precision switch is still pending, then quantize."""
        if step_zero:
            self._moq_step0_done = True
        if self.global_steps < self.quantizer.cfg.schedule_offset:
            # full-precision warmup (shared_parameters.schedule_offset —
            # the compression scheduler gates the reference the same way)
            return
        self._gas_boundary_ctr += 1
        factors = None
        ev_enabled = self.eigenvalue is not None
        if (ev_enabled and not step_zero and
                self._gas_boundary_ctr %
                self.config.eigenvalue.gas_boundary_resolution == 0 and
                self.quantizer.any_precision_switch()):
            self.block_eigenvalue = self._compute_block_eigenvalues(batch)
            from deepspeed_tpu.runtime.quantize import (
                eigen_factors_from_blocks)
            factors = eigen_factors_from_blocks(self.block_eigenvalue,
                                                self.quantizer.paths)
        self.quantizer.on_boundary(overflow, factors, ev_enabled)
        # Quantize even when the schedule skipped (fp16 overflow): the
        # step re-derived the compute params from the UNQUANTIZED master,
        # so declining to re-apply would leak full-precision weights into
        # the next forward. (The reference gets this for free: its
        # overflow path skips the master->fp16 copy, leaving the fp16
        # groups quantized from the previous boundary.)
        self._rng, qrng = jax.random.split(self._rng)
        self.state = self.state.replace(
            params=self.quantizer.apply(self.state.params, qrng))

    def _compute_block_eigenvalues(self, batch) -> Dict[str, float]:
        """Dominant |Hessian eigenvalue| per layer block via jvp power
        iteration on one micro-batch (reference Eigenvalue.compute_
        eigenvalue walks layer_name-matched modules). The per-block HVP is
        jitted ONCE (params/batch/tangent are arguments, not closure
        constants) — recomputes at later boundaries reuse the executable."""
        from deepspeed_tpu.runtime.quantize import layer_blocks, merge_block
        ev_cfg = self.config.eigenvalue
        params = self.state.params
        blocks = layer_blocks(params, ev_cfg.layer_name, ev_cfg.layer_num)
        micro = jax.tree.map(lambda x: x[:self.micro_batch_size], batch)
        rng = jax.random.PRNGKey(0)
        if not hasattr(self, "_eigen_hvp_cache"):
            self._eigen_hvp_cache = {}
        out: Dict[str, float] = {}
        loss_fn = self.loss_fn
        for i, (prefix, sub) in enumerate(blocks.items()):
            if prefix not in self._eigen_hvp_cache:
                def hvp_fn(full, s32, mb, v, _prefix=prefix):
                    def sub_loss(s):
                        merged = merge_block(full, _prefix, s)
                        out = loss_fn(merged, mb, jax.random.PRNGKey(0))
                        if isinstance(out, tuple):   # (loss, aux) models
                            out = out[0]
                        return out.astype(jnp.float32)
                    return jax.jvp(jax.grad(sub_loss), (s32,), (v,))[1]
                self._eigen_hvp_cache[prefix] = jax.jit(hvp_fn)
            hvp_jit = self._eigen_hvp_cache[prefix]
            sub32 = jax.tree.map(lambda x: x.astype(jnp.float32), sub)
            out[prefix] = self.eigenvalue.compute_eigenvalue(
                None, sub, jax.random.fold_in(rng, i),
                hvp=lambda v, _h=hvp_jit, _s=sub32: _h(params, _s, micro, v))
        if self.config.eigenvalue.verbose:
            log_dist(f"block eigenvalues: {out}", ranks=[0])
        return out

    def _maybe_swap_params_out(self):
        """NVMe param tier: after the step, spill the host-resident params
        to swap files and drop the host arrays (inter-step host RAM is
        bounded by the aio buffers, not the model)."""
        if self._param_swapper is not None:
            self.state = self.state.replace(
                params=self._param_swapper.swap_out(self.state.params))

    def _ensure_params_resident(self):
        """Restore NVMe-swapped params before any consumer that reads
        ``state.params`` outside train_batch (checkpointing, eval,
        micro-batch API)."""
        if self._param_swapper is not None and self._param_swapper.on_disk:
            self.state = self.state.replace(
                params=self._param_swapper.swap_in(
                    self._state_shardings.params))

    # -- DS-shaped micro-batch API -------------------------------------
    def _global_micro_batch(self, batch):
        """Multi-host: the micro-batch API follows the same per-process
        local-shard feeding convention as train_batch — assemble the
        global micro-batch before the jitted consumer."""
        if jax.process_count() > 1:
            from deepspeed_tpu.runtime.dataloader import assemble_global_batch
            batch = assemble_global_batch(batch, self.mesh)
        return batch

    def forward(self, batch):
        """Loss for one micro-batch (no grad) — engine.forward analog.
        In eval mode (``engine.eval()``) no rng is passed, so dropout and
        any other rng-gated stochasticity are off."""
        if self._grad_fn is None:
            self._build_grad_fn()
        self._ensure_params_resident()
        batch = self._global_micro_batch(batch)
        if not getattr(self, "_train_mode", True):
            return self._loss_only_fn(self.state.params, batch, None)
        self._rng, rng = jax.random.split(self._rng)
        return self._loss_only_fn(self.state.params, batch, rng)

    def backward(self, batch):
        """Accumulate gradients for one micro-batch (engine.backward analog;
        takes the micro-batch because reverse-mode AD needs the function).
        Collective-wise this matches DS with GAS: grads accumulate locally
        (sharded per policy) and the reduction happens where the sharding
        says, every micro-step, overlapped by XLA."""
        if self.host_opt is not None:
            raise RuntimeError(
                "the micro-batch backward()/step() API is not supported "
                "under ZeRO-Offload — use train_batch(), which fuses the "
                "host optimizer step")
        if self._grad_fn is None:
            self._build_grad_fn()
        self._ensure_params_resident()
        batch = self._global_micro_batch(batch)
        if self.quantizer is not None and self.global_steps == 0 and \
                self._micro_steps == 0 and \
                not getattr(self, "_moq_step0_done", False):
            # step-0 quantization on this path too (engine.py:1786);
            # one-shot — zero_grad() must not re-arm it
            self._moq_boundary(batch, overflow=False, step_zero=True)
        self._last_micro_batch = batch  # eigenvalue probe batch for step()
        self._rng, rng = jax.random.split(self._rng)
        loss, aux, grads = self._grad_fn(
            self.state.params, self.state.loss_scale.scale, batch, rng)
        if self._pending_grads is None:
            self._pending_grads = grads
        else:
            self._pending_grads = self._accum_fn(self._pending_grads, grads)
        self._pending_losses.append(loss)
        self._pending_aux.append(aux)
        self._micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._micro_steps % self.gas == 0

    def step(self):
        """Apply the optimizer using grads accumulated via ``backward`` —
        engine.step analog (engine.py:2124). No-op off-boundary, like the
        reference under GAS."""
        if not self.is_gradient_accumulation_boundary():
            self._last_skipped = True  # no-op step: nothing applied
            return None
        if self._pending_grads is None:
            raise RuntimeError("step() called with no accumulated gradients")
        if self._apply_fn is None:
            self._build_grad_fn()
        self.state, metrics = self._apply_fn(self.state, self._pending_grads)
        metrics["loss"] = sum(jnp.float32(l) for l in self._pending_losses) \
            / max(len(self._pending_losses), 1)
        if self._pending_aux and self._pending_aux[0]:
            n = len(self._pending_aux)
            for k in self._pending_aux[0]:
                metrics[k] = sum(jnp.float32(a[k])
                                 for a in self._pending_aux) / n
        self._pending_grads = None
        self._pending_losses = []
        self._pending_aux = []
        if self.quantizer is not None:
            # same boundary semantics as train_batch (_take_model_step
            # quantizes on the forward/backward/step path too)
            overflow = self.config.fp16.enabled and bool(metrics["skipped"])
            self._moq_boundary(self._last_micro_batch, overflow=overflow)
        self.global_steps += 1
        self._last_skipped = metrics.get("skipped")
        self._last_grad_norm = metrics.get("grad_norm")
        if self.config.fp16.enabled and bool(metrics["skipped"]):
            self._count_overflow_skip()
        return metrics

    def _build_grad_fn(self):
        loss_fn = self.loss_fn
        gas = self.gas
        fp16 = self.config.fp16.enabled
        mesh = self.mesh
        grad_spec = self.policy.spec_of(
            self.policy.grad_sharding(self.state.params))

        def constrain(tree):
            return jax.tree.map(
                lambda x, s: jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, s)), tree, grad_spec)

        @jax.jit
        def grad_fn(params, scale, mb, rng):
            def scaled(p):
                loss, aux = _split_loss_out(loss_fn(p, mb, rng))
                return (loss * scale / gas).astype(jnp.float32), (loss, aux)
            (_, (loss, aux)), grads = jax.value_and_grad(
                scaled, has_aux=True)(params)
            return loss, aux, constrain(cast_tree(grads, jnp.float32))

        @jax.jit
        def accum_fn(a, b):
            return constrain(jax.tree.map(jnp.add, a, b))

        @jax.jit
        def loss_only(params, mb, rng):
            return _split_loss_out(loss_fn(params, mb, rng))[0]

        optimizer = self.optimizer
        schedule = self.lr_scheduler
        mixed = self.mixed_precision
        clip = self.config.gradient_clipping
        compute_dtype = self.compute_dtype

        def apply_fn(state: TrainState, grads):
            scale = state.loss_scale.scale if fp16 else jnp.float32(1.0)
            grads = jax.tree.map(lambda g: g / scale, grads)
            finite = grads_finite(grads) if fp16 else jnp.bool_(True)
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
            if clip > 0.0:
                coef = clip_coef(clip, gnorm)
                grads = jax.tree.map(lambda g: g * coef, grads)
            lr = schedule(state.step)
            master = state.master if mixed else state.params

            def do(operand):
                g, m, o = operand
                updates, new_opt = optimizer.update(g, o, m, lr)
                return jax.tree.map(jnp.add, m, updates), new_opt

            def skip(operand):
                _, m, o = operand
                return m, o

            if fp16:
                new_master, new_opt = jax.lax.cond(
                    finite, do, skip, (grads, master, state.opt_state))
            else:
                new_master, new_opt = do((grads, master, state.opt_state))
            new_params = cast_tree(new_master, compute_dtype) if mixed \
                else new_master
            return state.replace(
                step=state.step + 1, params=new_params,
                master=new_master if mixed else None, opt_state=new_opt,
                loss_scale=update_loss_scale(state.loss_scale, finite)), \
                {"grad_norm": gnorm, "lr": lr, "loss_scale": scale,
                 "skipped": jnp.logical_not(finite)}

        self._grad_fn = grad_fn
        self._accum_fn = accum_fn
        self._loss_only_fn = loss_only
        self._apply_fn = jax.jit(
            apply_fn,
            in_shardings=(self._state_shardings, None),
            out_shardings=(self._state_shardings, None),
            donate_argnums=(0,))

    # ------------------------------------------------------------------
    # introspection / DS API parity
    # ------------------------------------------------------------------
    @property
    def params(self):
        return self.state.params

    def get_lr(self):
        return [float(self.lr_scheduler(self.state.step))]

    def get_loss_scale(self) -> float:
        """Current dynamic loss scale (fp16) or 1.0 (reference
        engine.cur_scale property)."""
        if self.host_opt is not None and self.config.fp16.enabled:
            return float(self._host_loss_scale.scale)
        if self.config.fp16.enabled:
            return float(self.state.loss_scale.scale)
        return 1.0

    @property
    def global_samples(self) -> int:
        """Samples consumed so far (reference engine.global_samples)."""
        return self.global_steps * self.train_batch_size

    def get_global_grad_norm(self):
        """Global gradient norm of the most recent step, or ``None``
        before the first one (reference ``engine.get_global_grad_norm``).

        Contract: the return value is always a host ``float`` (or
        ``None``) — never a device array. The device→host conversion
        happens HERE, once, when the caller asks; handing back the raw
        metrics array would instead trigger an implicit sync at whatever
        logging/formatting site touches it first, which is exactly the
        hidden-stall class the flight recorder exists to catch."""
        g = self._last_grad_norm
        if g is None:
            return None
        return float(g)

    def set_numerics_enabled(self, enabled: bool) -> None:
        """Toggle the in-graph numerics observatory at runtime
        (``telemetry.numerics_enabled`` sets the initial state). The
        flag is a static argument of the compiled step, so the toggle
        costs exactly one retrace — attributed by the compile watch as
        ``numerics_on: static:False -> static:True`` — and nothing when
        toggled back (both executables stay cached). The ZeRO-Offload
        gradient program bakes the flag as a closure constant instead
        and is rebuilt on toggle."""
        enabled = bool(enabled)
        if enabled and not self._telemetry_on:
            # telemetry.enabled=false isolates this engine from the
            # process scrape surface; the watch would still write the
            # process-global event ring and anomaly dump — refuse,
            # mirroring the init-time gate
            logger.warning(
                "numerics requires telemetry.enabled — ignoring")
            return
        if enabled and (self._onebit_axes or self._sparse_grad_axes):
            logger.warning(
                "numerics is not supported on the explicit-DP "
                "(1-bit/sparse) shard_map step — ignoring")
            return
        if enabled == self._numerics_on:
            return
        self._numerics_on = enabled
        if getattr(self, "_offload_grad_fn", None) is not None:
            self._offload_grad_fn = None

    def set_goodput_enabled(self, enabled: bool) -> None:
        """Toggle goodput accounting (host timers only — no retrace).
        The device bucket costs one ``block_until_ready`` per step while
        enabled (docs/observability.md)."""
        self.goodput.enabled = bool(enabled)

    def zero_optimization_stage(self) -> int:
        return self.zero_stage

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.micro_batch_size

    def gradient_accumulation_steps(self) -> int:
        return self.gas

    def set_train_batch_size(self, train_batch_size: int) -> None:
        """Adjust the global batch by changing the number of micro-batches
        (GAS); the micro-batch size is unchanged (reference
        ``set_train_batch_size``, engine.py:444). The fused step bakes the
        GAS scan length in, so the compiled executables are invalidated —
        the next ``train_batch`` recompiles with the new schedule."""
        dp = get_data_parallel_world_size(self.mesh)
        if train_batch_size % (self.micro_batch_size * dp) != 0:
            raise ValueError(
                f"train_batch_size {train_batch_size} is not divisible "
                f"by micro_batch*dp = {self.micro_batch_size}*{dp}")
        self.gas = train_batch_size // (self.micro_batch_size * dp)
        self.train_batch_size = train_batch_size
        self.config.gradient_accumulation_steps = self.gas
        self.config.train_batch_size = train_batch_size
        self._step_fn = None
        self._grad_fn = None
        if getattr(self, "_offload_grad_fn", None) is not None:
            self._offload_grad_fn = None
        log_dist(f"train_batch_size -> {train_batch_size} "
                 f"(gas={self.gas})", ranks=[0])

    # ------------------------------------------------------------------
    # DS engine API compat: the reference exposes a large family of
    # config accessors and mode toggles on the engine object
    # (engine.py:612-1030 properties, :1734 train/eval, :2321 get_mom).
    # Thin and honest — each returns the live config/engine state.
    # ------------------------------------------------------------------
    def get_batch_info(self):
        """(train_batch_size, micro_batch_size, gas) — engine.py:428."""
        return self.train_batch_size, self.micro_batch_size, self.gas

    def optimizer_name(self):
        return self.config.optimizer.type if self.config.optimizer else None

    def optimizer_params(self):
        return dict(self.config.optimizer.params) \
            if self.config.optimizer else None

    def scheduler_name(self):
        return self.config.scheduler.type if self.config.scheduler else None

    def scheduler_params(self):
        return dict(self.config.scheduler.params) \
            if self.config.scheduler else None

    def get_mom(self):
        """Momentum (SGD/RMSprop) or betas (Adam family) — engine.py:2321."""
        params = self.optimizer_params() or {}
        if (self.optimizer_name() or "").lower() in ("sgd", "rmsprop"):
            return [params.get("momentum", 0.0)]
        return [tuple(params.get("betas", (0.9, 0.999)))]

    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def loss_scale(self) -> float:
        return self.get_loss_scale()

    def dynamic_loss_scale(self) -> bool:
        return (self.config.fp16.enabled and
                self.config.fp16.dynamic_loss_scale)

    def steps_per_print(self) -> int:
        return self.config.steps_per_print

    def wall_clock_breakdown(self) -> bool:
        return self.config.wall_clock_breakdown

    def memory_breakdown(self) -> bool:
        return self.config.memory_breakdown

    def communication_data_type(self):
        return self.config.communication_data_type

    def zero_optimization(self) -> bool:
        return self.zero_stage > 0

    def zero_cpu_offload(self) -> bool:
        return self._offload_cfg is not None

    def zero_offload_optimizer(self):
        return self._offload_cfg

    def zero_offload_param(self):
        return self._param_offload_cfg

    def sparse_gradients_enabled(self) -> bool:
        return self.config.sparse_gradients

    def curriculum_enabled(self) -> bool:
        return self.curriculum_scheduler is not None

    def train(self, mode: bool = True):
        """Training/eval mode toggle (engine.py:1734): in eval mode
        ``forward`` runs without an rng, so dropout is disabled."""
        self._train_mode = bool(mode)

    def eval(self):
        self.train(False)

    def zero_grad(self) -> None:
        """Drop gradients accumulated via ``backward`` (the reference's
        hook-based zero_grad; here the pending accumulator)."""
        self._pending_grads = None
        self._pending_losses = []
        self._pending_aux = []
        # roll the boundary counter back to the last boundary (not to 0 —
        # a monotonic counter must not re-arm one-shot step-0 hooks)
        self._micro_steps -= self._micro_steps % self.gas

    def was_step_applied(self) -> bool:
        """True if the latest step updated parameters (engine.py:1660);
        False after an fp16 overflow skip or off-boundary step(). The
        skipped flag stays on device until asked for (no per-step sync)."""
        skipped = getattr(self, "_last_skipped", None)
        if skipped is None:
            return False
        return not bool(skipped)

    def module_state_dict(self):
        """Module weights as a flat {path: numpy} dict (engine.py
        module_state_dict analog)."""
        self._ensure_params_resident()
        from deepspeed_tpu.utils.tree import flatten_with_names
        params = self.state.params
        if jax.process_count() > 1:
            # cross-process sharded leaves are not addressable from one
            # process; replicate first (every process then holds full
            # values, like the TP checksum in tests/launcher_worker.py)
            rep = jax.tree.map(
                lambda _: NamedSharding(self.mesh, P()), params)
            params = jax.jit(lambda t: t, out_shardings=rep)(params)
        return {k: np.asarray(v) for k, v in
                flatten_with_names(params).items()}

    def load_module_state_dict(self, state_dict) -> None:
        """Load module weights only (engine load_module_state_dict):
        optimizer state is untouched, the fp32 master resyncs from the
        loaded weights (same contract as load_checkpoint(
        load_module_only=True))."""
        from deepspeed_tpu.utils.tree import flatten_with_names
        cur = flatten_with_names(self.state.params)
        missing = set(cur) - set(state_dict)
        if missing:
            raise KeyError(f"state_dict missing params: {sorted(missing)[:5]}")
        leaves, treedef = jax.tree_util.tree_flatten(self.state.params)
        names = list(flatten_with_names(self.state.params))
        new = [jnp.asarray(state_dict[n], dtype=l.dtype)
               for n, l in zip(names, leaves)]
        params = jax.device_put(jax.tree_util.tree_unflatten(treedef, new),
                                self._state_shardings.params)
        self.state = self.state.replace(params=params)
        if self.mixed_precision and self.state.master is not None:
            self.state = self.state.replace(master=jax.device_put(
                cast_tree(params, jnp.float32),
                self._state_shardings.master))

    def deepspeed_io(self, dataset, batch_size=None, route=None,
                     pin_memory=None, data_sampler=None, collate_fn=None,
                     num_local_io_workers=None):
        """Build a loader over ``dataset`` (engine.deepspeed_io analog,
        engine.py:1506 — the Megatron integration entry point). torch-
        specific knobs (pin_memory, worker counts, samplers) are accepted
        and ignored; sampling is the loader's seeded shuffle with
        per-process sharding."""
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(
            dataset, batch_size=batch_size or self.train_batch_size,
            collate_fn=collate_fn, seed=self.config.seed)

    def destroy(self) -> None:
        """Release compiled executables, pending state, monitor file
        handles, the telemetry endpoint, and the flight-recorder
        watchdog/memory registrations (engine.destroy). Joins an
        in-flight async checkpoint finalize FIRST — a teardown must
        never abandon a checkpoint mid-publication, and a finalize that
        failed must surface here rather than die with the engine."""
        from deepspeed_tpu.runtime.checkpointing import (
            _join_pending_finalize)
        ckpt_err = None
        try:
            _join_pending_finalize(self)
        except RuntimeError as e:
            # surface AFTER the full teardown below — raising here would
            # leak the scrape port, monitor handles, and watchdog thread
            ckpt_err = e
        finally:
            ce = getattr(self, "_ckpt_engine", None)
            if ce is not None:
                self._ckpt_engine = None
                try:
                    ce.close()
                except Exception as e:  # noqa: BLE001
                    # close() performing its own final wait can raise
                    # the same stashed failure — it must not abort the
                    # teardown below (port/monitor/watchdog would leak)
                    # or shadow the join's error
                    if ckpt_err is None:
                        ckpt_err = RuntimeError(
                            f"checkpoint engine close failed: {e!r}")
        self.telemetry.remove_collector(self._movement_collector)
        self._step_fn = None
        self._grad_fn = None
        self._apply_fn = None
        self._offload_grad_fn = None
        self.zero_grad()
        if self.monitor is not None:
            self.monitor.close()
        if self._telemetry_http is not None:
            self._telemetry_http.close()
            self._telemetry_http = None
        if getattr(self, "_flight", None) is not None:
            self._flight.close()
            self.watchdog = None
        if getattr(self, "numerics", None) is not None:
            from deepspeed_tpu.telemetry.numerics import (
                unregister_numerics_watch)
            unregister_numerics_watch("train", self.numerics)
        if ckpt_err is not None:
            raise ckpt_err

    def fp32_master_params(self):
        """Consolidated fp32 weights (analog of
        _zero3_consolidated_16bit_state_dict / zero_to_fp32, engine.py:3396):
        shardings make this a simple device_get of global arrays."""
        self._ensure_params_resident()
        master = self.state.master if self.mixed_precision else self.state.params
        return jax.device_get(cast_tree(master, jnp.float32))

    def save_16bit_model(self, save_dir,
                         save_filename: str = "model.safetensors") -> str:
        """Export the compute-precision weights as ONE flat file
        (reference ``save_16bit_model``, engine.py:3466 — its
        'pytorch_model.bin' for downstream serving/upload; here
        safetensors with dotted names, loadable by
        ``module_inject.state_dict_loader`` and HF tooling)."""
        import os

        from safetensors.numpy import save_file
        self._ensure_params_resident()
        flat = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.state.params)[0]:
            name = ".".join(str(getattr(p, "key", getattr(p, "idx", p)))
                            for p in path)
            flat[name] = np.asarray(jax.device_get(leaf))
        os.makedirs(save_dir, exist_ok=True)
        out = os.path.join(save_dir, save_filename)
        save_file(flat, out)
        log_dist(f"saved 16-bit model: {out} ({len(flat)} tensors)",
                 ranks=[0])
        return out

    # ------------------------------------------------------------------
    # checkpointing (full impl in runtime/checkpointing.py)
    # ------------------------------------------------------------------
    def save_checkpoint(self, save_dir, tag=None, client_state=None):
        from deepspeed_tpu.runtime.checkpointing import save_checkpoint
        self._ensure_params_resident()
        prev_state = None
        opt = self.state.opt_state
        if self._onebit_axes and hasattr(opt, "worker_error"):
            # Under the compressed-DP shard_map step the error-feedback
            # buffers are physically PER-WORKER even though their out_spec
            # claims replication (check_vma=False) — host materialization
            # would silently persist only worker 0's residuals and feed
            # them to every worker on restore. They are transient
            # compensation, so checkpoint zeros instead: the cost is one
            # uncompensated exchange after resume.
            prev_state = self.state
            self.state = self.state.replace(opt_state=opt.replace(
                worker_error=jax.tree.map(jnp.zeros_like,
                                          opt.worker_error),
                server_error=jax.tree.map(jnp.zeros_like,
                                          opt.server_error)))
        try:
            out = save_checkpoint(self, save_dir, tag=tag,
                                  client_state=client_state or {})
            from deepspeed_tpu.telemetry import events as _ev
            _ev.record_event(_ev.CHECKPOINT, dir=str(save_dir),
                             tag=str(tag), step=self.global_steps)
            return out
        finally:
            if prev_state is not None:
                self.state = prev_state

    def load_checkpoint(self, load_dir, tag=None, **kwargs):
        from deepspeed_tpu.runtime.checkpointing import load_checkpoint
        return load_checkpoint(self, load_dir, tag=tag, **kwargs)

    # ------------------------------------------------------------------
    # misc plumbing
    # ------------------------------------------------------------------
    def _build_dataloader(self, training_data, collate_fn=None):
        if training_data is None:
            return None
        from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader
        return DeepSpeedDataLoader(training_data,
                                   batch_size=self.train_batch_size,
                                   collate_fn=collate_fn,
                                   seed=self.config.seed)

    def _build_monitor(self):
        try:
            from deepspeed_tpu.monitor.monitor import MonitorMaster
            return MonitorMaster(self.config)
        except Exception:
            return None

    def _write_monitor_events(self, metrics):
        """Reference event parity (runtime/engine.py:1946-1954): loss, lr,
        and — when present — the dynamic loss scale and global grad norm.
        Fans out to every live sink: the telemetry-registry sink (always,
        unless telemetry.enabled=false) and MonitorMaster (when a backend
        is configured)."""
        samples = self.global_steps * self.train_batch_size
        events = [("Train/Samples/train_loss", float(metrics["loss"]),
                   samples),
                  ("Train/Samples/lr", float(metrics["lr"]), samples)]
        if self.config.fp16.enabled and "loss_scale" in metrics:
            events.append(("Train/Samples/loss_scale",
                           float(metrics["loss_scale"]), samples))
        if "grad_norm" in metrics and metrics["grad_norm"] is not None:
            events.append(("Train/Samples/grad_norm",
                           float(metrics["grad_norm"]), samples))
        for sink in (self._registry_sink, self.monitor):
            if sink is not None and sink.enabled:
                sink.write_events(events)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               config=None,
               config_params=None,
               loss_fn=None,
               tp_specs=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               rng=None,
               sparse_grad_paths=None):
    """``deepspeed.initialize`` analog (deepspeed/__init__.py:52).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` like
    the reference. ``model`` may be any object exposing
    ``loss_fn(params, batch, rng) -> scalar``; alternatively pass ``loss_fn``
    directly. ``model_parameters`` is the initial fp32 parameter pytree.
    """
    if dist_init_required is None or dist_init_required:
        comm.init_distributed()
    cfg = config if isinstance(config, DeepSpeedConfig) else DeepSpeedConfig(
        config if config is not None else (config_params or {}))
    # PipelineModule routes to the 1F1B PipelineEngine, like the reference
    # (deepspeed/__init__.py:124-148 chooses PipelineEngine by model type)
    from deepspeed_tpu.parallel.pipe.module import PipelineModule
    if isinstance(model, PipelineModule):
        from deepspeed_tpu.parallel.pipe.executor import PipelineEngine
        if model_parameters is None:
            raise ValueError("model_parameters: one param tree per layer")
        if training_data is not None or collate_fn is not None:
            raise NotImplementedError(
                "training_data/collate_fn are not wired into the pipeline "
                "path yet — iterate your dataloader and call "
                "engine.train_batch(inputs, labels) directly")
        if tp_specs is not None:
            raise NotImplementedError(
                "tp_specs are not applied on the pipeline path yet (stage "
                "params are replicated within each stage sub-mesh)")
        mesh = mesh or build_mesh(cfg.mesh)
        set_global_mesh(mesh)
        # the batch triad holds on this path too: the number of pipeline
        # microbatches IS the gradient-accumulation factor
        cfg.resolve_batch_config(get_data_parallel_world_size(mesh))
        micro = cfg.gradient_accumulation_steps
        if optimizer is None:
            import optax
            oc = cfg.optimizer
            otype = (oc.type if oc else "AdamW").lower()
            p = dict(oc.params) if oc else {}
            lr = (lr_scheduler if callable(lr_scheduler)
                  else build_schedule(cfg.scheduler, p)
                  if cfg.scheduler else p.get("lr", 1e-3))
            if otype in ("adam", "adamw", "fusedadam"):
                b1, b2 = p.get("betas", (0.9, 0.999))
                optimizer = optax.adamw(
                    lr, b1=b1, b2=b2, eps=p.get("eps", 1e-8),
                    weight_decay=p.get("weight_decay",
                                       0.0 if otype == "adam" else 0.01))
            elif otype == "sgd":
                optimizer = optax.sgd(lr, momentum=p.get("momentum", 0.0))
            else:
                raise NotImplementedError(
                    f"pipeline path supports Adam/AdamW/SGD configs (got "
                    f"{otype!r}); pass an optax GradientTransformation as "
                    f"optimizer= for anything else")
        engine = PipelineEngine(model, list(model_parameters), optimizer,
                                micro_batches=micro, loss_fn=loss_fn,
                                mesh=mesh,
                                zero_stage=cfg.zero_config.stage,
                                telemetry=getattr(cfg, "telemetry", None))
        return engine, optimizer, None, lr_scheduler
    if loss_fn is None:
        if model is None or not hasattr(model, "loss_fn"):
            raise ValueError(
                "provide loss_fn or a model exposing .loss_fn(params, batch, rng)")
        loss_fn = model.loss_fn
    if model_parameters is None:
        raise ValueError("model_parameters (initial param pytree) is required")
    if tp_specs is None and model is not None:
        tp_specs = getattr(model, "tp_specs", None)
        if callable(tp_specs):
            tp_specs = tp_specs()
    if mesh is None:
        mesh = build_mesh(cfg.mesh)
    engine = DeepSpeedEngine(loss_fn=loss_fn, params=model_parameters,
                             config=cfg, mesh=mesh, optimizer=optimizer,
                             lr_scheduler=lr_scheduler, tp_specs=tp_specs,
                             training_data=training_data, rng=rng,
                             model_handles_param_offload=bool(
                                 getattr(model, "handles_param_offload",
                                         False)),
                             sparse_grad_paths=(
                                 sparse_grad_paths if sparse_grad_paths
                                 is not None else getattr(
                                     model, "sparse_grad_paths", None)))
    if engine._param_offload_cfg is not None and \
            engine._model_fetches_params:
        setter = getattr(model, "set_param_fetch_shardings", None)
        if callable(setter):
            # None disables the model's in-jit fetches on backends where
            # the engine stages params eagerly instead (non-TPU SPMD)
            setter(jax.tree.map(lambda s: s.with_memory_kind("device"),
                                engine._device_param_shardings)
                   if engine._param_offload_in_jit else None)
    return engine, engine.optimizer, engine.training_dataloader, \
        engine.lr_scheduler
