"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Capability parity with DeepSpeed v0.8.0 (reference: ``deepspeed/__init__.py``),
re-designed for JAX/XLA/Pallas on TPU meshes. Public surface mirrors the
reference where it makes sense:

* :func:`initialize` — build a training engine (deepspeed/__init__.py:52)
* :func:`init_inference` — build an inference engine (:233)
* :mod:`deepspeed_tpu.comm` — collective facade (deepspeed/comm)
* :func:`add_config_arguments` — argparse helper (:210)
"""
from deepspeed_tpu.version import __version__, git_branch, git_hash
from deepspeed_tpu import comm
# reference namespace parity: deepspeed.zero.Init, deepspeed.pipe.*,
# deepspeed.moe.*, deepspeed.module_inject.* resolve without an explicit
# submodule import (deepspeed/__init__.py imports these eagerly)
from deepspeed_tpu import zero, pipe, moe, module_inject  # noqa: F401
# deepspeed.checkpointing analog (activation checkpointing, NOT model
# save/load — that lives on the engine): reference runtime/
# activation_checkpointing/checkpointing.py
from deepspeed_tpu.runtime import activation_checkpointing as checkpointing
from deepspeed_tpu.config.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine, TrainState, initialize
from deepspeed_tpu.comm.mesh import MeshConfig, build_mesh
from deepspeed_tpu.utils.logging import logger
from deepspeed_tpu.telemetry.compile_watch import install_phase_listeners

# compile phases by function name (telemetry/compile_watch.py
# phase_totals): from the first program this process compiles
install_phase_listeners()


def init_distributed(dist_backend="xla", **kwargs):
    """deepspeed.init_distributed analog (deepspeed/__init__.py:29)."""
    comm.init_distributed(dist_backend=dist_backend, **kwargs)


def init_inference(model=None, config=None, **kwargs):
    """deepspeed.init_inference analog (deepspeed/__init__.py:233).

    ``model`` may be a live HF torch model, an
    ``(InferenceTransformerConfig, params)`` pair, or a **path to an HF
    checkpoint directory** — the file-based route loads safetensors /
    sharded / torch-pickle weights straight into the fused tree without
    instantiating a torch model (reference ``state_dict_factory.py`` /
    ``module_inject/load_checkpoint.py``)."""
    try:
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    except ImportError as e:
        raise NotImplementedError(
            "the inference engine is not available in this build") from e
    if config is None:
        config = {}
    if isinstance(config, dict):
        merged = dict(config)
        merged.update(kwargs)
        config = DeepSpeedInferenceConfig(**merged)
    if config.checkpoint is not None:
        # reference init_inference(checkpoint=..., base_dir=...): load
        # from files with no model object (inference/engine.py:268)
        if model is not None:
            raise ValueError(
                "pass ONE weight source: either a model/path argument or "
                "config.checkpoint — with both, which weights serve "
                "would be ambiguous (the reference overwrites the live "
                "module from the checkpoint; here load from the "
                "checkpoint alone)")
        import os as _os
        ckpt = config.checkpoint
        if isinstance(ckpt, dict):
            ckpt = ckpt.get("checkpoint") or ckpt.get("path") or \
                ckpt.get("checkpoints")
        if isinstance(ckpt, (list, tuple)):
            if len(ckpt) != 1:
                raise NotImplementedError(
                    "multi-file 'checkpoints' lists are model-parallel "
                    "shards — point at the directory instead (Megatron "
                    "mp_rank_* layouts merge automatically)")
            ckpt = ckpt[0]
        if not isinstance(ckpt, str):
            raise ValueError(
                "config.checkpoint must be a path (or a dict with a "
                f"'checkpoint'/'path' entry), got {config.checkpoint!r}")
        model = _os.path.join(config.base_dir, ckpt) if config.base_dir \
            else ckpt
    if isinstance(model, str):
        from deepspeed_tpu.module_inject.state_dict_loader import (
            load_inference_checkpoint)
        import jax.numpy as _jnp
        load_dtype = (_jnp.bfloat16 if config.jnp_dtype == _jnp.int8
                      else config.jnp_dtype)
        model = load_inference_checkpoint(model, dtype=load_dtype)
    return InferenceEngine(model, config)


def default_inference_config():
    """Default inference configuration dict (deepspeed/__init__.py:226)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().dict()


def add_config_arguments(parser):
    """Augment an argparse parser with DS flags (deepspeed/__init__.py:210)."""
    group = parser.add_argument_group("DeepSpeed-TPU",
                                      "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed-TPU (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed-TPU json configuration")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Discover ranks via MPI environment")
    return parser
