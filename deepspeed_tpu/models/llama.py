"""LLaMA model family (flax) — modern decoder training, TPU-first.

The reference serves the LLaMA family through inference policy injection
(deepspeed/module_inject — our ``module_inject/policies.py`` carries the
LLaMA/Mistral policies) and trains it through the Megatron-DeepSpeed
stack. This module is the training-side counterpart of those policies: a
functional flax decoder with the LLaMA architecture — RMSNorm, rotary
position embeddings, grouped-query attention, SwiGLU MLP, no biases —
matching HuggingFace ``LlamaForCausalLM`` numerics (the de-facto weight
layout; pinned by tests/test_llama_model.py against the torch model).

TPU-first choices mirror models/gpt2.py: bf16 matmuls with fp32-stat
norms, the Pallas flash-attention path with its remat-visible
``flash_attn_out`` tag, Megatron-style tensor-parallel PartitionSpecs
(column-parallel q/k/v/gate/up, row-parallel o/down, vocab-parallel
embedding), and ring/Ulysses sequence parallelism expressed as global-view
SPMD (positions are global under jit, so RoPE needs no per-shard offset
bookkeeping).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import DATA_AXES
from deepspeed_tpu.comm.mesh import seq_axis_active as _seq_axis_active
from deepspeed_tpu.ops.int8_training import (lm_logits,
                                              maybe_switchback)
from deepspeed_tpu.utils.jit import instance_cached_jit
from deepspeed_tpu.utils.sharding import maybe_constrain as _maybe_constrain


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_positions: int = 2048
    n_embd: int = 2048
    n_layer: int = 16
    n_head: int = 16
    n_kv_head: int = 16            # < n_head => grouped-query attention
    intermediate_size: int = 5504  # SwiGLU hidden (~8/3 * n_embd rounded)
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    use_flash_attention: bool = True
    # flash tile-size override (0 = kernel default 256)
    flash_block: int = 0
    sequence_parallel: bool = False
    sp_mode: str = "ring"
    # Mixtral-style MoE: num_experts > 0 replaces the SwiGLU FFN of the
    # layers in ``moe_layers`` (None → EVERY layer, the Mixtral layout)
    # with top-k gated SwiGLU experts sharded over the data/fsdp axes.
    # Gate aux loss folds into loss_fn with moe_aux_weight.
    num_experts: int = 0
    moe_layers: Optional[tuple] = None
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # SwitchBack int8 projections (ops/int8_training.py; see GPT2Config)
    int8_training: bool = False

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head={self.n_head} must be divisible by "
                             f"n_kv_head={self.n_kv_head}")
        if self.sp_mode not in ("ring", "ulysses"):
            raise ValueError(f"sp_mode must be 'ring' or 'ulysses', got "
                             f"{self.sp_mode!r}")
        if self.num_experts > 0:
            layers = self.moe_layer_set
            if not layers:
                raise ValueError("num_experts > 0 needs at least one MoE "
                                 "layer (moe_layers is empty)")
            bad = sorted(i for i in layers if not 0 <= i < self.n_layer)
            if bad:
                raise ValueError(f"moe_layers {bad} out of range for "
                                 f"n_layer={self.n_layer}")

    @property
    def moe_layer_set(self) -> frozenset:
        if self.num_experts <= 0:
            return frozenset()
        if self.moe_layers is not None:
            return frozenset(self.moe_layers)
        return frozenset(range(self.n_layer))

    @property
    def head_dim(self) -> int:
        return self.n_embd // self.n_head


PRESETS: Dict[str, dict] = {
    # HF config shapes for the common ladder
    "llama-tiny": dict(vocab_size=512, n_positions=256, n_embd=128,
                       n_layer=2, n_head=4, n_kv_head=2,
                       intermediate_size=352),
    "llama-1b": dict(n_embd=2048, n_layer=16, n_head=16, n_kv_head=16,
                     intermediate_size=5504),
    "llama-3b": dict(n_embd=2560, n_layer=26, n_head=20, n_kv_head=20,
                     intermediate_size=6912),
    "llama-7b": dict(n_embd=4096, n_layer=32, n_head=32, n_kv_head=32,
                     intermediate_size=11008, n_positions=4096),
    # mistral-style GQA variant
    "llama-7b-gqa": dict(n_embd=4096, n_layer=32, n_head=32, n_kv_head=8,
                         intermediate_size=14336, n_positions=4096),
    # Mixtral layout: GQA + top-2 gated-SwiGLU experts in EVERY layer
    "mixtral-tiny": dict(vocab_size=512, n_positions=256, n_embd=128,
                         n_layer=2, n_head=4, n_kv_head=2,
                         intermediate_size=352, num_experts=4,
                         moe_capacity_factor=2.0),
    "mixtral-8x7b": dict(n_embd=4096, n_layer=32, n_head=32, n_kv_head=8,
                         intermediate_size=14336, n_positions=4096,
                         num_experts=8, moe_top_k=2),
}


def config_for(name: str, **overrides) -> LlamaConfig:
    if name not in PRESETS:
        raise ValueError(f"unknown preset {name!r}: {sorted(PRESETS)}")
    return LlamaConfig(**{**PRESETS[name], **overrides})


def _rms_norm(x, weight, eps):
    """RMSNorm with fp32 statistics (HF LlamaRMSNorm semantics: variance
    in fp32, scaled output cast back to the input dtype)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(dt) * weight.astype(dt)


def _rope(q, k, positions, theta):
    """HF rotate-half rotary embedding. q/k ``[B, T, H, D]``, positions
    ``[T]`` (global under jit — sequence sharding slices them)."""
    D = q.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]  # [T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None]

    def rot(x):
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([-x2, x1], -1)

    qf, kf = q.astype(jnp.float32), k.astype(jnp.float32)
    q_out = qf * cos + rot(qf) * sin
    k_out = kf * cos + rot(kf) * sin
    return q_out.astype(q.dtype), k_out.astype(k.dtype)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, T, C = x.shape
        H, HKV, D = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        dense = lambda feat, name: nn.Dense(  # noqa: E731
            feat, use_bias=False, dtype=cfg.dtype, name=name,
            dot_general=maybe_switchback(cfg.int8_training))
        q = dense(H * D, "wq")(x).reshape(B, T, H, D)
        k = dense(HKV * D, "wk")(x).reshape(B, T, HKV, D)
        v = dense(HKV * D, "wv")(x).reshape(B, T, HKV, D)
        q, k = _rope(q, k, jnp.arange(T), cfg.rope_theta)
        sp_active = cfg.sequence_parallel and _seq_axis_active()
        if sp_active:
            from deepspeed_tpu.comm.mesh import get_global_mesh
        if HKV != H and sp_active and cfg.sp_mode == "ulysses":
            if HKV % get_global_mesh().shape["seq"]:
                # Ulysses' head all-to-all only preserves GQA group
                # alignment when kv heads split evenly across the seq
                # axis; otherwise fall back to expanded k/v. Ring, flash,
                # and the reference path always consume unexpanded k/v.
                k = jnp.repeat(k, H // HKV, axis=2)
                v = jnp.repeat(v, H // HKV, axis=2)

        if sp_active:
            if cfg.sp_mode == "ulysses":
                from deepspeed_tpu.ops.ulysses_attention import (
                    ulysses_self_attention)
                y = ulysses_self_attention(q, k, v, get_global_mesh(),
                                           block=cfg.flash_block)
            else:
                from deepspeed_tpu.ops.ring_attention import (
                    ring_self_attention)
                y = ring_self_attention(q, k, v, get_global_mesh())
        elif cfg.use_flash_attention:
            from deepspeed_tpu.ops.attention import causal_attention
            y = causal_attention(q, k, v, block_q=cfg.flash_block,
                                 block_k=cfg.flash_block)
        else:
            from deepspeed_tpu.ops.attention import (
                causal_attention_reference)
            y = causal_attention_reference(q, k, v)
        return dense(C, "wo")(y.reshape(B, T, H * D))


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda feat, name: nn.Dense(  # noqa: E731
            feat, use_bias=False, dtype=cfg.dtype, name=name,
            dot_general=maybe_switchback(cfg.int8_training))
        g = dense(cfg.intermediate_size, "gate")(x)
        u = dense(cfg.intermediate_size, "up")(x)
        return dense(cfg.n_embd, "down")(jax.nn.silu(g) * u)


class LlamaBlock(nn.Module):
    """Decoder block. With ``moe=True`` (Mixtral layout) the FFN slot
    holds top-k gated-SwiGLU experts and ``__call__`` returns
    ``(x, l_aux)`` — one class for both so the norm/attention/residual
    structure cannot drift. ``train`` is static under remat."""
    config: LlamaConfig
    moe: bool = False

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.config
        ln1 = self.param("ln_attn", nn.initializers.ones, (cfg.n_embd,),
                         jnp.float32)
        ln2 = self.param("ln_mlp", nn.initializers.ones, (cfg.n_embd,),
                         jnp.float32)
        x = x + LlamaAttention(cfg, name="attn")(
            _rms_norm(x, ln1, cfg.rms_eps))
        h = _rms_norm(x, ln2, cfg.rms_eps)
        if self.moe:
            from deepspeed_tpu.moe.layer import MoE
            B, T, C = x.shape
            y, l_aux, _ = MoE(hidden_size=C, num_experts=cfg.num_experts,
                              ffn_hidden_size=cfg.intermediate_size,
                              k=cfg.moe_top_k,
                              capacity_factor=cfg.moe_capacity_factor,
                              eval_capacity_factor=cfg.moe_capacity_factor,
                              min_capacity=4, dtype=cfg.dtype,
                              activation=jax.nn.silu, gated_experts=True,
                              int8_training=cfg.int8_training,
                              name="moe")(h.reshape(B * T, C), train=train)
            return x + y.reshape(B, T, C), l_aux
        return x + LlamaMLP(cfg, name="mlp")(h)


class Llama(nn.Module):
    """Causal LM trunk + head. ``__call__`` returns logits [B, T, V] —
    or ``(logits, l_aux_total)`` when the config has MoE layers."""
    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, train: bool = False):
        cfg = self.config
        B, T = input_ids.shape
        embed = self.param("embed", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.n_embd), jnp.float32)
        # gather rows then cast (same HBM-traffic reasoning as gpt2.py)
        x = embed[input_ids].astype(cfg.dtype)
        x = _maybe_constrain(x, P(DATA_AXES, "seq", None))

        block = LlamaBlock
        if cfg.remat:
            policy = jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "flash_attn_out"))
            # train is control flow (MoE capacity mode), not data — static
            # under the remat trace (argnum 2; the instance is 0)
            block = nn.remat(block, prevent_cse=False, policy=policy,
                             static_argnums=(2,))
        moe_set = cfg.moe_layer_set
        l_aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layer):
            if i in moe_set:
                x, l_aux = block(cfg, moe=True,
                                 name=f"layers_{i}")(x, train)
                l_aux_total = l_aux_total + l_aux.astype(jnp.float32)
            else:
                x = block(cfg, name=f"layers_{i}")(x, train)

        ln_f = self.param("ln_f", nn.initializers.ones, (cfg.n_embd,),
                          jnp.float32)
        x = _rms_norm(x, ln_f, cfg.rms_eps)
        if cfg.tie_embeddings:
            w_head = embed
        else:
            w_head = self.param("lm_head", nn.initializers.normal(0.02),
                                (cfg.vocab_size, cfg.n_embd), jnp.float32)
        logits = lm_logits(x, w_head.astype(cfg.dtype),
                           cfg.int8_training)
        if moe_set:
            return logits, l_aux_total
        return logits


class LlamaLMModel:
    """Engine-facing wrapper: init + loss_fn + tp_specs (the same contract
    GPT2LMModel satisfies, so every engine feature — ZeRO stages, offload,
    precision modes, curriculum — applies unchanged)."""

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.module = Llama(config)

    def init(self, rng, example_batch=None, batch_size: int = 2,
             seq_len=None):
        seq_len = seq_len or min(self.config.n_positions, 128)
        if example_batch is not None:
            ids = example_batch["input_ids"]
        else:
            ids = jnp.zeros((batch_size, seq_len), jnp.int32)
        # one compiled executable, wrapper cached on the instance
        # (utils/jit.py): no per-op dispatch round trips at init
        return instance_cached_jit(self, self.module.init)(
            rng, ids)["params"]

    def apply(self, params, input_ids, deterministic=True, rngs=None):
        """Returns logits; with MoE layers, ``(logits, l_aux_total)``."""
        return self.module.apply({"params": params}, input_ids,
                                 train=not deterministic, rngs=rngs)

    def loss_fn(self, params, batch, rng=None):
        cfg = self.config
        input_ids = batch["input_ids"]
        labels = batch.get("labels")
        rngs = ({"gating": jax.random.fold_in(rng, 1)}
                if (rng is not None and cfg.num_experts > 0) else None)
        out = self.apply(params, input_ids, deterministic=rng is None,
                         rngs=rngs)
        l_aux = None
        if cfg.num_experts > 0:
            logits, l_aux = out
        else:
            logits = out
        if labels is None:
            labels = input_ids[:, 1:]
            logits = logits[:, :-1]
        logits = logits.astype(jnp.float32)
        # lse - gold: no materialized [B, T, V] log-prob tensor
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None],
                                   axis=-1)[..., 0]
        nll = lse - gold
        mask = (labels >= 0) & (labels < self.config.vocab_size)
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
        if l_aux is not None:
            loss = loss + cfg.moe_aux_weight * l_aux
        return loss

    def tp_specs(self):
        """Megatron placement: q/k/v/gate/up column-parallel, o/down
        row-parallel, embedding + head vocab-parallel; MoE experts
        EP-sharded on their leading expert dim."""
        cfg = self.config
        block = {
            "ln_attn": P(), "ln_mlp": P(),
            "attn": {"wq": {"kernel": P(None, "tensor")},
                     "wk": {"kernel": P(None, "tensor")},
                     "wv": {"kernel": P(None, "tensor")},
                     "wo": {"kernel": P("tensor", None)}},
            "mlp": {"gate": {"kernel": P(None, "tensor")},
                    "up": {"kernel": P(None, "tensor")},
                    "down": {"kernel": P("tensor", None)}},
        }
        moe_set = cfg.moe_layer_set
        if moe_set:
            from deepspeed_tpu.moe.layer import MoE
            moe_block = dict(block)
            del moe_block["mlp"]
            moe_block["moe"] = MoE.tp_specs(gated=True)
        specs: dict = {"embed": P("tensor", None), "ln_f": P()}
        if not cfg.tie_embeddings:
            specs["lm_head"] = P("tensor", None)
        for i in range(cfg.n_layer):
            specs[f"layers_{i}"] = moe_block if i in moe_set else block
        return specs

    def param_count(self, params) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def flops_per_token(self) -> float:
        """~6 * N_active_params per token; MoE layers count top_k expert
        FFNs (active compute), like GPT2LMModel.flops_per_token."""
        cfg = self.config
        attn = (2 * cfg.n_embd * (cfg.n_head * cfg.head_dim)           # q,o
                + 2 * cfg.n_embd * (cfg.n_kv_head * cfg.head_dim))     # k,v
        ffn = 3 * cfg.n_embd * cfg.intermediate_size
        n_moe = len(cfg.moe_layer_set)
        n = (cfg.vocab_size * cfg.n_embd * (1 if cfg.tie_embeddings else 2)
             + cfg.n_layer * attn
             + (cfg.n_layer - n_moe) * ffn
             + n_moe * cfg.moe_top_k * ffn)
        return 6.0 * n


def params_from_hf(hf_state_dict, cfg: LlamaConfig):
    """Map a HuggingFace ``LlamaForCausalLM`` or ``MixtralForCausalLM``
    state dict onto this model's param tree (torch [out, in] kernels
    transpose to flax [in, out]). MoE layers read the Mixtral layout
    (``block_sparse_moe.gate`` + per-expert ``w1/w2/w3``, stacked on the
    leading expert dim: w1→wg gate, w3→wi up, w2→wo down). Accepts torch
    tensors or numpy arrays."""
    import numpy as np

    def raw(name):
        w = hf_state_dict[name]
        return np.asarray(w.detach().cpu().numpy()
                          if hasattr(w, "detach") else w, np.float32)

    def t(name, transpose=False):
        w = raw(name)
        return jnp.asarray(w.T if transpose else w)

    def moe_subtree(p):
        E = cfg.num_experts
        ex = f"{p}block_sparse_moe.experts."
        # torch per-expert [out, in] → stacked flax [E, in, out]
        stack = lambda w: jnp.asarray(np.stack(  # noqa: E731
            [raw(f"{ex}{e}.{w}.weight").T for e in range(E)]))
        return {
            "gate": {"wg": t(p + "block_sparse_moe.gate.weight", True)},
            "experts": {"wg": stack("w1"), "wo": stack("w2"),
                        "wi": stack("w3")},
        }

    moe_set = cfg.moe_layer_set
    params: dict = {"embed": t("model.embed_tokens.weight"),
                    "ln_f": t("model.norm.weight")}
    if not cfg.tie_embeddings:
        params["lm_head"] = t("lm_head.weight")
    for i in range(cfg.n_layer):
        p = f"model.layers.{i}."
        layer = {
            "ln_attn": t(p + "input_layernorm.weight"),
            "ln_mlp": t(p + "post_attention_layernorm.weight"),
            "attn": {
                "wq": {"kernel": t(p + "self_attn.q_proj.weight", True)},
                "wk": {"kernel": t(p + "self_attn.k_proj.weight", True)},
                "wv": {"kernel": t(p + "self_attn.v_proj.weight", True)},
                "wo": {"kernel": t(p + "self_attn.o_proj.weight", True)},
            },
        }
        if i in moe_set:
            layer["moe"] = moe_subtree(p)
        else:
            layer["mlp"] = {
                "gate": {"kernel": t(p + "mlp.gate_proj.weight", True)},
                "up": {"kernel": t(p + "mlp.up_proj.weight", True)},
                "down": {"kernel": t(p + "mlp.down_proj.weight", True)},
            }
        params[f"layers_{i}"] = layer
    return params
