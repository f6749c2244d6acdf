"""Attention ops with hardware dispatch.

The hot-path analog of the reference's fused attention kernels
(``csrc/transformer/softmax_kernels.cu`` for training,
``softmax_context`` in ``csrc/transformer/inference/csrc/pt_binding.cpp``
for decode). On TPU the MXU does the matmuls; the win is avoiding the
O(T²) attention-matrix round-trip to HBM — a Pallas flash-attention kernel
(deepspeed_tpu/ops/pallas/flash_attention.py) on TPU, with a pure-jnp
reference path on CPU (used by the unit tests and as the numerics oracle).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.comm.mesh import DATA_AXES
from deepspeed_tpu.ops.pallas.flash_attention import (
    DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention)
from deepspeed_tpu.utils.sharding import engine_mesh, map_kernel


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def causal_attention_reference(q, k, v, scale=None, causal=True):
    """Numerics oracle: plain softmax attention, fp32 accumulation.

    Shapes: q ``[B, T, H, D]`` → ``[B, T, H, D]``; k/v may carry fewer
    heads (``[B, T, HKV, D]``, HKV | H — grouped-query attention,
    broadcast per query group without materializing repeated k/v). Also
    serves the sequence-parallel modes' dense core and degenerate-mesh
    fallbacks, so scale/causal overrides live HERE, once.
    """
    B, T, H, D = q.shape
    HKV = k.shape[2]
    if H % HKV:
        raise ValueError(f"q heads {H} not divisible by kv heads {HKV}")
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    # one body serves MHA (g=1) and GQA: the group axis broadcasts k/v per
    # query group without materializing repeats, and XLA drops the
    # degenerate axis for plain attention
    g = H // HKV
    q5 = q.reshape(B, T, HKV, g, D)
    att = (jnp.einsum("bqhgd,bkhd->bhgqk", q5, k).astype(jnp.float32)
           * scale)
    if causal:
        mask = jnp.tril(jnp.ones((T, T), bool))
        att = jnp.where(mask[None, None, None], att, -1e30)
    att = jax.nn.softmax(att, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", att.astype(v.dtype), v)
    return out.reshape(B, T, H, D)


def causal_attention(q, k, v, block_q: int = 0, block_k: int = 0):
    """Causal self-attention ``[B, T, H, D] -> [B, T, H, D]``; k/v may
    carry fewer heads (grouped-query attention — both the flash kernel
    and the reference path consume unexpanded k/v). ``block_q/block_k``
    override the flash kernel's tile sizes (0 = kernel default) — the
    long-context block-size A/B knob (docs/mfu_analysis.md).

    The flash output is tagged with ``checkpoint_name('flash_attn_out')``:
    under ``jax.checkpoint`` the dots-saveable remat policy cannot see
    inside the kernel's custom_vjp, so without the tag the whole flash
    forward would re-run during backward — measured as a net train-step
    LOSS vs unfused attention at seq 1024 despite the kernel itself being
    several times faster. Models extend their policy with
    ``save_only_these_names('flash_attn_out')`` (models/gpt2.py).
    """
    if _on_tpu() and q.shape[1] >= 256:
        kernel = functools.partial(
            flash_attention, causal=True,
            block_q=block_q or DEFAULT_BLOCK_Q,
            block_k=block_k or DEFAULT_BLOCK_K)
        return checkpoint_name(_over_global_mesh(kernel, q, k, v),
                               "flash_attn_out")
    return causal_attention_reference(q, k, v)


def _over_global_mesh(kernel, q, k, v):
    """Run the flash kernel under the engine's mesh (utils/sharding.py
    ``map_kernel``): batch over the data axes, heads over ``tensor``,
    wherever they divide — an axis that does not divide stays
    replicated. Inside an already-manual region (ring/Ulysses SP, the
    explicit-DP steps) the caller owns the mapping and the kernel runs
    as is."""
    mesh = engine_mesh()
    if mesh is None:
        return kernel(q, k, v)
    dp = mesh.shape["data"] * mesh.shape["fsdp"]
    tp = mesh.shape["tensor"]
    batch = DATA_AXES if q.shape[0] % dp == 0 else None
    heads = "tensor" if q.shape[2] % tp == k.shape[2] % tp == 0 else None
    spec = P(batch, None, heads, None)
    return map_kernel(kernel, mesh, (spec, spec, spec), spec)(q, k, v)
