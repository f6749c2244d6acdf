"""Grouped-query attention with NO positional encoding over the K/V
block pool, for the hybrids whose few attention layers sit among
state-space mixers (``granite_hybrid.py``, ``nemotron_h.py``): the
projections, a sequence against itself (prefill) and one token a slot
against the pool (decode), at a softmax scale the family gives (a
multiplier of its own, or ``1 / sqrt(head_dim)``). Parameters: ``wq [E,
Hq, d]  wk [E, KH, d]  wv [E, KH, d]  wo [Hq, d, E]``.

Shared code: it imports no model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import PagedKVCache
from deepspeed_tpu.ops.pallas import decode_attention as _kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

F32 = jnp.float32
NEG_INF = -1e30


def project(h, a):
    dt = h.dtype
    return (jnp.einsum("...e,ehd->...hd", h, a["wq"].astype(dt)),
            jnp.einsum("...e,ehd->...hd", h, a["wk"].astype(dt)),
            jnp.einsum("...e,ehd->...hd", h, a["wv"].astype(dt)))


def sequence_attention(q, k, v, scale: float):
    """Causal attention of one sequence against itself, no positional
    encoding: ``q [T, H, d]``, ``k`` / ``v [T, KH, d]`` -> ``[T, H, d]``.
    On a TPU the flash kernel; the masked einsum elsewhere and for a
    prompt the kernel's blocks do not tile."""
    T, H, d = q.shape
    if jax.default_backend() == "tpu" and T >= 128 and T % 128 == 0:
        return flash_attention(q[None], k[None], v[None], causal=True,
                               scale=scale)[0]
    rep = H // k.shape[1]
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, axis=1),
                   preferred_element_type=F32) * scale
    seen = jnp.arange(T)[None] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype),
                      jnp.repeat(v, rep, axis=1))


def token_attention(q, cache: PagedKVCache, i: int, live, scale: float):
    """One token a slot against attention layer ``i`` of the pool: ``q
    [S, H, d]`` -> ``[S, H, d]``; ``live [S]`` counts the token just
    appended. The paged kernel on a TPU; its oracle elsewhere (which
    scales by 1 / sqrt(d): the query carries the difference)."""
    if jax.default_backend() == "tpu":
        return _kernels.paged_decode_attention(
            q, cache.k, cache.v, cache.block_tables, live, layer=i,
            scale=scale)
    q = (q.astype(F32) * (scale * math.sqrt(q.shape[-1]))).astype(q.dtype)
    return _kernels.paged_decode_attention_reference(
        q, cache.k[i], cache.v[i], cache.block_tables, live)


def attn_out(a, attn):
    return jnp.einsum("...hd,hde->...e", a, attn["wo"].astype(a.dtype))
