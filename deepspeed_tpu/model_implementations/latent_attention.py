"""Latent attention (MLA) as every family that has it runs it: queries
through a normed rank-``q_lora`` latent, keys and values through a
normed rank-``kv_lora`` latent and one rotary key shared by all heads.
The cache holds ``[c_kv ; k_rope]`` a token an attention
(``kv_cache.LatentPagedCache``). Three forms of the same attention:

* :func:`materialised_attention`: a whole sequence against its own rows,
  K and V built per head from the latent (the flash kernel on a TPU):
  monolithic prefill and the uncached forward;
* :func:`absorbed_attention`: one token a slot appended to the latent
  pool and attended there, the query carried into the latent space
  (``ops/pallas/latent_decode_attention.py``): decode;
* :func:`chunk_attention`: a prompt chunk of one slot against the slot's
  rows in the pool, its own included, K and V rebuilt block by block
  inside the kernel (``ops/pallas/latent_chunk_attention.py``): chunked
  prefill and the tail of a prefix-cache hit.

A family hands over its configuration (``qk_nope_head_dim``,
``kv_lora_rank``, ``v_head_dim``, ``rms_norm_eps``, ``attn_scale``, the
factors ``q_latent_scale`` / ``kv_latent_scale`` on the normed latents)
and its rotary function; the parameter names of one attention are::

    wq_a [E, Rq]  q_norm [Rq]  wq_b [Rq, H, Dn + Dr]
    wkv_a [E, Rkv + Dr]  kv_norm [Rkv]
    wk_b [Rkv, H, Dn]  wv_b [Rkv, H, Dv]  (the published W_kvb's key and
    value columns, kept apart: decode uses them on either side of the
    kernel)  wo [H, Dv, E]

Shared code: it imports no model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (latent_append_token,
                                              with_latent_rows)
from deepspeed_tpu.ops.pallas import latent_chunk_attention as _chunk
from deepspeed_tpu.ops.pallas import latent_decode_attention as _latent
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32
NEG_INF = -1e30


@scoped("ln")
def rms(x, g, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(F32)).astype(x.dtype)


@scoped("mla_qkv")
def project(h, a, cfg, positions, rope):
    """``h [..., E]`` -> ``q_nope [..., H, Dn]``, ``q_rope [..., H, Dr]``
    (rotated) and the row to cache ``[c_kv ; k_rope] [..., Rkv + Dr]``.
    ``rope(x [..., n, Dr], positions)`` is the family's rotary."""
    dt = h.dtype
    Dn, Rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    c_q = rms(h @ a["wq_a"].astype(dt), a["q_norm"], cfg.rms_norm_eps)
    q = jnp.einsum("...r,rhd->...hd", c_q, a["wq_b"].astype(dt))
    if cfg.q_latent_scale != 1.0:
        q = q * jnp.asarray(cfg.q_latent_scale, dt)
    kv = h @ a["wkv_a"].astype(dt)
    c_kv = rms(kv[..., :Rkv], a["kv_norm"], cfg.rms_norm_eps)
    if cfg.kv_latent_scale != 1.0:
        c_kv = c_kv * jnp.asarray(cfg.kv_latent_scale, dt)
    k_rope = rope(kv[..., None, Rkv:], positions)[..., 0, :]
    q_rope = rope(q[..., Dn:], positions)
    return q[..., :Dn], q_rope, jnp.concatenate([c_kv, k_rope], -1)


@scoped("mla_attn")
def materialised_attention(q_nope, q_rope, rows, a, cfg):
    """Causal attention of a whole sequence against its own rows, K and V
    built per head from the latent: ``q_* [B, T, H, .]``, ``rows [B, T,
    W]`` -> ``[B, T, H, Dv]``. On a TPU the flash kernel (QK width Dn +
    Dr; a narrower V is padded with zero columns to that width, which
    the kernel asks for, and the padding cut off its output)."""
    B, T, H, Dn = q_nope.shape
    Rkv, Dv = cfg.kv_lora_rank, cfg.v_head_dim
    dt = q_nope.dtype
    c_kv = rows[..., :Rkv]
    k = jnp.concatenate(
        [jnp.einsum("btr,rhd->bthd", c_kv, a["wk_b"].astype(dt)),
         jnp.broadcast_to(rows[:, :, None, Rkv:],
                          (B, T, H, rows.shape[-1] - Rkv))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    v = jnp.einsum("btr,rhd->bthd", c_kv, a["wv_b"].astype(dt))
    if jax.default_backend() == "tpu" and T >= 128 and T % 128 == 0:
        pad = q.shape[-1] - Dv
        if pad:
            v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, pad)))
        return flash_attention(q, k, v, causal=True,
                               scale=cfg.attn_scale)[..., :Dv]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=F32) * cfg.attn_scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(dt), v)


@scoped("mla_attn")
def absorbed_attention(q_nope, q_rope, rows, cache, idx, active, a, cfg):
    """One token a slot through the latent pool, absorbed form: ``q_* [S,
    H, .]``, the step's new ``rows [S, W]`` -> (cache, ``[S, H, Dv]``).
    Every active slot's row goes to position ``lengths[s]`` of attention
    ``idx``'s pool (lengths advance once a step,
    :func:`~deepspeed_tpu.inference.kv_cache.paged_advance`) and its
    query, carried into the latent space through ``wk_b``, attends whole
    rows up to and with that one; the latent output comes back through
    ``wv_b``. A slot that is not active writes nothing and gets zeros.
    On a TPU one kernel does both (the row's block is in VMEM for the
    attention anyway); the scatter and the ``jax.numpy`` attention
    elsewhere."""
    Rkv = cfg.kv_lora_rank
    dt = q_nope.dtype
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, a["wk_b"].astype(dt))
    if jax.default_backend() == "tpu":
        o_lat, pool = _latent.paged_latent_decode_attention(
            q_lat, q_rope, rows, cache.rows[idx], cache.block_tables,
            jnp.where(active, cache.lengths, -1), scale=cfg.attn_scale)
        cache = with_latent_rows(cache, idx, pool)
    else:
        cache = latent_append_token(cache, idx, rows, active)
        o_lat = _latent.paged_latent_decode_attention_reference(
            jnp.concatenate([q_lat, q_rope], -1), cache.rows[idx],
            cache.block_tables, jnp.where(active, cache.lengths + 1, 0),
            value_dim=Rkv, scale=cfg.attn_scale)
    return cache, jnp.einsum("shr,rhd->shd", o_lat, a["wv_b"].astype(dt))


@scoped("mla_attn")
def chunk_attention(q_nope, q_rope, pool, table, start, a, cfg):
    """A prompt chunk of one slot at positions ``start .. start + C - 1``
    against the slot's rows in the pool (its own already written),
    materialised form: ``q_* [C, H, .]`` -> ``[C, H, Dv]``; ``table
    [MB]`` is the slot's block table. The kernel rebuilds K and V of one
    pool block at a time from its latents: nothing of the context's size
    times the heads exists outside it."""
    dt = q_nope.dtype
    q = jnp.swapaxes(jnp.concatenate([q_nope, q_rope], -1), 0, 1)
    attend = (_chunk.latent_chunk_attention
              if jax.default_backend() == "tpu" else
              _chunk.latent_chunk_attention_reference)
    out = attend(q, pool, table, start,
                 jnp.transpose(a["wk_b"].astype(dt), (1, 2, 0)),
                 jnp.transpose(a["wv_b"].astype(dt), (1, 2, 0)),
                 scale=cfg.attn_scale)                    # [H, C, Dv]
    return jnp.swapaxes(out, 0, 1)


@scoped("attn_out")
def attn_out(o, a):
    return jnp.einsum("...hd,hde->...e", o, a["wo"].astype(o.dtype))
