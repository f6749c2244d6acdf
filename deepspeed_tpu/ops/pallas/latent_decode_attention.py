"""Pallas decode attention over a LATENT paged pool (MLA, absorbed form).

Latent attention caches one row a token, ``[c_kv ; k_rope]`` (the normed
compressed latent and the shared rotary key; 512 + 64 values at the
published widths), and decodes in the absorbed form: the query of head
``h`` is carried into the latent space (``q_nope[h] W_kvb_k[h]^T``,
beside its rotary part), scores are taken against the whole row, and the
value is the row's first ``value_dim`` columns. So every query head
reads the SAME row: one shared "KV head" of width ``W`` whose value is a
prefix of its key, attended by all ``H`` query heads at once as one
``[H, W] x [W, BS]`` product per pool block.

A pool block is stored TRANSPOSED, ``[W, BS]``: the ``BS`` positions
of a block lie on the lanes, the ``W`` values of a row down the
sublanes. ``W`` = 576 is not a multiple of the 128 lanes, so a
``[BS, W]`` block is not the chip's own layout of the array: the compiler
stored such a pool the other way round and converted every attention's
whole pool to ``[BS, W]`` and back around each decode step's append and
kernel call (16 copies of 302 MB, a third of the step; my chip run, PR
29). ``[W, BS]`` with ``BS`` = 128 is exactly tiled, is what the
compiler had chosen, and makes the score product a plain ``[H, W] x [W,
BS]``.

The kernel streams pool blocks through VMEM by the scalar-prefetched
block table, as ``decode_attention.py``'s family does (same null block,
same dead-tail rule: a table entry past the live length re-names the
last live block, so it costs no DMA, and ``pl.when`` skips its compute),
with the online-softmax recurrence in float32 scratch. It is named
``paged_latent_decode_attention`` in the compiled program and the trace;
``paged_latent_append`` is the pool's decode-time writer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NAME = "paged_latent_decode_attention"


def _kernel(base_ref, bt_ref, q_ref, r_ref, o_ref, m_ref, l_ref, acc_ref, *,
            block_size: int, value_dim: int, scale: float):
    """Grid (slot, block-table entry). ``base[s]`` is the last visible
    key position of slot ``s`` (live length - 1; -1 for an idle slot)."""
    s, i = pl.program_id(0), pl.program_id(1)
    nb = pl.num_programs(1)
    base = base_ref[s]

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i * block_size <= base)
    def _update():
        q = q_ref[0]                                   # [H, W]
        rows = r_ref[0]                                # [W, BS]
        lat, rope = rows[:value_dim], rows[value_dim:]
        # two products (latent part, rotary part): both contractions are
        # lane-aligned, which one over W = 576 is not
        sc = jnp.dot(q[:, :value_dim], lat,
                     preferred_element_type=jnp.float32)
        sc += jnp.dot(q[:, value_dim:], rope,
                      preferred_element_type=jnp.float32)
        sc = sc * scale                                # [H, BS]
        col = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        sc = jnp.where(col <= base, sc, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(lat.dtype), lat, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_latent_decode_attention(q: jax.Array, pool: jax.Array,
                                  block_tables: jax.Array,
                                  lengths: jax.Array, *, value_dim: int,
                                  scale: float,
                                  interpret: bool | None = None
                                  ) -> jax.Array:
    """One-token absorbed-form attention through a latent pool.

    q: ``[S, H, W]`` (one query per slot: latent part ``[:value_dim]``,
    rotary part after it); pool: ``[NB, W, BS]`` (one attention's rows of
    a :class:`~deepspeed_tpu.inference.kv_cache.LatentPagedCache`, each
    block transposed);
    block_tables: ``[S, MB]`` int32 (dead entries must be valid ids: the
    null block); lengths: ``[S]`` int32 live lengths (the query attends
    positions ``< lengths[s]``). Returns the latent outputs ``[S, H,
    value_dim]``; the caller carries them through ``W_kvb_v``. An idle
    slot (length 0) costs no compute and one null-block DMA, and returns
    zeros."""
    S, H, W = q.shape
    NB, Wp, BS = pool.shape
    MB = block_tables.shape[1]
    if Wp != W or not 0 < value_dim < W:
        raise ValueError(f"q width {W}, pool width {Wp}, value_dim "
                         f"{value_dim} do not describe one latent row")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def row_map(s, i, base, bt):
        last = jnp.maximum(base[s], 0) // BS
        return (bt[s, jnp.minimum(i, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, MB),
        in_specs=[pl.BlockSpec((1, H, W), lambda s, i, base, bt: (s, 0, 0)),
                  pl.BlockSpec((1, W, BS), row_map)],
        out_specs=pl.BlockSpec((1, H, value_dim),
                               lambda s, i, base, bt: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, 1), jnp.float32),
                        pltpu.VMEM((H, value_dim), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, block_size=BS, value_dim=value_dim,
                          scale=float(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim), q.dtype),
        interpret=interpret,
        name=NAME,
    )(lengths.astype(jnp.int32) - 1, block_tables.astype(jnp.int32), q,
      pool)


def _append_kernel(blk_ref, off_ref, row_ref, pool_ref, out_ref):
    """Grid (slot,): the block that holds slot ``s``'s next position is
    in VMEM; its column ``off[s]`` takes the new row, and the block goes
    back where it came from (the pool is aliased in and out)."""
    s = pl.program_id(0)
    block = pool_ref[0]                                  # [W, BS]
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    out_ref[0] = jnp.where(lane == off_ref[s], row_ref[0], block)


def paged_latent_append(pool: jax.Array, rows: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        interpret: bool | None = None) -> jax.Array:
    """Append one row a slot to a latent pool, in place: ``rows [S, W]``
    goes to position ``lengths[s]`` of slot ``s`` (block ``block_tables[s,
    lengths[s] // BS]``, column ``lengths[s] % BS`` of the transposed
    block). Idle slots (all-zero table, length 0) write into the null
    block. Returns the pool (``pool`` is donated to it).

    A column of a ``[W, BS]`` block is strided in memory, and the XLA
    scatter that writes one converts the whole pool to the other layout
    and back (twice 302 MB an attention a step; my chip run, PR 29).
    This call reads and rewrites the one block a slot appends to: 2 x
    147 KB a slot."""
    NB, W, BS = pool.shape
    S = rows.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    pos = lengths.astype(jnp.int32)
    blk = jnp.take_along_axis(block_tables.astype(jnp.int32),
                              (pos // BS)[:, None], axis=1)[:, 0]

    def block_map(s, blk, off):
        return (blk[s], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[pl.BlockSpec((1, W, 1), lambda s, blk, off: (s, 0, 0)),
                  pl.BlockSpec((1, W, BS), block_map)],
        out_specs=pl.BlockSpec((1, W, BS), block_map))
    return pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},      # the pool, after blk, off, rows
        interpret=interpret,
        name="paged_latent_append",
    )(blk, pos % BS, rows.astype(pool.dtype)[:, :, None], pool)


def paged_latent_decode_attention_reference(q, pool, block_tables, lengths,
                                            *, value_dim: int,
                                            scale: float) -> jax.Array:
    """The same attention in plain ``jax.numpy`` (float32 softmax): rows
    gathered through the block tables, positions at or past a slot's
    length masked. The CPU path of the model, and the kernel's oracle."""
    S, MB = block_tables.shape
    rows = jnp.swapaxes(pool[block_tables], 2, 3)      # [S, MB, BS, W]
    rows = rows.reshape(S, MB * pool.shape[2], -1)
    sc = jnp.einsum("shw,stw->sht", q, rows,
                    preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(rows.shape[1])[None, None, :]
    sc = jnp.where(pos < lengths[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    p = jnp.where(lengths[:, None, None] > 0, p, 0.0)    # idle slot: zeros
    return jnp.einsum("sht,stv->shv", p.astype(rows.dtype),
                      rows[..., :value_dim],
                      preferred_element_type=jnp.float32).astype(q.dtype)
