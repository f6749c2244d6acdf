"""Pallas attention of a PROMPT CHUNK over a latent paged pool (MLA,
materialised form).

Chunked prefill and the tail of a prefix-cache hit run ``C`` query rows
of one slot, at positions ``start .. start + C - 1``, against the
slot's earlier rows (earlier chunks, blocks another request wrote and
the prefix cache mapped into this slot's table) plus their own, all read
through the slot's block table from the pool ``[NB, W, BS]`` that decode
walks (``latent_decode_attention.py`` has why a block lies transposed).

Of the two forms of latent attention this is the MATERIALISED one: K
and V of a pool block are rebuilt from its latents inside the kernel,
per head (``W_kb[h]^T lat`` and ``W_vb[h]^T lat``: 512 x (Dn + Dv) MACs
a cached row a head), and attended as ordinary heads (Dn + Dr + Dv MACs
a query a key a head). With C = 1024 query rows the rebuild adds 42 % to
the attention's arithmetic; the absorbed form (queries carried into the
latent space, as decode does for its one row) would cost 2 x 512 + 64 =
1088 MACs a query a key a head against 384 + 160 here: 2.0 x as much.
Neither form needs K or V of the context outside the kernel: what would
be ``context x H x (Dn + Dr + Dv)`` values in HBM (1.6 GB at 33k
positions, 64 heads, bfloat16) exists one ``[BS, Dn + Dv]`` tile a head
at a time in VMEM.

Grid ``(head groups, table entries)``; the pool is indexed by the
scalar-prefetched table row, so a grid step's block is whatever block
the table names, and entries past the chunk's last row are clamped to
the last live one (no new copy) and skipped. The online-softmax state of
a head group lives in float32 scratch across its walk. A pool block
arrives keys-across (``[W, BS]``), so K and V of a block come out of
their rebuild as ``[Dn, BS]`` and ``[Dv, BS]``, and the kernel keeps
that orientation to the end: scores ``[BS, C]`` (keys down the sublanes,
the chunk's queries across the lanes), output ``[Dv, C]``. The softmax's
maximum and sum over the keys are then elementwise over registers; with
queries down and keys across they were a lane reduction a query a head a
block, and the kernel ran at 19.5 % of the chip's bfloat16 peak where it
now runs at 63 % (62.1 -> 19.1 ms a call at a 33k-row context; PERF.md
section 6, PR 45). The kernel is named ``latent_chunk_attention`` in the
compiled program and the trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NAME = "latent_chunk_attention"
# heads a grid step attends (one pool block against each): four heads'
# queries, weights, output and softmax state are ~12 MB of VMEM at C =
# 1024, and a step then carries ~0.6 GFLOP against its fixed cost (eight
# heads a step read 18.6 ms a call against 19.1: PERF.md section 6)
HEADS_PER_STEP = 4
_VMEM_LIMIT = 96 * 1024 * 1024


def _kernel(start_ref, bt_ref, q_ref, wk_ref, wv_ref, pool_ref, o_ref,
            m_ref, l_ref, acc_ref, *, block_size: int, rank: int):
    """Grid (head group, table entry): ``q_ref [G, Dn + Dr, C]`` (scaled,
    a head's queries TRANSPOSED: positions on the lanes), ``wk_ref [G,
    Dn, R]``, ``wv_ref [G, Dv, R]``, ``pool_ref [1, W, BS]`` (the block
    table entry ``k`` names), ``o_ref [G, Dv, C]``. Everything a block
    yields is kept keys-down, queries-across (``[BS, C]`` scores, ``[Dv,
    C]`` output): the softmax's maximum and sum run down the sublanes,
    elementwise over whole registers, and its state is one lane-dense
    row a head; with queries down and keys across they were 1024 lane
    reductions a head a block."""
    k, K = pl.program_id(1), pl.num_programs(1)
    G, _, C = q_ref.shape
    start = start_ref[0]

    @pl.when(k == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(masked: bool):
        lat = pool_ref[0, :rank]                             # [R, BS]
        rope = pool_ref[0, rank:]                            # [Dr, BS]
        dt = lat.dtype
        if masked:
            key = k * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (block_size, C), 0)
            query = start + jax.lax.broadcasted_iota(
                jnp.int32, (block_size, C), 1)
            seen = key <= query
        for g in range(G):
            kT = jnp.dot(wk_ref[g], lat,
                         preferred_element_type=jnp.float32).astype(dt)
            vT = jnp.dot(wv_ref[g], lat,
                         preferred_element_type=jnp.float32).astype(dt)
            # one product over the whole key width: K's rebuilt part on
            # top of the block's shared rotary rows (``q`` comes scaled)
            sc = jax.lax.dot_general(
                jnp.concatenate([kT, rope], axis=0), q_ref[g],
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)          # [BS, C]
            if masked:
                sc = jnp.where(seen, sc, NEG_INF)
            m = m_ref[g]                                     # [1, C]
            m_new = jnp.maximum(m, jnp.max(sc, axis=0, keepdims=True))
            p = jnp.exp(sc - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                vT, p.astype(dt), preferred_element_type=jnp.float32)
            m_ref[g] = m_new                                 # [Dv, C] above

    # a block wholly before the chunk is seen by every row and needs no
    # mask; the chunk's own blocks do; entries past its last row hold
    # nothing a query may see
    first_own = start // block_size
    pl.when(k < first_own)(lambda: attend(False))
    pl.when((k >= first_own) & (k * block_size < start + C))(
        lambda: attend(True))

    @pl.when(k == K - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] /
                      jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def latent_chunk_attention(q: jax.Array, pool: jax.Array, table: jax.Array,
                           start, wk: jax.Array, wv: jax.Array, *,
                           scale: float, interpret: bool | None = None
                           ) -> jax.Array:
    """Causal attention of one slot's prompt chunk through a latent pool.

    q: ``[H, C, Dn + Dr]`` (row ``i`` is position ``start + i``; its
    rotary part last); pool: ``[NB, W, BS]`` (one attention's rows of a
    :class:`~deepspeed_tpu.inference.kv_cache.LatentPagedCache`, the
    chunk's own rows already written); table: ``[MB]`` int32, the slot's
    block table (entry ``j`` covers positions ``j*BS .. (j+1)*BS - 1``;
    entries past ``start + C - 1`` are never read); start: int32 scalar;
    wk: ``[H, Dn, R]`` and wv: ``[H, Dv, R]``: W_kvb's key and value
    parts, a head's transposed. Row ``i`` attends positions ``<= start +
    i``. Returns ``[H, C, Dv]``."""
    H, C, D = q.shape
    NB, W, BS = pool.shape
    _, Dn, R = wk.shape
    Dv = wv.shape[1]
    if D - Dn != W - R or wv.shape[2] != R:
        raise ValueError(f"q width {D}, pool width {W}, W_kb {wk.shape} and "
                         f"W_vb {wv.shape} do not describe one latent row")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    call = _chunk_call(bool(interpret), q.shape, q.dtype.name, pool.shape,
                       pool.dtype.name, table.shape[0], Dn, Dv)
    # the softmax scale rides on the queries: one pass over [H, C, D]
    # here, not one over the scores a head a block in the kernel, which
    # takes a head's queries and gives its outputs positions-last
    scaled = jnp.swapaxes((q.astype(jnp.float32) * scale).astype(q.dtype),
                          1, 2)
    return jnp.swapaxes(
        call(jnp.reshape(start, (1,)).astype(jnp.int32),
             table.astype(jnp.int32), scaled, wk, wv, pool), 1, 2)


@functools.lru_cache(maxsize=None)
def _chunk_call(interpret: bool, q_shape, q_dtype: str, pool_shape,
                pool_dtype: str, MB: int, Dn: int, Dv: int):
    """The ``pallas_call`` of one static signature, kept and jitted as
    ``latent_decode_attention._latent_call`` is: a chunk program's
    attentions have one signature and a pool buffer each."""
    H, C, D = q_shape
    _, W, BS = pool_shape
    R = W - (D - Dn)
    G = next(g for g in range(min(HEADS_PER_STEP, H), 0, -1) if H % g == 0)
    f32 = jnp.float32

    def head_map(h, k, start, bt):
        return (h, 0, 0)

    def block_map(h, k, start, bt):
        last = (start[0] + C - 1) // BS
        return (bt[jnp.minimum(jnp.minimum(k, last), MB - 1)], 0, 0)

    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, block_size=BS, rank=R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(H // G, MB),
            in_specs=[pl.BlockSpec((G, D, C), head_map),
                      pl.BlockSpec((G, Dn, R), head_map),
                      pl.BlockSpec((G, Dv, R), head_map),
                      pl.BlockSpec((1, W, BS), block_map)],
            out_specs=pl.BlockSpec((G, Dv, C), head_map),
            scratch_shapes=[pltpu.VMEM((G, 1, C), f32),
                            pltpu.VMEM((G, 1, C), f32),
                            pltpu.VMEM((G, Dv, C), f32)]),
        out_shape=jax.ShapeDtypeStruct((H, Dv, C), q_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=NAME,
    ))


def latent_chunk_attention_reference(q, pool, table, start, wk, wv, *,
                                     scale: float) -> jax.Array:
    """The same attention in plain ``jax.numpy`` (float32 softmax): the
    slot's rows gathered through its table, K and V built for every head
    at once. The CPU path of the model, and the kernel's oracle."""
    H, C, _ = q.shape
    R, Dn = wk.shape[2], wk.shape[1]
    rows = jnp.swapaxes(pool[table], 1, 2).reshape(
        table.shape[0] * pool.shape[2], -1)                  # [T, W]
    k_nope = jnp.einsum("tr,hdr->htd", rows[:, :R], wk)
    v = jnp.einsum("tr,hdr->htd", rows[:, :R], wv)
    sc = (jnp.einsum("hcd,htd->hct", q[..., :Dn], k_nope,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("hcd,td->hct", q[..., Dn:], rows[:, R:],
                       preferred_element_type=jnp.float32)) * scale
    seen = (jnp.arange(rows.shape[0])[None, :]
            <= start + jnp.arange(C)[:, None])
    p = jax.nn.softmax(jnp.where(seen[None], sc, NEG_INF), axis=-1)
    return jnp.einsum("hct,htd->hcd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
