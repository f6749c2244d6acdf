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

The kernel walks a slot's LIVE blocks as ``decode_attention.py``'s
family does, by the same scaffold (``block_walk.walk_live_blocks``): the
grid is the slots, in order; the pool stays in HBM; one grid step reads
its trip count from the scalar-prefetched lengths and copies the table
entries its slot has, each the contiguous ``[W, BS]`` slab it is, into
VMEM buffers while the entries before them are attended, and its last
iteration starts the first entries of the next slot that has any. A dead
table entry is never read (it need not even be a valid id), an idle slot
costs one empty grid step that writes zeros, and the step's own cost is
paid once a slot: 256 grid steps a call at 256 slots, where a grid over
``(slot, table entry)`` paid 2048 for the same bytes.

A latent block is small, and on the chip what one costs is the latency
of its chain (a 147 KB copy, two score products with 64 rows, two lane
reductions, ``exp``, ``p . lat``: ~0.5 us where its bytes are 0.18), not
work. So an iteration attends ``ENTRIES`` table entries, each copied
through a stream of its own: their score products and copies are
independent and overlap, and only the online-softmax recurrence (float32
scratch, re-initialised once a slot) runs over them one after another,
in table order, as it would a block at a time: the sums and their order
are a block-at-a-time kernel's, to the bit. The kernel is named
``paged_latent_decode_attention`` in the compiled program and the trace;
``paged_latent_append`` is the pool's decode-time writer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.block_walk import live_blocks, walk_live_blocks

NEG_INF = -1e30
NAME = "paged_latent_decode_attention"
# table entries a loop iteration attends, each through a copy stream of
# its own (the module docstring has why): three is where the LongCat
# cell's contexts, 2-6 live blocks a slot, gain most (PERF.md section 6,
# PR 39: 1 / 2 / 3 / 4 / 8 entries read 381 / 310 / 295 / 311 / 323 us a
# call there)
ENTRIES = 3


def _kernel(base_ref, bt_ref, q_ref, pool_hbm, o_ref, *rest,
            block_size: int, value_dim: int, scale: float):
    """Grid (slot,): one step walks ITS slot's live blocks
    (:func:`~deepspeed_tpu.ops.pallas.block_walk.walk_live_blocks`),
    ``ENTRIES`` table entries an iteration, entry ``k`` of a group
    through stream ``k`` (the same pool, buffers of its own). An entry
    past the slot's last live block sits out: no copy, no arithmetic.
    ``base[s]`` is the last visible key position of slot ``s`` (live
    length - 1; -1 for an idle slot)."""
    bufs = rest[:ENTRIES]
    sems, next_buf, m_ref, l_ref, acc_ref = rest[ENTRIES:]
    s, S = pl.program_id(0), pl.num_programs(0)
    MB = bt_ref.shape[1]
    base = base_ref[s]

    def blocks(slot):
        return live_blocks(base_ref[slot] + 1, block_size, MB)

    def groups(slot):
        return jax.lax.div(blocks(slot) + (ENTRIES - 1), ENTRIES)

    def block_ids(slot, j):
        first = j * ENTRIES     # live whenever the walk reaches group j
        return (bt_ref[slot, first],) + tuple(
            (bt_ref[slot, jnp.minimum(first + k, MB - 1)],
             first + k < blocks(slot)) for k in range(1, ENTRIES))

    def idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def walk(loop):
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def attend_entries(j, buf, live: int):
            """The first ``live`` entries of group ``j``: every entry's
            scores (independent of one another), then the recurrence
            over them in table order."""
            q = q_ref[0]                                   # [H, W]
            scores = []
            for k in range(live):
                lat = bufs[k][buf, :value_dim]             # [V, BS]
                rope = bufs[k][buf, value_dim:]
                # two products (latent part, rotary part): both
                # contractions are lane-aligned, one over W = 576 is not
                sc = jnp.dot(q[:, :value_dim], lat,
                             preferred_element_type=jnp.float32)
                sc += jnp.dot(q[:, value_dim:], rope,
                              preferred_element_type=jnp.float32)
                sc = sc * scale                            # [H, BS]
                col = (j * ENTRIES + k) * block_size + (
                    jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1))
                scores.append(jnp.where(col <= base, sc, NEG_INF))
            m, l, acc = m_ref[...], l_ref[...], acc_ref[...]
            for k, sc in enumerate(scores):
                lat = bufs[k][buf, :value_dim]
                m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
                p = jnp.exp(sc - m_new)
                alpha = jnp.exp(m - m_new)
                l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jax.lax.dot_general(
                    p.astype(lat.dtype), lat, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m = m_new
            m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

        def attend(j, buf):
            live = jnp.minimum(blocks(s) - j * ENTRIES, ENTRIES)
            for k in range(1, ENTRIES + 1):
                pl.when(live == k)(
                    functools.partial(attend_entries, j, buf, k))

        loop(attend)
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    s_next = jnp.minimum(s + 1, S - 1)
    walk_live_blocks(
        tuple((pool_hbm, buf) for buf in bufs), sems, next_buf, block_ids,
        slot=s, n=groups(s), first=s == 0, slot_next=s_next,
        n_next=jnp.where(s + 1 == S, 0, groups(s_next)), idle=idle,
        walk=walk)


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
    block_tables: ``[S, MB]`` int32 (entry j covers positions ``j*BS ..
    (j+1)*BS - 1``; entries beyond a slot's length are never read);
    lengths: ``[S]`` int32 live lengths (the query attends positions
    ``< lengths[s]``). Returns the latent outputs ``[S, H, value_dim]``;
    the caller carries them through ``W_kvb_v``. An idle slot (length 0)
    reads nothing and returns zeros."""
    S, H, W = q.shape
    NB, Wp, BS = pool.shape
    if Wp != W or not 0 < value_dim < W:
        raise ValueError(f"q width {W}, pool width {Wp}, value_dim "
                         f"{value_dim} do not describe one latent row")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    call = _latent_call(bool(interpret), q.shape, q.dtype.name, pool.shape,
                        pool.dtype.name, block_tables.shape[1], value_dim,
                        float(scale))
    return call(lengths.astype(jnp.int32) - 1,
                block_tables.astype(jnp.int32), q, pool)


@functools.lru_cache(maxsize=None)
def _latent_call(interpret: bool, q_shape, q_dtype: str, pool_shape,
                 pool_dtype: str, MB: int, value_dim: int, scale: float):
    """The ``pallas_call`` of one static signature: ``(base [S], tables
    [S, MB], q [S, H, W], pool [NB, W, BS]) -> [S, H, value_dim]``. Grid
    ``(S,)``, in order; the pool stays in HBM and :func:`_kernel` copies
    the blocks it walks into two VMEM buffers a stream. Kept per
    signature, as ``decode_attention._paged_call`` is, and jitted: a
    decode program's attentions have one signature and a pool buffer
    each, so jax finds every call after the first in its caches: the
    kernel body is traced once and the call lowered once a program
    (un-jitted, each call re-did everything around the body's trace:
    0.6 s more of the cell's set-up on the chip's host)."""
    S, H, W = q_shape
    _, _, BS = pool_shape
    f32 = jnp.float32
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, block_size=BS, value_dim=value_dim,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, H, value_dim),
                                   lambda s, *_: (s, 0, 0)),
            scratch_shapes=[*[pltpu.VMEM((2, W, BS), pool_dtype)] * ENTRIES,
                            pltpu.SemaphoreType.DMA((ENTRIES, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((H, 1), f32), pltpu.VMEM((H, 1), f32),
                            pltpu.VMEM((H, value_dim), f32)]),
        out_shape=jax.ShapeDtypeStruct((S, H, value_dim), q_dtype),
        # in order: a step starts the next step's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    ))


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
