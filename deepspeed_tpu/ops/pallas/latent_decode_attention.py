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
are a block-at-a-time kernel's, to the bit.

The kernel is also the pool's decode-time WRITER. A decode step stores
one new row a slot, at position ``lengths[s]``, and its query attends
that row with the rest: the block it belongs to is the walk's last
entry, in VMEM anyway. So the kernel takes the step's rows as the pool
stores them, ``[W, S]`` (a row down the sublanes, a slot a lane: the
transpose of what the projection computes, one small fusion), a slab of
``LANES`` slots resident at a time, and the pool aliased in and out.
Ahead of a slot's walk the slab is turned along its lanes until the
slot's row stands in lane ``lengths[s] % BS`` (``pltpu.roll``: data
moves, nothing is computed, so the column is the row to the bit); when
the tail block has landed, that lane is selected into it: in the walk's
buffer, where it is attended in its place like any row, and in one of
two staging buffers, from which one copy takes the block back where it
came from. Nothing waits for that copy but the staging buffer's next
use, two tails later, and the grid's end. The walk's last group is
peeled from the loop's other iterations (one branch an iteration, and
only the last group's code holds the append), so a long walk pays for
the append once a slot. A separate writer (``paged_latent_append``, PRs
29-53) read and rewrote the same block through the pipeline, 2 x 147 KB
a slot, behind a ``[S, W, 1]`` operand that the compiler laid out with
``W`` down the sublanes over ONE live lane of 128 (37.7 MB written to
hand over 295 KB): 1.2 GB a LongCat step for 2.4 MB of rows (the
compiled program and the ledger, PR 53). An idle slot (a negative
position) reads nothing, writes nothing and returns zeros; a slot's tail
block is its own (shared prefixes are whole blocks), so no two grid
steps touch one block. The kernel is named
``paged_latent_decode_attention`` in the compiled program and the trace.
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
# slots whose new rows are resident at a time, a lane each
LANES = 128


def _kernel(base_ref, bt_ref, q_lat_ref, q_rope_ref, rows_ref, _pool_in,
            o_ref, pool_hbm, *rest, block_size: int, scale: float):
    """Grid (slot,): one step walks ITS slot's live blocks
    (:func:`~deepspeed_tpu.ops.pallas.block_walk.walk_live_blocks`),
    ``ENTRIES`` table entries an iteration, entry ``k`` of a group
    through stream ``k`` (the same pool, buffers of its own). An entry
    past the slot's last live block sits out: no copy, no arithmetic.
    ``base[s]`` is the position slot ``s``'s new row takes, which is the
    last key position its query sees (-1 for an idle slot). ``rows_ref``
    is a slab of ``LANES`` slots' new rows, a row down the sublanes.
    ``pool_hbm`` is the pool, aliased in and out: read and written
    through the one ref."""
    bufs = rest[:ENTRIES]
    (sems, next_buf, m_ref, l_ref, acc_ref, col_ref, tail_buf, tail_sems,
     tails) = rest[ENTRIES:]
    s, S = pl.program_id(0), pl.num_programs(0)
    MB = bt_ref.shape[1]
    base = base_ref[s]
    off = jax.lax.rem(base, block_size)     # the new row's column

    def blocks(slot):
        return live_blocks(base_ref[slot] + 1, block_size, MB)

    def groups(slot):
        return jax.lax.div(blocks(slot) + (ENTRIES - 1), ENTRIES)

    def block_ids(slot, j):
        first = j * ENTRIES     # live whenever the walk reaches group j
        return (bt_ref[slot, first],) + tuple(
            (bt_ref[slot, jnp.minimum(first + k, MB - 1)],
             first + k < blocks(slot)) for k in range(1, ENTRIES))

    def tail_copy(parity, block):
        return pltpu.make_async_copy(tail_buf.at[parity],
                                     pool_hbm.at[block],
                                     tail_sems.at[parity])

    def words(x):
        """A packed dtype as the 32-bit words it is stored in, two rows
        of one lane a word: what moves or selects whole lanes does the
        same to both, on half the registers and with a mask of its own
        width."""
        return pltpu.bitcast(x, jnp.uint32) if x.dtype.itemsize == 2 else x

    def new_column():
        """The slab turned along its lanes until the slot's row, lane
        ``s % LANES``, stands in lane ``off``."""
        shift = jax.lax.rem(off - jax.lax.rem(s, LANES) + LANES, LANES)
        return pltpu.roll(words(rows_ref[...]), shift, 1)[:, :block_size]

    def append_row(k, buf):
        """The slot's tail block has landed in ``bufs[k][buf]``: its
        column ``off`` takes the new row there, where the walk attends
        it, and a copy of the block starts back to where it came from,
        out of a staging buffer of its own (the walk refills
        ``bufs[k][buf]`` two groups on, and waits for nothing here). The
        staging buffers take turns; one is waited for when its turn
        comes again, two tails later."""
        block = words(bufs[k][buf])
        lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        block = jnp.where(lane == off, col_ref[...], block)
        if block.dtype != bufs[k].dtype:
            block = pltpu.bitcast(block, bufs[k].dtype)
        bufs[k][buf] = block
        turn = tails[0]
        parity = jax.lax.rem(turn, 2)

        @pl.when(turn >= 2)
        def _():
            tail_copy(parity, 0).wait()
        tail_buf[parity] = block
        tail_copy(parity, bt_ref[s, jax.lax.div(base, block_size)]).start()
        tails[0] = turn + 1

    def idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    def walk(loop):
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # ahead of the walk, while its first blocks are on their way
        col_ref[...] = new_column()
        value_dim = q_lat_ref.shape[-1]

        def attend_entries(j, buf, live: int):
            """The first ``live`` entries of group ``j``: every entry's
            scores (independent of one another), then the recurrence
            over them in table order."""
            scores = []
            for k in range(live):
                lat = bufs[k][buf, :value_dim]             # [V, BS]
                rope = bufs[k][buf, value_dim:]
                # two products (latent part, rotary part): both
                # contractions are lane-aligned, one over W = 576 is not
                sc = jnp.dot(q_lat_ref[0], lat,
                             preferred_element_type=jnp.float32)
                sc += jnp.dot(q_rope_ref[0], rope,
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
            # a group inside the walk is whole and nothing is appended
            # to it: one branch an iteration of a long walk. The walk's
            # last group ends in the slot's tail block, wherever in the
            # group that falls
            last = j + 1 == groups(s)
            pl.when(jnp.logical_not(last))(
                functools.partial(attend_entries, j, buf, ENTRIES))

            @pl.when(last)
            def _():
                live = blocks(s) - j * ENTRIES
                for k in range(1, ENTRIES + 1):
                    @pl.when(live == k)
                    def _(k=k):
                        append_row(k - 1, buf)
                        attend_entries(j, buf, k)

        loop(attend)
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)

    @pl.when(s == 0)
    def _():
        tails[0] = 0

    s_next = jnp.minimum(s + 1, S - 1)
    walk_live_blocks(
        tuple((pool_hbm, buf) for buf in bufs), sems, next_buf, block_ids,
        slot=s, n=groups(s), first=s == 0, slot_next=s_next,
        n_next=jnp.where(s + 1 == S, 0, groups(s_next)), idle=idle,
        walk=walk)

    @pl.when(s + 1 == S)
    def _drain():
        # the last two tails are still on their way
        for back in (1, 2):
            @pl.when(tails[0] >= back)
            def _(back=back):
                tail_copy(jax.lax.rem(tails[0] - back, 2), 0).wait()


def paged_latent_decode_attention(q_lat: jax.Array, q_rope: jax.Array,
                                  rows: jax.Array, pool: jax.Array,
                                  block_tables: jax.Array,
                                  positions: jax.Array, *, scale: float,
                                  interpret: bool | None = None):
    """One decode step of one attention through a latent pool, absorbed
    form: every live slot's new row is appended and its query attends
    the slot's rows, the new one among them.

    q_lat: ``[S, H, V]`` and q_rope: ``[S, H, W - V]`` (one query per
    slot: its part in the latent space, its rotary part); rows: ``[S,
    W]`` (the step's new row a slot); pool: ``[NB, W, BS]`` (one
    attention's rows of a
    :class:`~deepspeed_tpu.inference.kv_cache.LatentPagedCache`, each
    block transposed; it comes back updated in place where the caller
    donates it); block_tables: ``[S, MB]`` int32 (entry j covers
    positions ``j*BS .. (j+1)*BS - 1``; entries past a slot's last live
    block are never read); positions: ``[S]`` int32, where slot ``s``'s
    row goes (its length before the step; the block that holds it is the
    slot's own): the query attends positions ``<= positions[s]``. A
    negative position marks an idle slot: it reads nothing, writes
    nothing and returns zeros. Returns the latent outputs ``[S, H, V]``
    (the caller carries them through ``W_kvb_v``) and the pool."""
    S, H, V = q_lat.shape
    NB, W, BS = pool.shape
    if (q_rope.shape != (S, H, W - V) or rows.shape != (S, W) or V >= W
            or BS > LANES):
        raise ValueError(
            f"q {q_lat.shape} + {q_rope.shape} and rows {rows.shape} do "
            f"not describe one latent row a slot of a pool {pool.shape}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    call = _latent_call(bool(interpret), q_lat.shape, q_lat.dtype.name,
                        pool.shape, pool.dtype.name, block_tables.shape[1],
                        float(scale))
    # the rows as the pool stores them, a row down the sublanes and a
    # slot a lane (every lane live: ``rows[:, :, None]`` would be the
    # same values over ONE live lane of 128)
    slabs = jnp.pad(rows.astype(pool.dtype), ((0, -S % LANES), (0, 0))).T
    return call(positions.astype(jnp.int32), block_tables.astype(jnp.int32),
                q_lat, q_rope, slabs, pool)


@functools.lru_cache(maxsize=None)
def _latent_call(interpret: bool, q_shape, q_dtype: str, pool_shape,
                 pool_dtype: str, MB: int, scale: float):
    """The ``pallas_call`` of one static signature: ``(base [S], tables
    [S, MB], q_lat [S, H, V], q_rope [S, H, W - V], rows [W, S up to a
    multiple of LANES], pool [NB, W, BS]) -> ([S, H, V], pool)``. Grid
    ``(S,)``, in order; the pool stays in HBM, aliased in and out, and
    :func:`_kernel` copies the blocks it walks into two VMEM buffers a
    stream and each slot's tail block back out of one of two staging
    buffers. Kept per signature, as ``decode_attention._paged_call`` is,
    and jitted: a decode program's attentions have one signature and a
    pool buffer each, so jax finds every call after the first in its
    caches: the kernel body is traced once and the call lowered once a
    program (un-jitted, each call re-did everything around the body's
    trace: 0.6 s more of the cell's set-up on the chip's host)."""
    S, H, V = q_shape
    _, W, BS = pool_shape
    f32 = jnp.float32
    packed = jnp.dtype(pool_dtype).itemsize == 2
    return jax.jit(pl.pallas_call(
        functools.partial(_kernel, block_size=BS, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((1, H, V), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, H, W - V), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((W, LANES), lambda s, *_: (0, s // LANES)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((1, H, V), lambda s, *_: (s, 0, 0)),
                       pl.BlockSpec(memory_space=pl.ANY)],
            scratch_shapes=[*[pltpu.VMEM((2, W, BS), pool_dtype)] * ENTRIES,
                            pltpu.SemaphoreType.DMA((ENTRIES, 2)),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.VMEM((H, 1), f32), pltpu.VMEM((H, 1), f32),
                            pltpu.VMEM((H, V), f32),
                            # the new row as a column (in words), the
                            # tails' staging buffers, their semaphores
                            # and their count
                            (pltpu.VMEM((W // 2, BS), jnp.uint32) if packed
                             else pltpu.VMEM((W, BS), pool_dtype)),
                            pltpu.VMEM((2, W, BS), pool_dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((S, H, V), q_dtype),
                   jax.ShapeDtypeStruct(pool_shape, pool_dtype)],
        # the pool, after base, tables, q_lat, q_rope, rows
        input_output_aliases={5: 1},
        # in order: a step starts the next step's first block
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=NAME,
    ))


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
