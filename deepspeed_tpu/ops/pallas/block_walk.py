"""The live-block walk of the paged decode kernels.

A paged pool stays in HBM (``memory_space=pl.ANY``) and a kernel's grid
runs over slots, in order on one core. One grid step walks ITS slot's
live blocks in a loop whose trip count the kernel reads from its
scalar-prefetched bounds: table entry ``j`` is copied whole, as the
contiguous slab it is in HBM, into one of two VMEM buffers a stream
(``make_async_copy``) while block ``j - 1`` is attended, and the last
block's iteration starts the FIRST block of the next grid step that has
one, so the stream does not drain where one slot ends and the next
begins. A dead table entry is never read and costs nothing; an idle
step (trip count 0) reads nothing and passes the start on. Every byte
moved is a live block's, and what a grid step costs beyond its bytes is
paid once a slot, not once a table entry.

:func:`walk_live_blocks` is that scaffold and nothing else: which arrays
stream, how a table entry becomes a block id and what is done with a
block that has landed are the kernel's. Both callers walk several table
entries an iteration where a block is too small to hide the latency of
its own chain, each entry through streams of its own:
``decode_attention._paged_kernel`` streams K and V (one to four entries
an iteration by the bytes of a block; an int8 pool's scale tiles, verify
windows and prefill chunks an entry an iteration),
``latent_decode_attention._kernel`` one latent pool, three entries an
iteration.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def live_blocks(seen, block_size: int, max_blocks: int):
    """Table entries a query that sees ``seen`` positions has to walk."""
    blocks = jax.lax.div(jnp.maximum(seen, 0) + (block_size - 1), block_size)
    return jnp.minimum(blocks, max_blocks)


def walk_live_blocks(streams, sems, next_buf, block_id, *, slot, n, first,
                     slot_next, n_next, idle, walk):
    """One grid step's part of the walk.

    ``streams``: ``(hbm, vmem)`` pairs, ``hbm [blocks, ...]`` left in HBM
    and ``vmem [2, ...]`` its two buffers; ``sems``: DMA semaphores
    ``[len(streams), 2]``; ``next_buf``: SMEM ``[1]`` int32, the buffer
    the next step's first block lands in (it rides from step to step:
    the grid must run in order, ``dimension_semantics`` "arbitrary");
    ``block_id(slot, j)``: the block of every ``hbm`` that table entry
    ``j`` of ``slot`` names, or a tuple with an entry a stream, each a
    block or ``(block, live)``: a stream whose ``live`` is false sits
    that iteration out, no copy started and none waited for (a kernel
    that walks its table several entries an iteration, a stream each,
    has a last group short of entries). ``slot`` / ``n``: this step's
    slot and trip count; ``first``: whether it is the grid's first step
    (nobody has started its first block); ``slot_next`` / ``n_next``:
    the slot and trip count of the step after it (``n_next`` 0 at the
    grid's end).

    A step with ``n == 0`` calls ``idle()``; any other calls
    ``walk(loop)``, which sets its state up, calls ``loop(attend)`` once
    and finishes: ``attend(j, buf)`` is called for ``j = 0 .. n - 1`` in
    order with block ``j`` resident in ``vmem[buf]`` of every stream
    that did not sit out.
    """
    def each_copy(slot, j, buf, act: str):
        """``start`` or ``wait`` for table entry ``j`` of ``slot``, a
        copy a stream into its buffer ``buf``."""
        blocks = block_id(slot, j)
        if not isinstance(blocks, tuple):
            blocks = (blocks,) * len(streams)
        for i, ((hbm, vmem), block) in enumerate(zip(streams, blocks)):
            live = None
            if isinstance(block, tuple):    # a stream that may sit out
                block, live = block
            copy = pltpu.make_async_copy(hbm.at[block], vmem.at[buf],
                                         sems.at[i, buf])
            if live is None:
                getattr(copy, act)()
            else:
                pl.when(live)(getattr(copy, act))

    def start(slot, j, buf):
        each_copy(slot, j, buf, "start")

    def start_next(buf):
        @pl.when(n_next > 0)
        def _():
            start(slot_next, 0, buf)

    @pl.when(first)
    def _first():
        next_buf[0] = 0

        @pl.when(n > 0)
        def _():
            start(slot, 0, 0)

    buf0 = next_buf[0]      # where this step's first block is landing

    @pl.when(n == 0)
    def _idle():
        idle()
        start_next(buf0)

    @pl.when(n > 0)
    def _walk():
        def loop(attend):
            def block(j, carry):
                buf = (buf0 + j) % 2

                @pl.when(j + 1 < n)
                def _():
                    start(slot, j + 1, 1 - buf)

                @pl.when(j + 1 == n)
                def _():
                    start_next(1 - buf)
                each_copy(slot, j, buf, "wait")
                attend(j, buf)
                return carry

            jax.lax.fori_loop(0, n, block, 0)
            next_buf[0] = (buf0 + n) % 2

        walk(loop)
