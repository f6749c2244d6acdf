"""A grouped matmul for SHORT groups: ``xs [R, K]`` sorted by group
against ``w [X, K, N]``, row ``r`` of group ``g`` times ``w[g]``, with
``jax.lax.ragged_dot``'s meaning for the rows inside the groups.

The compiler's own ``ragged_dot`` kernel tiles its rows by the largest
power of two up to 512 that divides ``R`` (128 at 640 rows, 256 at 768)
and computes a whole tile for every group that touches it, in weight
blocks of ``[512, 512]``: a decode step's buffer (36 groups of ~13 rows
in 640) pays for 128 rows a group and a thousand grid steps a call, and
ran at 45-60 % of the weight read that should bound it. This kernel
walks (group, row tile) pairs with a row tile no larger than the groups
it is given (megablox's walk,
``jax.experimental.pallas.ops.tpu.megablox``) and whole-``K`` weight
blocks of megabytes; on a v5e it streams a decode step's expert weights
at ~675 GB/s of the chip's 819 (PERF.md section 6, PR 52):

* the work list comes from ``group_sizes`` by scalar prefetch, at most
  ``X + ceil(R / tm) - 1`` items; an empty group gets none, so its
  weights are never fetched;
* ``K`` is whole in a block (no accumulator across grid steps) and the
  column tile ``tn`` is as wide as :data:`WEIGHT_BLOCK_BYTES` lets it
  be; the grid is (column tile, item) with the items innermost, so the
  items of one group keep one weight block (no second copy) and every
  ``(group, column tile)`` block crosses HBM once a call; the row tile
  is read again for each column tile, which is kilobytes;
* rows of a tile that belong to a neighbouring group are masked on
  store; rows past the last group are never written;
* float32 sums, the output in the rows' dtype.

Two weight buffers of at most 4 MiB and the row and output tiles stay
inside the default 16 MiB of scoped VMEM: the call sets no
``vmem_limit_bytes`` (a call that raises it re-lays the fusions of the
whole program around it). The operands' memory space is the compiler's
to choose: a ``w`` that fits its VMEM budget whole (Laguna's ``w_out``,
64 MiB of a v5e's 128) it copies there ahead of the call, under the
program's earlier instructions, and the kernel's block copies then
never touch HBM.

An ``N`` that is no multiple of the 128 lanes is ONE block (a toy
width) or refused (:func:`column_tile`): a width such as 1856 (14.5 x
128) is served STORED rounded up to whole lanes, zeros past the width
(``nemotron_h.expert_stored_width``; an ungated ``relu(.)^2`` keeps
them zero). Tiles cannot cure what the storage causes: the chip lays a
``w [64, 2688, 1856]`` out with the 2688 minor, the call wants it
row-major, and the program then copies the whole 638 MB array before
every call (compiled for a described v5e: PERF.md section 6, PR 58).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "held_experts_grouped_matmul"

# the least rows a tile holds (bfloat16 packs 16 rows a sublane tile)
# and the most (the MXU's own 128 rows)
MIN_ROW_TILE, MAX_ROW_TILE = 16, 128
# one weight block ``[K, tn]`` and one row block ``[tm, K]``: two of
# each are in flight
WEIGHT_BLOCK_BYTES = 4 << 20
ROW_BLOCK_BYTES = 1 << 20
LANES = 128


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def row_tile(R: int, X: int, K: int, itemsize: int) -> int:
    """The smallest power of two from :data:`MIN_ROW_TILE` that is not
    below the mean group (``R / X`` rounded up), held to
    :data:`MAX_ROW_TILE` and to a row block of
    :data:`ROW_BLOCK_BYTES`."""
    tm = MIN_ROW_TILE
    while tm < min(math.ceil(R / X), MAX_ROW_TILE):
        tm *= 2
    while tm > MIN_ROW_TILE and tm * K * itemsize > ROW_BLOCK_BYTES:
        tm //= 2
    return tm


def column_tile(K: int, N: int, itemsize: int) -> int:
    """The widest tile of whole lanes that divides ``N`` with a weight
    block ``[K, tn]`` of at most :data:`WEIGHT_BLOCK_BYTES`. An ``N``
    that is no multiple of the lanes is one block if that fits the
    budget (a toy width) and refused if not: its weights are to be
    stored padded to whole lanes."""
    if N % LANES:
        if K * N * itemsize > WEIGHT_BLOCK_BYTES:
            raise ValueError(
                f"grouped_matmul: N = {N} is no multiple of {LANES} and a "
                f"[{K}, {N}] weight block is over {WEIGHT_BLOCK_BYTES} "
                "bytes: store the weights padded to whole lanes (zero "
                "columns of an up projection, zero rows of the down "
                "projection that contracts over them)")
        return N
    fit = [tn for tn in range(LANES, N + 1, LANES)
           if N % tn == 0 and K * tn * itemsize <= WEIGHT_BLOCK_BYTES]
    return max(fit, default=LANES)


def work_items(group_sizes, R: int, tm: int):
    """The walk: ``(group [I], tile [I], start [X], end [X], n [1])``
    int32, ``I = X + ceil(R / tm) - 1``. Item ``i < n`` is row tile
    ``tile[i]`` of group ``group[i]``, whose rows are ``start .. end``;
    groups in order and a group's tiles in order, empty groups left out.
    Items from ``n`` on repeat the last one, so that they move nothing."""
    X = group_sizes.shape[0]
    items = X + pl.cdiv(R, tm) - 1
    end = jnp.minimum(jnp.cumsum(group_sizes.astype(jnp.int32)), R)
    start = jnp.concatenate([jnp.zeros(1, jnp.int32), end[:-1]])
    first = start // tm
    tiles = jnp.where(end > start, (end - 1) // tm - first + 1, 0)
    item_end = jnp.cumsum(tiles)
    n = item_end[-1]
    i = jnp.clip(jnp.arange(items, dtype=jnp.int32), 0, jnp.maximum(n - 1, 0))
    group = jnp.minimum(jnp.sum(item_end[None] <= i[:, None], axis=1,
                                dtype=jnp.int32), X - 1)
    tile = first[group] + i - (item_end[group] - tiles[group])
    return group, jnp.maximum(tile, 0), start, end, n[None]


def _kernel(group, tile, start, end, n, x_ref, w_ref, o_ref, *, tm: int):
    i = pl.program_id(1)

    @pl.when(i < n[0])
    def _():
        g = group[i]
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = tile[i] * tm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        mine = (row >= start[g]) & (row < end[g])
        o_ref[...] = jnp.where(mine, acc, o_ref[...].astype(jnp.float32)
                               ).astype(o_ref.dtype)


def _call(R: int, X: int, K: int, N: int, dtype, interpret: bool):
    """The ``pallas_call`` of one static signature."""
    itemsize = jnp.dtype(dtype).itemsize
    tm, tn = row_tile(R, X, K, itemsize), column_tile(K, N, itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((R, N), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N // tn, X + pl.cdiv(R, tm) - 1),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, i, g, t, *_: (t[i], 0)),
                pl.BlockSpec((None, K, tn),
                             lambda j, i, g, t, *_: (g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, g, t, *_: (t[i], j)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * R * K * N, transcendentals=0,
            bytes_accessed=(X * K * N + R * K * (N // tn) + R * N)
            * itemsize),
        interpret=interpret,
        name=NAME,
    )


@functools.partial(jax.jit, static_argnames="interpret")
def _grouped_matmul(xs, w, group_sizes, interpret: bool):
    R, K = xs.shape
    X, _, N = w.shape
    dtype = jnp.dtype(xs.dtype)
    items = work_items(group_sizes, R, row_tile(R, X, K, dtype.itemsize))
    return _call(R, X, K, N, dtype, interpret)(*items, xs, w.astype(dtype))


def grouped_matmul(xs, w, group_sizes):
    """``xs [R, K]`` x ``w [X, K, N]`` by ``group_sizes [X]`` -> ``[R,
    N]`` in ``xs``'s dtype: the first ``group_sizes[0]`` rows times
    ``w[0]``, the next ``group_sizes[1]`` times ``w[1]``, ... Rows past
    the groups come back as whatever was there. A function under ``jit``
    of its own, so a program whose layers call it with one signature
    traces and lowers the walk and the kernel once, not once a layer
    (Laguna's six programs hold 264 call sites: +7 s of set-up
    otherwise)."""
    return _grouped_matmul(xs, w, group_sizes, _should_interpret())
