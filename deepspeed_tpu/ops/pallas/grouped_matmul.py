"""A grouped matmul for SHORT groups: ``xs [R, K]`` against ``w [X, K,
N]``, every group's rows starting on a row-tile boundary of the buffer
(row ``astart[g] + r`` times ``w[g]`` for ``r < group_sizes[g]``,
``astart`` the exclusive cumulative sum of the sizes rounded up to whole
tiles: :func:`aligned_starts`), with ``jax.lax.ragged_dot``'s meaning for
the rows inside the groups.

The compiler's own ``ragged_dot`` kernel tiles its rows by the largest
power of two up to 512 that divides ``R`` (128 at 640 rows, 256 at 768)
and computes a whole tile for every group that touches it, in weight
blocks of ``[512, 512]``: a decode step's buffer (36 groups of ~13 rows
in 640) pays for 128 rows a group and a thousand grid steps a call, and
ran at 45-60 % of the weight read that should bound it. This kernel
walks the buffer's row tiles with a tile sized to the groups it is given
(:func:`row_tile`) and whole-``K`` weight blocks of megabytes:

* a row tile belongs to ONE group (the layout's doing: the caller lays
  the groups out on tile boundaries, ``held_experts._align``), so an item
  of the walk is a tile, a hit expert's weight block goes through the MXU
  ``ceil(n_g / tm)`` times, and the kernel is a dot and a store. (A walk
  over groups packed end to end, megablox's, computes a tile once for
  every group that straddles it: 1.4-1.7 passes a weight block at 12-14
  rows a group, each pass MXU time that the one-item lookahead of the
  pipeline cannot hide under the next block's fetch: -12 to -14 % of a
  call at 64 groups in 1024 rows, PERF.md section 6, PR 59.)
* the tiles' groups come from ``group_sizes`` by scalar prefetch
  (:func:`work_items`): ``R / tm`` items, of which the first ``n`` (the
  tiles the groups reach) do anything; an empty group gets none, so its
  weights are never fetched;
* ``K`` is whole in a block (no accumulator across grid steps) and the
  column tile ``tn`` is as wide as :data:`WEIGHT_BLOCK_BYTES` lets it
  be; the grid is (column tile, item) with the items innermost, so the
  tiles of one group keep one weight block (no second copy) and every
  ``(group, column tile)`` block crosses HBM once a call; the row tile
  is read again for each column tile, which is kilobytes;
* rows of a group's last tile past its size are computed from whatever
  the caller put there (a finite row) and are the caller's to mask;
  tiles past the last group are never written;
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
    """The smallest power of two from :data:`MIN_ROW_TILE` ABOVE the mean
    group of ``R`` rows packed end to end (``R / X``), held to
    :data:`MAX_ROW_TILE` and to a row block of :data:`ROW_BLOCK_BYTES`.
    A tile belongs to one group, so a group longer than the tile pays one
    more pass of its weight block through the MXU, and groups are uneven
    (the busiest near twice the mean): a tile AT the mean is outgrown by
    the busy half of the groups (16 against 32 at 64 groups in 1024 rows:
    1.22 passes a hit expert against 1.0-1.02 and -7.5 % against -12 % of
    the packed walk's time; a tile twice as wide again only pads: PERF.md
    section 7, PR 59)."""
    tm = MIN_ROW_TILE
    while tm * X <= R and tm < MAX_ROW_TILE:
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


def aligned_rows(R: int, X: int, tm: int) -> int:
    """Rows of the buffer that holds any ``R`` rows in ``X`` groups laid
    out on boundaries of ``tm``: a group that is not empty wastes at most
    ``tm - 1`` rows of its last tile, in whole tiles."""
    return (R + min(X, R) * (tm - 1)) // tm * tm


def aligned_starts(group_sizes, tm: int):
    """``(astart [X], aend [X])`` int32: the row each group starts on
    and the row after its last tile, every group given whole tiles of
    ``tm`` (an empty group none)."""
    padded = pl.cdiv(group_sizes.astype(jnp.int32), tm) * tm
    aend = jnp.cumsum(padded)
    return aend - padded, aend


def work_items(group_sizes, tiles: int, tm: int):
    """The walk: ``(group [tiles], n [1])`` int32. Item ``i < n`` is row
    tile ``i`` of the buffer and belongs to ``group[i]``; ``n`` is the
    tiles the groups reach (``ceil(size / tm)`` a group, groups in
    order). Items from ``n`` on repeat the last one, so that they move
    nothing."""
    X = group_sizes.shape[0]
    tile_end = aligned_starts(group_sizes, tm)[1] // tm
    n = jnp.minimum(tile_end[-1], tiles)
    i = jnp.clip(jnp.arange(tiles, dtype=jnp.int32), 0, jnp.maximum(n - 1, 0))
    group = jnp.minimum(jnp.sum(tile_end[None] <= i[:, None], axis=1,
                                dtype=jnp.int32), X - 1)
    return group, n[None]


def _kernel(group, n, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < n[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _call(R: int, X: int, K: int, N: int, tm: int, dtype, interpret: bool):
    """The ``pallas_call`` of one static signature."""
    itemsize = jnp.dtype(dtype).itemsize
    tn = column_tile(K, N, itemsize)

    def tile(i, n):         # items past the walk stay on its last tile
        return jnp.minimum(i, jnp.maximum(n[0] - 1, 0))
    return pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((R, N), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, R // tm),
            in_specs=[
                pl.BlockSpec((tm, K), lambda j, i, g, n: (tile(i, n), 0)),
                pl.BlockSpec((None, K, tn), lambda j, i, g, n: (g[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, g, n: (tile(i, n), j)),
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


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _grouped_matmul(xs, w, group_sizes, tm: int, interpret: bool):
    R, K = xs.shape
    X, _, N = w.shape
    dtype = jnp.dtype(xs.dtype)
    if R % tm:
        raise ValueError(f"grouped_matmul: {R} rows are no whole tiles of "
                         f"{tm}")
    items = work_items(group_sizes, R // tm, tm)
    return _call(R, X, K, N, tm, dtype, interpret)(*items, xs,
                                                   w.astype(dtype))


def grouped_matmul(xs, w, group_sizes, tm: int):
    """``xs [R, K]`` x ``w [X, K, N]`` by ``group_sizes [X]`` -> ``[R,
    N]`` in ``xs``'s dtype, the groups laid out on boundaries of the row
    tile ``tm`` (:func:`aligned_starts`; ``R`` whole tiles,
    :func:`aligned_rows` for any ``R`` rows packed end to end): rows
    ``astart[g] .. astart[g] + group_sizes[g]`` times ``w[g]``. The rest
    of a group's last tile comes back as the product of whatever was
    there, tiles past the last group as whatever was there. A function
    under ``jit`` of its own, so a program whose layers call it with one
    signature traces and lowers the walk and the kernel once, not once a
    layer (Laguna's six programs hold 264 call sites: +7 s of set-up
    otherwise)."""
    return _grouped_matmul(xs, w, group_sizes, tm, _should_interpret())
