"""An expert layer's HELD share: what one chip of an expert-parallel
deployment computes of a sparse layer, for any family that routes over
all of its router's outputs and holds a contiguous range of the real
experts (``held = (lo, hi)``).

The family routes (scores, selection and weights are its own: a softmax
with zero-compute experts, a sigmoid with normalised weights and a
shared expert, the same under a group limit: :func:`group_limited_top_k`,
:func:`sigmoid_route`) and names its experts' form (:data:`EXPERT_FORMS`:
gated SwiGLU, ungated relu squared);
what is here is everything after the picks: the picks
that landed on a held expert sorted by expert and laid out for the
grouped matmul (:func:`_align`), a grouped matmul over those rows only,
the weighted sum back to tokens, and the routing counters. Picks on
absent experts are left out: their holders add those parts. Nothing
stands in for the other chips. The scopes
(``moe_dispatch``, ``moe_experts``, ``moe_combine``) are the names the
trace readers know.

The grouped matmul has two forms of one algorithm (sorted rows against
per-group weights), chosen from its static shapes (:func:`matmul_form`):
the small-tile Pallas kernel of ``ops/pallas/grouped_matmul.py``
(``held_experts_grouped_matmul``: a row tile of 16-128 rows chosen from
the mean group, every hit expert's weights read once in blocks of
megabytes; interpret mode off the TPU) wherever the buffer holds a row
tile, and ``jax.lax.ragged_dot`` under one (a toy batch). The kernel
reads ONE layout: every group starts on a boundary of the row tile
(sorted pick ``j`` of group ``g`` at row ``astart[g] + j - start[g]``,
``astart`` the running sum of the sizes rounded up to whole tiles), so
that a row tile belongs to one group and a hit expert's weight block goes
through the MXU ``ceil(n_g / tm)`` times; the buffer is a static function
of the rows it had packed end to end (``grouped_matmul.aligned_rows``:
at most ``tm - 1`` rows more a group), the rows between a group's last
pick and its tile's end hold a finite row that is no pick and are masked
in the combine, and ``ragged_dot`` reads the same layout at a tile of 1,
which is the picks packed end to end. The padding is rows the kernel
never computes, so what it costs is what the layer around the kernel does
to every row of the buffer, and both of those adapt from static shapes:
the dispatch copies a decode batch's rows by a one-hot product and
gathers a prompt's (:data:`ONE_HOT_CELLS`), the combine multiplies by the
weights' three bfloat16 parts or gathers each pick's row
(:func:`combine_form`). On a TPU the
compiler makes a Mosaic kernel of its own of a ``ragged_dot``
(``ragged-dot-none``), which computes a whole row tile of up to 512 rows
for every group that touches it and moves its weights in ``[512, 512]``
blocks: a decode step's groups of 1-13 rows paid for 128-256 rows and a
thousand grid steps a call.

Shared code: it imports no model.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.grouped_matmul import (MIN_ROW_TILE,
                                                     aligned_rows,
                                                     aligned_starts,
                                                     grouped_matmul,
                                                     row_tile)
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.telemetry.registry import get_registry

F32 = jnp.float32

# the counters a family's ``cache.aux`` row ends with, after the picks on
# each held expert
COUNTER_TAIL = ("identity_picks", "absent_picks", "tokens_routed",
                "layer_calls", "held_experts_hit", "row_tiles_walked")


def counter_series(reg, num_held: int, programs) -> list:
    """The registry counter behind each cell of an ``aux`` of routing
    rows (docs/observability.md "Latent attention and the expert
    layer"), ``[program][column]``: the picks on each held expert, then
    :data:`COUNTER_TAIL`."""
    def series(program: str) -> list:
        by = {"program": program}
        tail = {
            "identity_picks": reg.counter(
                "serve_moe_identity_picks_total", labels=by,
                help="top-k picks on zero-compute (identity) "
                     "experts: (sum of weights) x hidden, no matmul"),
            "absent_picks": reg.counter(
                "serve_moe_absent_picks_total", labels=by,
                help="top-k picks on real experts this process does "
                     "not hold (their holders add those parts)"),
            "tokens_routed": reg.counter(
                "serve_moe_tokens_routed_total", labels=by,
                help="tokens the expert layers routed (one per "
                     "token per MoE layer)"),
            "layer_calls": reg.counter(
                "serve_moe_layer_calls_total", labels=by,
                help="expert-layer executions"),
            "held_experts_hit": reg.counter(
                "serve_moe_held_experts_hit_total", labels=by,
                help="held experts with at least one pick, summed "
                     "over expert-layer executions (the weights an "
                     "execution has to read)"),
            "row_tiles_walked": reg.counter(
                "serve_moe_row_tiles_walked_total", labels=by,
                help="row tiles the small-tile grouped matmul walked "
                     "(ceil(picks / row tile) a held expert, summed over "
                     "expert-layer executions; 0 under ragged_dot): over "
                     "held_experts_hit, the times a weight block goes "
                     "through the MXU"),
        }
        return [reg.counter(
            "serve_moe_held_expert_picks_total",
            help="top-k picks that landed on a real expert this "
                 "process holds, by held expert",
            labels={"program": program, "expert": str(x)})
            for x in range(num_held)
        ] + [tail[name] for name in COUNTER_TAIL]
    return [series(program) for program in programs]


def group_limited_top_k(choice, k: int, n_group: int, topk_group: int):
    """Group-limited (node-limited) selection, a routing rule that comes
    before :func:`sort_picks`: ``choice [T, R]`` float32 (scores plus the
    selection bias) -> picks ``[T, k]``. The ``R`` outputs are
    ``n_group`` groups of consecutive experts; a group's score is the sum
    of its two largest entries, the ``topk_group`` best groups are kept
    and the ``k`` largest entries among THEIR experts are the picks. Ties
    go to the lower group and the lower expert."""
    if topk_group == n_group:       # every group is kept: no limit
        return jax.lax.top_k(choice, k)[1]
    T, R = choice.shape
    per = R // n_group
    group_score = jnp.sum(jax.lax.top_k(
        choice.reshape(T, n_group, per), 2)[0], axis=-1)     # [T, n_group]
    _, keep = jax.lax.top_k(group_score, topk_group)
    kept = jnp.any(keep[:, :, None] == jnp.arange(n_group)[None, None],
                   axis=1)                                   # [T, n_group]
    return jax.lax.top_k(jnp.where(jnp.repeat(kept, per, axis=1), choice,
                                   -jnp.inf), k)[1]


def spread_selection_bias(n: int, spread: float):
    """A seeded model's selection bias ``[n]`` float32 that is NOT drawn
    from the seed: +-``spread``, evenly spaced and centred, the same set
    in every aligned group of 16 experts (so any contiguous share of
    whole groups carries the same set)."""
    i = jnp.arange(n)
    return (spread * (2.0 * ((7 * i) % 16 + 0.5) / 16.0 - 1.0)).astype(F32)


def sigmoid_route(u, router, bias, k: int, n_group: int, topk_group: int,
                  scale: float):
    """The sigmoid router with a selection bias (DeepSeek-V3's): ``u [T,
    E]`` -> picks ``[T, k]`` and their weights ``[T, k]`` float32. Scores
    are a float32 sigmoid over ALL router outputs; the bias moves the
    selection (of groups and of experts) and never the weights; the
    weights are the picked scores normalised to sum to ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(F32), router.astype(F32),
        precision=jax.lax.Precision.HIGHEST))
    picks = group_limited_top_k(scores + bias.astype(F32), k, n_group,
                                topk_group)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    return picks, scale * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


@scoped("moe_dispatch")
def sort_picks(picks, valid, held_range):
    """The picks in the order a grouped matmul wants them: those that
    landed on a held expert first, by expert. Returns ``order [T k]``
    (pick numbers, sorted), ``where [T, k]`` (each pick's place in that
    order), ``held [T, k]`` and ``group_sizes [X]``."""
    lo, hi = held_range
    T, k = picks.shape
    held = (picks >= lo) & (picks < hi) & valid[:, None]
    key = jnp.where(held, picks - lo, hi - lo).reshape(-1)
    order = jnp.argsort(key, stable=True)
    where = jnp.argsort(order).reshape(T, k)       # the order's inverse
    group_sizes = jnp.sum(
        key[:, None] == jnp.arange(hi - lo, dtype=key.dtype)[None],
        axis=0, dtype=jnp.int32)
    return order, where, held, group_sizes


def _exact(dtype):
    """The precision at which a product against rows of ``dtype`` keeps
    every bit of them: float32 rows want the compiler's six bfloat16
    passes, bfloat16 rows are one pass as they are."""
    return jax.lax.Precision.HIGHEST if dtype == F32 else None


# the dispatch copies ``rows`` rows of ``u [T, E]``. A gather moves a row of
# 4-14 KB in ~15 ns whatever ``T`` is; a one-hot ``[rows, T] x [T, E]``
# product in the rows' dtype copies the same rows to the bit (one 1 a row,
# float32 sums) in ``2 T E`` operations a row, which is under that while ``T
# E`` is at most this (on the chip at 3008 rows of 256 tokens x 2688: 0.096
# ms against 0.137, at 1728 of 96 x 4096: 0.089 against 0.112; at 7680 of
# 512 x 4096 the product loses, 0.311 against 0.204: PERF.md section 6, PR
# 59). A decode batch takes the product, a prompt's bucket the gather
ONE_HOT_CELLS = 1 << 20


@scoped("moe_dispatch")
def _align(u, order, where, held, group_sizes, k: int, rows: int, tm: int):
    """The landed picks' tokens laid out for the grouped matmul in a
    buffer of ``rows`` rows (``grouped_matmul.aligned_rows``), every
    group on a boundary of the row tile ``tm``: sorted pick ``j`` of group
    ``g`` at row ``astart[g] + (j - start[g])``
    (``grouped_matmul.aligned_starts``; at ``tm`` 1 the picks packed end
    to end, what ``ragged_dot`` reads). Returns ``xs [rows, E]`` (a row
    past its group's size is finite and no pick: zeros under the one-hot
    product, some token's row under the gather: :data:`ONE_HOT_CELLS`),
    ``landed [rows]`` (the rows that are picks) and ``where [T, k]`` (each
    pick's row)."""
    T, E = u.shape
    end = jnp.cumsum(group_sizes)
    astart, aend = aligned_starts(group_sizes, tm)
    # rows of a group's last tile past its size: what every later group
    # is moved by, and the rows of the buffer that are no pick
    waste = aend - astart - group_sizes
    at = where + jnp.sum(jnp.where(end <= where[..., None], waste, 0),
                         axis=-1)
    if T * E <= ONE_HOT_CELLS:
        hot = jnp.any((at[..., None] == jnp.arange(rows, dtype=at.dtype))
                      & held[..., None], axis=1)              # [T, rows]
        xs = jax.lax.dot_general(
            hot.astype(u.dtype), u, (((0,), (0,)), ((), ())),
            precision=_exact(u.dtype),
            preferred_element_type=F32).astype(u.dtype)
        return xs, jnp.any(hot, axis=0), at
    r = jnp.arange(rows, dtype=jnp.int32)[:, None]
    j = r[:, 0] - jnp.sum(jnp.where(aend <= r, waste, 0), axis=1)
    landed = (r[:, 0] < aend[-1]) & ~jnp.any(
        (r >= aend - waste) & (r < aend), axis=1)
    xs = u[order[jnp.clip(j, 0, order.shape[0] - 1)] // k]
    return xs, landed, at


def matmul_form(R: int) -> str:
    """``"tiled"`` or ``"ragged_dot"`` for a buffer of ``R`` rows: the
    small-tile kernel wherever the buffer holds one of its row tiles. On
    the chip it was measured faster than the compiler's kernel at every
    geometry the four families give it, from 3 rows a group in a 256-row
    buffer to 711 in 25,600 (PERF.md section 6, PR 52), so no shape with
    a tile to fill is left to the compiler. Under a row tile (a toy
    batch) there is nothing to tile, and the compiler expands such a
    ``ragged_dot`` into plain products."""
    return "tiled" if R >= MIN_ROW_TILE else "ragged_dot"


def expert_row_tile(R: int, X: int, E: int, Fe: int, itemsize: int) -> int:
    """The row tile the groups of ``R`` landed picks are laid out on: ONE
    value for both matmuls of an expert (``w_in [X, E, .]``, ``w_out [X,
    Fe, E]``), the smaller of what ``grouped_matmul.row_tile`` gives each
    from its shapes; 1 (the picks packed end to end) where
    :func:`matmul_form` leaves the buffer to ``ragged_dot``."""
    if matmul_form(R) != "tiled":
        return 1
    return min(row_tile(R, X, E, itemsize), row_tile(R, X, Fe, itemsize))


def swiglu(gu):
    """``silu(gate) * up`` of ``gu [R, 2 Fe]`` (gate ; up), float32."""
    Fe = gu.shape[-1] // 2
    return jax.nn.silu(gu[:, :Fe].astype(F32)) * gu[:, Fe:].astype(F32)


def relu2(u):
    """``relu(u)^2`` of an UNGATED expert's ``u [R, Fe]``, float32."""
    return jnp.square(jax.nn.relu(u.astype(F32)))


# an expert's form, by the name a family gives :func:`held_experts_part`:
# what stands between its two grouped matmuls. ``w_in`` is ``[X, E, 2
# Fe]`` (gate ; up) for a gated form and ``[X, E, Fe]`` for an ungated one
EXPERT_FORMS = {"swiglu": swiglu, "relu2": relu2}


@scoped("moe_experts")
def _experts(xs, group_sizes, ex, act: str = "swiglu", *, tm: int):
    """Each row's expert (``act``: :data:`EXPERT_FORMS`) over rows laid
    out by :func:`_align` at the row tile ``tm`` (:func:`expert_row_tile`):
    a grouped matmul that visits only the tiles the groups reach (counted
    once a traced call site in ``serve_moe_expert_matmul_sites_total``).
    Rows that are no pick come back as whatever the kernel left there."""
    dt = xs.dtype
    R = xs.shape[0]
    form = matmul_form(R)   # the aligned buffer holds a tile iff the picks did
    get_registry().counter(
        "serve_moe_expert_matmul_sites_total",
        help="held-experts grouped matmuls traced into a program, by the "
             "form their static shapes chose (tiled: the small-tile "
             "Pallas kernel; ragged_dot: the compiler's), the rows of "
             "their buffer and the row tile its groups are laid out on, "
             "and the experts' own form (act)",
        labels={"form": form, "rows": str(R), "row_tile": str(tm),
                "act": act}).inc()
    matmul = (functools.partial(grouped_matmul, tm=tm) if form == "tiled"
              else jax.lax.ragged_dot)
    h = EXPERT_FORMS[act](matmul(xs, ex["w_in"].astype(dt), group_sizes))
    return matmul(h.astype(dt), ex["w_out"].astype(dt), group_sizes)


@scoped("moe_combine")
def _combine_landed(out, landed, where, held, weights):
    """Each token's weighted sum over its landed picks: ``assign [T,
    rows]`` holds a pick's weight at its row and the sum is one float32
    product, so no per-pick copy of ``out`` is made. ``landed [rows]``
    masks the rows that are no pick (the rest of a group's last tile,
    and the tiles no group reached, which hold whatever was there).
    Against bfloat16 rows the float32 weights go in as their three
    bfloat16 parts (8 + 8 + 8 bits of mantissa: every bit), one pass
    each with float32 sums: what ``Precision.HIGHEST`` computes in six
    passes, three of them against the zero low parts of the rows."""
    rows = out.shape[0]
    at = where[..., None] == jnp.arange(rows, dtype=where.dtype)
    assign = jnp.sum(jnp.where(at & held[..., None], weights[..., None],
                               0.0), axis=1)                  # [T, rows]
    out = jnp.where(landed[:, None], out, 0)
    if out.dtype != jnp.bfloat16:
        return jnp.dot(assign, out.astype(F32),
                       precision=jax.lax.Precision.HIGHEST)
    hi = assign.astype(out.dtype)
    mid = (assign - hi.astype(F32)).astype(out.dtype)
    lo = (assign - hi.astype(F32) - mid.astype(F32)).astype(out.dtype)

    def part(a):
        return jnp.dot(a, out, preferred_element_type=F32)
    return part(lo) + part(mid) + part(hi)


@scoped("moe_combine")
def _combine_gathered(out, where, held, weights):
    """:func:`_combine_landed` for a long buffer: each pick's row gathered
    and weighted, ``[T, k, E]`` float32 summed over the picks (a row that
    is no pick is never selected, so no mask of the rows is read)."""
    rows = out.shape[0]
    ok = held & (where < rows)
    picked = out[jnp.minimum(where, rows - 1)].astype(F32)    # [T, k, E]
    return jnp.sum(jnp.where(ok[..., None], picked * weights[..., None],
                             0.0), axis=1)


# :func:`_combine_landed` keeps ``[T, rows]`` float32 beside its product:
# at most this many cells (16 MB), whatever else decides
ASSIGN_CELLS = 1 << 22
# both forms cost in proportion to ``T E``: the product ``rows`` x three
# passes (3.4-3.7e-14 s a cell on the chip), the gather ``k`` x a row read
# and weighted in float32 (7e-12 s a cell where it is large), so what
# decides between them is ROWS A PICK, padding included: ~200. Read on the
# chip either side of it (PERF.md section 6, PR 59): 96 tokens x 10 picks of
# 4096 over 1728 rows (173 a pick) 0.086 ms by the product and 0.111 by the
# gather; 1024 x 8 of 7168 over 1600 rows (200 a pick) 0.481 and 0.410; 256
# x 6 of 2688 over 3008 rows (501) 0.129 and 0.105
ASSIGN_ROWS_A_PICK = 192


def combine_form(T: int, k: int, rows: int, exact: bool) -> str:
    """``"landed"`` (:func:`_combine_landed`) or ``"gathered"`` for ``T``
    tokens' ``k`` picks out of a buffer of ``rows`` rows. The product
    wherever its ``[T, rows]`` fits :data:`ASSIGN_CELLS` and the gather is
    not the cheaper one (:data:`ASSIGN_ROWS_A_PICK`). The ``exact`` ``T k``
    fallback hardly ever runs, so what it costs the program is its
    temporaries: it keeps the product (``[T, rows]``) wherever that fits,
    and never holds ``[T, k, E]`` float32 for speed's sake (151 MB in
    LongCat's admission program, beside a pool of 302)."""
    if T * rows > ASSIGN_CELLS:
        return "gathered"
    return ("landed" if exact or rows <= ASSIGN_ROWS_A_PICK * k
            else "gathered")


def fast_rows(T: int, k: int) -> int:
    """Rows the expert matmul is given when the landed picks fit them
    (nearly always: 1 pick in 48 lands at LongCat-Flash's published
    sizes, 1 in 8 at an eighth of 256 experts, and this is T / 2 or
    128). The buffer bounds the dispatch gather and the combine's
    ``[T, rows]`` product; the small-tile grouped matmul itself visits
    only the row tiles the groups reach, so its slack costs little
    (under the compiler's kernel, which computed a whole tile of up to
    512 rows a group, the buffer's size WAS the matmul's work); ``T k``
    rows stay the exact fallback."""
    return min(T * k, max(128, T // 2))


def expected_rows(T: int, k: int, share: float) -> int:
    """Rows for the expert matmul of a family whose picks land on the
    held share evenly (a pick in ``1 / share``): what ``T`` tokens'
    ``k`` picks land there plus six standard deviations, in whole tiles
    of 128; the rare step with more takes the exact ``T k`` fallback."""
    picks = T * k
    landed = picks * share + 6.0 * math.sqrt(picks * share * (1 - share))
    return min(picks, 128 * max(1, math.ceil(landed / 128)))


def held_experts_part(u, order, where, held, weights, group_sizes, ex,
                      fast=None, act: str = "swiglu"):
    """The held real experts' part of the layer, ``[T, E]`` float32: over
    the first ``fast`` sorted picks (:func:`fast_rows` unless the family
    knows its share better) when all the landed ones are among them,
    else over all ``T k``. Exact either way. ``ex``: ``w_in [X, E, 2
    Fe]`` (gate ; up; ``[X, E, Fe]`` under an ungated ``act``) and
    ``w_out [X, Fe, E]``. With it, the row tiles the grouped matmul
    walked (int32; 0 under ``ragged_dot``), for
    :func:`routing_counts`."""
    T, k = weights.shape
    X, Fe, E = ex["w_out"].shape

    def over(picks, exact=False):
        tm = expert_row_tile(picks, X, E, Fe, jnp.dtype(u.dtype).itemsize)
        rows = aligned_rows(picks, X, tm)
        form = combine_form(T, k, rows, exact)

        def run():
            xs, landed, at = _align(u, order, where, held, group_sizes, k,
                                    rows, tm)
            out = _experts(xs, group_sizes, ex, act, tm=tm)
            walked = (jnp.sum(-(-group_sizes // tm), dtype=jnp.int32)
                      if matmul_form(picks) == "tiled" else jnp.int32(0))
            part = (_combine_landed(out, landed, at, held, weights)
                    if form == "landed"
                    else _combine_gathered(out, at, held, weights))
            return part, walked
        return run
    fast = min(fast or fast_rows(T, k), T * k)
    if fast == T * k:
        return over(fast)()
    return jax.lax.cond(jnp.sum(group_sizes) <= fast, over(fast),
                        over(T * k, exact=True))


def routing_counts(picks, held, group_sizes, valid, n_routed: int, walked):
    """One call's row of counters (int32): the picks on each held expert,
    then :data:`COUNTER_TAIL`. Router outputs from ``n_routed`` on are
    zero-compute (identity) experts; ``walked`` is what
    :func:`held_experts_part` returned beside its part."""
    v = valid[:, None]
    identity = jnp.sum((picks >= n_routed) & v, dtype=jnp.int32)
    absent = jnp.sum((picks < n_routed) & v & ~held, dtype=jnp.int32)
    return jnp.concatenate([group_sizes, jnp.stack([
        identity, absent, jnp.sum(valid, dtype=jnp.int32), jnp.int32(1),
        jnp.sum(group_sizes > 0, dtype=jnp.int32), walked])])
