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
that landed on a held expert sorted by expert, a grouped matmul over
those rows only, the weighted sum back to tokens, and the routing
counters. Picks on absent experts are left out: their holders add those
parts. Nothing stands in for the other chips. The scopes
(``moe_dispatch``, ``moe_experts``, ``moe_combine``) are the names the
trace readers know.

The grouped matmul has two forms of one algorithm (sorted rows against
per-group weights), chosen from its static shapes (:func:`matmul_form`):
the small-tile Pallas kernel of ``ops/pallas/grouped_matmul.py``
(``held_experts_grouped_matmul``: a row tile of 16-128 rows chosen from
the mean group, every hit expert's weights read once in blocks of
megabytes; interpret mode off the TPU) wherever the buffer holds a row
tile, and ``jax.lax.ragged_dot`` under one (a toy batch). On a TPU the
compiler makes a Mosaic kernel of its own of a ``ragged_dot``
(``ragged-dot-none``), which computes a whole row tile of up to 512 rows
for every group that touches it and moves its weights in ``[512, 512]``
blocks: a decode step's groups of 1-13 rows paid for 128-256 rows and a
thousand grid steps a call.

Shared code: it imports no model.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.grouped_matmul import (MIN_ROW_TILE,
                                                     grouped_matmul)
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.telemetry.registry import get_registry

F32 = jnp.float32

# the counters a family's ``cache.aux`` row ends with, after the picks on
# each held expert
COUNTER_TAIL = ("identity_picks", "absent_picks", "tokens_routed",
                "layer_calls", "held_experts_hit")


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


@scoped("moe_dispatch")
def _gather_rows(u, order, k: int, rows: int):
    """The tokens of the first ``rows`` sorted picks, ``[rows, E]``."""
    return u[order[:rows] // k]


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
def _experts(xs, group_sizes, ex, act: str = "swiglu"):
    """Each row's expert (``act``: :data:`EXPERT_FORMS`): a grouped
    matmul that visits only the rows inside the groups, in the form
    :func:`matmul_form` gives its shapes (counted once a traced call site
    in ``serve_moe_expert_matmul_sites_total``). Rows past the groups
    come back as whatever the kernel left there."""
    dt = xs.dtype
    R = xs.shape[0]
    form = matmul_form(R)
    get_registry().counter(
        "serve_moe_expert_matmul_sites_total",
        help="held-experts grouped matmuls traced into a program, by the "
             "form their static shapes chose (tiled: the small-tile "
             "Pallas kernel; ragged_dot: the compiler's) and the rows "
             "of their buffer, and the experts' own form (act)",
        labels={"form": form, "rows": str(R), "act": act}).inc()
    matmul = grouped_matmul if form == "tiled" else jax.lax.ragged_dot
    h = EXPERT_FORMS[act](matmul(xs, ex["w_in"].astype(dt), group_sizes))
    return matmul(h.astype(dt), ex["w_out"].astype(dt), group_sizes)


@scoped("moe_combine")
def _combine_landed(out, where, held, weights):
    """Each token's weighted sum over its landed picks: ``assign [T,
    rows]`` holds a pick's weight at its row and the sum is one float32
    product, so no per-pick copy of ``out`` is made."""
    rows = out.shape[0]
    at = where[..., None] == jnp.arange(rows, dtype=where.dtype)
    assign = jnp.sum(jnp.where(at & held[..., None], weights[..., None],
                               0.0), axis=1)                  # [T, rows]
    landed = jnp.arange(rows) < jnp.sum(held)
    return jnp.dot(assign, jnp.where(landed[:, None], out.astype(F32), 0.0),
                   precision=jax.lax.Precision.HIGHEST)


# above this many cells the ``[T, rows]`` assignment of
# :func:`_combine_landed` (and its float32 product) costs more than
# gathering each pick's row: a decode batch and a short prompt stay
# under it, a prompt of thousands of tokens does not
ASSIGN_CELLS = 1 << 22


@scoped("moe_combine")
def _combine_gathered(out, where, held, weights):
    """:func:`_combine_landed` for many tokens: each pick's row gathered
    and weighted, ``[T, k, E]`` summed over the picks (rows past the
    groups are never selected)."""
    rows = out.shape[0]
    ok = held & (where < rows)
    picked = out[jnp.minimum(where, rows - 1)].astype(F32)    # [T, k, E]
    return jnp.sum(jnp.where(ok[..., None], picked * weights[..., None],
                             0.0), axis=1)


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
    ``w_out [X, Fe, E]``."""
    T, k = weights.shape

    def over(rows):
        combine = (_combine_landed if T * rows <= ASSIGN_CELLS
                   else _combine_gathered)

        def run():
            out = _experts(_gather_rows(u, order, k, rows), group_sizes, ex,
                           act)
            return combine(out, where, held, weights)
        return run
    fast = min(fast or fast_rows(T, k), T * k)
    if fast == T * k:
        return over(fast)()
    return jax.lax.cond(jnp.sum(group_sizes) <= fast, over(fast),
                        over(T * k))


def routing_counts(picks, held, group_sizes, valid, n_routed: int):
    """One call's row of counters (int32): the picks on each held expert,
    then :data:`COUNTER_TAIL`. Router outputs from ``n_routed`` on are
    zero-compute (identity) experts."""
    v = valid[:, None]
    identity = jnp.sum((picks >= n_routed) & v, dtype=jnp.int32)
    absent = jnp.sum((picks < n_routed) & v & ~held, dtype=jnp.int32)
    return jnp.concatenate([group_sizes, jnp.stack([
        identity, absent, jnp.sum(valid, dtype=jnp.int32), jnp.int32(1),
        jnp.sum(group_sizes > 0, dtype=jnp.int32)])])
