"""Pallas flash attention (training) — fused causal attention for the MXU.

TPU-native replacement for the reference's fused attention-softmax kernels
(``csrc/transformer/softmax_kernels.cu:attn_softmax``, used by the training
transformer kernel N10). Instead of materializing the [T, T] attention matrix
in HBM, the kernel streams K/V blocks through VMEM with the online-softmax
recurrence, accumulating in fp32 — O(T) memory, MXU-shaped [128, D] matmuls.

Layout: q ``[B, T, H, D]``; k/v may carry fewer heads (``[B, T, HKV, D]``,
HKV | H — grouped-query attention without materializing repeated k/v).
The kernels work on ``[B*H, T, D]`` q with a (kv-head, group) grid: ONE
GRID STEP IS ONE QUERY HEAD, whole in VMEM with K and V of its kv head
(the group axis is the fastest, so a kv head streams in once for its
whole query group), and the sweep over its ``[BQ, BK]`` score blocks is
code inside the step. The wrapper counts the step's bytes: a head within
the 16 MiB every kernel gets asks for nothing, a longer one for what it
counts, and one that does not fit the chip is refused (T <= 32k forward,
16k backward at D=128 in bf16 — longer sequences shard over the ``seq``
axis via ring attention, see ops/ring_attention.py).

What a score block costs is set by how much of it the scheduler can
overlap, not by the products: a block is a chain (scores -> exponentials
-> second product -> accumulate), and a loop runs the chains one after
another. So a head of up to ``_UNROLL_BLOCKS`` blocks (T <= 2048 at the
default blocks) is written out block by block, with static slices and
masks, and the scheduler interleaves one block's products with its
neighbour's vector work: 2.5 x the looped form on the chip at T 1024
(PERF.md section 6, PR 46). A longer head loops over its q (k) blocks;
inside one, a row whose bounds are static (no mask) and that is itself
at most ``_UNROLL_BLOCKS`` long is still written out (81-89 % of peak at
T 4096 / 8192 against 53-55 % in groups), any other loops over groups of
``_GROUP`` score blocks written out, then over the blocks left over.

Forward: queries down, keys across (``s [BQ, BK]``, both products plain
for the MXU); the row logsumexp is turned once a q block and stored with
positions on the lanes, ``[B*H, T/BQ, BQ]``. Backward: ONE kernel (the
``custom_vjp`` residuals are (q, k, v, out, logsumexp)). For a K block it
sweeps the visible Q blocks once, keys DOWN and queries ACROSS (``s``,
``p``, ``dp``, ``ds`` are ``[BK, BQ]``), so that ``lse`` and ``delta`` are
one lane-dense row a q block, broadcast down the sublanes, and ``dv += p
do`` and ``dk += ds q`` are plain products; only ``dq += ds^T k`` turns an
operand. Five products and one exponential pass a block where the
two-kernel form (a dQ sweep and a dK/dV sweep, each rebuilding ``s``,
``p``, ``dp``, ``ds``) took seven and two, two of them turned.

The windowed forward (``window=``, serving prefill of a sliding-window
layer) is the forward's score block (:func:`_fwd_block`, one more term in
its mask) under its own (kv-head, group, q-block) grid, a loop a q block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30
# what a call may ask of VMEM (128 MiB on the chip; a call that asks for
# nothing gets 16 MiB), and what of it is left to a step's own values
# (score blocks, carries) beside the blocks and scratch the wrapper counts
_VMEM_LIMIT = 96 * 1024 * 1024
_VMEM_DEFAULT = 16 * 1024 * 1024
_VMEM_WORKING = 8 * 1024 * 1024
# no straight-line body holds more score blocks than this: a head of at
# most so many is written out whole (causal T <= 2048 at the default
# blocks: 36), a longer one loops over its q (k) blocks, an unmasked row
# of at most so many written out inside (T <= 16k), else in groups
_UNROLL_BLOCKS = 64
_GROUP = 4


def effective_block(block: int, seq: int) -> int:
    """Largest power-of-two fraction of the requested ``block`` >= 128
    that tiles ``seq`` exactly (callers gate on seq % 128 == 0, so 128
    always fits; the 256 default would otherwise reject seq = 384, 640,
    ...). A non-power-of-two request whose halvings never land on a
    divisor of a 128-multiple seq (e.g. 384 into seq 512) snaps to 128 —
    the MXU-minimum tile every such seq accepts — rather than returning
    a sub-128 block the kernel can neither run nor should ever label a
    record with. Ragged seqs (seq % 128 != 0) keep the non-dividing
    block so flash_attention still rejects them loudly, as before."""
    b = min(block, seq)
    while b > 128 and seq % b:
        b //= 2
    if seq % b and seq % 128 == 0:
        b = 128
    return b


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


_NT = (((1,), (1,)), ((), ()))  # a @ b^T: both operands contract their lanes
_TN = (((0,), (0,)), ((), ()))  # a^T @ b: both contract their rows


# ---------------------------------------------------------------------------
# a head's sweep over its score blocks: written out, or looped
# ---------------------------------------------------------------------------


def _sweep(lo, hi, body, carry=(), *, static: bool, group: int = 1):
    """``carry = body(i, carry)`` for i in [lo, hi): straight-line code
    where ``static`` (the bounds are Python ints then), else a loop over
    groups of ``group`` steps written out, then the steps left over one
    by one: the scheduler overlaps one block's products with its
    neighbour's vector work only inside straight-line code."""
    if static:
        for i in range(lo, hi):
            carry = body(i, carry)
        return carry
    if group == 1:
        return jax.lax.fori_loop(lo, hi, body, carry)
    groups = (hi - lo) // group

    def grouped(gi, carry):
        for j in range(group):
            carry = body(lo + gi * group + j, carry)
        return carry
    carry = jax.lax.fori_loop(0, groups, grouped, carry)
    return jax.lax.fori_loop(lo + groups * group, hi, body, carry)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_block(qs, k_ref, v_ref, kb, block_k: int, carry, q0=None,
               window: int | None = None):
    """One ``[BQ, BK]`` score block of the online softmax, queries down
    and keys across (both products plain for the MXU): ``carry`` is (m,
    l, acc) of the q block ``qs [BQ, D]``, ``kb`` the K block. ``q0`` is
    the first query's position where the block needs its mask (the
    diagonal; with ``window`` also the window's lower edge: row ``i``
    sees keys ``i - window < j <= i``), None where every query sees
    every key."""
    m, l, acc = carry
    cols = pl.ds(kb * block_k, block_k)
    k = k_ref[0, cols, :]
    v = v_ref[0, cols, :]
    s = jax.lax.dot_general(qs, k, _NT, preferred_element_type=jnp.float32)
    if q0 is not None:
        row = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = row >= col
        if window is not None:
            seen = jnp.logical_and(seen, row - col < window)
        s = jnp.where(seen, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(p.astype(qs.dtype), v,
                                    preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def _fwd_carry(block_q: int, d: int):
    return (jnp.full((block_q, 1), NEG_INF, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32),
            jnp.zeros((block_q, d), jnp.float32))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                block_q: int, block_k: int, causal: bool, static: bool):
    """One grid step is one query head: ``q_ref``/``o_ref [1, T, D]``,
    ``k_ref [1, T, D]``/``v_ref [1, T, Dv]`` of its kv head (``o_ref [1,
    T, Dv]`` where a value is not as wide as a key), ``lse_ref [1, T/BQ,
    BQ]``."""
    seq_len = q_ref.shape[1]
    d = v_ref.shape[2]      # a value's width, the output's
    num_kb = seq_len // block_k
    dtype = q_ref.dtype
    # under a looped q axis an unmasked row of K blocks still has static
    # bounds: written out if the row alone is short enough
    row_static = static or (not causal and num_kb <= _UNROLL_BLOCKS)

    def q_block(qi, _):
        # keep the dot INPUTS in the storage dtype (bf16): the MXU runs
        # bf16 at full rate and accumulates fp32 via
        # preferred_element_type; an upfront fp32 cast would quarter the
        # matmul throughput. The softmax scale is folded into q ONCE
        # ([BQ, D] mul) instead of into every score block
        rows = pl.ds(qi * block_q, block_q)
        qs = (q_ref[0, rows, :].astype(jnp.float32) * scale).astype(dtype)
        # K blocks strictly below the diagonal are FULLY visible — only
        # the <= cdiv(bq, bk) diagonal blocks pay the iota/compare/select
        # passes (for kb < diag_start: (kb+1)*bk <= qi*bq, every key
        # precedes every query)
        diag_start = (qi * block_q) // block_k if causal else num_kb

        def block(kb, carry, q0=None):
            return _fwd_block(qs, k_ref, v_ref, kb, block_k, carry, q0)

        carry = _sweep(0, diag_start, block, _fwd_carry(block_q, d),
                       static=row_static, group=_GROUP)
        if causal:
            carry = _sweep(
                diag_start, diag_start + pl.cdiv(block_q, block_k),
                functools.partial(block, q0=qi * block_q), carry,
                static=static, group=_GROUP)
        m, l, acc = carry
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        # the backward reads lse as a ROW (its score blocks have the
        # queries across): turn the column once a q block
        lse = jnp.broadcast_to(m + jnp.log(l), (block_q, 128))
        lse_ref[0, pl.ds(qi, 1), :] = lse.T[:1]
        return _

    _sweep(0, seq_len // block_q, q_block, static=static)


def _score_blocks(seq_len: int, block_q: int, block_k: int,
                  causal: bool) -> int:
    """Score blocks a head's sweep visits."""
    nq, nk = seq_len // block_q, seq_len // block_k
    if not causal:
        return nq * nk
    return sum((qi * block_q) // block_k + pl.cdiv(block_q, block_k)
               for qi in range(nq))


def _vmem_params(nbytes: int, what: str):
    """Compiler parameters of a whole-head call that holds ``nbytes``
    of blocks and scratch (the blocks counted twice: the pipeline keeps
    the next step's beside this one's). A call that fits what every
    kernel gets asks for no more, and a longer head for what it counts,
    to the next MiB: a raised limit is a setting of the WHOLE program,
    and the compiler then lays out the fusions around the kernel
    differently too."""
    need = nbytes + _VMEM_WORKING
    if need > _VMEM_LIMIT:
        raise ValueError(
            f"flash_attention: {what} keeps {nbytes / 2**20:.0f} MB of one "
            f"head in VMEM, over {(_VMEM_LIMIT - _VMEM_WORKING) / 2**20:.0f} "
            "MB: shard the sequence (ops/ring_attention.py)")
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=(-(-need // 2**20) * 2**20
                          if need > _VMEM_DEFAULT else None))


def _head_specs(BH: int, BKH: int, T: int, D: int, block_q: int):
    """Block specs of the (kv-head, group) grid: a query head's ``[1, T,
    D]``, its statistics' ``[1, T/BQ, BQ]``, its kv head's ``[1, T, D]``."""
    rep = BH // BKH
    qmap = lambda bkh, g: (bkh * rep + g, 0, 0)  # noqa: E731
    return (pl.BlockSpec((1, T, D), qmap),
            pl.BlockSpec((1, T // block_q, block_q), qmap),
            pl.BlockSpec((1, T, D), lambda bkh, g: (bkh, 0, 0)))


@functools.lru_cache(maxsize=None)
def _fwd_call(BH: int, BKH: int, T: int, D: int, dtype, scale: float,
              block_q: int, block_k: int, causal: bool, interpret: bool,
              Dv: int | None = None):
    """``(q3 [B*H, T, D], k3, v3 [B*HKV, T, D]) -> (o, lse)`` (``v3`` and
    ``o`` ``Dv`` wide where ``Dv`` is given; HKV | H —
    grouped-query attention streams each K/V head into VMEM ONCE for its
    whole query group: the grid is (kv-head, group) with the group
    fastest, so the K/V block index is constant across a group and pallas
    reloads it only when the kv-head changes). ``lse`` is the row
    logsumexp ``[B*H, T/BQ, BQ]`` float32: O(BH*T) for the backward,
    positions on the lanes. Kept per static signature and jitted, because
    jax traces a call it has seen before from its cache: the 24 layers of
    a step trace and lower the written-out body once."""
    Dv = D if Dv is None else Dv
    head, stat, kv = _head_specs(BH, BKH, T, D, block_q)
    o_head, _, v_kv = _head_specs(BH, BKH, T, Dv, block_q)
    return jax.jit(pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal,
            static=_score_blocks(T, block_q, block_k,
                                 causal) <= _UNROLL_BLOCKS),
        grid=(BKH, BH // BKH),
        in_specs=[head, kv, v_kv],
        out_specs=[o_head, stat],
        out_shape=[jax.ShapeDtypeStruct((BH, T, Dv), dtype),
                   jax.ShapeDtypeStruct((BH, T // block_q, block_q),
                                        jnp.float32)],
        compiler_params=_vmem_params(
            2 * 2 * T * (D + Dv) * dtype.itemsize + 2 * T * 4,
            "the forward"),
        interpret=interpret,
        name="flash_attention_fwd"))


def _window_fwd_kernel(q_ref, k_ref, v_ref, *rest, scale: float,
                       block_q: int, block_k: int, window: int):
    """The windowed forward (serving prefill of a sliding-window layer;
    grid (kv-head, group, q-block)): query row ``i`` sees keys ``j`` with
    ``i - window < j <= i``. K blocks wholly before the block's first
    window are skipped, the blocks a window's lower edge cuts are masked,
    the ones between them and the diagonal are fully visible. With a
    fourth operand, ``sink_ref [1, 1, 128]`` (the query head's learned
    logit on every lane), the softmax's denominator has one more term
    that carries no value: the carry starts at ``(sink, 1, 0)``."""
    sink_ref, o_ref, lse_ref = rest if len(rest) == 3 else (None, *rest)
    qi = pl.program_id(2)
    q = q_ref[0]  # [BQ, D]
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    diag_start = (qi * block_q) // block_k
    num_kb = diag_start + pl.cdiv(block_q, block_k)
    # the first row's window starts at qi*bq - window + 1; a block is
    # inside EVERY row's window from column (last row) - window + 1
    first_kb = jnp.maximum(qi * block_q - window + 1, 0) // block_k
    inside = jnp.maximum(qi * block_q + block_q - window, 0)
    edge_end = jnp.clip((inside + block_k - 1) // block_k, first_kb,
                        diag_start)

    def block(kb, carry, q0=None):
        return _fwd_block(qs, k_ref, v_ref, kb, block_k, carry, q0, window)

    masked = functools.partial(block, q0=qi * block_q)
    start = _fwd_carry(block_q, v_ref.shape[2])
    if sink_ref is not None:
        start = (jnp.broadcast_to(sink_ref[0, :, :1], start[0].shape),
                 jnp.ones_like(start[1]), start[2])
    carry = jax.lax.fori_loop(first_kb, edge_end, masked, start)
    carry = jax.lax.fori_loop(edge_end, diag_start, block, carry)
    m, l, acc = jax.lax.fori_loop(diag_start, num_kb, masked, carry)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [BQ, 1]


def _flash_window_fwd(q3, k3, v3, sink, *, scale, block_q, block_k, window,
                      interpret):
    """The windowed forward's call: K/V of one kv head whole in VMEM, one
    q block a grid step, the q-block axis fastest. ``v3 [B*HKV, T, Dv]``
    may be narrower or wider than a key; ``sink [B*H]`` float32 or None."""
    BH, T, D = q3.shape
    BKH, _, Dv = v3.shape
    rep = BH // BKH
    qmap = lambda bkh, g, qi: (bkh * rep + g, qi, 0)  # noqa: E731
    kvmap = lambda bkh, g, qi: (bkh, 0, 0)  # noqa: E731
    sinks, sink_spec = (), []
    if sink is not None:
        # a head's logit on every lane of one row: a block that spans its
        # array's last two dims
        sinks = (jnp.broadcast_to(sink.astype(jnp.float32)[:, None, None],
                                  (BH, 1, 128)),)
        sink_spec = [pl.BlockSpec(
            (1, 1, 128), lambda bkh, g, qi: (bkh * rep + g, 0, 0))]
    o, _ = pl.pallas_call(
        functools.partial(_window_fwd_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, window=window),
        grid=(BKH, rep, T // block_q),
        in_specs=[pl.BlockSpec((1, block_q, D), qmap),
                  pl.BlockSpec((1, T, D), kvmap),
                  pl.BlockSpec((1, T, Dv), kvmap)] + sink_spec,
        out_specs=[pl.BlockSpec((1, block_q, Dv), qmap),
                   pl.BlockSpec((1, block_q, 1), qmap)],
        out_shape=[jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype),
                   jax.ShapeDtypeStruct((BH, T, 1), jnp.float32)],
        interpret=interpret,
        name="flash_attention_window_fwd",
    )(q3, k3, v3, *sinks)
    return o


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, qs_ref, dq_acc, dk_acc, dv_acc, *,
                scale: float, block_q: int, block_k: int, causal: bool,
                rep: int, static: bool):
    """One grid step is one query head, as in the forward; every visible
    score block is rebuilt ONCE (``s``, ``p``, ``dp``, ``ds``, all ``[BK,
    BQ]``, keys down) and feeds ``dv += p do``, ``dk += ds q`` and ``dq +=
    ds^T k``. ``qs_ref [T, D]`` is q with the softmax scale folded in as
    the forward folded it, so that ``p`` is the forward's ``p`` to the bit
    (dk accumulates against raw q and dq against raw k, each scaled once
    at its flush); ``dq_acc [T/BQ, BQ, D]`` float32 holds the head's
    ``dq`` until the step's end; ``dk_acc``/``dv_acc [T, D]`` float32 add
    up the query group of a kv head (the group axis is the INNERMOST grid
    axis, so the dk/dv output block is revisited on consecutive steps and
    the last group member flushes it)."""
    g = pl.program_id(1)
    seq_len = q_ref.shape[1]
    num_qb, num_kb = seq_len // block_q, seq_len // block_k
    dtype = q_ref.dtype
    # as the forward's row_static: an unmasked column of Q blocks
    col_static = static or (not causal and num_qb <= _UNROLL_BLOCKS)

    @pl.when(g == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def init_q(qb, _):
        rows = pl.ds(qb * block_q, block_q)
        qs_ref[rows, :] = (q_ref[0, rows, :].astype(jnp.float32)
                           * scale).astype(dtype)
        dq_acc[qb] = jnp.zeros(dq_acc.shape[1:], jnp.float32)
        return _

    _sweep(0, num_qb, init_q, static=static)

    def k_block(ki, _):
        rows = pl.ds(ki * block_k, block_k)
        k = k_ref[0, rows, :]
        v = v_ref[0, rows, :]

        def make_body(masked):
            def body(qb, carry):
                dk, dv = carry
                q_rows = pl.ds(qb * block_q, block_q)
                do = do_ref[0, q_rows, :]
                s = jax.lax.dot_general(k, qs_ref[q_rows, :], _NT,
                                        preferred_element_type=jnp.float32)
                if masked:
                    key = ki * block_k + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 0)
                    query = qb * block_q + jax.lax.broadcasted_iota(
                        jnp.int32, (block_k, block_q), 1)
                    s = jnp.where(query >= key, s, NEG_INF)
                p = jnp.exp(s - lse_ref[0, pl.ds(qb, 1), :])
                dv = dv + jnp.dot(p.astype(dtype), do,
                                  preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(v, do, _NT,
                                         preferred_element_type=jnp.float32)
                ds = (p * (dp - delta_ref[0, pl.ds(qb, 1), :])).astype(dtype)
                dk = dk + jnp.dot(ds, q_ref[0, q_rows, :],
                                  preferred_element_type=jnp.float32)
                # the one product that contracts its rows: ds is turned
                # on the way in (a K transposed once a kv head and dq^T +=
                # k^T ds, turned back at the end, read within 3 % of
                # this either way: PERF.md section 6, PR 46)
                dq_acc[qb] += jax.lax.dot_general(
                    ds, k, _TN, preferred_element_type=jnp.float32)
                return dk, dv
            return body

        carry = (dk_acc[rows, :], dv_acc[rows, :])
        diag_end = 0
        if causal:
            # q blocks split three ways around this k block: before
            # first_qb nothing is visible (skipped), [first_qb, diag_end)
            # touches the diagonal (masked), [diag_end, num_qb) is fully
            # visible
            first_qb = (ki * block_k) // block_q
            diag_end = -(-((ki + 1) * block_k - 1) // block_q)  # ceil div
            carry = _sweep(first_qb, diag_end, make_body(True), carry,
                           static=static, group=_GROUP)
        dk, dv = _sweep(diag_end, num_qb, make_body(False), carry,
                        static=col_static, group=_GROUP)
        dk_acc[rows, :] = dk
        dv_acc[rows, :] = dv
        return _

    _sweep(0, num_kb, k_block, static=static)

    def flush_q(qb, _):
        dq_ref[0, pl.ds(qb * block_q, block_q), :] = (
            (dq_acc[qb] * scale).astype(dq_ref.dtype))
        return _

    _sweep(0, num_qb, flush_q, static=static)

    @pl.when(g == rep - 1)
    def _flush_kv():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


@functools.lru_cache(maxsize=None)
def _bwd_call(BH: int, BKH: int, T: int, D: int, dtype, scale: float,
              block_q: int, block_k: int, causal: bool, interpret: bool):
    """``(q3, k3, v3, do3, lse, delta) -> (dq, dk, dv)``; ``delta`` laid
    out as ``lse``. Kept per static signature, as :func:`_fwd_call`."""
    head, stat, kv = _head_specs(BH, BKH, T, D, block_q)
    item = dtype.itemsize
    return jax.jit(pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
            causal=causal, rep=BH // BKH,
            static=_score_blocks(T, block_q, block_k,
                                 causal) <= _UNROLL_BLOCKS),
        grid=(BKH, BH // BKH),
        in_specs=[head, kv, kv, head, stat, stat],
        out_specs=[head, kv, kv],
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), dtype),
                   jax.ShapeDtypeStruct((BKH, T, D), dtype),
                   jax.ShapeDtypeStruct((BKH, T, D), dtype)],
        scratch_shapes=[pltpu.VMEM((T, D), dtype),
                        pltpu.VMEM((T // block_q, block_q, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=_vmem_params(
            (2 * 7 + 1) * T * D * item + 3 * T * D * 4 + 4 * T * 4,
            "the backward"),
        interpret=interpret,
        name="flash_attention_bwd"))


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q3, k3, v3, scale, block_q, block_k, causal):
    return _flash_attention_fwd(q3, k3, v3, scale, block_q, block_k,
                                causal)[0]


def _signature(q3, k3, scale, block_q, block_k, causal):
    return (q3.shape[0], k3.shape[0], *q3.shape[1:], q3.dtype, scale,
            block_q, block_k, causal, _should_interpret())


def _flash_attention_fwd(q3, k3, v3, scale, block_q, block_k, causal):
    o, lse = _fwd_call(*_signature(q3, k3, scale, block_q, block_k,
                                   causal), v3.shape[2])(q3, k3, v3)
    return o, (q3, k3, v3, o, lse)


def _flash_attention_bwd(scale, block_q, block_k, causal, res, do3):
    q3, k3, v3, o3, lse = res
    if v3.shape[2] != q3.shape[2]:
        raise NotImplementedError(
            f"flash_attention with values D_v = {v3.shape[2]} wide beside "
            f"keys D = {q3.shape[2]} wide has no backward kernel (D_v != "
            "D is forward only: serving prefill)")
    # delta = rowsum(do * o), one fused pass that leaves it as the
    # forward left lse: positions on the lanes
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    return tuple(_bwd_call(*_signature(q3, k3, scale, block_q, block_k,
                                       causal))(q3, k3, v3, do3, lse, delta))


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention_window(q3, k3, v3, sink, scale, block_q, block_k,
                            window):
    """The windowed forward (``sink [B*H]`` float32 or None). It has no
    backward kernels: differentiating it is refused by name instead of
    falling through to a backward pass that would ignore the window (or
    the sink)."""
    return _flash_window_fwd(q3, k3, v3, sink, scale=scale, block_q=block_q,
                             block_k=block_k, window=window,
                             interpret=_should_interpret())


def _flash_attention_window_fwd(q3, k3, v3, sink, scale, block_q, block_k,
                                window):
    return _flash_attention_window(q3, k3, v3, sink, scale, block_q,
                                   block_k, window), None


def _flash_attention_window_bwd(scale, block_q, block_k, window, res, do3):
    raise NotImplementedError(
        f"flash_attention(window={window}) has no backward kernels, with "
        "or without a sink: the windowed kernel is forward only (serving "
        "prefill); train a windowed layer through the masked einsum")


_flash_attention_window.defvjp(_flash_attention_window_fwd,
                               _flash_attention_window_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    scale: float | None = None,
                    window: int | None = None,
                    sink: jax.Array | None = None):
    """Fused attention, ``q [B, T, H, D] -> [B, T, H, Dv]``.

    ``window`` (causal only, forward only): query ``i`` sees keys ``i -
    window < j <= i``; K blocks wholly outside a query block's windows
    are skipped. Differentiating a windowed call raises by name.

    ``v [B, T, HKV, Dv]`` may be narrower or wider than a key (forward
    only: differentiating ``Dv != D`` raises by name). ``sink [H]``
    float32 (windowed forward only; refused elsewhere by name): a
    learned logit a query head that joins the softmax's denominator and
    carries no value, ``p_ij = exp(s_ij) / (exp(sink_h) + sum_j'
    exp(s_ij'))``.

    ``k``/``v`` may carry fewer heads (``[B, T, HKV, D]`` with HKV | H):
    grouped-query attention runs WITHOUT materializing the repeated k/v —
    each kv head streams through VMEM once for its whole query group, so
    GQA's HBM-bandwidth saving survives into the kernel (models pass
    unexpanded k/v; see models/llama.py).

    Sequence length must be divisible by the block sizes (the model layer
    pads to n_positions, itself a multiple of 128).
    """
    B, T, H, D = q.shape
    HKV = k.shape[2]
    if k.shape[:3] != v.shape[:3] or k.shape[:2] != (B, T) or k.shape[3] != D:
        raise ValueError(
            f"k/v shape {k.shape}/{v.shape} incompatible with q {q.shape}: "
            f"k is [B, T, HKV, D] with q's D = {D}, v [B, T, HKV, D_v] "
            "with k's heads (D_v may differ from D)")
    if sink is not None and (window is None or sink.shape != (H,)):
        raise ValueError(
            f"sink of shape {sink.shape} with window={window}: a sink is "
            f"one float32 a query head ([{H}]) of the windowed forward; "
            "the full forward takes none")
    if H % HKV:
        raise ValueError(f"q heads {H} not divisible by kv heads {HKV}")

    block_q = effective_block(block_q, T)
    block_k = effective_block(block_k, T)
    if T % block_q or T % block_k:
        raise ValueError(f"seq len {T} not divisible by blocks "
                         f"({block_q}, {block_k})")
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    def to3(x):
        return jnp.swapaxes(x, 1, 2).reshape(B * x.shape[2], T, x.shape[3])

    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal=True and a "
                             "window of at least one position")
        o3 = _flash_attention_window(
            to3(q), to3(k), to3(v),
            None if sink is None else jnp.tile(sink, B), float(scale),
            block_q, block_k, int(window))
    else:
        o3 = _flash_attention(to3(q), to3(k), to3(v), float(scale),
                              block_q, block_k, causal)
    return jnp.swapaxes(o3.reshape(B, H, T, v.shape[3]), 1, 2)
