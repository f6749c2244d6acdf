"""Pallas flash attention (training) — fused causal attention for the MXU.

TPU-native replacement for the reference's fused attention-softmax kernels
(``csrc/transformer/softmax_kernels.cu:attn_softmax``, used by the training
transformer kernel N10). Instead of materializing the [T, T] attention matrix
in HBM, the kernel streams K/V blocks through VMEM with the online-softmax
recurrence, accumulating in fp32 — O(T) memory, MXU-shaped [128, D] matmuls.

Layout: q ``[B, T, H, D]``; k/v may carry fewer heads (``[B, T, HKV, D]``,
HKV | H — grouped-query attention without materializing repeated k/v).
The kernel works on ``[B*H, T, D]`` q with a (kv-head, group, q-block)
grid whose group axis revisits each K/V block, so one kv head streams
through VMEM once for its whole query group. K/V for one batch-head live
whole in VMEM (T·D·2B·2 ≤ ~8 MB ⇒ T ≤ 16k at D=128, independent of the
group size — longer sequences shard over the ``seq`` axis via ring
attention, see ops/ring_attention.py).

Backward is the standard two-kernel flash decomposition (dQ sweep over K
blocks; dK/dV sweep over Q blocks) wired through ``jax.custom_vjp`` with the
(out, logsumexp) residuals.
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


def effective_block(block: int, seq: int) -> int:
    """Largest power-of-two fraction of the requested ``block`` >= 128
    that tiles ``seq`` exactly (callers gate on seq % 128 == 0, so 128
    always fits; the 256 default would otherwise reject seq = 384, 640,
    ...). A non-power-of-two request whose halvings never land on a
    divisor of a 128-multiple seq (e.g. 384 into seq 512) snaps to 128 —
    the MXU-minimum tile every such seq accepts — rather than returning
    a sub-128 block the kernel can neither run nor should ever label a
    record with. Ragged seqs (seq % 128 != 0) keep the non-dividing
    block so flash_attention still rejects them loudly, as before. Pure
    int math, shared with bench.py's record labeling so salvage/baseline
    keys always name the block that actually ran."""
    b = min(block, seq)
    while b > 128 and seq % b:
        b //= 2
    if seq % b and seq % 128 == 0:
        b = 128
    return b


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                block_q: int, block_k: int, seq_len: int, causal: bool,
                window: int | None = None):
    """``window`` (static; None: the kernel as it always was): query row
    ``i`` sees keys ``j`` with ``i - window < j <= i``. K blocks wholly
    before the block's first window are skipped, the blocks a window's
    lower edge cuts are masked, the ones between them and the diagonal
    are fully visible."""
    qi = pl.program_id(2)
    # keep the dot INPUTS in the storage dtype (bf16): the MXU runs bf16
    # at full rate and accumulates fp32 via preferred_element_type; an
    # upfront fp32 cast would quarter the matmul throughput
    q = q_ref[0]  # [BQ, D]
    bq, d = q.shape
    # fold the softmax scale into q ONCE ([BQ, D] mul) instead of into
    # every [BQ, BK] score block: the kernel is VPU-bound at small D (the
    # dots are tiny, the elementwise passes over the score block are not),
    # so every saved pass over [BQ, BK] is wall-clock
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    m = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, d), jnp.float32)

    if causal:
        # K blocks strictly below the diagonal are FULLY visible — only
        # the ≤ cdiv(bq, bk) diagonal blocks pay the iota/compare/select
        # masking passes (for kb < diag_start: (kb+1)·bk ≤ qi·bq, i.e.
        # every column precedes every row of this q block)
        diag_start = (qi * block_q) // block_k
        num_kb = diag_start + pl.cdiv(block_q, block_k)
    else:
        diag_start = num_kb = seq_len // block_k
    first_kb = edge_end = 0
    if window is not None:
        # the first row's window starts at qi*bq - window + 1; a block is
        # inside EVERY row's window from column (last row) - window + 1
        first_kb = jnp.maximum(qi * block_q - window + 1, 0) // block_k
        inside = jnp.maximum(qi * block_q + block_q - window, 0)
        edge_end = jnp.clip((inside + block_k - 1) // block_k, first_kb,
                            diag_start)

    def make_body(masked):
        def body(kb, carry):
            m, l, acc = carry
            k = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                col = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1)
                seen = row >= col
                if window is not None:
                    seen = jnp.logical_and(seen, row - col < window)
                s = jnp.where(seen, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    carry = (m, l, acc)
    if window is not None:
        carry = jax.lax.fori_loop(first_kb, edge_end, make_body(True),
                                  carry)
    carry = jax.lax.fori_loop(edge_end, diag_start, make_body(False), carry)
    if causal:
        carry = jax.lax.fori_loop(diag_start, num_kb, make_body(True),
                                  carry)
    m, l, acc = carry
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l)  # [BQ, 1]


def _flash_fwd(q3, k3, v3, *, scale, block_q, block_k, causal, interpret,
               window=None):
    """q3 ``[B*H, T, D]``; k3/v3 ``[B*HKV, T, D]`` (HKV | H — grouped-query
    attention streams each K/V head into VMEM ONCE for its whole query
    group: grid order is (kv-head, group, q-block) with the q-block axis
    fastest, so the K/V block index is constant across an entire group and
    pallas reloads it only when the kv-head changes)."""
    BH, T, D = q3.shape
    BKH = k3.shape[0]
    rep = BH // BKH
    grid = (BKH, rep, T // block_q)
    out_shape = [
        jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        # trailing singleton lane dim satisfies TPU tiling (block last dim
        # equals the array dim); keeps lse O(BH·T) instead of the official
        # kernel's 128-lane broadcast
        jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
    ]
    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, seq_len=T, causal=causal,
                               window=window)
    qmap = lambda bkh, g, qi: (bkh * rep + g, qi, 0)  # noqa: E731
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, T, D), lambda bkh, g, qi: (bkh, 0, 0)),
            pl.BlockSpec((1, T, D), lambda bkh, g, qi: (bkh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
        ],
        out_shape=out_shape,
        interpret=interpret,
        name=("flash_attention_fwd" if window is None
              else "flash_attention_window_fwd"),
    )(q3, k3, v3)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale: float, block_q: int, block_k: int,
                   seq_len: int, causal: bool):
    qi = pl.program_id(2)
    # bf16 dot inputs, fp32 accumulation (see _fwd_kernel note)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]  # [BQ, 1]
    delta = delta_ref[0]  # [BQ, 1]
    bq, d = q.shape
    dq = jnp.zeros((bq, d), jnp.float32)
    # scale folded into q for the score dot (see _fwd_kernel); the dq
    # accumulation uses raw k and applies scale once at the end, as before
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    if causal:
        diag_start = (qi * block_q) // block_k
        num_kb = diag_start + pl.cdiv(block_q, block_k)
    else:
        diag_start = num_kb = seq_len // block_k

    def make_body(masked):
        def body(kb, dq):
            k = k_ref[0, pl.ds(kb * block_k, block_k), :]
            v = v_ref[0, pl.ds(kb * block_k, block_k), :]
            s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 0)
                col = kb * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, block_k), 1)
                s = jnp.where(row >= col, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(q.dtype)
            return dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return body

    dq = jax.lax.fori_loop(0, diag_start, make_body(False), dq)
    if causal:
        dq = jax.lax.fori_loop(diag_start, num_kb, make_body(True), dq)
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    block_q: int, block_k: int, seq_len: int, causal: bool,
                    rep: int):
    ki = pl.program_id(1)
    g = pl.program_id(2)
    # bf16 dot inputs, fp32 accumulation (see _fwd_kernel note)
    k = k_ref[0]  # [BK, D]
    v = v_ref[0]
    bk, d = k.shape

    # grouped-query attention: this K/V head serves `rep` query heads.
    # The group axis is the INNERMOST grid dim, so the dk/dv output block
    # is revisited on consecutive steps: fp32 VMEM scratch accumulates
    # across the group (q/do blocks stay (1, T, D) — no rep-times VMEM
    # inflation), and the final group member flushes to the output.
    @pl.when(g == 0)
    def _init():
        dk_acc[...] = jnp.zeros((bk, d), jnp.float32)
        dv_acc[...] = jnp.zeros((bk, d), jnp.float32)

    num_qb = seq_len // block_q
    if causal:
        # q blocks split three ways around this k block: before first_qb
        # nothing is visible (skipped), [first_qb, diag_end) touches the
        # diagonal (masked), [diag_end, num_qb) is fully visible — the
        # iota/compare/select passes run on ≤ cdiv(bk, bq) blocks only
        first_qb = (ki * block_k) // block_q
        diag_end = -(-((ki + 1) * block_k - 1) // block_q)  # ceil div
    else:
        first_qb = diag_end = 0
    # scale folded into the resident k for the score dot (see
    # _fwd_kernel); dk accumulates against raw q, scaled once at flush
    ks = (k.astype(jnp.float32) * scale).astype(k.dtype)

    def make_body(masked):
        def body(qb, carry):
            dk, dv = carry
            q = q_ref[0, pl.ds(qb * block_q, block_q), :]
            do = do_ref[0, pl.ds(qb * block_q, block_q), :]
            lse = lse_ref[0, pl.ds(qb * block_q, block_q), :]  # [BQ, 1]
            delta = delta_ref[0, pl.ds(qb * block_q, block_q), :]
            s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if masked:
                row = qb * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 0)
                col = ki * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, bk), 1)
                s = jnp.where(row >= col, s, NEG_INF)
            p = jnp.exp(s - lse)
            p16 = p.astype(k.dtype)
            dv_new = dv + jax.lax.dot_general(
                p16, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(k.dtype)
            dk_new = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    carry = (dk_acc[...], dv_acc[...])
    if causal:
        carry = jax.lax.fori_loop(first_qb, diag_end, make_body(True),
                                  carry)
    dk, dv = jax.lax.fori_loop(diag_end, num_qb, make_body(False), carry)
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when(g == rep - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, o3, lse, do3, *, scale, block_q, block_k,
               causal, interpret):
    BH, T, D = q3.shape
    BKH = k3.shape[0]
    rep = BH // BKH
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [BH, T, 1]

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  seq_len=T, causal=causal)
    qmap = lambda bkh, g, qi: (bkh * rep + g, qi, 0)  # noqa: E731
    kvmap = lambda bkh, g, qi: (bkh, 0, 0)  # noqa: E731
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BKH, rep, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, T, D), kvmap),
            pl.BlockSpec((1, T, D), kvmap),
            pl.BlockSpec((1, block_q, D), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
            pl.BlockSpec((1, block_q, 1), qmap),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), qmap),
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q3, k3, v3, do3, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   seq_len=T, causal=causal, rep=rep)
    # group axis INNERMOST: consecutive grid steps revisit the same dk/dv
    # block (and the same k/v block), so the scratch accumulation in the
    # kernel is a legal sequential reduction and k/v stay resident in VMEM
    # across the whole query group
    gq = lambda bkh, ki, g: (bkh * rep + g, 0, 0)  # noqa: E731
    kvm = lambda bkh, ki, g: (bkh, ki, 0)  # noqa: E731
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BKH, T // block_k, rep),
        in_specs=[
            pl.BlockSpec((1, T, D), gq),
            pl.BlockSpec((1, block_k, D), kvm),
            pl.BlockSpec((1, block_k, D), kvm),
            pl.BlockSpec((1, T, D), gq),
            pl.BlockSpec((1, T, 1), gq),
            pl.BlockSpec((1, T, 1), gq),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), kvm),
            pl.BlockSpec((1, block_k, D), kvm),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k3.shape, k3.dtype),
            jax.ShapeDtypeStruct(v3.shape, v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q3, k3, v3, scale, block_q, block_k, causal):
    o, _ = _flash_fwd(q3, k3, v3, scale=scale, block_q=block_q,
                      block_k=block_k, causal=causal,
                      interpret=_should_interpret())
    return o


def _flash_attention_fwd(q3, k3, v3, scale, block_q, block_k, causal):
    o, lse = _flash_fwd(q3, k3, v3, scale=scale, block_q=block_q,
                        block_k=block_k, causal=causal,
                        interpret=_should_interpret())
    return o, (q3, k3, v3, o, lse)


def _flash_attention_bwd(scale, block_q, block_k, causal, res, do3):
    q3, k3, v3, o3, lse = res
    dq, dk, dv = _flash_bwd(q3, k3, v3, o3, lse, do3, scale=scale,
                            block_q=block_q, block_k=block_k, causal=causal,
                            interpret=_should_interpret())
    return dq, dk, dv


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention_window(q3, k3, v3, scale, block_q, block_k, window):
    """The windowed forward. It has no backward kernels: differentiating
    it is refused by name instead of falling through to a backward pass
    that would ignore the window."""
    o, _ = _flash_fwd(q3, k3, v3, scale=scale, block_q=block_q,
                      block_k=block_k, causal=True, window=window,
                      interpret=_should_interpret())
    return o


def _flash_attention_window_fwd(q3, k3, v3, scale, block_q, block_k,
                                window):
    return _flash_attention_window(q3, k3, v3, scale, block_q, block_k,
                                   window), None


def _flash_attention_window_bwd(scale, block_q, block_k, window, res, do3):
    raise NotImplementedError(
        f"flash_attention(window={window}) has no backward kernels: the "
        "windowed kernel is forward only (serving prefill); train a "
        "windowed layer through the masked einsum")


_flash_attention_window.defvjp(_flash_attention_window_fwd,
                               _flash_attention_window_bwd)


def flash_attention(q, k, v, causal: bool = True,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    scale: float | None = None,
                    window: int | None = None):
    """Fused attention, ``q [B, T, H, D] -> [B, T, H, D]``.

    ``window`` (causal only, forward only): query ``i`` sees keys ``i -
    window < j <= i``; K blocks wholly outside a query block's windows
    are skipped. Differentiating a windowed call raises by name.

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
    if k.shape != v.shape or k.shape[:2] != (B, T) or k.shape[3] != D:
        raise ValueError(f"k/v shape {k.shape}/{v.shape} incompatible "
                         f"with q {q.shape}")
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
        h = x.shape[2]
        return jnp.swapaxes(x, 1, 2).reshape(B * h, T, D)

    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"window={window} needs causal=True and a "
                             "window of at least one position")
        o3 = _flash_attention_window(to3(q), to3(k), to3(v), float(scale),
                                     block_q, block_k, int(window))
    else:
        o3 = _flash_attention(to3(q), to3(k), to3(v), float(scale),
                              block_q, block_k, causal)
    return jnp.swapaxes(o3.reshape(B, H, T, D), 1, 2)
