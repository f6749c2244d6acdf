"""Pallas block-sparse flash attention.

The reference's sparse stack is three Triton kernels — SDD matmul, fused
block-sparse softmax, DSD matmul (``ops/sparse_attention/matmul.py:12``,
``softmax.py``) — plus a C++ LUT builder
(``csrc/sparse_attention/utils.cpp``). On TPU those fuse into ONE kernel:
for each (batch, head, q-block) the kernel walks only that row's active
key blocks (host-built LUT, scalar-prefetched) with the online-softmax
recurrence, so the sparse attention matrix never exists in HBM and skipped
blocks cost nothing.

Layout blocks must match the kernel block (≥128 recommended on TPU: MXU/
lane tiling; the reference defaults to 16 for Triton — configs port, just
pick a TPU-friendly ``block``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def build_lut(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """layout [H, nb, nb] → (lut [H, nb, max_active] int32 padded with 0,
    counts [H, nb] int32). The utils.cpp analog, host-side."""
    H, nb, _ = layout.shape
    counts = layout.sum(-1).astype(np.int32)
    max_active = max(1, int(counts.max()))
    lut = np.zeros((H, nb, max_active), np.int32)
    for h in range(H):
        for qb in range(nb):
            cols = np.nonzero(layout[h, qb])[0]
            lut[h, qb, :len(cols)] = cols
    return lut, counts


def _kernel(counts_ref, lut_ref, q_ref, k_ref, v_ref, o_ref, *,
            block: int, scale: float, causal: bool):
    h = pl.program_id(1)
    qb = pl.program_id(2)
    count = counts_ref[h, qb]
    # bf16 dot inputs, fp32 accumulation via preferred_element_type —
    # an upfront fp32 cast would quarter the MXU rate (see
    # pallas/flash_attention.py)
    q = q_ref[0, 0]                                    # [block, D]
    D = q.shape[-1]

    m = jnp.full((block, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block, 1), jnp.float32)
    acc = jnp.zeros((block, D), jnp.float32)

    row = qb * block + jax.lax.broadcasted_iota(jnp.int32,
                                               (block, block), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = lut_ref[h, qb, j]
        k = k_ref[0, 0, pl.ds(kb * block, block), :]
        v = v_ref[0, 0, pl.ds(kb * block, block), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            col = kb * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, block), 1)
            s = jnp.where(col <= row, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(q.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, count, body, (m, l, acc))
    # rows whose every active block was causally masked (m never rose
    # above NEG_INF) must output zero, not mean(v): their p=exp(0)=1
    # weights are an artifact of the NEG_INF bookkeeping
    live = m > NEG_INF / 2
    out = jnp.where(live, acc / jnp.maximum(l, 1e-30), 0.0)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           lut: jax.Array, counts: jax.Array,
                           block: int, causal: bool = False,
                           scale: float | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """q/k/v ``[B, H, T, D]`` + LUT → ``[B, H, T, D]``. Rows whose count
    is 0 output zeros (fully-masked rows have no defined softmax — the
    reference's layouts never produce them)."""
    B, H, T, D = q.shape
    if T % block:
        raise ValueError(f"seq {T} not divisible by block {block}")
    nb = T // block
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, nb),
        in_specs=[
            pl.BlockSpec((1, 1, block, D),
                         lambda b, h, qb, c, t: (b, h, qb, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, qb, c, t: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, T, D), lambda b, h, qb, c, t: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block, D),
                               lambda b, h, qb, c, t: (b, h, qb, 0)),
    )
    kernel = functools.partial(_kernel, block=block, scale=float(scale),
                               causal=causal)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, T, D), q.dtype),
        interpret=interpret,
        name="block_sparse_attention",
    )(counts.astype(jnp.int32), lut.astype(jnp.int32), q, k, v)
