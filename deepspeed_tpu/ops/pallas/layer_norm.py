"""Pallas fused LayerNorm (+ optional residual add) with custom VJP.

Analog of the reference's training-kernel LayerNorm family
(``csrc/transformer/normalize_kernels.cu`` — fused LN with fp32
accumulation, plus the residual-fused variants in
``csrc/transformer/inference/csrc/layer_norm.cu``). XLA already fuses LN
chains well; this kernel exists for (a) the residual+LN fusion the inference
engine calls per layer and (b) saving (mean, rstd) residuals so backward
recomputes nothing.

x: [..., N] normalized over the last dim; weight/bias fp32 [N].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 128


def _should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, mean_ref, rstd_ref,
                   *, eps: float):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    o_ref[:] = (xhat * w_ref[:].astype(jnp.float32) +
                b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(x_ref, w_ref, mean_ref, rstd_ref, g_ref,
                   dx_ref, dw_ref, db_ref, *, rows_total: int):
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (x - mean) * rstd
    gw = g * w
    n = x.shape[-1]
    # dx = rstd * (gw - mean(gw) - xhat * mean(gw * xhat))
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[:] = (rstd * (gw - m1 - xhat * m2)).astype(dx_ref.dtype)

    # dw/db accumulate across row blocks (sequential grid on TPU)
    @pl.when(i == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)
    dw_ref[:] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(g, axis=0, keepdims=True)


def _ln_fwd(x2, w, b, *, eps, block_rows, interpret):
    R, N = x2.shape
    kernel = functools.partial(_ln_fwd_kernel, eps=eps)
    o, mean, rstd = pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_fwd",
    )(x2, w[None], b[None])
    return o, mean, rstd


def _ln_bwd(x2, w, mean, rstd, g2, *, block_rows, interpret):
    R, N = x2.shape
    kernel = functools.partial(_ln_bwd_kernel, rows_total=R)
    dx, dw, db = pl.pallas_call(
        kernel,
        grid=(R // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, N), lambda i: (i, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
            pl.BlockSpec((1, N), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=interpret,
        name="layer_norm_bwd",
    )(x2, w[None], mean, rstd, g2)
    return dx, dw[0], db[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, weight, bias, eps: float = 1e-5):
    """LayerNorm over the last dim, fp32 accumulation. x: [..., N]."""
    o, _ = _fused_ln_fwd(x, weight, bias, eps)
    return o


def _pick_block_rows(rows: int) -> int:
    br = DEFAULT_BLOCK_ROWS
    while rows % br:
        br //= 2
    return max(br, 1)


def _fused_ln_fwd(x, weight, bias, eps):
    shape = x.shape
    N = shape[-1]
    x2 = x.reshape(-1, N)
    br = _pick_block_rows(x2.shape[0])
    o, mean, rstd = _ln_fwd(x2, weight, bias, eps=eps, block_rows=br,
                            interpret=_should_interpret())
    return o.reshape(shape), (x2, weight, mean, rstd, shape)


def _fused_ln_fwd_vjp(x, weight, bias, eps):
    return _fused_ln_fwd(x, weight, bias, eps)


def _fused_ln_bwd_vjp(eps, res, g):
    x2, weight, mean, rstd, shape = res
    g2 = g.reshape(x2.shape)
    br = _pick_block_rows(x2.shape[0])
    dx, dw, db = _ln_bwd(x2, weight, mean, rstd, g2, block_rows=br,
                         interpret=_should_interpret())
    return (dx.reshape(shape), dw.astype(weight.dtype),
            db.astype(weight.dtype))


fused_layer_norm.defvjp(_fused_ln_fwd_vjp, _fused_ln_bwd_vjp)


def fused_residual_layer_norm(x, residual, weight, bias, eps: float = 1e-5):
    """(x + residual) then LayerNorm — the per-layer inference fusion
    (reference ds_layer_norm_residual, layer_norm.cu). Returns (normed, sum)
    so the caller can carry the pre-norm residual stream."""
    s = x + residual
    return fused_layer_norm(s, weight, bias, eps), s


def layer_norm_reference(x, weight, bias, eps: float = 1e-5):
    """Numerics oracle."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xhat = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (xhat * weight.astype(jnp.float32) +
            bias.astype(jnp.float32)).astype(x.dtype)
