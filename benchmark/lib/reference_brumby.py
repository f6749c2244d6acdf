"""Brumby-14B's forward pass in plain ``jax.numpy`` and float32: the
ATTENTION FORM of power retention, no kernels, no state, no chunks, no
batching, ``default_matmul_precision "highest"``. Independent of the code
under test: written from the layer equations of Buckman, Gelada, Zhang,
Bach, "Scaling Context Requires Rethinking Attention" (arXiv:2507.04239),
Manifest AI's release note of 2025-10 and the model's published
``config.json`` (a Qwen3-14B body with every attention replaced by a
power retention layer). It never builds the degree-2 features ``phi`` and
keeps no recurrent state, so it shares no arithmetic path with the
program, which serves through ``phi`` and a state (chunked prefill,
token-recurrence decode). It takes the weights in its own layout, which
``benchmark/models/brumby.py`` fills from the program's tree.

One layer (``N`` RMSNorm, ``d`` the head size, query head ``n`` reads
key/value head ``n // (H / KH)``)::

    h = N_in(x)
    q = h W_q [H, d]   k = h W_k [KH, d]   v = h W_v [KH, d]
    gamma = h W_g + b_g [KH]            log g = logsigmoid(gamma)
    q, k <- N_head(q), N_head(k)        q, k <- RoPE(q), RoPE(k)
    G_t = sum_{s<=t} log g_s
    a_tj = (q_t . k_j / sqrt(d))^2 exp(G_t - G_j)          j <= t
    y_t = sum_j a_tj v_j / (sum_j a_tj + eps)
    x <- x + concat_n(y_t[n]) W_o
    x <- x + (silu(N_post(x) W_gate) * (N_post(x) W_up)) W_down

then a final RMSNorm and an untied head.

Layout (``weights``): ``wte [V, E]``, ``lm_head [E, V]``, ``norm_f [E]``,
``sizes`` (a dict: ``heads kv_heads head_dim eps theta ret_eps``) and
``layers``, a list of dicts ``g_in [E]  g_post [E]  w_q [E, H, d]  w_k
[E, KH, d]  w_v [E, KH, d]  w_g [E, KH]  b_g [KH]  g_qn [d]  g_kn [d]
w_o [H, d, E]  w_gate_up [E, 2 F]`` (gate first) ``w_down [F, E]``.
Leaves may be stored in any float type (the benchmark hands over the
served bfloat16 arrays): every matrix is raised to float32 inside the
jitted function that uses it, one at a time; the ``[H, T, T]`` weights
are built a block of queries at a time (at T = 2056 one layer's are 676
MB whole) and the head is applied a block of vocabulary columns at a
time (a float32 copy of it is 3.1 GB and would not fit beside the
engine).

Every assumption beyond the published ``config.json`` (the configuration
file lists each under ``assumed``):

* the degree is 2 (not a key of ``config``; the paper's and the
  release's default);
* one gate a KEY/VALUE head a token, ``log g = logsigmoid(h W_g + b_g)``
  of the normed hidden. The bias ``b_g`` is this build's: a bias-free
  projection is centred on ``g`` = 0.5, a memory of a few tokens;
* per-head RMSNorm with a learned gain on q and k before RoPE, as the
  parent model (Qwen3);
* RoPE in half-rotation pairs ``(x_i, x_{i + d/2})`` over all ``d``
  dims, theta 1e6, no scaling;
* the output is NORMALISED by ``sum_j a_tj + eps`` (``eps`` 1e-6); the
  scale ``1 / sqrt(d)`` sits inside the power (it cancels in the
  normalised output up to ``eps``);
* SiLU in the gated MLP, a final RMSNorm, an untied head, no biases
  except ``b_g``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HI = functools.partial(jax.default_matmul_precision, "highest")
QUERY_BLOCK = 256
VOCAB_BLOCK = 16384


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta):
    """``x [T, n, d]`` at positions ``0 .. T - 1``: pairs ``(x[i], x[i +
    d/2])`` turned by ``pos * theta ** (-2i / d)``."""
    T, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]          # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _project(x, layer, sizes):
    """``x [T, E]`` -> ``q [T, H, d]``, ``k``, ``v [T, KH, d]`` and the
    cumulative log gate ``G [T, KH]``."""
    z = dict(sizes)
    with _HI():
        h = _rms(x, layer["g_in"], z["eps"])
        q = jnp.einsum("te,ehd->thd", h, layer["w_q"].astype(F32))
        k = jnp.einsum("te,ehd->thd", h, layer["w_k"].astype(F32))
        v = jnp.einsum("te,ehd->thd", h, layer["w_v"].astype(F32))
        gamma = h @ layer["w_g"].astype(F32) + layer["b_g"].astype(F32)
    q = _rope(_rms(q, layer["g_qn"], z["eps"]), z["theta"])
    k = _rope(_rms(k, layer["g_kn"], z["eps"]), z["theta"])
    return q, k, v, jnp.cumsum(jax.nn.log_sigmoid(gamma), axis=0)


@functools.partial(jax.jit, static_argnames=("sizes", "block"))
def _retention(q, k, v, G, sizes, block):
    """The attention form, ``block`` queries at a time: ``[T, H, d]``.
    Also returns the largest weight ``a_tj`` and the smallest normaliser
    ``sum_j a_tj`` met (what squares without a running maximum come
    to)."""
    z = dict(sizes)
    T, H, d = q.shape
    KH = k.shape[1]
    Tp = -(-T // block) * block
    qp = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0)))
    Gp = jnp.pad(G, ((0, Tp - T), (0, 0)))
    qp = qp.reshape(Tp // block, block, KH, H // KH, d)
    Gp = Gp.reshape(Tp // block, block, KH)
    t0 = jnp.arange(0, Tp, block)

    def rows(args):
        qb, Gb, start = args                          # [B, KH, G, d] ...
        with _HI():
            s = jnp.einsum("tmgd,jmd->mgtj", qb, k) / jnp.sqrt(F32(d))
        t = start + jnp.arange(block)
        seen = jnp.arange(T)[None, :] <= t[:, None]                 # [B, T]
        decay = jnp.exp(jnp.where(
            seen[None], Gb.T[:, :, None] - G.T[:, None, :], -jnp.inf))
        a = s * s * decay[:, None]                           # [KH, G, B, T]
        total = a.sum(-1)
        with _HI():
            y = jnp.einsum("mgtj,jmv->tmgv", a, v)
        y = y / (jnp.moveaxis(total, -1, 0) + z["ret_eps"])[..., None]
        live = (t < T)[None, None, :]     # not the last block's padding
        return (y, jnp.max(jnp.where(live[..., None], a, 0.0)),
                jnp.min(jnp.where(live, total, jnp.inf)))
    y, biggest, smallest = jax.lax.map(rows, (qp, Gp, t0))
    return (y.reshape(Tp, H, d)[:T], jnp.max(biggest), jnp.min(smallest))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _mix_out(x, y, w_o, sizes):
    with _HI():
        return x + jnp.einsum("thd,hde->te", y, w_o.astype(F32))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _mlp(x, g_post, w_gate_up, w_down, sizes):
    z = dict(sizes)
    F = w_down.shape[0]
    with _HI():
        h = _rms(x, g_post, z["eps"])
        gate = h @ w_gate_up[:, :F].astype(F32)
        up = h @ w_gate_up[:, F:].astype(F32)
        return x + (jax.nn.silu(gate) * up) @ w_down.astype(F32)


def _sizes(weights: dict) -> tuple:
    return tuple(sorted(weights["sizes"].items()))


def hidden(weights: dict, ids, extremes: list | None = None) -> jax.Array:
    """Final residual stream ``[T, E]`` (before the last norm) of ONE
    sequence ``ids [T]``. ``extremes``: a list that receives, per layer,
    ``(largest a_tj, smallest normaliser)``."""
    sizes = _sizes(weights)
    x = weights["wte"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for layer in weights["layers"]:
        q, k, v, G = _project(x, layer, sizes)
        y, biggest, smallest = _retention(q, k, v, G, sizes,
                                          min(QUERY_BLOCK, len(ids)))
        if extremes is not None:
            extremes.append((biggest, smallest))
        x = _mix_out(x, y, layer["w_o"], sizes)
        x = _mlp(x, layer["g_post"], layer["w_gate_up"], layer["w_down"],
                 sizes)
        # a layer at a time: dispatched ahead, every layer's float32
        # matrices and weights [40, 256, T] would be reserved at once,
        # beside an engine that fills 85 % of the chip
        x.block_until_ready()
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head_block(x, g, lm_head_block, eps):
    with _HI():
        return _rms(x, g, eps) @ lm_head_block.astype(F32)


def _head(weights: dict, x) -> jax.Array:
    V = weights["lm_head"].shape[1]
    return jnp.concatenate([
        _head_block(x, weights["norm_f"],
                    weights["lm_head"][:, lo:lo + VOCAB_BLOCK],
                    eps=weights["sizes"]["eps"])
        for lo in range(0, V, VOCAB_BLOCK)], axis=-1)


def logits(weights: dict, ids) -> jax.Array:
    """``[B, T, V]`` float32 logits of the full forward, a sequence at a
    time."""
    return jnp.stack([_head(weights, hidden(weights, row)) for row in ids])


def logits_at(weights: dict, ids, positions,
              extremes: list | None = None) -> np.ndarray:
    """Logits ``[B, K, V]`` at ``positions [B, K]`` only, a sequence at a
    time."""
    out = []
    for row, at in zip(ids, positions):
        x = hidden(weights, row, extremes)
        out.append(np.asarray(_head(weights, x[jnp.asarray(at)])))
    return np.stack(out)        # on the host: K x V x 4 B a sequence
