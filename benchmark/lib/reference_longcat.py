"""LongCat-Flash's forward pass in plain ``jax.numpy`` and float32: no
kernels, no cache, no batching, materialised attention only, a Python
loop over experts, ``default_matmul_precision "highest"``. Independent of
the code under test: it is written from the layer equations of the
LongCat-Flash Technical Report (Meituan, 2025-09) and the model's
published ``config.json``, and takes the weights in its own layout, which
``benchmark/models/longcat_flash.py`` fills from the program's tree.

One layer (``N`` RMSNorm, ``A`` latent attention, ``F`` dense SwiGLU FFN,
``M`` the MoE; the MoE is the shortcut: it reads the FIRST sub-block's
normed hidden and is added at the END of the layer)::

    h1 = x  + A0(N_in0(x))          u = N_post0(h1)
    m  = M(u)
    h2 = h1 + F0(u)
    h3 = h2 + A1(N_in1(h2))
    y  = h3 + F1(N_post1(h3)) + m

Latent attention::

    c_q  = N_q(x W_qa)               q = s_q (c_q W_qb) -> q_nope, q_rope
    [c, r] = x W_kva                 c_kv = s_kv N_kv(c)
    k_rope = RoPE(r) (one for all heads)        q_rope = RoPE(q_rope)
    k_nope = c_kv W_kb      v = c_kv W_vb      (W_kvb = [W_kb ; W_vb])
    p = causal softmax((q_nope . k_nope + q_rope . k_rope) / sqrt(Dn + Dr))
    out = concat_h(p v) W_o

Router and experts (real experts ``0 .. n_routed - 1``, identity experts
after them)::

    s = softmax(float32(u) W_r)      T = top_k(s + b)      w_i = f s_i
    M(u) = sum_{i in T, i real} w_i E_i(u) + (sum_{i in T, i identity} w_i) u

Layout (``weights``): ``wte [V, E]``, ``lm_head [E, V]``, ``norm_f
[E]``, ``sizes`` (a dict: ``heads nope rope v_dim q_scale kv_scale
n_routed top_k factor eps theta held_lo held_hi``) and ``layers``, a list
of dicts ``norm_in [2, E]``, ``norm_post [2, E]``, ``attn`` (two dicts
``w_qa [E, Rq] g_q w_qb [Rq, H, Dn + Dr] w_kva [E, Rkv + Dr] g_kv
w_kb [Rkv, H, Dn] w_vb [Rkv, H, Dv] w_o [H, Dv, E]``; the published
``W_kvb``'s key and value columns), ``ffn`` (two dicts
``w_gate_up [E, 2 F]`` gate first, ``w_down [F, E]``), ``router [E, R]``,
``router_bias [R]``, ``experts`` (``w_gate_up [X, E, 2 Fe]``, ``w_down
[X, Fe, E]``: the experts ``held_lo .. held_hi - 1``). Leaves may be
stored in any float type; every matrix is raised to float32 inside the
jitted function that uses it, one sub-block at a time (an expert at a
time), so the reference of the 5 B-parameter share needs well under a GB
beside the stored weights.

Departures from the published description, each the deployment's or an
assumption the configuration file lists under ``assumed``:

* THE SHARE. ``held_lo .. held_hi`` are the real experts this process
  holds; picks on the other real experts are left out of ``M`` (their
  holders add those parts), exactly as the program leaves them out. With
  all experts held this is the whole layer. ``V`` may be a slice of the
  vocabulary: logits are over the slice.
* ``mla_scale_q_lora`` / ``mla_scale_kv_lora`` are booleans in the
  config; that they mean the factors ``sqrt(hidden / rank)`` on the
  normed latents is assumed (``q_scale``, ``kv_scale`` here).
* RoPE in interleaved pairs over the rope dims, no scaling, is assumed.
* SiLU in both FFN kinds, no renormalisation of the top-k weights, the
  identity experts after the real ones, a final RMSNorm and an untied
  head are assumed.
"""
from __future__ import annotations

import functools
from typing import List, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = functools.partial(jax.default_matmul_precision, "highest")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta):
    """``x [B, T, n, D]`` at positions ``0 .. T - 1``: interleaved pairs
    ``(x[2i], x[2i+1])`` turned by ``pos * theta ** (-2i / D)``."""
    T, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None]          # [T, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _attention(x, g_in, a, sizes):
    """``x + A(N_in(x))`` on ``x [B, T, E]`` float32."""
    z = dict(sizes)
    with _HI():
        a = jax.tree.map(lambda w: w.astype(F32), a)
        T, Dn = x.shape[1], z["nope"]
        h = _rms(x, g_in, z["eps"])
        c_q = _rms(h @ a["w_qa"], a["g_q"], z["eps"])
        q = z["q_scale"] * jnp.einsum("btr,rhd->bthd", c_q, a["w_qb"])
        q_nope, q_rope = q[..., :Dn], _rope(q[..., Dn:], z["theta"])
        kv = h @ a["w_kva"]
        R = a["g_kv"].shape[0]
        c_kv = z["kv_scale"] * _rms(kv[..., :R], a["g_kv"], z["eps"])
        k_rope = _rope(kv[..., None, R:], z["theta"])       # [B, T, 1, Dr]
        k_nope = jnp.einsum("btr,rhd->bthd", c_kv, a["w_kb"])
        v = jnp.einsum("btr,rhd->bthd", c_kv, a["w_vb"])
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope[:, :, 0]))
        s = s / jnp.sqrt(F32(Dn + q_rope.shape[-1]))
        s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        return x + jnp.einsum("bthd,hde->bte", o, a["w_o"])


def _swiglu(x, w_gate_up, w_down):
    gu = x @ w_gate_up.astype(F32)
    F = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@jax.jit
def _ffn(x, w_gate_up, w_down):
    with _HI():
        return _swiglu(x, w_gate_up, w_down)


@functools.partial(jax.jit, static_argnames=("sizes",))
def _route(u, router, bias, sizes):
    """Scores ``[N, R]``, free picks ``[N, k]`` and the margin between
    the last pick's and the first loser's biased score ``[N]``."""
    z = dict(sizes)
    with _HI():
        s = jax.nn.softmax(u @ router.astype(F32), axis=-1)
    top, picks = jax.lax.top_k(s + bias.astype(F32), z["top_k"] + 1)
    return s, picks[:, :-1], top[:, -2] - top[:, -1]


@jax.jit
def _add_expert(m, u, weight, w_gate_up, w_down):
    with _HI():
        return m + weight[:, None] * _swiglu(u, w_gate_up, w_down)


def _moe(u, layer, sizes, route_as=None, record: Optional[list] = None):
    """``M(u)`` on ``u [N, E]``: this share's part. ``route_as [N, k]``
    int, where given, replaces the free picks of the rows it names (a
    row of -1 stays free)."""
    z = dict(sizes)
    s, picks, margin = _route(u, layer["router"], layer["router_bias"],
                              sizes)
    if record is not None:
        record.append({"picks": picks, "margin": margin})
    if route_as is not None:
        route_as = jnp.asarray(route_as)
        picks = jnp.where(route_as[:, :1] >= 0, route_as, picks)
    w = z["factor"] * jnp.take_along_axis(s, picks, axis=-1)      # [N, k]
    identity = jnp.sum(jnp.where(picks >= z["n_routed"], w, 0.0), -1)
    m = identity[:, None] * u
    ex = layer["experts"]
    for j, e in enumerate(range(z["held_lo"], z["held_hi"])):
        m = _add_expert(m, u, jnp.sum(jnp.where(picks == e, w, 0.0), -1),
                        ex["w_gate_up"][j], ex["w_down"][j])
    return m


def layer_forward(x, layer, sizes, route_as=None, record=None):
    """One double block on ``x [B, T, E]`` float32."""
    eps = dict(sizes)["eps"]
    h1 = _attention(x, layer["norm_in"][0], layer["attn"][0], sizes)
    u = _norm(h1, layer["norm_post"][0], eps)
    m = _moe(u.reshape(-1, u.shape[-1]), layer, sizes, route_as,
             record).reshape(u.shape)
    f0 = layer["ffn"][0]
    h2 = h1 + _ffn(u, f0["w_gate_up"], f0["w_down"])
    h3 = _attention(h2, layer["norm_in"][1], layer["attn"][1], sizes)
    f1 = layer["ffn"][1]
    return h3 + _ffn(_norm(h3, layer["norm_post"][1], eps),
                     f1["w_gate_up"], f1["w_down"]) + m


def _sizes(weights: dict) -> tuple:
    return tuple(sorted(weights["sizes"].items()))


def hidden(weights: dict, ids, route_as: Optional[List] = None,
           record: Optional[list] = None) -> jax.Array:
    """Final residual stream ``[B, T, E]`` (before the last norm).
    ``route_as``: per layer, picks ``[B * T, k]`` to route as (None or a
    row of -1: free routing, the default). ``record``: a list that
    receives, per layer, the free picks and their margins."""
    ids = jnp.asarray(ids, jnp.int32)
    sizes = _sizes(weights)
    x = weights["wte"][ids].astype(F32)
    for i, layer in enumerate(weights["layers"]):
        x = layer_forward(x, layer, sizes,
                          None if route_as is None else route_as[i], record)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps):
    with _HI():
        return _rms(x, g, eps) @ lm_head.astype(F32)


def logits(weights: dict, ids, route_as=None, record=None) -> jax.Array:
    """``[B, T, V]`` float32 logits of the full forward."""
    return _head(hidden(weights, ids, route_as, record), weights["norm_f"],
                 weights["lm_head"], eps=weights["sizes"]["eps"])


def logits_at(weights: dict, ids, positions, rows: int = 4) -> jax.Array:
    """Logits ``[B, K, V]`` at ``positions [B, K]`` only, ``rows``
    sequences at a time (free routing)."""
    out = []
    for i in range(0, len(ids), rows):
        x = hidden(weights, ids[i:i + rows])
        x = jnp.take_along_axis(
            x, jnp.asarray(positions[i:i + rows])[..., None], axis=1)
        out.append(_head(x, weights["norm_f"], weights["lm_head"],
                         eps=weights["sizes"]["eps"]))
    return jnp.concatenate(out, 0)
