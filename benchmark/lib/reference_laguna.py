"""Laguna-XS.2's forward pass in plain ``jax.numpy`` and float32: masks
and no cache, no kernels, no batching, a Python loop over experts,
``default_matmul_precision "highest"``. Independent of the code under
test: written from the layer equations below (the model's published
``config.json`` plus the assumptions listed at the end), it takes the
weights in its own layout, which ``benchmark/models/laguna.py`` fills
from the program's tree.

One layer (``N`` RMSNorm, ``d`` the head size, query head ``n`` of a
layer with ``H_l`` query heads reads key/value head ``n // (H_l / KH)``;
a layer is ``full`` or ``sliding`` by ``layer_types``)::

    h = N_in(x)
    q = h W_q [H_l, d]     k = h W_k [KH, d]     v = h W_v [KH, d]
    q, k <- RoPE_l(q, k)                by layer type, below
    s_ij = q_i . k_j / sqrt(d)          j <= i;  sliding: i - window < j
    a = softmax(s) v
    a_n <- sigmoid(h W_g)_n a_n         one gate a query head a token
    x <- x + concat_n(a_n) W_o
    u = N_post(x)
    dense layer:   x <- x + (silu(u W_gate) * (u W_up)) W_down
    sparse layer:  s = sigmoid(u W_r)  in float32, all router outputs
                   P = top_k(s + b)                 b selects, never weighs
                   w_e = f s_e / sum_{j in P} s_j
                   x <- x + sum_{e in P, e held} w_e E_e(u) + S(u)

then a final RMSNorm and an untied head. RoPE by layer type (the
published ``rope_parameters``): the first ``r = partial_rotary_factor d``
dims of a head are turned in half-rotation pairs ``(x_i, x_{i + r/2})``
by ``pos * inv_freq_i``, the rest pass through. ``default``:
``inv_freq_i = theta^(-2i/r)``. ``yarn``: ``inv_freq_i`` is blended
between ``theta^(-2i/r)`` and that divided by ``factor`` with a linear
ramp over ``i`` between the dimensions that make ``beta_fast`` and
``beta_slow`` turns over ``original_max_position_embeddings`` positions,
and cos and sin are multiplied by ``attention_factor``.

Layout (``weights``): ``wte [V, E]``, ``lm_head [E, V]``, ``norm_f [E]``,
``sizes`` (a dict: ``kv_heads head_dim window top_k factor eps n_experts
held_lo held_hi``) and ``layers``, a list of dicts ``kind`` (``"full"``
or ``"sliding"``), ``rope`` (a tuple of the layer type's
``rope_parameters`` items), ``g_in [E]  g_post [E]  w_q [E, H_l, d]  w_k
[E, KH, d]  w_v [E, KH, d]  w_g [E, H_l]  w_o [H_l, d, E]`` and either
``ffn`` (``w_gate_up [E, 2 F]`` gate first, ``w_down [F, E]``) or
``router [E, R]``, ``router_bias [R]``, ``experts`` (``w_gate_up [X, E,
2 Fe]``, ``w_down [X, Fe, E]``: the experts ``held_lo .. held_hi - 1``)
and ``shared`` (as ``ffn``). Leaves may be stored in any float type (the
benchmark hands over the served bfloat16 arrays): every matrix is raised
to float32 inside the jitted function that uses it, one at a time, and
each layer is WAITED FOR before the next one's weights are raised (jax
dispatches ahead, and the float32 copies of twelve layers would not fit
beside the engine: Brumby's lesson). Attention is computed ``QUERY_BLOCK``
query rows at a time (the ``[64, T, T]`` float32 scores of an 8352-token
sample are 17.9 GB whole) and the head a block of vocabulary columns at
a time.

Departures from the published description, each the deployment's or an
assumption the configuration file lists under ``assumed``:

* THE SHARE. ``held_lo .. held_hi`` are the routed experts this process
  holds; picks on the others are left out of the layer (their holders
  add those parts), exactly as the program leaves them out. With all
  experts held this is the whole layer.
* SiLU-gated MLPs (dense, routed, shared) without biases.
* RoPE in half-rotation pairs; YaRN's ramp computed as the reference
  implementations do (``low`` floored, ``high`` ceiled, both clamped).
* ``gating: true`` read as ONE gate a query head a token from the normed
  hidden, ``W_g [E, H_l]``: the published parameter count leaves room
  for ~0.1 M a layer, not for an elementwise gate.
* The router: sigmoid scores, a per-expert bias used for selection only,
  the picked scores normalised to sum 1 and multiplied by
  ``moe_routed_scaling_factor``, weights on the expert's OUTPUT
  (``moe_apply_router_weight_on_input`` false), ungrouped.
* No q/k head norm; a final RMSNorm; an untied head.
"""
from __future__ import annotations

import functools
import math

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


def rope_frequencies(rope: dict, head_dim: int):
    """``(inv_freq [r / 2] float64, the factor on cos and sin)``."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    i = np.arange(0, r, 2, dtype=np.float64)
    inv = theta ** (-i / r)
    if rope.get("rope_type", "default") == "default":
        return inv, 1.0
    orig = rope["original_max_position_embeddings"]

    def dim_of(turns):          # the dimension that makes ``turns`` turns
        return r * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))
    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0.0, 1.0)
    inv = inv * (1.0 - ramp) + inv / rope["factor"] * ramp
    times = rope.get("attention_factor")
    if times is None:
        times = 0.1 * math.log(rope["factor"]) + 1.0
    return inv, float(times)


def _rope(x, rope: tuple):
    """``x [T, n, d]`` at positions ``0 .. T - 1``."""
    inv, times = rope_frequencies(dict(rope), x.shape[-1])
    half = inv.shape[0]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(
        inv, F32)[None]
    cos, sin = (jnp.cos(ang) * times)[:, None], (jnp.sin(ang)
                                                 * times)[:, None]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


@functools.partial(jax.jit, static_argnames=("rope", "eps"))
def _project(x, g_in, w_q, w_k, w_v, w_g, rope, eps):
    """``x [T, E]`` -> ``q [T, H, d]``, ``k``, ``v [T, KH, d]`` and the
    gate ``[T, H]``."""
    with _HI():
        h = _rms(x, g_in, eps)
        q = jnp.einsum("te,ehd->thd", h, w_q.astype(F32))
        k = jnp.einsum("te,ehd->thd", h, w_k.astype(F32))
        v = jnp.einsum("te,ehd->thd", h, w_v.astype(F32))
        gate = jax.nn.sigmoid(h @ w_g.astype(F32))
    return _rope(q, rope), _rope(k, rope), v, gate


@functools.partial(jax.jit, static_argnames=("window", "block"))
def _attention(q, k, v, window, block):
    """Masked softmax attention, ``block`` query rows at a time: ``[T, H,
    d]``. ``window`` None: causal; else ``i - window < j <= i``."""
    T, H, d = q.shape
    KH = k.shape[1]
    Tp = -(-T // block) * block
    qp = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))).reshape(
        Tp // block, block, KH, H // KH, d)
    starts = jnp.arange(0, Tp, block)
    j = jnp.arange(T)[None]

    def rows(args):
        qb, start = args
        i = start + jnp.arange(block)[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        with _HI():
            s = jnp.einsum("qmgd,kmd->mgqk", qb, k) / jnp.sqrt(F32(d))
            p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
            return jnp.einsum("mgqk,kmd->qmgd", p, v)
    out = jax.lax.map(rows, (qp, starts))
    return out.reshape(Tp, H, d)[:T]


@jax.jit
def _attn_out(x, a, gate, w_o):
    with _HI():
        return x + jnp.einsum("thd,hde->te", a * gate[..., None],
                              w_o.astype(F32))


def _swiglu(u, w_gate_up, w_down):
    gu = u @ w_gate_up.astype(F32)
    F = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@jax.jit
def _add_ffn(x, u, w_gate_up, w_down):
    with _HI():
        return x + _swiglu(u, w_gate_up, w_down)


@functools.partial(jax.jit, static_argnames=("top_k", "factor"))
def _route(u, router, bias, top_k, factor):
    """Picks ``[N, k]`` and their weights ``[N, k]``."""
    with _HI():
        s = jax.nn.sigmoid(u @ router.astype(F32))
    _, picks = jax.lax.top_k(s + bias.astype(F32), top_k)
    picked = jnp.take_along_axis(s, picks, axis=-1)
    return picks, factor * picked / jnp.sum(picked, -1, keepdims=True)


@jax.jit
def _add_expert(x, u, weight, w_gate_up, w_down):
    with _HI():
        return x + weight[:, None] * _swiglu(u, w_gate_up, w_down)


def _sparse(x, u, layer, z, record=None):
    """``x + sum_{held picks} w_e E_e(u) + S(u)`` on ``[T, E]``."""
    picks, w = _route(u, layer["router"], layer["router_bias"],
                      top_k=z["top_k"], factor=z["factor"])
    if record is not None:
        record.append({"picks": picks, "weights": w})
    x = _add_ffn(x, u, layer["shared"]["w_gate_up"],
                 layer["shared"]["w_down"])
    ex = layer["experts"]
    for n, e in enumerate(range(z["held_lo"], z["held_hi"])):
        x = _add_expert(x, u, jnp.sum(jnp.where(picks == e, w, 0.0), -1),
                        ex["w_gate_up"][n], ex["w_down"][n])
    return x


def layer_forward(x, layer, sizes: dict, record=None):
    """One layer on ``x [T, E]`` float32."""
    z = sizes
    q, k, v, gate = _project(x, layer["g_in"], layer["w_q"], layer["w_k"],
                             layer["w_v"], layer["w_g"], rope=layer["rope"],
                             eps=z["eps"])
    a = _attention(q, k, v, window=(z["window"] if layer["kind"] ==
                                    "sliding" else None),
                   block=min(QUERY_BLOCK, max(x.shape[0], 1)))
    x = _attn_out(x, a, gate, layer["w_o"])
    u = _norm(x, layer["g_post"], eps=z["eps"])
    if "ffn" in layer:
        return _add_ffn(x, u, layer["ffn"]["w_gate_up"],
                        layer["ffn"]["w_down"])
    return _sparse(x, u, layer, z, record)


def hidden(weights: dict, ids, record=None) -> jax.Array:
    """Final residual stream ``[T, E]`` of ONE sequence ``ids [T]``
    (before the last norm). ``record``: a list that receives, per sparse
    layer, the picks and their weights."""
    x = weights["wte"][jnp.asarray(ids, jnp.int32)].astype(F32)
    for layer in weights["layers"]:
        # wait: the next layer's float32 copies are not made before this
        # one's are dropped
        x = jax.block_until_ready(
            layer_forward(x, layer, weights["sizes"], record))
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, lm_head, eps):
    with _HI():
        return _rms(x, g, eps) @ lm_head.astype(F32)


def _logits_of(weights: dict, x) -> jax.Array:
    V = weights["lm_head"].shape[1]
    eps = weights["sizes"]["eps"]
    return jnp.concatenate([
        _head(x, weights["norm_f"], weights["lm_head"][:, c:c + VOCAB_BLOCK],
              eps=eps) for c in range(0, V, VOCAB_BLOCK)], -1)


def logits(weights: dict, ids, record=None) -> jax.Array:
    """``[B, T, V]`` float32 logits of the full forward."""
    return jnp.stack([_logits_of(weights, hidden(weights, row, record))
                      for row in np.asarray(ids)])


def logits_at(weights: dict, ids, positions) -> jax.Array:
    """Logits ``[B, K, V]`` at ``positions [B, K]`` only, a sequence at a
    time, every one at the batch's common length (what follows a
    sequence's last position is padding, and causal attention never
    looks ahead): one shape, so each function above compiles once a
    layer kind and not once a sample."""
    ids, positions = np.asarray(ids), np.asarray(positions)
    T = int(positions.max()) + 1
    return jnp.stack([
        _logits_of(weights, hidden(weights, row[:T])[jnp.asarray(pos)])
        for row, pos in zip(ids, positions)])
