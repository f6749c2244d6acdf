"""MiMo-V2-Flash's forward pass in plain ``jax.numpy`` and float32: masks
and no cache, no kernels, no batching, a Python loop over experts,
``default_matmul_precision "highest"``. Independent of the code under
test (it imports nothing from ``deepspeed_tpu``): written from the layer
equations below (the model's published ``config.json`` plus the
assumptions listed at the end), it takes the weights in its own layout,
which ``benchmark/models/mimo_v2.py`` fills from the program's tree.

One layer (``N`` RMSNorm; ``d_k`` the key width, ``d_v`` the value
width; a layer is ``full`` or ``window`` by ``hybrid_layer_pattern``;
query head ``n`` of the ``H`` reads key/value head ``n // (H / KH)``,
``KH`` the layer's OWN key/value head count)::

    h = N_in(x)
    q = h W_q [H, d_k]     k = h W_k [KH, d_k]     v = c h W_v [KH, d_v]
    q[..., :r], k[..., :r] <- RoPE(theta_kind)     pairs (i, i + r/2);
                                                   dims r .. d_k - 1 pass
    s_ij = q_i . k_j / sqrt(d_k)        j <= i;  window: i - w < j
    full:    p_i. = softmax_j(s_i.)
    window:  p_i. = softmax([s_i., b_n])[:-1]      the sink: one more logit
                                                   column a head, dropped
    x <- x + concat_n(sum_j p_ij v_j) W_o [H d_v, E]
    u = N_post(x)
    dense layer:   x <- x + (silu(u W_gate) * (u W_up)) W_down
    sparse layer:  s = sigmoid(u W_r)  in float32, all router outputs
                   P = top_k(s + b)                 b selects, never weighs
                   w_e = f s_e / sum_{j in P} s_j   (f = 1: no factor)
                   x <- x + sum_{e in P, e held} w_e E_e(u)     no shared

then a final RMSNorm and an untied head.

Layout (``weights``): ``wte [V, E]``, ``lm_head [E, V]``, ``norm_f [E]``,
``sizes`` (a dict: ``rotary_dim window value_scale top_k factor
norm_topk eps held_lo held_hi``) and ``layers``, a list of dicts
``kind`` (``"full"`` or ``"window"``), ``theta``, ``g_in [E]  g_post [E]
w_q [E, H, d_k]  w_k [E, KH, d_k]  w_v [E, KH, d_v]  w_o [H, d_v, E]``,
``sink [H]`` (float32) where the layer has one, and either ``ffn``
(``w_gate_up [E, 2 F]`` gate first, ``w_down [F, E]``) or ``router [E,
R]``, ``router_bias [R]`` and ``experts`` (``w_gate_up [X, E, 2 Fe]``,
``w_down [X, Fe, E]``: the experts ``held_lo .. held_hi - 1``). Leaves
may be stored in any float type (the benchmark hands over the served
bfloat16 arrays): every matrix is raised to float32 inside the jitted
function that uses it, one at a time, each layer is WAITED FOR before the
next one's weights are raised, attention is computed ``QUERY_BLOCK``
query rows at a time, the dense FFN ``FFN_BLOCK`` hidden channels at a
time (its ``[4096, 2 x 16384]`` input matrix is 0.5 GB in float32 and
three times that split for "highest"), the head a block of vocabulary
columns at a time, and each sample's logits leave the device before the
next sample starts: 4096 + 320 positions fit beside a 14 GB server.

Departures from the published description, each the deployment's or an
assumption the configuration file lists under ``assumed``:

* THE SHARE. ``held_lo .. held_hi`` are the routed experts this process
  holds; picks on the others are left out of the layer (their holders
  add those parts), exactly as the program leaves them out. With all
  experts held this is the whole layer.
* SiLU-gated MLPs (dense and routed) without biases.
* RoPE in half-rotation pairs over the first ``r = int(0.334 x 192) =
  64`` dims of a head, plain (no scaling), a base a layer kind.
* ``attention_value_scale`` multiplies V (so the attention's output).
* The sink: a learned logit a query head, concatenated to the scores as
  one more column, its probability dropped (it joins the denominator and
  carries no value). Window layers only.
* ``attention_chunk_size`` is the published kernels' tile, not a mask.
* The router: sigmoid scores, a per-expert bias used for selection only
  (``noaux_tc``, one group), the picked scores normalised to sum 1, no
  scaling factor, weights on the expert's output.
* A final RMSNorm; an untied head. The multi-token-prediction modules
  are not part of this forward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HI = functools.partial(jax.default_matmul_precision, "highest")
QUERY_BLOCK = 256
FFN_BLOCK = 4096
VOCAB_BLOCK = 16384


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * g.astype(F32)


def _rope(x, theta: float, r: int):
    """``x [T, n, d]`` at positions ``0 .. T - 1``: the first ``r`` dims
    turned in pairs ``(i, i + r/2)``."""
    half = r // 2
    inv = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(
        inv, F32)[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


@functools.partial(jax.jit, static_argnames=("theta", "r", "scale", "eps"))
def _project(x, g_in, w_q, w_k, w_v, theta, r, scale, eps):
    """``x [T, E]`` -> ``q [T, H, d_k]``, ``k [T, KH, d_k]`` (rotated),
    ``v [T, KH, d_v]`` (scaled)."""
    with _HI():
        h = _rms(x, g_in, eps)
        q = jnp.einsum("te,ehd->thd", h, w_q.astype(F32))
        k = jnp.einsum("te,ehd->thd", h, w_k.astype(F32))
        v = scale * jnp.einsum("te,ehd->thd", h, w_v.astype(F32))
    return _rope(q, theta, r), _rope(k, theta, r), v


@functools.partial(jax.jit, static_argnames=("window", "block"))
def _attention(q, k, v, sink, window, block):
    """Masked softmax attention, ``block`` query rows at a time: ``[T, H,
    d_v]``. ``window`` None: causal; else ``i - window < j <= i``.
    ``sink [H]`` or None: a logit column whose probability is dropped."""
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
            s = jnp.where(seen[None, None], s, -jnp.inf)
            if sink is not None:
                col = jnp.broadcast_to(
                    sink.astype(F32).reshape(KH, H // KH, 1, 1),
                    (*s.shape[:3], 1))
                s = jnp.concatenate([s, col], -1)
            p = jax.nn.softmax(s, -1)[..., :T]
            return jnp.einsum("mgqk,kmd->qmgd", p, v)
    out = jax.lax.map(rows, (qp, starts))
    return out.reshape(Tp, H, v.shape[-1])[:T]


@jax.jit
def _attn_out(x, a, w_o):
    with _HI():
        return x + jnp.einsum("thd,hde->te", a, w_o.astype(F32))


def _swiglu(u, w_gate, w_up, w_down):
    return (jax.nn.silu(u @ w_gate.astype(F32)) * (u @ w_up.astype(F32))
            ) @ w_down.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@jax.jit
def _add_ffn(x, u, w_gate, w_up, w_down):
    with _HI():
        return x + _swiglu(u, w_gate, w_up, w_down)


def _dense(x, u, ffn):
    """``x + FFN(u)``, ``FFN_BLOCK`` hidden channels at a time."""
    F = ffn["w_down"].shape[0]
    for c in range(0, F, FFN_BLOCK):
        e = min(c + FFN_BLOCK, F)
        x = _add_ffn(x, u, ffn["w_gate_up"][:, c:e],
                     ffn["w_gate_up"][:, F + c:F + e], ffn["w_down"][c:e])
    return x


@functools.partial(jax.jit, static_argnames=("top_k", "factor",
                                             "norm_topk"))
def _route(u, router, bias, top_k, factor, norm_topk):
    """Picks ``[N, k]`` and their weights ``[N, k]``."""
    with _HI():
        s = jax.nn.sigmoid(u @ router.astype(F32))
    _, picks = jax.lax.top_k(s + bias.astype(F32), top_k)
    w = jnp.take_along_axis(s, picks, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return picks, factor * w


@jax.jit
def _add_expert(x, u, weight, w_gate_up, w_down):
    Fe = w_down.shape[0]
    with _HI():
        return x + weight[:, None] * _swiglu(
            u, w_gate_up[:, :Fe], w_gate_up[:, Fe:], w_down)


def _sparse(x, u, layer, z, record=None):
    """``x + sum_{held picks} w_e E_e(u)`` on ``[T, E]``."""
    picks, w = _route(u, layer["router"], layer["router_bias"],
                      top_k=z["top_k"], factor=z["factor"],
                      norm_topk=z["norm_topk"])
    if record is not None:
        record.append({"picks": picks, "weights": w})
    ex = layer["experts"]
    for n, e in enumerate(range(z["held_lo"], z["held_hi"])):
        x = _add_expert(x, u, jnp.sum(jnp.where(picks == e, w, 0.0), -1),
                        ex["w_gate_up"][n], ex["w_down"][n])
    return x


def layer_forward(x, layer, sizes: dict, record=None):
    """One layer on ``x [T, E]`` float32."""
    z = sizes
    q, k, v = _project(x, layer["g_in"], layer["w_q"], layer["w_k"],
                       layer["w_v"], theta=float(layer["theta"]),
                       r=z["rotary_dim"], scale=z["value_scale"],
                       eps=z["eps"])
    a = _attention(q, k, v, layer.get("sink"),
                   window=(z["window"] if layer["kind"] == "window"
                           else None),
                   block=min(QUERY_BLOCK, max(x.shape[0], 1)))
    x = _attn_out(x, a, layer["w_o"])
    u = _norm(x, layer["g_post"], eps=z["eps"])
    if "ffn" in layer:
        return _dense(x, u, layer["ffn"])
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


def logits_at(weights: dict, ids, positions) -> np.ndarray:
    """Logits ``[B, K, V]`` at ``positions [B, K]`` only, a sequence at a
    time, every one at the batch's common length (what follows a
    sequence's last position is padding, and causal attention never
    looks ahead): ONE shape, so each function above compiles once a
    layer kind and not once a sample (at "highest" the compiles cost
    more than the arithmetic: four samples at their own lengths took
    ~140 s of a run's set-up on the chip, PERF.md section 6, PR 53).
    Each sequence's logits leave the device before the next one
    starts."""
    ids, positions = np.asarray(ids), np.asarray(positions)
    T = int(positions.max()) + 1
    return np.stack([
        np.asarray(_logits_of(
            weights, hidden(weights, row[:T])[jnp.asarray(pos)]))
        for row, pos in zip(ids, positions)])
