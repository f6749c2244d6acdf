"""NVIDIA-Nemotron-3-Nano-30B-A3B's forward pass (``nemotron_h``) in plain
``jax.numpy`` and float32: the Mamba-2 layer as the token-by-token
RECURRENCE (a ``lax.scan`` over positions, not the chunked form the
program uses), attention with masks and no cache, no kernels, no
batching, a Python loop over experts, ``default_matmul_precision
"highest"``. Independent of the code under test: written from the layer
equations below (the model's published ``config.json``, the Mamba-2 paper,
Dao & Gu 2024, arXiv:2405.21060, the family's published description,
Nemotron-H, arXiv:2504.03624, and the assumptions listed at the end), it
takes the weights in its own layout, which ``benchmark/models/
nemotron_h.py`` fills from the program's tree.

The model (``N`` RMSNorm; a layer is ONE mixer)::

    x0 = Emb(ids)      every layer: x <- x + Mix(N(x))
    logits = N_f(x) W_head                              an untied head

    Mix, "M" (Mamba-2: H heads of P, state N, G groups, k taps):
      [z | xBC | dt] = h W_in            Di | Di + 2 G N | H,  Di = H P
      xBC_t <- silu(b + sum_j w_j xBC_{t - (k - 1) + j})   zeros before 0
      [x | B | C] = xBC                  x [H, P];  B, C [G, N]
      dt <- softplus(dt + dt_bias)       a = exp(dt A),  A = -exp(A_log)
      g(h) = h // (H / G)
      S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]   S_{-1} = 0
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
      v = y silu(z)                      the gate BEFORE the norm
      o[c] = v[c] / sqrt(mean_{c' in c's group of Di / G} v[c']^2 + eps) w[c]
      Mix = o W_out
    Mix, "*" (attention: Hq query heads of d over KH key/value heads, query
      head n reads key/value head n // (Hq / KH); NO positional encoding):
      s_ij = q_i . k_j / sqrt(d)  (j <= i)   a = softmax(s) v   Mix = a W_o
    Mix, "E" (expert layer):
      s = sigmoid(u W_r) in float32, all R experts
      P = the k largest of s + b         b: e_score_correction_bias
      g_e = c s_e / (sum_{e' in P} s_e' + 1e-20)        c = 2.5; s, not s + b
      Mix = sum_{e in P, e held} g_e relu(u W_up,e)^2 W_down,e
            + relu(u W_up,sh)^2 W_down,sh               the shared expert

Layout (``weights``): ``wte [V, E]``, ``w_head [E, V]`` (the held slice of
the vocabulary), ``norm_f [E]``, ``sizes`` (a dict: ``kv_heads head_dim
top_k eps n_experts held_lo held_hi heads d_head d_state groups
routed_scaling_factor``) and ``layers``, a list of dicts ``kind`` (``"M"``,
``"*"`` or ``"E"``), ``g [E]`` and, by kind, ``w_in`` (the three column
blocks of ``W_in`` in the order ``z, xBC, dt``), ``conv_w [k, Di + 2 G
N]``, ``conv_b``, ``dt_bias [H]``, ``A_log [H]``, ``D [H]``, ``g_norm
[Di]``, ``w_out [Di, E]``; or ``w_q [E, Hq, d]  w_k [E, KH, d]  w_v [E, KH,
d]  w_o [Hq, d, E]``; or ``router [E, R]``, ``bias [R]``, ``experts``
(``w_up [X, E, Fe]``, ``w_down [X, Fe, E]``: the experts ``held_lo ..
held_hi - 1``), ``shared`` (``w_up [E, Fs]``, ``w_down [Fs, E]``). Leaves
may be stored in any float type (the benchmark hands over the served
bfloat16 arrays): every matrix is raised to float32 inside the jitted
function that uses it, one at a time. A sequence is computed
``POSITION_BLOCK`` positions at a time, a layer after another: a Mamba
layer carries its state and the last ``k - 1`` inputs of its convolution
from block to block, an attention layer keeps the sequence's keys and
values, and every block is WAITED FOR before the next one's weights are
raised (jax dispatches ahead, and the float32 copies would not fit beside
the engine).

Departures from the published description, each the deployment's or an
assumption the configuration file lists under ``assumed``:

* THE SHARE. ``held_lo .. held_hi`` are the routed experts this process
  holds; picks on the others are left out of the layer (their holders
  add those parts), exactly as the program leaves them out. With all
  experts held this is the whole layer. ``wte`` / ``w_head`` hold a slice
  of the vocabulary: token ids and logits are over the slice.
* No positional encoding (the config's ``rope_theta`` is unused).
* ``W_in``'s columns are ``[z | xBC | dt]``; handed over as its three
  blocks. ``dt`` is not clamped.
* The gate multiplies ``y`` before the norm; the norm's mean square is
  over each of the ``G`` groups' ``Di / G`` channels.
* No bias anywhere but the convolution's.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_HI = functools.partial(jax.default_matmul_precision, "highest")
POSITION_BLOCK = 512
VOCAB_BLOCK = 16384


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + eps) * g.astype(F32)


# ------------------------------------------------------------ Mamba layer

@functools.partial(jax.jit, static_argnames=("heads", "d_state", "groups",
                                             "eps"))
def _mamba_block(x, tail, S, g_in, w_z, w_xbc, w_dt, conv_w, conv_b,
                 dt_bias, A_log, D, g_norm, w_out, heads, d_state, groups,
                 eps):
    """``x + Mix(N(x))`` on one block ``x [T, E]`` that follows the
    convolution's last inputs ``tail [k - 1, C]`` and the state ``S [H,
    P, N]``; returns the new three."""
    with _HI():
        h = _rms(x, g_in, eps)
        z, xbc = h @ w_z.astype(F32), h @ w_xbc.astype(F32)
        dt = jax.nn.softplus(h @ w_dt.astype(F32) + dt_bias.astype(F32))
        T, k = x.shape[0], conv_w.shape[0]
        seen = jnp.concatenate([tail, xbc])                  # [k - 1 + T, C]
        conv = conv_b.astype(F32) + sum(
            conv_w[j].astype(F32) * seen[j:j + T] for j in range(k))
        act = jax.nn.silu(conv)
        Di, GN = z.shape[-1], groups * d_state
        xs = act[:, :Di].reshape(T, heads, Di // heads)
        # a head's own B and C: its group's, written out a head
        of_head = jnp.arange(heads) // (heads // groups)
        B = act[:, Di:Di + GN].reshape(T, groups, d_state)[:, of_head]
        C = act[:, Di + GN:].reshape(T, groups, d_state)[:, of_head]
        A = -jnp.exp(A_log.astype(F32))

        def step(S, now):
            x_t, B_t, C_t, dt_t = now                # [H, P] [H, N] [H, N] [H]
            S = (jnp.exp(dt_t * A)[:, None, None] * S
                 + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
            return S, jnp.sum(S * C_t[:, None, :], -1)

        S, y = jax.lax.scan(step, S, (xs, B, C, dt))
        y = (y + D.astype(F32)[:, None] * xs).reshape(T, Di)
        v = (y * jax.nn.silu(z)).reshape(T, groups, Di // groups)
        o = (v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
             ).reshape(T, Di) * g_norm.astype(F32)
        return x + o @ w_out.astype(F32), seen[T:], S


# -------------------------------------------------------- attention layer

@functools.partial(jax.jit, static_argnames=("eps",))
def _qkv(x, g_in, w_q, w_k, w_v, eps):
    with _HI():
        h = _rms(x, g_in, eps)
        return (jnp.einsum("te,ehd->thd", h, w_q.astype(F32)),
                jnp.einsum("te,ehd->thd", h, w_k.astype(F32)),
                jnp.einsum("te,ehd->thd", h, w_v.astype(F32)))


@jax.jit
def _attention_block(x, q, k, v, start, w_o):
    """``x + softmax(q k^T / sqrt(d) + causal) v W_o`` for the block of
    queries ``q [T, Hq, d]`` at positions ``start ..`` against the whole
    sequence's ``k`` / ``v [N, KH, d]`` (what lies ahead is masked)."""
    T, H, d = q.shape
    KH = k.shape[1]
    seen = (jnp.arange(k.shape[0])[None]
            <= start + jnp.arange(T)[:, None])              # [T, N]
    with _HI():
        s = jnp.einsum("qmgd,kmd->mgqk", q.reshape(T, KH, H // KH, d),
                       k) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        a = jnp.einsum("mgqk,kmd->qmgd", p, v).reshape(T, H, d)
        return x + jnp.einsum("thd,hde->te", a, w_o.astype(F32))


# ----------------------------------------------------------- expert layer

@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, g, eps):
    return _rms(x, g, eps)


@functools.partial(jax.jit, static_argnames=("top_k", "scaling"))
def route(u, router, bias, top_k, scaling):
    """Picks ``[T, k]`` and their weights ``[T, k]``: the ``k`` largest of
    ``sigmoid(u W_r) + bias``; the weights are the picked scores (without
    the bias) over their sum, times ``scaling``."""
    with _HI():
        s = jax.nn.sigmoid(u @ router.astype(F32))
    picks = jax.lax.top_k(s + bias.astype(F32), top_k)[1]
    w = jnp.take_along_axis(s, picks, -1)
    return picks, scaling * w / (jnp.sum(w, -1, keepdims=True) + 1e-20)


@jax.jit
def _add_expert(out, u, weight, w_up, w_down):
    with _HI():
        h = jnp.square(jnp.maximum(u @ w_up.astype(F32), 0.0))
        return out + weight[:, None] * (h @ w_down.astype(F32))


def expert_layer(u, layer, z, record=None):
    """``sum_{held picks} g_e E_e(u) + Shared(u)`` on ``u [T, E]``."""
    picks, w = route(u, layer["router"], layer["bias"], top_k=z["top_k"],
                     scaling=z["routed_scaling_factor"])
    if record is not None:
        record.append({"picks": picks, "weights": w})
    out = _add_expert(jnp.zeros_like(u), u, jnp.ones((u.shape[0],), F32),
                      layer["shared"]["w_up"], layer["shared"]["w_down"])
    ex = layer["experts"]
    for n, e in enumerate(range(z["held_lo"], z["held_hi"])):
        out = _add_expert(out, u, jnp.sum(jnp.where(picks == e, w, 0.0), -1),
                          ex["w_up"][n], ex["w_down"][n])
    return out


@jax.jit
def _add(x, m):
    return x + m


# ------------------------------------------------------------------ model

def _blocks(T: int):
    return range(0, T, POSITION_BLOCK)


def _mamba_layer(x, layer, z):
    H, N = z["heads"], z["d_state"]
    w_z, w_xbc, w_dt = layer["w_in"]
    k, C = layer["conv_w"].shape
    tail = jnp.zeros((k - 1, C), F32)
    S = jnp.zeros((H, w_z.shape[1] // H, N), F32)
    out = []
    for t in _blocks(x.shape[0]):
        y, tail, S = jax.block_until_ready(_mamba_block(
            x[t:t + POSITION_BLOCK], tail, S, layer["g"], w_z, w_xbc, w_dt,
            layer["conv_w"], layer["conv_b"], layer["dt_bias"],
            layer["A_log"], layer["D"], layer["g_norm"], layer["w_out"],
            heads=H, d_state=N, groups=z["groups"], eps=z["eps"]))
        out.append(y)
    return jnp.concatenate(out)


def _attention_layer(x, layer, z):
    q, k, v = _qkv(x, layer["g"], layer["w_q"], layer["w_k"], layer["w_v"],
                   eps=z["eps"])
    return jnp.concatenate([jax.block_until_ready(_attention_block(
        x[t:t + POSITION_BLOCK], q[t:t + POSITION_BLOCK], k, v, t,
        layer["w_o"])) for t in _blocks(x.shape[0])])


def _expert_layer(x, layer, z, record=None):
    out = []
    for t in _blocks(x.shape[0]):
        xb = x[t:t + POSITION_BLOCK]
        m = expert_layer(_norm(xb, layer["g"], eps=z["eps"]), layer, z,
                         record)
        out.append(jax.block_until_ready(_add(xb, m)))
    return jnp.concatenate(out)


def layer_forward(x, layer, sizes: dict, record=None):
    """One layer on ``x [T, E]`` float32, ``T`` a whole number of
    position blocks."""
    if layer["kind"] == "M":
        return _mamba_layer(x, layer, sizes)
    if layer["kind"] == "*":
        return _attention_layer(x, layer, sizes)
    return _expert_layer(x, layer, sizes, record)


def hidden(weights: dict, ids, record=None) -> jax.Array:
    """Final residual stream ``[T, E]`` of ONE sequence ``ids [T]``
    (before the last norm). ``record``: a list that receives, per expert
    layer and position block, the picks and their weights."""
    ids = np.asarray(ids, np.int32)
    T = len(ids)
    pad = -T % POSITION_BLOCK      # causal: what follows changes nothing
    x = weights["wte"][jnp.asarray(np.pad(ids, (0, pad)))].astype(F32)
    for layer in weights["layers"]:
        x = layer_forward(x, layer, weights["sizes"], record)
    return x[:T]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w_head, eps):
    with _HI():
        return _rms(x, g, eps) @ w_head.astype(F32)


def _logits_of(weights: dict, x) -> jax.Array:
    V = weights["w_head"].shape[1]
    return jnp.concatenate([
        _head(x, weights["norm_f"], weights["w_head"][:, c:c + VOCAB_BLOCK],
              eps=weights["sizes"]["eps"])
        for c in range(0, V, VOCAB_BLOCK)], -1)


def logits(weights: dict, ids, record=None) -> jax.Array:
    """``[B, T, V]`` float32 logits of the full forward."""
    return jnp.stack([_logits_of(weights, hidden(weights, row, record))
                      for row in np.asarray(ids)])


def logits_at(weights: dict, ids, positions) -> np.ndarray:
    """Logits ``[B, K, V]`` at ``positions [B, K]`` only, a sequence at a
    time and each only as far as its last position asked for (what
    follows is padding, and neither the recurrence nor causal attention
    looks ahead). Each sequence's logits leave the device before the
    next one starts."""
    ids, positions = np.asarray(ids), np.asarray(positions)
    return np.stack([
        np.asarray(_logits_of(weights, hidden(
            weights, row[:int(pos.max()) + 1])[jnp.asarray(pos)]))
        for row, pos in zip(ids, positions)])
