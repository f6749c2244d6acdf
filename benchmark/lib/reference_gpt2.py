"""GPT-2's forward pass and loss in plain ``jax.numpy`` and float32:
no kernels, no cache, no batching tricks, ``default_matmul_precision
"highest"`` (on a TPU a float32 matmul otherwise runs in bf16 passes).
It follows Radford et al. 2019 (pre-LN blocks, learned positions, tanh
GELU, tied LM head). Independent of the code under test: it takes the
weights in its own plain layout, which ``benchmark/models/gpt2.py``
fills from either of the program's parameter trees.

Layout (``weights``): ``wte [V, E]``, ``wpe [P, E]``, ``lnf_g``,
``lnf_b``, ``eps``, ``n_head`` and ``layers``, a list of dicts with
``ln1_g ln1_b w_qkv [E, 3E] b_qkv w_o [E, E] b_o ln2_g ln2_b w_fc [E, F]
b_fc w_proj [F, E] b_proj``. Leaves may be stored in any float type;
every layer is raised to float32 as it is used, one layer at a time, so
the reference of a 1.3B model needs a few hundred MB beside the stored
weights.

Departures from the paper, both the program's own definitions: the
embedding table may have more rows than the vocabulary (padding to a
multiple of 128) and the softmax of the loss then runs over all rows, as
the program's does; ``eps`` is whatever the program's layer norm uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g.astype(F32) + b.astype(F32)


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def _layer(x, w, n_head, eps):
    """One block on ``x [B, T, E]`` float32."""
    with jax.default_matmul_precision("highest"):
        w = jax.tree.map(lambda a: a.astype(F32), w)
        B, T, E = x.shape
        D = E // n_head
        h = _ln(x, w["ln1_g"], w["ln1_b"], eps)
        qkv = h @ w["w_qkv"] + w["b_qkv"]
        q, k, v = (t.reshape(B, T, n_head, D)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(D))
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, T, E)
        x = x + a @ w["w_o"] + w["b_o"]
        h = _ln(x, w["ln2_g"], w["ln2_b"], eps)
        h = _gelu(h @ w["w_fc"] + w["b_fc"])
        return x + h @ w["w_proj"] + w["b_proj"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, wte, g, b, eps):
    with jax.default_matmul_precision("highest"):
        return _ln(x, g, b, eps) @ wte.astype(F32).T


def hidden(weights: dict, ids) -> jax.Array:
    """Final residual stream ``[B, T, E]`` (before the last layer norm)."""
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[1]
    x = (weights["wte"][ids].astype(F32)
         + weights["wpe"][:T].astype(F32)[None])
    for w in weights["layers"]:
        x = _layer(x, w, n_head=weights["n_head"], eps=weights["eps"])
    return x


def logits(weights: dict, ids) -> jax.Array:
    """``[B, T, rows of wte]`` float32 logits of the full forward."""
    return _head(hidden(weights, ids), weights["wte"], weights["lnf_g"],
                 weights["lnf_b"], eps=weights["eps"])


def logits_at(weights: dict, ids, positions) -> jax.Array:
    """Logits ``[B, K, rows of wte]`` at ``positions [B, K]`` only (the
    head over every position of a long batch is hundreds of MB)."""
    x = hidden(weights, ids)
    x = jnp.take_along_axis(x, jnp.asarray(positions)[..., None], axis=1)
    return _head(x, weights["wte"], weights["lnf_g"], weights["lnf_b"],
                 eps=weights["eps"])


def nll(weights: dict, ids, vocab_size: int):
    """Summed next-token cross entropy over ``ids [B, T]`` and the count
    of labels it was summed over, as arrays (differentiable). Labels are
    the inputs shifted by one; labels outside ``[0, vocab_size)`` are
    ignored."""
    ids = jnp.asarray(ids, jnp.int32)
    lg = logits(weights, ids)[:, :-1]
    labels = ids[:, 1:]
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    ok = (labels >= 0) & (labels < vocab_size)
    return jnp.sum((lse - gold) * ok), jnp.sum(ok)


def loss(weights: dict, ids, vocab_size: int, rows: int = 2) -> float:
    """Mean next-token cross entropy over ``ids [B, T]``, ``rows``
    sequences at a time so that the score and logit tensors stay
    small."""
    total, count = 0.0, 0
    for i in range(0, len(ids), rows):
        t, c = nll(weights, ids[i:i + rows], vocab_size)
        total += float(t)
        count += int(c)
    return total / max(count, 1)
