"""Laguna: window and full attention layers of different head counts in
one model, over a sigmoid-routed expert layer with a shared expert,
served as ONE CHIP'S SHARE of an expert-parallel deployment.

The generic decoder (``transformer.py``) gives every layer the same head
count, one rotary table and a whole-context cache. This model's layers
come in two kinds (``layer_types``), so it is a module of its own that
the same serving entry points (``paged_prefill`` / ``paged_decode_step``,
reached through ``transformer.model_family``) run:

* **full layers** (``full_attention``) see the whole context. They keep
  block tables over the shared pool (``kv_cache.PagedKVCache.k`` / ``v``)
  and decode through the paged kernel. Rotary over the FIRST
  ``partial_rotary_factor`` of a head's dims, YaRN-scaled.
* **window layers** (``sliding_attention``) see the last
  ``sliding_window`` positions. They keep a bounded ring a slot
  (``PagedKVCache.ring_k`` / ``ring_v``: position ``p`` at row ``p mod
  R``), written by prefill with the prompt's last ``R`` rows only, and
  decode through the same kernel walking the ring
  (``paged_window_decode_attention``). Rotary over all dims, unscaled.
  Their query head count differs from the full layers' (groups of 8 and
  of 6 over the same 8 key/value heads at the published sizes).
* **every attention is gated** head-wise: ``a_h <- sigmoid(h W_g)_h a_h``.
* **the FFN** is a dense SwiGLU on the layers ``mlp_layer_types`` marks
  ``dense`` and an expert layer elsewhere: float32 sigmoid scores over
  ALL router outputs, a selection bias, top-k weights normalised to sum
  ``moe_routed_scaling_factor``, the held experts' part through
  ``held_experts.py`` (picks on absent experts are left out: their
  holders add those parts; nothing stands in for the other chips), and
  one shared expert every token passes through.

One layer (``N`` RMSNorm)::

    h = N_in(x)
    q = h W_q [H_l, d]    k = h W_k [KH, d]    v = h W_v [KH, d]
    q, k <- RoPE_l(q, k, pos)
    a = softmax(q k^T / sqrt(d) + mask_l) v       window: i - w < j <= i
    x <- x + concat_h(sigmoid(h W_g)_h a_h) W_o
    u = N_post(x)
    dense:   x <- x + W_down(silu(W_gate u) * W_up u)
    sparse:  s = sigmoid(float32(u) W_r)    P = top_k(s + b)
             w_e = f s_e / sum_{j in P} s_j
             x <- x + sum_{e in P, e held} w_e E_e(u) + S(u)

then a final RMSNorm and an untied head. What the published
``config.json`` does not state and is assumed here (the benchmark's
configuration file lists each): SiLU-gated MLPs without biases, rotary in
half-rotation pairs, the gate's form (one scalar a head from the normed
hidden), the router's form (sigmoid scores, a selection-only bias,
normalised top-k times the factor, weights on the expert's output), no
q/k head norm, a final RMSNorm and an untied head. Out of scope: chunked
prefill, prefix reuse, speculation and int8 rows over a ring (refused by
the server by switch name), training.

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      wq [E, H_l, d]  wk [E, KH, d]  wv [E, KH, d]  wg [E, H_l]
      wo [H_l, d, E]
      ffn {w_in [E, 2 F] (gate ; up), w_out [F, E]}          dense layers
      moe {router [E, n_experts], router_bias [n_experts],   sparse layers
           experts {w_in [X, E, 2 Fe], w_out [X, Fe, E]}   X = experts held
           shared {w_in [E, 2 Fs], w_out [Fs, E]}}
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (PagedKVCache, paged_advance,
                                              paged_append_token,
                                              paged_write_prompt,
                                              ring_append_token,
                                              ring_write_prompt,
                                              window_layer_map)
from deepspeed_tpu.model_implementations import held_experts as _held
from deepspeed_tpu.model_implementations.rope import RopeSpec, rope_table
from deepspeed_tpu.ops.pallas import decode_attention as _kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32
NEG_INF = -1e30
FULL, WINDOW = "full_attention", "sliding_attention"

# what this model keeps in PagedKVCache.aux, ``[program, column]``: the
# expert layer's routing row (held_experts.COUNTER_TAIL after the picks
# on each held expert), then the cache rows a decode step had to read,
# by layer kind (a row: one position of one layer, K and V)
PROGRAMS = ("decode", "prefill")
ROW_COUNTERS = ("full_rows_read", "window_rows_read")


def aux_series(cfg: "LagunaConfig", reg) -> list:
    """The registry counter behind each cell of this model's
    ``cache.aux`` (docs/observability.md "Window and full layers"),
    ``[program][column]``."""
    out = _held.counter_series(reg, cfg.num_held, PROGRAMS)
    for program, series in zip(PROGRAMS, out):
        series.extend(reg.counter(
            "serve_kv_rows_read_total",
            labels={"program": program, "kind": kind},
            help="cache rows (one position of one layer, K and V) a "
                 "decode step had to read, by layer kind: a live slot's "
                 "whole context a full layer, min(context, window) a "
                 "window layer") for kind in ("full", "window"))
    return out


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Sizes under the names the published ``config.json`` gives them
    (``rope_parameters`` as its two entries), and the share this process
    holds (``experts_held``)."""
    vocab_size: int
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_attention_heads_per_layer: Tuple[int, ...]
    rope_full: RopeSpec
    rope_sliding: RopeSpec
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    experts_held: Tuple[int, int] = (0, 256)
    dtype: Any = jnp.bfloat16
    # what InferenceEngine and ContinuousBatchingServer read of any
    # model configuration
    head: str = "lm"
    pre_layer_norm: bool = True
    seq_shard_kv: bool = False
    int8_compute: bool = False
    # not fields: the kind of pool the server builds (the K/V pool with
    # a layer-kind map), and the module whose entry points run this
    # model (``transformer.py`` hands over)
    cache_kind = "kv_window"
    family = __name__

    def __post_init__(self):
        L = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != L:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {L} layers")
        if set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError(f"mlp_layer_types {set(self.mlp_layer_types)}")
        for H in self.num_attention_heads_per_layer:
            if H % self.num_key_value_heads:
                raise ValueError(
                    f"{H} query heads do not group over "
                    f"{self.num_key_value_heads} key/value heads")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.num_experts} routed experts")

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_head(self) -> int:
        return max(self.num_attention_heads_per_layer)

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def window_layers(self) -> Tuple[bool, ...]:
        """What the pool is built from: which layers keep a ring."""
        return tuple(t == WINDOW for t in self.layer_types)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), self.num_held + len(_held.COUNTER_TAIL)
                + len(ROW_COUNTERS))

    def rope(self, layer: int) -> RopeSpec:
        return (self.rope_sliding if self.layer_types[layer] == WINDOW
                else self.rope_full)


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), embedding rows N(0, 1), norm
# gains 1. These depart from that, so that the benchmark's check against
# the float32 reference bites (each lesson is an earlier model's: PERF.md
# section 6, PRs 29 and 34) while the bfloat16 program stays inside it:
# * ``attn_out_x``: a softmax over n keys with logits of standard
#   deviation s averages about n / exp(s^2) random values, and an average
#   of m random values is 1 / sqrt(m) of one: at random weights a head
#   that sees 4000 rows gives a sixtieth of a value, attention would be a
#   few percent of the residual stream, and a window layer that read the
#   whole context (or a full layer that read only its window) would hide
#   inside bfloat16 rounding. ``wo`` is scaled so that a head stays a
#   like share of the stream at the cell's contexts: by kind, because a
#   window layer averages at most its window and a full layer (logits of
#   standard deviation 1.6: YaRN's attention factor on the rotated half)
#   its whole context;
# * ``gate_std``: the head-wise gate's logits (a gate stuck at 0.5 would
#   be a factor, not a gate);
# * the router: logits of standard deviation ``router_std``, and a
#   selection bias of +-``router_bias_spread`` (half the spacing of
#   neighbouring scores at the 8th place of 256), evenly spaced, centred,
#   alike in every aligned group of 32 experts (one chip's share of an
#   EP-8 deployment) and NOT drawn from the seed: every chip is loaded
#   alike and each held expert sees its share whatever the seed (a seeded
#   bias made tokens/s follow the seed, PR 29);
# * ``expert_out_x``: the routed experts' part about as large as the
#   shared expert's. Not more: where the bfloat16 program and the
#   float32 reference part at a near tie for the 8th place, a held
#   expert comes or goes, and the largest gap of a clean run grows with
#   this factor as fast as a missing scaling factor's does (x 6 on the
#   chip: clean 0.11-0.16, no factor 0.21; x 2: 0.011-0.017 and 0.063);
# * ``channel_gain_sd`` / ``ffn_gain_sd``: trained checkpoints have
#   channels of unequal size. K's channels carry log-normal gains that
#   Q's undo (alike on the dims rotary pairs up, so a rotation leaves
#   q . k as it was) and V's carry gains that ``wo``'s rows undo
#   (``channel_gain_sd``); the up half of every SwiGLU carries gains
#   that the down projection's rows undo (``ffn_gain_sd``; at 1.25 the
#   chip read 8-bit expert weights at 0.038 against a clean 0.017, at 2
#   at 0.063 against 0.011). In exact arithmetic the model is the one
#   with all gains 1; an 8-bit format with one scale a row (a cache row
#   a head) or a column (weights) loses the small channels, as it does
#   on trained weights.
INIT_SCALES = {"embedding_std": 1.0,
               "attn_out_x": {FULL: 16.0, WINDOW: 12.0},
               "gate_std": 1.5, "router_std": 1.5,
               "router_bias_spread": 0.005, "expert_out_x": 2.0,
               "channel_gain_sd": 1.25, "ffn_gain_sd": 2.0,
               "rope_pair_period": 32}


def router_bias(cfg: "LagunaConfig") -> jax.Array:
    """The seeded selection bias ``[num_experts]`` float32: every aligned
    group of 32 experts carries the same evenly spaced, centred set."""
    i = jnp.arange(cfg.num_experts)
    spread = 2.0 * ((7 * i) % 32 + 0.5) / 32.0 - 1.0
    return (INIT_SCALES["router_bias_spread"] * spread).astype(F32)


def _gains(key, shape, sd="channel_gain_sd"):
    return jnp.exp(INIT_SCALES[sd] * jax.random.normal(key, shape, F32))


def _dense(key, shape, fan_in, dt, times=1.0):
    return (jax.random.normal(key, shape, F32)
            * (times / math.sqrt(fan_in))).astype(dt)


def _swiglu(key, lead, d_in, d_hidden, dt, out_x=1.0):
    """``w_in [*lead, d_in, 2 d_hidden]`` (gate ; up) and ``w_out [*lead,
    d_hidden, d_in]`` with per-channel gains on the up half that the
    down projection's rows undo."""
    k0, k1, k2 = jax.random.split(key, 3)
    c = _gains(k2, (*lead, d_hidden), "ffn_gain_sd")
    w_in = jax.random.normal(k0, (*lead, d_in, 2 * d_hidden), F32)
    w_in = w_in * jnp.concatenate([jnp.ones_like(c), c], -1)[..., None, :]
    w_out = jax.random.normal(k1, (*lead, d_hidden, d_in), F32)
    return {"w_in": (w_in / math.sqrt(d_in)).astype(dt),
            "w_out": (w_out * (out_x / math.sqrt(d_hidden))
                      / c[..., None]).astype(dt)}


def _init_layer(key, cfg: "LagunaConfig", H: int, kind: str,
                mlp: str) -> Dict:
    """One layer of ``H`` query heads, attention ``kind`` and FFN
    ``mlp`` (``dense`` or ``sparse``)."""
    E, KH, d, dt = cfg.hidden_size, cfg.kv_heads, cfg.head_dim, cfg.dtype
    k = jax.random.split(key, 12)
    # K's channel gains repeat with the period every rotary pairing of
    # this model divides (pairs (i, i + 64) over 128 dims, (i, i + 32)
    # over 64), so both halves of a pair carry the same gain
    period = min(INIT_SCALES["rope_pair_period"], d)
    gk = jnp.tile(_gains(k[0], (KH, period)), (1, d // period))  # [KH, d]
    gv = _gains(k[1], (KH, d))
    per_q = lambda g: jnp.repeat(g, H // KH, axis=0)             # [H, d]
    layer = {
        "norm_in": jnp.ones((E,), dt), "norm_post": jnp.ones((E,), dt),
        "wq": (_dense(k[2], (E, H, d), E, F32) / per_q(gk)).astype(dt),
        "wk": (_dense(k[3], (E, KH, d), E, F32) * gk).astype(dt),
        "wv": (_dense(k[4], (E, KH, d), E, F32) * gv).astype(dt),
        "wg": _dense(k[5], (E, H), E, dt, INIT_SCALES["gate_std"]),
        "wo": (_dense(k[6], (H, d, E), H * d, F32,
                      INIT_SCALES["attn_out_x"][kind])
               / per_q(gv)[..., None]).astype(dt)}
    if mlp == "dense":
        layer["ffn"] = _swiglu(k[7], (), E, cfg.intermediate_size, dt)
        return layer
    layer["moe"] = {
        "router": _dense(k[8], (E, cfg.num_experts), E, dt,
                         INIT_SCALES["router_std"]),
        "router_bias": router_bias(cfg),
        "experts": _swiglu(k[9], (cfg.num_held,), E,
                           cfg.moe_intermediate_size, dt,
                           INIT_SCALES["expert_out_x"]),
        "shared": _swiglu(k[10], (), E,
                          cfg.shared_expert_intermediate_size, dt)}
    return layer


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: "LagunaConfig", H: int, kind: str, mlp: str):
    return jax.jit(lambda k: _init_layer(k, cfg, H, kind, mlp))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt, times):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt, times))


def init_params(rng: jax.Array, cfg: "LagunaConfig") -> Dict:
    """Seeded weights made on the device, one jitted call a tensor of the
    vocabulary's size and one a layer (layers of one head count, kind
    and FFN share the executable): a single program would hold every
    float32 draw at once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    return {
        "wte": _jit_dense((V, E), 1.0, dt,
                          INIT_SCALES["embedding_std"])(keys[0]),
        "lm_head": _jit_dense((E, V), E, dt, 1.0)(keys[1]),
        "norm_f": jnp.ones((E,), dt),
        "layers": [_jit_init_layer(
            cfg, cfg.num_attention_heads_per_layer[li], cfg.layer_types[li],
            cfg.mlp_layer_types[li])(k) for li, k in enumerate(keys[2:])]}


# ------------------------------------------------------------------ math

@scoped("ln")
def _rms(x, g, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(F32)).astype(x.dtype)


def _rope(x, positions, spec: RopeSpec):
    """Half-rotation pairs ``(x[i], x[i + r/2])`` over the first ``r``
    dims of ``x [..., n, d]`` (``positions`` matches the leading dims);
    the rest pass through."""
    inv, times = rope_table(spec, x.shape[-1])
    ang = positions[..., None].astype(F32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * times)[..., None, :]
    sin = (jnp.sin(ang) * times)[..., None, :]
    half = inv.shape[0]
    rot = x[..., :2 * half].astype(F32)
    a, b = rot[..., :half], rot[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., 2 * half:]], -1)


def _project(h, layer, spec: RopeSpec, positions):
    """``h [..., E]`` -> ``q [..., H, d]``, ``k`` / ``v [..., KH, d]``
    (rotated) and the head-wise gate ``[..., H]`` float32."""
    dt = h.dtype
    q = jnp.einsum("...e,ehd->...hd", h, layer["wq"].astype(dt))
    k = jnp.einsum("...e,ehd->...hd", h, layer["wk"].astype(dt))
    v = jnp.einsum("...e,ehd->...hd", h, layer["wv"].astype(dt))
    gate = jax.nn.sigmoid(jnp.einsum(
        "...e,eh->...h", h, layer["wg"].astype(dt),
        preferred_element_type=F32))
    return _rope(q, positions, spec), _rope(k, positions, spec), v, gate


def _sequence_attention(q, k, v, window: Optional[int]):
    """Causal attention of one sequence against itself: ``q [T, H, d]``,
    ``k`` / ``v [T, KH, d]`` -> ``[T, H, d]``. On a TPU the flash kernel
    (its windowed forward on a window layer); the masked einsum
    elsewhere and for a prompt the kernel's blocks do not tile."""
    T, H, d = q.shape
    if jax.default_backend() == "tpu" and T >= 128 and T % 128 == 0:
        return flash_attention(q[None], k[None], v[None], causal=True,
                               window=window)[0]
    rep = H // k.shape[1]
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, axis=1),
                   preferred_element_type=F32) / math.sqrt(d)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype),
                      jnp.repeat(v, rep, axis=1))


def _token_attention(q, cache: PagedKVCache, kind: str, i: int, live,
                     window: int):
    """One token a slot against layer ``i`` of its kind's cache: ``q [S,
    H, d]`` -> ``[S, H, d]``; ``live [S]`` counts the token just
    appended. The paged kernel on a TPU (a window layer: over the ring,
    at most ``ring_rows`` rows whatever the context); its oracles
    elsewhere."""
    on_tpu = jax.default_backend() == "tpu"
    if kind == "window":
        if on_tpu:
            return _kernels.paged_window_decode_attention(
                q, cache.ring_k, cache.ring_v, live, window, layer=i)
        return _kernels.paged_window_decode_attention_reference(
            q, cache.ring_k[i], cache.ring_v[i], live, window)
    if on_tpu:
        return _kernels.paged_decode_attention(
            q, cache.k, cache.v, cache.block_tables, live, layer=i)
    return _kernels.paged_decode_attention_reference(
        q, cache.k[i], cache.v[i], cache.block_tables, live)


def _gated_out(a, gate, layer):
    """``a [..., H, d]`` gated head-wise, through ``W_o`` -> ``[..., E]``."""
    a = (a.astype(F32) * gate[..., None]).astype(a.dtype)
    return jnp.einsum("...hd,hde->...e", a, layer["wo"].astype(a.dtype))


def _swiglu_ffn(x, f):
    dt = x.dtype
    gu = x @ f["w_in"].astype(dt)
    F = gu.shape[-1] // 2
    h = jax.nn.silu(gu[..., :F].astype(F32)) * gu[..., F:].astype(F32)
    return h.astype(dt) @ f["w_out"].astype(dt)


_dense_ffn = scoped("dense_ffn")(_swiglu_ffn)
_shared_expert = scoped("moe_shared")(_swiglu_ffn)


# ----------------------------------------------------------- expert layer

@scoped("moe_router")
def _route(u, moe, cfg: LagunaConfig):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32. Scores are a float32 sigmoid over ALL router outputs; the
    bias moves the selection and never the weights; the weights are the
    picked scores normalised to sum to the scaling factor."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(F32), moe["router"].astype(F32),
        precision=jax.lax.Precision.HIGHEST))
    _, picks = jax.lax.top_k(scores + moe["router_bias"].astype(F32),
                             cfg.num_experts_per_tok)
    picked = jnp.take_along_axis(scores, picks, axis=-1)
    return picks, cfg.moe_routed_scaling_factor * picked / jnp.sum(
        picked, axis=-1, keepdims=True)


def _expected_rows(T: int, cfg: LagunaConfig) -> int:
    """Rows the held experts' matmul is given
    (``held_experts.expected_rows``: a pick in ``num_experts /
    num_held`` lands)."""
    return _held.expected_rows(T, cfg.num_experts_per_tok,
                               cfg.num_held / cfg.num_experts)


def moe_layer(u, moe, cfg: LagunaConfig, valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, the routing counters' row): the held experts' weighted outputs
    for the picks that landed on them, and the shared expert."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    m, walked = _held.held_experts_part(
        u, order, where, held, weights, group_sizes, moe["experts"],
        fast=_expected_rows(u.shape[0], cfg))
    m = (m + _shared_expert(u, moe["shared"]).astype(F32)).astype(u.dtype)
    return m, _held.routing_counts(picks, held, group_sizes, valid,
                                   cfg.num_experts, walked)


# ------------------------------------------------------------------ block

def _ffn(x, layer, cfg: LagunaConfig, valid, counts):
    """``x + FFN(N_post(x))`` on ``x [T, E]`` and the summed counters."""
    u = _rms(x, layer["norm_post"], cfg.rms_norm_eps)
    if "ffn" in layer:
        return x + _dense_ffn(u, layer["ffn"]), counts
    m, row = moe_layer(u, layer["moe"], cfg, valid)
    return x + m, counts + row


@scoped("embed")
def _embed(params, cfg, ids):
    return params["wte"][ids].astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(x.dtype)).astype(F32)


def _count(cache: PagedKVCache, program: str, routing, rows=(0, 0)):
    row = jnp.concatenate([routing, jnp.stack(
        [jnp.asarray(r, jnp.int32) for r in rows])])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


def _routing_zero(cfg: LagunaConfig):
    return jnp.zeros((cfg.num_held + len(_held.COUNTER_TAIL),), jnp.int32)


def _sequence_trunk(params, cfg: LagunaConfig, ids, length, cache=None,
                    slot=None):
    """Embed -> layers over one right-padded sequence ``ids [T]`` with
    ``length`` live tokens; with a cache, a full layer's rows scatter
    into ``slot``'s blocks and a window layer's last rows into its ring.
    Returns the final residual stream ``[T, E]``, the cache and the
    summed routing counters."""
    T = ids.shape[0]
    positions = jnp.arange(T)
    valid = positions < length
    x = _embed(params, cfg, ids)
    counts = _routing_zero(cfg)
    layer_map = window_layer_map(cfg.window_layers)
    for li, (layer, (kind, i)) in enumerate(zip(params["layers"],
                                                layer_map)):
        window = cfg.sliding_window if kind == "window" else None
        with jax.named_scope("attn_" + kind):
            q, k, v, gate = _project(
                _rms(x, layer["norm_in"], cfg.rms_norm_eps), layer,
                cfg.rope(li), positions)
            if cache is not None and kind == "window":
                cache = ring_write_prompt(cache, i, k, v, slot, length)
            elif cache is not None:
                cache = paged_write_prompt(cache, i, k, v, slot)
            x = x + _gated_out(_sequence_attention(q, k, v, window), gate,
                               layer)
        x, counts = _ffn(x, layer, cfg, valid, counts)
    return x, cache, counts


def paged_prefill(params, cfg: LagunaConfig, input_ids, length,
                  cache: PagedKVCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot`` (the contract of
    ``transformer.paged_prefill``): the right-padded ``[1, T]`` prompt
    runs through the trunk, full layers' rows scatter into the slot's
    blocks, window layers keep the prompt's last ``ring_rows`` rows in
    the slot's ring, ``lengths[slot]`` is pinned. Returns (next-token
    logits ``[1, V]``, cache)."""
    n = length[0].astype(jnp.int32)
    x, cache, counts = _sequence_trunk(params, cfg, input_ids[0], n, cache,
                                       slot)
    cache = _count(cache, "prefill", counts).replace(
        lengths=jax.lax.dynamic_update_index_in_dim(cache.lengths, n, slot,
                                                    0))
    last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
    return _logits(params, cfg, last), cache


def paged_decode_step(params, cfg: LagunaConfig, tokens,
                      cache: PagedKVCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). A full layer appends at ``lengths[s]`` through the
    block tables and attends its live blocks; a window layer appends at
    row ``lengths[s] mod ring_rows`` of the slot's ring and attends the
    ring. Idle slots write into the null block (their own ring's row 0),
    route nowhere and are not advanced."""
    positions = cache.lengths
    live = cache.lengths + 1
    x = _embed(params, cfg, tokens)
    counts = _routing_zero(cfg)
    layer_map = window_layer_map(cfg.window_layers)
    for li, (layer, (kind, i)) in enumerate(zip(params["layers"],
                                                layer_map)):
        with jax.named_scope("attn_" + kind):
            q, k, v, gate = _project(
                _rms(x, layer["norm_in"], cfg.rms_norm_eps), layer,
                cfg.rope(li), positions)
            cache = (ring_append_token if kind == "window"
                     else paged_append_token)(cache, i, k, v)
            x = x + _gated_out(_token_attention(
                q, cache, kind, i, live, cfg.sliding_window), gate, layer)
        x, counts = _ffn(x, layer, cfg, active, counts)
    seen = jnp.where(active, live, 0)
    kinds = [kind for kind, _ in layer_map]
    cache = _count(cache, "decode", counts, (
        jnp.sum(seen) * kinds.count("full"),
        jnp.sum(jnp.minimum(seen, cfg.sliding_window))
        * kinds.count("window")))
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: LagunaConfig, input_ids,
                   attention_mask=None, mesh=None):
    """Full-sequence logits ``[B, T, V]`` (no cache): what
    ``InferenceEngine.forward`` returns. A mask has to be a right-padding
    one (the live tokens first)."""
    B, T = input_ids.shape
    lengths = (jnp.full((B,), T, jnp.int32) if attention_mask is None
               else jnp.sum(attention_mask.astype(jnp.int32), axis=1))
    return jnp.stack([
        _logits(params, cfg, _sequence_trunk(params, cfg, input_ids[b],
                                             lengths[b])[0])
        for b in range(B)])
