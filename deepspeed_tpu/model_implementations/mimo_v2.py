"""MiMo-V2: window layers with a learned attention sink beside full
layers of FEWER key/value heads, keys wider than values, over a
sigmoid-routed expert layer with no shared expert, served as ONE CHIP'S
SHARE of an expert-parallel deployment.

The same serving entry points as every family (``paged_prefill`` /
``paged_decode_step``, reached through ``transformer.model_family``) over
the K/V pool with rings (``cache_kind = "kv_window"``), which here holds
four row widths in one object (``kv_cache.PagedKVCache``): a full layer
keeps ``num_key_value_heads`` heads in blocks of the pool, a window layer
``swa_num_key_value_heads`` heads in a ring a slot, and a key head is
``head_dim`` wide where a value head is ``v_head_dim`` (4 and 8 heads of
192 / 128 at the published sizes: rows of 768 / 512 lanes in the pool and
1536 / 1024 in the rings).

One layer (``N`` RMSNorm; ``d_k`` / ``d_v`` the key and value widths;
query group ``g`` of ``H / KH`` consecutive query heads reads key/value
head ``g``)::

    kind(l) = full if hybrid_layer_pattern[l] == 0 else window
    KH = num_key_value_heads (full) | swa_num_key_value_heads (window)
    theta = rope_theta (full) | swa_rope_theta (window)
    h = N_in(x)
    q = h W_q [H, d_k]   k = h W_k [KH, d_k]   v = c h W_v [KH, d_v]
    q[..., :r], k[..., :r] <- RoPE(theta)     r = int(factor d_k), half-
                                              rotation pairs (i, i + r/2)
    s_ij = q_i . k_j / sqrt(d_k)     j <= i; window: i - w < j
    full:    p_ij = exp(s_ij) / sum_j' exp(s_ij')
    window:  p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))
    x <- x + concat_h(sum_j p_ij v_j) W_o [H d_v, E]
    u = N_post(x)
    dense  (moe_layer_freq[l] == 0):  x <- x + W_down(silu(W_gate u) * W_up u)
    sparse: s = sigmoid(float32(u) W_r)    P = top_k(s + bias)
            w_e = s_e / sum_{j in P} s_j   (norm_topk_prob; x the factor,
                                            if the config gives one)
            x <- x + sum_{e in P, e held} w_e E_e(u)      no shared expert

then a final RMSNorm and an untied head. ``c`` is
``attention_value_scale``; ``b_h`` (the sink) is one learned float32 a
query head, a logit column whose probability is dropped: it joins the
denominator and carries no value. Which kinds carry one is the config's
(``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``: the
published model has it on window layers only, and the flash forward
takes none on a full layer, so a full-layer sink is refused by name).

What the published ``config.json`` does not state and is assumed here
(the benchmark's configuration file lists each): the rotation's pairing;
the value scale applied to V (so to the attention's output); the sink's
form; a selection-only router bias; SiLU-gated MLPs without biases;
``attention_chunk_size`` read as the published kernels' tile and not a
mask; a final RMSNorm and an untied head. Out of scope: the model's
multi-token-prediction modules (not served), chunked prefill, prefix
reuse, speculation and int8 rows over a ring (refused by the server by
switch name), training (the windowed flash kernel, a sink and ``d_v !=
d_k`` have no backward).

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      wq [E, H, d_k]  wk [E, KH, d_k]  wv [E, KH, d_v]  wo [H, d_v, E]
      sink [H] float32                             layers with a sink
      ffn {w_in [E, 2 F] (gate ; up), w_out [F, E]}          dense layers
      moe {router [E, n_routed], router_bias [n_routed],     sparse layers
           experts {w_in [X, E, 2 Fe], w_out [X, Fe, E]}}  X = experts held
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

# what this model keeps in PagedKVCache.aux, ``[program, column]``: the
# expert layer's routing row (held_experts.COUNTER_TAIL after the picks
# on each held expert), then the cache rows a decode step had to read,
# by layer kind (a row: one position of one layer, K and V; its bytes
# differ by kind: docs/observability.md "Window and full layers")
PROGRAMS = ("decode", "prefill")
ROW_COUNTERS = ("full_rows_read", "window_rows_read")


def aux_series(cfg: "MiMoV2Config", reg) -> list:
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
class MiMoV2Config:
    """Sizes under the names the published ``config.json`` gives them,
    and the share this process holds (``experts_held``)."""
    vocab_size: int
    hybrid_layer_pattern: Tuple[int, ...]       # 0: full, 1: window
    moe_layer_freq: Tuple[int, ...]             # 0: dense, 1: sparse
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    sliding_window: int = 128
    rope_theta: float = 5000000.0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    layernorm_epsilon: float = 1e-5
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
        for name in ("hybrid_layer_pattern", "moe_layer_freq"):
            got = getattr(self, name)
            if len(got) != L or set(got) - {0, 1}:
                raise ValueError(f"{name} {got}: {L} entries of 0 or 1")
        # one cache object has ONE key width, one value width and one
        # query head count for its pool and its rings; the K/V head
        # count is what differs by kind
        for swa, full in (("swa_head_dim", "head_dim"),
                          ("swa_v_head_dim", "v_head_dim"),
                          ("swa_num_attention_heads",
                           "num_attention_heads")):
            if getattr(self, swa) != getattr(self, full):
                raise NotImplementedError(
                    f"{swa} {getattr(self, swa)} beside {full} "
                    f"{getattr(self, full)}: window and full layers "
                    "share their head widths and query head count")
        for KH in (self.num_key_value_heads, self.swa_num_key_value_heads):
            if self.num_attention_heads % KH:
                raise ValueError(
                    f"{self.num_attention_heads} query heads do not group "
                    f"over {KH} key/value heads")
        if self.add_full_attention_sink_bias:
            raise NotImplementedError(
                "add_full_attention_sink_bias: the full flash forward "
                "takes no sink (the published model has none there)")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.n_routed_experts} routed experts")

    @property
    def n_embd(self) -> int:
        return self.hidden_size

    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def kv_heads(self) -> int:
        """Key/value heads of a POOL row (the full layers')."""
        return self.num_key_value_heads

    @property
    def ring_kv_heads(self) -> int:
        """Key/value heads of a RING row (the window layers')."""
        return self.swa_num_key_value_heads

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def window_layers(self) -> Tuple[bool, ...]:
        """What the pool is built from: which layers keep a ring."""
        return tuple(bool(p) for p in self.hybrid_layer_pattern)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), self.num_held + len(_held.COUNTER_TAIL)
                + len(ROW_COUNTERS))

    def heads(self, kind: str) -> int:
        return (self.swa_num_key_value_heads if kind == "window"
                else self.num_key_value_heads)

    def has_sink(self, kind: str) -> bool:
        return (self.add_swa_attention_sink_bias if kind == "window"
                else self.add_full_attention_sink_bias)

    def rope(self, kind: str) -> RopeSpec:
        return RopeSpec(
            rope_theta=(self.swa_rope_theta if kind == "window"
                        else self.rope_theta),
            partial_rotary_factor=self.partial_rotary_factor)


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), embedding rows N(0, 1), norm
# gains 1. The departures are Laguna's (``laguna.INIT_SCALES`` has each
# one's lesson), at this model's sizes, so that the benchmark's check
# against the float32 reference bites while the bfloat16 program stays
# inside it:
# * ``attn_out_x``: ``wo`` scaled by kind so that a head stays a visible
#   share of the residual stream: a full layer averages its whole context
#   (thousands of rows at logits of standard deviation 1, times the value
#   scale 0.707), a window layer at most 128 rows less the sink's share;
# * ``sink_mean`` / ``sink_std``: the sinks are N(mean, std): 128 keys
#   with unit-variance logits sum to about 128 e^0.5 = 211 = e^5.35, so a
#   sink of 5 +- 1 takes between a fifth and two thirds of a full
#   window's probability (a sink at 0 would take half a percent, and a
#   missing one would hide inside bfloat16 rounding);
# * the router: logits of standard deviation ``router_std`` and a
#   selection bias of +-``router_bias_spread``, evenly spaced, centred,
#   alike in every aligned group of 8 experts (one chip's share of an
#   EP-32 deployment) and NOT drawn from the seed;
# * ``expert_out_x``, ``channel_gain_sd`` / ``ffn_gain_sd``,
#   ``rope_pair_period`` (K's gains repeat with the period the rotary
#   pairing (i, i + 32) divides): as Laguna's.
INIT_SCALES = {"embedding_std": 1.0,
               "attn_out_x": {"full": 32.0, "window": 16.0},
               "sink_mean": 5.0, "sink_std": 1.0, "router_std": 1.5,
               "router_bias_spread": 0.005, "expert_out_x": 2.0,
               "channel_gain_sd": 1.25, "ffn_gain_sd": 2.0,
               "rope_pair_period": 32}


def router_bias(cfg: "MiMoV2Config") -> jax.Array:
    """The seeded selection bias ``[n_routed_experts]`` float32: every
    aligned group of 8 experts carries the same evenly spaced, centred
    set."""
    i = jnp.arange(cfg.n_routed_experts)
    spread = 2.0 * ((3 * i) % 8 + 0.5) / 8.0 - 1.0
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


def _init_layer(key, cfg: "MiMoV2Config", kind: str, sparse: bool) -> Dict:
    """One layer of attention ``kind`` (``full`` / ``window``) over a
    dense FFN or the held share of an expert layer."""
    E, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    KH, dk, dv = cfg.heads(kind), cfg.head_dim, cfg.v_head_dim
    k = jax.random.split(key, 12)
    period = min(INIT_SCALES["rope_pair_period"], dk)
    gk = jnp.tile(_gains(k[0], (KH, period)), (1, dk // period))  # [KH, dk]
    gv = _gains(k[1], (KH, dv))
    per_q = lambda g: jnp.repeat(g, H // KH, axis=0)              # [H, .]
    layer = {
        "norm_in": jnp.ones((E,), dt), "norm_post": jnp.ones((E,), dt),
        "wq": (_dense(k[2], (E, H, dk), E, F32) / per_q(gk)).astype(dt),
        "wk": (_dense(k[3], (E, KH, dk), E, F32) * gk).astype(dt),
        "wv": (_dense(k[4], (E, KH, dv), E, F32) * gv).astype(dt),
        "wo": (_dense(k[6], (H, dv, E), H * dv, F32,
                      INIT_SCALES["attn_out_x"][kind])
               / per_q(gv)[..., None]).astype(dt)}
    if cfg.has_sink(kind):
        layer["sink"] = (INIT_SCALES["sink_mean"] + INIT_SCALES["sink_std"]
                         * jax.random.normal(k[5], (H,), F32))
    if not sparse:
        layer["ffn"] = _swiglu(k[7], (), E, cfg.intermediate_size, dt)
        return layer
    layer["moe"] = {
        "router": _dense(k[8], (E, cfg.n_routed_experts), E, dt,
                         INIT_SCALES["router_std"]),
        "router_bias": router_bias(cfg),
        "experts": _swiglu(k[9], (cfg.num_held,), E,
                           cfg.moe_intermediate_size, dt,
                           INIT_SCALES["expert_out_x"])}
    return layer


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: "MiMoV2Config", kind: str, sparse: bool):
    return jax.jit(lambda k: _init_layer(k, cfg, kind, sparse))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt, times):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt, times))


def init_params(rng: jax.Array, cfg: "MiMoV2Config") -> Dict:
    """Seeded weights made on the device, one jitted call a tensor of the
    vocabulary's size and one a layer (layers of one kind and FFN share
    the executable): a single program would hold every float32 draw at
    once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    kinds = window_layer_map(cfg.window_layers)
    return {
        "wte": _jit_dense((V, E), 1.0, dt,
                          INIT_SCALES["embedding_std"])(keys[0]),
        "lm_head": _jit_dense((E, V), E, dt, 1.0)(keys[1]),
        "norm_f": jnp.ones((E,), dt),
        "layers": [_jit_init_layer(cfg, kinds[li][0],
                                   bool(cfg.moe_layer_freq[li]))(k)
                   for li, k in enumerate(keys[2:])]}


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
    inv, _ = rope_table(spec, x.shape[-1])
    ang = positions[..., None].astype(F32) * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    half = inv.shape[0]
    rot = x[..., :2 * half].astype(F32)
    a, b = rot[..., :half], rot[..., half:]
    out = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., 2 * half:]], -1)


def _project(h, layer, cfg: "MiMoV2Config", kind: str, positions):
    """``h [..., E]`` -> ``q [..., H, d_k]``, ``k [..., KH, d_k]`` (both
    rotated) and ``v [..., KH, d_v]`` (times the value scale)."""
    dt = h.dtype
    spec = cfg.rope(kind)
    q = jnp.einsum("...e,ehd->...hd", h, layer["wq"].astype(dt))
    k = jnp.einsum("...e,ehd->...hd", h, layer["wk"].astype(dt))
    v = (jnp.einsum("...e,ehd->...hd", h, layer["wv"].astype(dt),
                    preferred_element_type=F32)
         * cfg.attention_value_scale).astype(dt)
    return _rope(q, positions, spec), _rope(k, positions, spec), v


def _sequence_attention(q, k, v, window: Optional[int], sink):
    """Causal attention of one sequence against itself: ``q [T, H,
    d_k]``, ``k [T, KH, d_k]``, ``v [T, KH, d_v]`` -> ``[T, H, d_v]``;
    ``sink [H]`` float32 or None. On a TPU the flash kernel (its
    windowed forward on a window layer); the masked einsum elsewhere and
    for a prompt the kernel's blocks do not tile."""
    T, H, d = q.shape
    if jax.default_backend() == "tpu" and T >= 128 and T % 128 == 0:
        return flash_attention(q[None], k[None], v[None], causal=True,
                               window=window, sink=sink)[0]
    rep = H // k.shape[1]
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, axis=1),
                   preferred_element_type=F32) / math.sqrt(d)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    s = jnp.where(seen[None], s, NEG_INF)
    if sink is not None:
        s = jnp.concatenate([s, jnp.broadcast_to(
            sink.astype(F32)[:, None, None], (H, T, 1))], -1)
    p = jax.nn.softmax(s, axis=-1)[..., :T]
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype),
                      jnp.repeat(v, rep, axis=1))


def _token_attention(q, cache: PagedKVCache, kind: str, i: int, live,
                     window: int, sink):
    """One token a slot against layer ``i`` of its kind's cache: ``q [S,
    H, d_k]`` -> ``[S, H, d_v]``; ``live [S]`` counts the token just
    appended. The paged kernel on a TPU (a window layer: over the ring,
    at most ``ring_rows`` rows whatever the context); its oracles
    elsewhere."""
    on_tpu = jax.default_backend() == "tpu"
    if kind == "window":
        if on_tpu:
            return _kernels.paged_window_decode_attention(
                q, cache.ring_k, cache.ring_v, live, window, layer=i,
                sink=sink)
        return _kernels.paged_window_decode_attention_reference(
            q, cache.ring_k[i], cache.ring_v[i], live, window, sink=sink)
    if on_tpu:
        return _kernels.paged_decode_attention(
            q, cache.k, cache.v, cache.block_tables, live, layer=i,
            sink=sink)
    return _kernels.paged_decode_attention_reference(
        q, cache.k[i], cache.v[i], cache.block_tables, live, sink=sink)


def _attn_out(a, layer):
    """``a [..., H, d_v]`` through ``W_o`` -> ``[..., E]``."""
    return jnp.einsum("...hd,hde->...e", a, layer["wo"].astype(a.dtype))


@scoped("dense_ffn")
def _dense_ffn(x, f):
    dt = x.dtype
    gu = x @ f["w_in"].astype(dt)
    F = gu.shape[-1] // 2
    h = jax.nn.silu(gu[..., :F].astype(F32)) * gu[..., F:].astype(F32)
    return h.astype(dt) @ f["w_out"].astype(dt)


# ----------------------------------------------------------- expert layer

@scoped("moe_router")
def _route(u, moe, cfg: MiMoV2Config):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32. Scores are a float32 sigmoid over ALL router outputs; the
    bias moves the selection and never the weights (``noaux_tc`` with
    one group); the weights are the picked scores, normalised to sum 1
    under ``norm_topk_prob``, times ``routed_scaling_factor`` if any."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(F32), moe["router"].astype(F32),
        precision=jax.lax.Precision.HIGHEST))
    _, picks = jax.lax.top_k(scores + moe["router_bias"].astype(F32),
                             cfg.num_experts_per_tok)
    w = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return picks, w * (cfg.routed_scaling_factor or 1.0)


# The held share is small (8 of 256: a pick in 32 lands), and a router
# loads its experts unevenly (granite_hybrid.LOAD_MARGIN has the lesson):
# where six standard deviations of the even share are little (a long
# prompt), the buffer is ``LOAD_MARGIN`` times the even share instead.
LOAD_MARGIN = 1.5


def _expert_rows(T: int, cfg: MiMoV2Config) -> int:
    """Rows the held experts' matmul is given for ``T`` tokens: the even
    share's picks plus six standard deviations
    (``held_experts.expected_rows``) or ``LOAD_MARGIN`` times the even
    share, whichever is more, in whole tiles of 128."""
    k = cfg.num_experts_per_tok
    even = cfg.num_held / cfg.n_routed_experts
    leaning = 128 * math.ceil(min(1.0, LOAD_MARGIN * even) * T * k / 128)
    return min(T * k, max(_held.expected_rows(T, k, even), leaning))


def moe_layer(u, moe, cfg: MiMoV2Config, valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, the routing counters' row): the held experts' weighted outputs
    for the picks that landed on them. There is no shared expert."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    m, walked = _held.held_experts_part(u, order, where, held, weights,
                                        group_sizes, moe["experts"],
                                        fast=_expert_rows(u.shape[0], cfg))
    return m.astype(u.dtype), _held.routing_counts(
        picks, held, group_sizes, valid, cfg.n_routed_experts, walked)


# ------------------------------------------------------------------ block

def _ffn(x, layer, cfg: MiMoV2Config, valid, counts):
    """``x + FFN(N_post(x))`` on ``x [T, E]`` and the summed counters."""
    u = _rms(x, layer["norm_post"], cfg.layernorm_epsilon)
    if "ffn" in layer:
        return x + _dense_ffn(u, layer["ffn"]), counts
    m, row = moe_layer(u, layer["moe"], cfg, valid)
    return x + m, counts + row


@scoped("embed")
def _embed(params, cfg, ids):
    return params["wte"][ids].astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    x = _rms(x, params["norm_f"], cfg.layernorm_epsilon)
    return (x @ params["lm_head"].astype(x.dtype)).astype(F32)


def _count(cache: PagedKVCache, program: str, routing, rows=(0, 0)):
    row = jnp.concatenate([routing, jnp.stack(
        [jnp.asarray(r, jnp.int32) for r in rows])])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


def _routing_zero(cfg: MiMoV2Config):
    return jnp.zeros((cfg.num_held + len(_held.COUNTER_TAIL),), jnp.int32)


def _sequence_trunk(params, cfg: MiMoV2Config, ids, length, cache=None,
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
    for layer, (kind, i) in zip(params["layers"],
                                window_layer_map(cfg.window_layers)):
        window = cfg.sliding_window if kind == "window" else None
        with jax.named_scope("attn_" + kind):
            q, k, v = _project(
                _rms(x, layer["norm_in"], cfg.layernorm_epsilon), layer,
                cfg, kind, positions)
            if cache is not None and kind == "window":
                cache = ring_write_prompt(cache, i, k, v, slot, length)
            elif cache is not None:
                cache = paged_write_prompt(cache, i, k, v, slot)
            x = x + _attn_out(_sequence_attention(
                q, k, v, window, layer.get("sink")), layer)
        x, counts = _ffn(x, layer, cfg, valid, counts)
    return x, cache, counts


def paged_prefill(params, cfg: MiMoV2Config, input_ids, length,
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


def paged_decode_step(params, cfg: MiMoV2Config, tokens,
                      cache: PagedKVCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). A full layer appends at ``lengths[s]`` through the
    block tables and attends its live blocks; a window layer appends at
    row ``lengths[s] mod ring_rows`` of the slot's ring and attends the
    ring with its sink. Idle slots write into the null block (their own
    ring's row 0), route nowhere and are not advanced."""
    positions = cache.lengths
    live = cache.lengths + 1
    x = _embed(params, cfg, tokens)
    counts = _routing_zero(cfg)
    layer_map = window_layer_map(cfg.window_layers)
    for layer, (kind, i) in zip(params["layers"], layer_map):
        with jax.named_scope("attn_" + kind):
            q, k, v = _project(
                _rms(x, layer["norm_in"], cfg.layernorm_epsilon), layer,
                cfg, kind, positions)
            cache = (ring_append_token if kind == "window"
                     else paged_append_token)(cache, i, k, v)
            x = x + _attn_out(_token_attention(
                q, cache, kind, i, live, cfg.sliding_window,
                layer.get("sink")), layer)
        x, counts = _ffn(x, layer, cfg, active, counts)
    seen = jnp.where(active, live, 0)
    kinds = [kind for kind, _ in layer_map]
    cache = _count(cache, "decode", counts, (
        jnp.sum(seen) * kinds.count("full"),
        jnp.sum(jnp.minimum(seen, cfg.sliding_window))
        * kinds.count("window")))
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: MiMoV2Config, input_ids,
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
