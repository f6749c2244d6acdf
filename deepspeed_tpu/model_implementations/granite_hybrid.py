"""Granite hybrid (``granitemoehybrid``): Mamba-2 state-space layers
beside a few attention layers in one model, every layer over a
softmax-routed expert layer with a shared MLP, under Granite's four
multipliers, served as ONE CHIP'S SHARE of an expert-parallel deployment
and one stage of its pipeline.

The generic decoder (``transformer.py``) gives every layer a K/V cache.
This model's layers come in two kinds (``layer_types``), so it is a
module of its own that the same serving entry points (``paged_prefill``
/ ``paged_decode_step``, reached through ``transformer.model_family``)
run over ONE ``kv_cache.PagedKVCache`` whose ``layer_map`` holds both:

* **attention layers** (``attention``) are plain grouped-query attention
  with NO positional encoding and a softmax scale of
  ``attention_multiplier`` (1/128 at the published sizes, not 1 /
  sqrt(128)). They keep block tables over the shared pool
  (``PagedKVCache.k`` / ``v``) and decode through the paged kernel.
* **Mamba layers** (``mamba``) are Mamba-2 mixers (``mamba2.py``, shared
  with the other state-space hybrid; Dao & Gu 2024, arXiv:2405.21060): a
  short causal depthwise convolution, then a recurrence with one scalar
  decay a head a token over a float32 state ``S [heads, head_dim,
  d_state]`` a slot (``PagedKVCache.state``) and the convolution's last
  inputs (``PagedKVCache.conv``). A state is a fixed cost a slot whatever
  the context: no block, no table entry. Prefill runs the chunked (SSD)
  form inside one program; decode the recurrence.
* **every layer's FFN** is an expert layer: float32 router logits over
  ALL experts, the ``k`` largest, a softmax over THOSE ``k`` (not over
  all), the held experts' part through ``held_experts.py`` (picks on
  absent experts are left out: their holders add those parts; nothing
  stands in for the other chips), and a shared MLP every token passes
  through, unweighted.

One layer (``N`` RMSNorm, ``r`` ``residual_multiplier``)::

    x <- x + r Mix(N_in(x))            u = N_post(x)
    x <- x + r (MoE(u) + Shared(u))

    Mix, Mamba-2 (h = N_in(x)):
      z = h W_z [Di]   xBC = h W_xBC [Di + 2 N]   dt = h W_dt [H]
      xBC <- silu(conv_k(xBC) + b)          causal, depthwise, k taps
      [x | B | C] = xBC                     x [H, P];  B, C [G, N]; the
                                            published sizes have one group
      dt <- softplus(dt + dt_bias)          a = exp(dt A),  A = -exp(A_log)
      S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
      y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
      Mix = (N_{Di/G}(y silu(z)) g) W_out   the gate BEFORE the norm
    Mix, attention: softmax(q k^T m_attn + causal) v W_o

around it ``x0 = embedding_multiplier * Emb(ids)`` and ``logits =
N_f(x) Emb^T / logits_scaling`` (a tied head). This chip holds a slice of
the vocabulary (``vocab_size`` rows: tokens in and logits out are over
the slice) and a range of every layer's experts (``experts_held``).

What the published ``config.json`` does not state and is assumed here
(the benchmark's configuration file lists each): a checkpoint's
``in_proj`` is cut ``[z | xBC | dt]`` (three matrices here, so that
``dt`` leaves its matmul in float32); ``dt`` has no upper clamp
(``time_step_limit`` ``(0, inf)``); the gate is applied before the
grouped norm (``mamba_n_groups`` groups of B, C and the norm: one at the
published sizes, any divisor of the heads runs); no bias but the
convolution's. Out of scope: chunked prefill, prefix reuse, speculation,
int8 rows and a host tier (refused by the server by switch name: a state
has no rows), training.

Parameter schema::

    wte [V, E]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      mamba {mamba2.py's schema}                               Mamba layers
      attn {wq [E, Hq, d]  wk [E, KH, d]  wv [E, KH, d]        attention
            wo [Hq, d, E]}                                     layers
      moe {router [E, n_experts]
           experts {w_in [X, E, 2 Fe] (gate ; up), w_out [X, Fe, E]}
           shared {w_in [E, 2 Fs], w_out [Fs, E]}}      X = experts held
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (PagedKVCache, kind_layer_map,
                                              paged_advance,
                                              paged_append_token,
                                              paged_write_prompt,
                                              with_state_layer)
from deepspeed_tpu.model_implementations import held_experts as _held
from deepspeed_tpu.model_implementations import mamba2 as _mamba
from deepspeed_tpu.model_implementations import nope_attention as _attn
# the mixer's parts under the names they had here (the family's tests
# reach them through this module)
from deepspeed_tpu.model_implementations.mamba2 import (  # noqa: F401
    decay_rates as _decay_rates, mixer_out as _mamba_out,
    scan_sequence as _scan_sequence, state_token as _state_token)
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32
MAMBA, ATTENTION = "mamba", "attention"

# what this model keeps in ``PagedKVCache.aux``: a state + attention
# hybrid's counters (``mamba2.py``)
PROGRAMS, COUNTERS, aux_series = (_mamba.PROGRAMS, _mamba.COUNTERS,
                                  _mamba.aux_series)


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Sizes under the names the published ``config.json`` gives them,
    and the share this process holds (``vocab_size`` rows of the
    vocabulary, ``experts_held``)."""
    vocab_size: int
    layer_types: Tuple[str, ...]
    hidden_size: int = 4096
    intermediate_size: int = 768            # one routed expert's width
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_local_experts: int = 72
    num_experts_per_tok: int = 10
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.0078125
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    experts_held: Tuple[int, int] = (0, 72)
    dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    # what InferenceEngine and ContinuousBatchingServer read of any
    # model configuration
    head: str = "lm"
    pre_layer_norm: bool = True
    seq_shard_kv: bool = False
    int8_compute: bool = False
    # not fields: the kind of pool the server builds (the K/V pool with
    # state layers in its map), and the module whose entry points run
    # this model (``transformer.py`` hands over)
    cache_kind = "kv_state"
    family = __name__

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} "
                             f"entries for {self.num_hidden_layers} layers")
        if set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {set(self.layer_types)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a whole number of heads")
        if (self.mamba_n_heads % self.mamba_n_groups
                or self.d_inner % self.mamba_n_groups):
            raise ValueError(
                f"{self.mamba_n_heads} Mamba heads do not split into "
                f"mamba_n_groups = {self.mamba_n_groups} groups of B and C")
        if self.mamba_n_heads * self.mamba_d_head != self.d_inner:
            raise ValueError(
                f"mamba_n_heads x mamba_d_head = "
                f"{self.mamba_n_heads * self.mamba_d_head} is not "
                f"mamba_expand x hidden_size = {self.d_inner}")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.num_local_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.num_local_experts} routed experts")

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
        return self.num_key_value_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts(self) -> int:
        return self.num_local_experts

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_channels(self) -> int:
        return _mamba.conv_channels(self)

    @property
    def state_layers(self) -> Tuple[bool, ...]:
        """What the pool is built from: which layers keep a state."""
        return tuple(t == MAMBA for t in self.layer_types)

    @property
    def state_shapes(self) -> Tuple[tuple, tuple]:
        return _mamba.state_shapes(self)

    @property
    def state_bytes(self) -> int:
        """One slot's state and convolution tail of one layer."""
        return _mamba.state_bytes(self)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return _mamba.aux_shape(self)

    @property
    def layer_map(self) -> tuple:
        return kind_layer_map("state" if s else "full"
                              for s in self.state_layers)


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), norm gains 1, the mixers'
# own draws ``mamba2.init_mixer``'s. These depart from that, so that
# the benchmark's check against the float32 reference bites while the
# bfloat16 program stays inside it (PERF.md section 6, PR 50, has the
# readings behind each):
# * ``embedding_rms`` / ``final_norm_gain``: the head is TIED, so a
#   token's own embedding row reads whatever of that row is left in the
#   stream: with rows of unit size after the multiplier the input token's
#   logit stood 60 standard deviations over the rest, every served token
#   repeated its input and the check compared nothing (the first chip
#   run: 1024 of 1024 tokens exact). Rows are drawn so that ``x0`` has
#   this RMS, a thirtieth of what twenty residual branches add, and its
#   own logit stays inside the others' spread; the final norm's gain
#   brings the logits back to a standard deviation of ~1;
# * ``attn_out_x``: a softmax over n random keys averages its values to
#   ~sqrt(exp(var) / n) of one; ``W_o`` is scaled so that the attention
#   layer stays a visible share of the stream at the cell's contexts (a
#   Mamba mixer needs none: its gated norm makes its output unit-sized
#   whatever the state's size);
# * ``attn_logit_x``: at 1/128 random q . k have a standard deviation of
#   0.09 and the softmax is flat whatever the scale; ``W_q`` is scaled so
#   that the logits swing (standard deviation ~1.4 at 1/128, which
#   1/sqrt(128) would make 16);
# * the router: logits of standard deviation ``router_std``;
# * ``expert_out_x``: the routed experts' part about as large as the
#   shared MLP's, and no more (a near tie for the k-th place moves a held
#   expert in or out; the window family's lesson, PR 43);
# * ``ffn_gain_sd``: log-normal channel gains on the up half of every
#   SwiGLU that the down projection's rows undo (exact arithmetic does
#   not see them; 8-bit weights lose the small channels).
INIT_SCALES = {"embedding_rms": 1.0 / 32, "final_norm_gain": 96.0,
               "attn_out_x": 12.0, "attn_logit_x": 16.0, "router_std": 1.5,
               "expert_out_x": 2.0, "ffn_gain_sd": 2.0}


def _dense(key, shape, fan_in, dt, times=1.0):
    return (jax.random.normal(key, shape, F32)
            * (times / math.sqrt(fan_in))).astype(dt)


def _swiglu(key, lead, d_in, d_hidden, dt, out_x=1.0):
    """``w_in [*lead, d_in, 2 d_hidden]`` (gate ; up) and ``w_out [*lead,
    d_hidden, d_in]`` with per-channel gains on the up half that the
    down projection's rows undo."""
    k0, k1, k2 = jax.random.split(key, 3)
    c = jnp.exp(INIT_SCALES["ffn_gain_sd"]
                * jax.random.normal(k2, (*lead, d_hidden), F32))
    w_in = jax.random.normal(k0, (*lead, d_in, 2 * d_hidden), F32)
    w_in = w_in * jnp.concatenate([jnp.ones_like(c), c], -1)[..., None, :]
    w_out = jax.random.normal(k1, (*lead, d_hidden, d_in), F32)
    return {"w_in": (w_in / math.sqrt(d_in)).astype(dt),
            "w_out": (w_out * (out_x / math.sqrt(d_hidden))
                      / c[..., None]).astype(dt)}


def _init_attention(key, cfg: "GraniteHybridConfig") -> Dict:
    E, H, KH, d, dt = (cfg.hidden_size, cfg.n_head, cfg.kv_heads,
                       cfg.head_dim, cfg.dtype)
    k = jax.random.split(key, 4)
    return {"wq": _dense(k[0], (E, H, d), E, dt,
                         INIT_SCALES["attn_logit_x"]),
            "wk": _dense(k[1], (E, KH, d), E, dt),
            "wv": _dense(k[2], (E, KH, d), E, dt),
            "wo": _dense(k[3], (H, d, E), H * d, dt,
                         INIT_SCALES["attn_out_x"])}


def _init_layer(key, cfg: "GraniteHybridConfig", kind: str) -> Dict:
    E, dt = cfg.hidden_size, cfg.dtype
    k = jax.random.split(key, 4)
    layer = {"norm_in": jnp.ones((E,), dt), "norm_post": jnp.ones((E,), dt),
             "moe": {
                 "router": _dense(k[1], (E, cfg.num_local_experts), E, dt,
                                  INIT_SCALES["router_std"]),
                 "experts": _swiglu(k[2], (cfg.num_held,), E,
                                    cfg.intermediate_size, dt,
                                    INIT_SCALES["expert_out_x"]),
                 "shared": _swiglu(k[3], (), E,
                                   cfg.shared_intermediate_size, dt)}}
    if kind == MAMBA:
        layer["mamba"] = _mamba.init_mixer(k[0], cfg)
    else:
        layer["attn"] = _init_attention(k[0], cfg)
    return layer


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: "GraniteHybridConfig", kind: str):
    return jax.jit(lambda k: _init_layer(k, cfg, kind))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt, times):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt, times))


def init_params(rng: jax.Array, cfg: "GraniteHybridConfig") -> Dict:
    """Seeded weights made on the device, one jitted call for the
    embedding and one a layer (layers of one kind share the executable):
    a single program would hold every float32 draw at once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 1)
    return {
        "wte": _jit_dense((V, E), 1.0, dt, INIT_SCALES["embedding_rms"]
                          / cfg.embedding_multiplier)(keys[0]),
        "norm_f": jnp.full((E,), INIT_SCALES["final_norm_gain"], dt),
        "layers": [_jit_init_layer(cfg, kind)(k)
                   for kind, k in zip(cfg.layer_types, keys[1:])]}


# ------------------------------------------------------------------ math

@scoped("ln")
def _rms(x, g, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(F32)).astype(x.dtype)


def _residual(x, branch, cfg: "GraniteHybridConfig"):
    """``x + residual_multiplier * branch``, summed in float32."""
    return (x.astype(F32) + cfg.residual_multiplier * branch.astype(F32)
            ).astype(x.dtype)


def _swiglu_ffn(x, f):
    dt = x.dtype
    gu = x @ f["w_in"].astype(dt)
    F = gu.shape[-1] // 2
    h = jax.nn.silu(gu[..., :F].astype(F32)) * gu[..., F:].astype(F32)
    return h.astype(dt) @ f["w_out"].astype(dt)


_shared_mlp = scoped("moe_shared")(_swiglu_ffn)


# ----------------------------------------------------------- expert layer

@scoped("moe_router")
def _route(u, moe, cfg: "GraniteHybridConfig"):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32: the ``k`` largest float32 logits over ALL experts, and a
    softmax over those ``k`` alone."""
    logits = jnp.dot(u.astype(F32), moe["router"].astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    top, picks = jax.lax.top_k(logits, cfg.num_experts_per_tok)
    return picks, jax.nn.softmax(top, axis=-1)


# The rows the held experts' matmul is given are for THIS share of the
# picks, not for num_held / num_experts of them: a router loads its
# experts unevenly, and which half of them a layer's tokens prefer moves
# with the weights (42-58 % of the picks landed on the held half, by
# layer and seed, on the chip: PERF.md section 6, PR 50). A buffer sized
# for the even share sent every such layer of a long prompt down the
# exact ``T k`` fallback, twice the rows, and tokens/s followed the seed.
LOAD_MARGIN = 1.25


def _expert_rows(T: int, cfg: "GraniteHybridConfig") -> int:
    """Rows the held experts' matmul is given for ``T`` tokens: the even
    share's picks plus six standard deviations
    (``held_experts.expected_rows``: what a decode batch needs, and what
    it had) or ``LOAD_MARGIN`` times the even share (what a long prompt
    needs, where six standard deviations are 3 %), whichever is more, in
    whole tiles of 128."""
    k = cfg.num_experts_per_tok
    even = cfg.num_held / cfg.num_local_experts
    leaning = 128 * math.ceil(min(1.0, LOAD_MARGIN * even) * T * k / 128)
    return min(T * k, max(_held.expected_rows(T, k, even), leaning))


def moe_layer(u, moe, cfg: "GraniteHybridConfig", valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, the routing counters' row): the held experts' weighted outputs
    for the picks that landed on them, and the shared MLP."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    fast = _expert_rows(u.shape[0], cfg)
    m, walked = _held.held_experts_part(u, order, where, held, weights,
                                        group_sizes, moe["experts"],
                                        fast=fast)
    m = (m + _shared_mlp(u, moe["shared"]).astype(F32)).astype(u.dtype)
    return m, _held.routing_counts(picks, held, group_sizes, valid,
                                   cfg.num_local_experts, walked)


def _ffn(x, layer, cfg: "GraniteHybridConfig", valid, counts):
    """``x + r (MoE + Shared)(N_post(x))`` on ``x [T, E]`` and the summed
    routing counters."""
    m, row = moe_layer(_rms(x, layer["norm_post"], cfg.rms_norm_eps),
                       layer["moe"], cfg, valid)
    return _residual(x, m, cfg), counts + row


# ------------------------------------------------------------------ block

@scoped("embed")
def _embed(params, cfg, ids):
    return (params["wte"][ids].astype(F32) * cfg.embedding_multiplier
            ).astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    """The tied head over the held rows of the vocabulary."""
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.einsum("te,ve->tv", x, params["wte"].astype(x.dtype),
                      preferred_element_type=F32) / cfg.logits_scaling


def _sequence_trunk(params, cfg: "GraniteHybridConfig", ids, length,
                    cache=None, slot=None):
    """Embed -> layers over one right-padded sequence ``ids [T]`` with
    ``length`` live tokens; with a cache, an attention layer's rows
    scatter into ``slot``'s blocks and a Mamba layer's final state and
    convolution tail overwrite the slot's. Returns the final residual
    stream ``[T, E]``, the cache and the summed routing counters."""
    valid = jnp.arange(ids.shape[0]) < length
    x = _embed(params, cfg, ids)
    counts = _mamba.routing_zero(cfg)
    for layer, (kind, i) in zip(params["layers"], cfg.layer_map):
        h = _rms(x, layer["norm_in"], cfg.rms_norm_eps)
        if kind == "state":
            mix, S, tail = _mamba.mixer_sequence(h, layer["mamba"], cfg,
                                                      length)
            if cache is not None:
                cache = with_state_layer(
                    cache, i,
                    jax.lax.dynamic_update_index_in_dim(
                        cache.state[i], S.astype(cache.state[i].dtype),
                        slot, 0),
                    jax.lax.dynamic_update_index_in_dim(
                        cache.conv[i], tail.astype(cache.conv[i].dtype),
                        slot, 1))
        else:
            with jax.named_scope("attn_full"):
                q, k, v = _attn.project(h, layer["attn"])
                if cache is not None:
                    cache = paged_write_prompt(cache, i, k, v, slot)
                mix = _attn.attn_out(_attn.sequence_attention(
                    q, k, v, cfg.attention_multiplier), layer["attn"])
        x = _residual(x, mix, cfg)
        x, counts = _ffn(x, layer, cfg, valid, counts)
    return x, cache, counts


def paged_prefill(params, cfg: "GraniteHybridConfig", input_ids, length,
                  cache: PagedKVCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot`` (the contract of
    ``transformer.paged_prefill``): the right-padded ``[1, T]`` prompt
    runs through the trunk (the chunked form on Mamba layers), attention
    layers' rows scatter into the slot's blocks, each Mamba layer's final
    state and convolution tail overwrite the slot's, ``lengths[slot]`` is
    pinned. Padding neither decays nor feeds a state. Returns (next-token
    logits ``[1, V]``, cache)."""
    n = length[0].astype(jnp.int32)
    x, cache, counts = _sequence_trunk(params, cfg, input_ids[0], n, cache,
                                       slot)
    n_state = sum(cfg.state_layers)
    chunk = min(cfg.mamba_chunk_size, input_ids.shape[1])
    cache = _mamba.count(
        cache, "prefill", counts, calls=1, state_passes=n_state,
        prefill_tokens=n, prefill_chunks=-(-n // chunk) * n_state).replace(
        lengths=jax.lax.dynamic_update_index_in_dim(cache.lengths, n, slot,
                                                    0))
    last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
    return _logits(params, cfg, last), cache


def paged_decode_step(params, cfg: "GraniteHybridConfig", tokens,
                      cache: PagedKVCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). A Mamba layer shifts every live slot's convolution tail
    and reads, updates and writes back its state once, in place; an
    attention layer appends at ``lengths[s]`` through the block tables
    and attends its live blocks. Idle slots' states and tails are not
    touched, their appends land in the null block, they route nowhere and
    are not advanced."""
    live = cache.lengths + 1
    x = _embed(params, cfg, tokens)
    counts = _mamba.routing_zero(cfg)
    for layer, (kind, i) in zip(params["layers"], cfg.layer_map):
        h = _rms(x, layer["norm_in"], cfg.rms_norm_eps)
        if kind == "state":
            mix, S, tail = _mamba.mixer_token(
                h, layer["mamba"], cfg, active, cache.state[i],
                cache.conv[i])
            cache = with_state_layer(cache, i, S, tail)
        else:
            with jax.named_scope("attn_full"):
                q, k, v = _attn.project(h, layer["attn"])
                cache = paged_append_token(cache, i, k, v)
                mix = _attn.attn_out(_attn.token_attention(
                    q, cache, i, live, cfg.attention_multiplier),
                    layer["attn"])
        x = _residual(x, mix, cfg)
        x, counts = _ffn(x, layer, cfg, active, counts)
    n_live = jnp.sum(active, dtype=jnp.int32)
    cache = _mamba.count(
        cache, "decode", counts, calls=1, live_slots=n_live,
        state_passes=n_live * sum(cfg.state_layers),
        kv_rows_read=jnp.sum(jnp.where(active, live, 0))
        * (cfg.num_hidden_layers - sum(cfg.state_layers)))
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: "GraniteHybridConfig", input_ids,
                   attention_mask=None, mesh=None):
    """Full-sequence logits ``[B, T, V]`` (no cache): what
    ``InferenceEngine.forward`` returns. Each row runs the chunked form
    from a zero state; a mask has to be a right-padding one (the live
    tokens first)."""
    B, T = input_ids.shape
    lengths = (jnp.full((B,), T, jnp.int32) if attention_mask is None
               else jnp.sum(attention_mask.astype(jnp.int32), axis=1))
    return jnp.stack([
        _logits(params, cfg, _sequence_trunk(params, cfg, input_ids[b],
                                             lengths[b])[0])
        for b in range(B)])
