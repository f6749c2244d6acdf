"""Nemotron-H (``nemotron_h``): a stack of layers that are ONE mixer
each, a Mamba-2 mixer, an attention or an expert layer alone, served as
ONE CHIP'S SHARE of an expert-parallel deployment and one stage of its
pipeline.

Where the other state-space hybrid (``granite_hybrid.py``) puts an expert
layer under every mixer, here the pattern (``hybrid_override_pattern``, a
character a layer) spends a layer on each: ``M`` a Mamba-2 mixer, ``*`` an
attention, ``E`` an expert layer. The serving entry points
(``paged_prefill`` / ``paged_decode_step``, reached through
``transformer.model_family``) run over ONE ``kv_cache.PagedKVCache`` whose
``layer_map`` gives every layer its place:

* ``M`` (kind ``"state"``): ``mamba2.py``'s mixer with ``n_groups`` groups
  of B and C (8 at the published sizes: head ``h`` reads group ``h // 8``,
  the gated norm is over each group's 512 channels); a float32 state and
  a convolution tail a slot, no block.
* ``*`` (kind ``"full"``): grouped-query attention with NO positional
  encoding, scale ``1 / sqrt(head_dim)``, few key/value heads (2 under 32
  query heads); block tables over the shared pool, the paged kernel.
* ``E`` (kind ``"none"``): float32 sigmoid scores over ALL routed experts,
  the ``k`` largest of ``scores + e_score_correction_bias``, weights the
  picked SCORES over their sum times ``routed_scaling_factor``
  (``held_experts.sigmoid_route``, DeepSeek-V3's router without a group
  limit); each expert UNGATED, ``relu(u W_up)^2 W_down``; the held
  experts' part through ``held_experts.py`` (picks on absent experts are
  left out: their holders add those parts); one shared expert of the same
  form every token passes through, unweighted. The layer keeps NOTHING in
  the cache: no slab of the pool, no ring, no state.

One layer and the model (``N`` RMSNorm)::

    x0 = Emb(ids)      x <- x + Mix_l(N_l(x))      logits = N_f(x) W_head

an untied head. This chip holds a slice of the vocabulary (``vocab_size``
rows of the embedding and columns of the head: tokens in and logits out
are over the slice) and a range of every expert layer's experts
(``experts_held``).

What the published ``config.json`` does not state and is assumed here
(the benchmark's configuration file lists each): no positional encoding
(``rope_theta`` and ``partial_rotary_factor`` are unused); ``in_proj`` is
cut ``[z | xBC | dt]`` and ``xBC = [x | B | C]``; ``dt`` has no upper
clamp; the gate is applied before the grouped norm; ``d_inner =
mamba_num_heads x mamba_head_dim`` (``expand`` is unused); no bias but
the convolution's. Out of scope: chunked prefill, prefix reuse,
speculation, int8 rows and a host tier (refused by the server by switch
name: a state has no rows), an expert exchange, training.

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of  norm [E]  and one of
      mamba {mamba2.py's schema}                                 M
      attn {wq [E, Hq, d]  wk [E, KH, d]  wv [E, KH, d]          *
            wo [Hq, d, E]}
      moe {router [E, n_routed]  router_bias [n_routed]          E
           experts {w_in [X, E, Fp], w_out [X, Fp, E]}    X = experts held
           shared {w_in [E, Fs], w_out [Fs, E]}}

``Fp`` is the routed experts' width as STORED (``expert_stored_width``):
``moe_intermediate_size`` rounded up to whole lanes of 128, the columns
of ``w_in`` and the rows of ``w_out`` past the published width zeros
(``relu(0)^2 = 0``: they add nothing). 1856 is 14.5 x 128: the chip's own
layout of a ``[.., 2688, 1856]`` array puts the 2688 minor, the grouped
matmul's kernel takes its weights row-major, and every call then copied
a layer's 638 MB ``w_in`` (compiled for a described v5e: PERF.md section
6, PR 58). The model's width, the reference's and the roofline's stay
the published one.
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
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32
MAMBA, ATTENTION, EXPERTS = "M", "*", "E"
KINDS = {MAMBA: "state", ATTENTION: "full", EXPERTS: "none"}

# what this model keeps in ``PagedKVCache.aux``: a state + attention
# hybrid's counters (``mamba2.py``)
PROGRAMS, COUNTERS, aux_series = (_mamba.PROGRAMS, _mamba.COUNTERS,
                                  _mamba.aux_series)


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Sizes under the names the published ``config.json`` gives them,
    and the share this process holds (``vocab_size`` rows of the
    vocabulary, ``experts_held``)."""
    vocab_size: int
    hybrid_override_pattern: str
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    experts_held: Tuple[int, int] = (0, 128)
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
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern has {len(pattern)} characters "
                f"for {self.num_hidden_layers} layers")
        if set(pattern) - set(KINDS):
            raise ValueError(f"hybrid_override_pattern {set(pattern)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads")
        if (self.mamba_num_heads % self.n_groups
                or self.d_inner % self.n_groups):
            raise ValueError(
                f"{self.mamba_num_heads} Mamba heads do not split into "
                f"n_groups = {self.n_groups} groups of B and C")
        lo, hi = self.experts_held
        if not 0 <= lo < hi <= self.n_routed_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{self.n_routed_experts} routed experts")
        if (self.n_routed_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.num_experts_per_tok > self.topk_group
                * (self.n_routed_experts // self.n_group)):
            raise ValueError(
                f"{self.n_routed_experts} experts in {self.n_group} groups, "
                f"{self.topk_group} kept, top-{self.num_experts_per_tok}")

    # ------------------- what the engine and the server read of a model

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
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    @property
    def expert_stored_width(self) -> int:
        """A routed expert's width as stored: whole lanes of 128."""
        return -(-self.moe_intermediate_size // 128) * 128

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    # -------------------------- the mixer's sizes under mamba2.py's names

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    mamba_n_heads = property(lambda self: self.mamba_num_heads)
    mamba_d_head = property(lambda self: self.mamba_head_dim)
    mamba_d_state = property(lambda self: self.ssm_state_size)
    mamba_n_groups = property(lambda self: self.n_groups)
    mamba_d_conv = property(lambda self: self.conv_kernel)
    mamba_chunk_size = property(lambda self: self.chunk_size)
    rms_norm_eps = property(lambda self: self.layer_norm_epsilon)

    # ----------------------------------- what the pool is built from

    @property
    def state_layers(self) -> Tuple[bool, ...]:
        return tuple(c == MAMBA for c in self.hybrid_override_pattern)

    @property
    def cacheless_layers(self) -> Tuple[bool, ...]:
        """The layers that keep nothing: an expert layer has no mixer
        over the sequence."""
        return tuple(c == EXPERTS for c in self.hybrid_override_pattern)

    @property
    def state_shapes(self) -> Tuple[tuple, tuple]:
        return _mamba.state_shapes(self)

    @property
    def state_bytes(self) -> int:
        return _mamba.state_bytes(self)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return _mamba.aux_shape(self)

    @property
    def layer_map(self) -> tuple:
        return kind_layer_map(KINDS[c]
                              for c in self.hybrid_override_pattern)


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), norm gains 1, embedding rows
# N(0, 1) (the head is UNTIED: no row reads itself back), the mixers' own
# draws ``mamba2.init_mixer``'s (global heads among them). What departs
# from that is CHOSEN FOR THE BENCHMARK'S CHECK AND ITS SPREAD, not for
# the program, which computes the same thing under any weights: a random
# model has to be one in which a float32 state can be told from a
# bfloat16 one by the largest gap of a few thousand tokens, and in which
# the seed does not decide how many experts a step hits (PERF.md section
# 6, PR 58, has each reading):
# * ``MIXER_SCALES``, the Mamba mixers' (``mamba2.INIT_SCALES`` has what
#   each does): a convolution bias that leaves ``x``, ``B`` and ``C``
#   without a mean, so that no constant of the WEIGHTS fills every
#   sequence's states and reaches every router (under a zero bias it was
#   a fifth of the stream: the routers starved a quarter of the held
#   experts, which ones following the seed, and tokens/s with them), and
#   global heads that remember 1000-30000 tokens at a step in the upper
#   decade of the reference range, so that what a state holds is a
#   visible share of ``y``;
# * ``attn_out_x``: a softmax over n random keys averages its values to
#   ~sqrt(e / n) of one; ``W_o`` is scaled so that this average of a
#   sequence's OWN context is the slowly moving part of its stream: the
#   Mamba layers after the first attention layer integrate it, their
#   global heads' states grow with the context, and a bfloat16 state
#   there stops taking in what a token adds (its error shows in the
#   largest gap within 1024 tokens; at x 12 it did not). The logits need
#   no help: at 1 / sqrt(128), q . k of unit-sized rows have a standard
#   deviation of 1;
# * the router: logits of standard deviation ``router_std`` and a
#   selection bias of +-``router_bias_spread``, evenly spaced, centred,
#   alike in every aligned group of 16 experts (so either half of the
#   experts carries the same set) and NOT drawn from the seed: the 6th
#   and 7th largest of 128 sigmoid scores lie ~0.01 apart, so the bias
#   moves most tokens' picks and never a weight;
# * ``expert_out_x`` / ``shared_out_x``: the down projections of the
#   routed experts x 0.25 and of the shared expert x 0.5. Half of all
#   picks land on a held expert, and bfloat16 activations against a
#   float32 reference flip a near tie for the 6th place in 1-3 % of a
#   run's 20,000 routing decisions: at unit scale ONE flipped pick moves
#   a logit by up to 0.8 standard deviations and a clean run's largest
#   gap hides every control but the coarsest;
# * every down projection is CENTRED over its hidden channels (the mean
#   of its rows taken out): ``relu(.)^2`` is positive, so under a random
#   down projection the activations' mean (0.5 a channel) becomes ONE
#   fixed vector that every token of every sequence gets and the routers
#   read (the latent family's lesson, PR 45).
INIT_SCALES = {"attn_out_x": 40.0, "router_std": 1.5,
               "router_bias_spread": 0.03, "expert_out_x": 0.25,
               "shared_out_x": 0.5}
MIXER_SCALES = {"zero_mean_conv": True, "global_dt": (1e-2, 1e-1),
                "global_memory": (1e3, 3e4)}


def router_bias(cfg: "NemotronHConfig") -> jax.Array:
    """The seeded selection bias ``[n_routed_experts]`` float32."""
    return _held.spread_selection_bias(cfg.n_routed_experts,
                                       INIT_SCALES["router_bias_spread"])


def _dense(key, shape, fan_in, dt, times=1.0):
    return (jax.random.normal(key, shape, F32)
            * (times / math.sqrt(fan_in))).astype(dt)


def _relu2_mlp(key, lead, d_in, d_hidden, dt, out_x, stored=None):
    """``w_in [*lead, d_in, stored]`` and ``w_out [*lead, stored, d_in]``
    (times ``out_x``, centred over the hidden channels) of an ungated
    expert ``d_hidden`` wide: zeros past ``d_hidden``."""
    k0, k1 = jax.random.split(key)
    none, extra = [(0, 0)] * len(lead), (0, (stored or d_hidden) - d_hidden)
    down = _dense(k1, (*lead, d_hidden, d_in), d_hidden, F32, out_x)
    down = (down - jnp.mean(down, axis=-2, keepdims=True)).astype(dt)
    return {"w_in": jnp.pad(_dense(k0, (*lead, d_in, d_hidden), d_in, dt),
                            none + [(0, 0), extra]),
            "w_out": jnp.pad(down, none + [extra, (0, 0)])}


def _init_layer(key, cfg: "NemotronHConfig", kind: str) -> Dict:
    E, dt = cfg.hidden_size, cfg.dtype
    layer = {"norm": jnp.ones((E,), dt)}
    if kind == MAMBA:
        layer["mamba"] = _mamba.init_mixer(key, cfg, MIXER_SCALES)
    elif kind == ATTENTION:
        H, KH, d = cfg.n_head, cfg.kv_heads, cfg.head_dim
        k = jax.random.split(key, 4)
        layer["attn"] = {
            "wq": _dense(k[0], (E, H, d), E, dt),
            "wk": _dense(k[1], (E, KH, d), E, dt),
            "wv": _dense(k[2], (E, KH, d), E, dt),
            "wo": _dense(k[3], (H, d, E), H * d, dt,
                         INIT_SCALES["attn_out_x"])}
    else:
        k = jax.random.split(key, 3)
        layer["moe"] = {
            "router": _dense(k[0], (E, cfg.n_routed_experts), E, dt,
                             INIT_SCALES["router_std"]),
            "router_bias": router_bias(cfg),
            "experts": _relu2_mlp(k[1], (cfg.num_held,), E,
                                  cfg.moe_intermediate_size, dt,
                                  INIT_SCALES["expert_out_x"],
                                  cfg.expert_stored_width),
            "shared": _relu2_mlp(
                k[2], (), E, cfg.moe_shared_expert_intermediate_size, dt,
                INIT_SCALES["shared_out_x"])}
    return layer


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: "NemotronHConfig", kind: str):
    return jax.jit(lambda k: _init_layer(k, cfg, kind))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt))


def init_params(rng: jax.Array, cfg: "NemotronHConfig") -> Dict:
    """Seeded weights made on the device, one jitted call a tensor of the
    vocabulary's size and one a layer (layers of one kind share the
    executable): a single program would hold every float32 draw at
    once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    return {
        "wte": _jit_dense((V, E), 1.0, dt)(keys[0]),
        "lm_head": _jit_dense((E, V), E, dt)(keys[1]),
        "norm_f": jnp.ones((E,), dt),
        "layers": [_jit_init_layer(cfg, kind)(k) for kind, k in
                   zip(cfg.hybrid_override_pattern, keys[2:])]}


# ------------------------------------------------------------------ math

@scoped("ln")
def _rms(x, g, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * g.astype(F32)).astype(x.dtype)


def _residual(x, branch):
    """``x + branch``, summed in float32."""
    return (x.astype(F32) + branch.astype(F32)).astype(x.dtype)


# ----------------------------------------------------------- expert layer

@scoped("moe_router")
def _route(u, moe, cfg: "NemotronHConfig"):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32 (``held_experts.sigmoid_route``)."""
    return _held.sigmoid_route(
        u, moe["router"], moe["router_bias"], cfg.num_experts_per_tok,
        cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor)


@scoped("moe_shared")
def _shared_expert(x, f):
    """``relu(x W_up)^2 W_down``: the shared expert, every token."""
    dt = x.dtype
    return _held.relu2(x @ f["w_in"].astype(dt)).astype(dt) @ f[
        "w_out"].astype(dt)


# The rows the held experts' matmul is given are for THIS share of the
# picks, not for num_held / n_routed of them: a seeded router loads its
# experts unevenly, and which half a layer's tokens prefer moves with the
# weights (granite_hybrid.LOAD_MARGIN has the lesson: 42-58 % of the picks
# landed on the held half, by layer and seed).
LOAD_MARGIN = 1.25


def _expert_rows(T: int, cfg: "NemotronHConfig") -> int:
    """Rows the held experts' matmul is given for ``T`` tokens: the even
    share's picks plus six standard deviations
    (``held_experts.expected_rows``) or ``LOAD_MARGIN`` times the even
    share, whichever is more, in whole tiles of 128; the rare step with
    more takes the exact ``T k`` fallback."""
    k = cfg.num_experts_per_tok
    even = cfg.num_held / cfg.n_routed_experts
    leaning = 128 * math.ceil(min(1.0, LOAD_MARGIN * even) * T * k / 128)
    return min(T * k, max(_held.expected_rows(T, k, even), leaning))


def moe_layer(u, moe, cfg: "NemotronHConfig", valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, the routing counters' row): the held experts' weighted outputs
    for the picks that landed on them, and the shared expert."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    m, walked = _held.held_experts_part(
        u, order, where, held, weights, group_sizes, moe["experts"],
        fast=_expert_rows(u.shape[0], cfg), act="relu2")
    m = (m + _shared_expert(u, moe["shared"]).astype(F32)).astype(u.dtype)
    return m, _held.routing_counts(picks, held, group_sizes, valid,
                                   cfg.n_routed_experts, walked)


# ------------------------------------------------------------------ block

@scoped("embed")
def _embed(params, cfg, ids):
    return params["wte"][ids].astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    """The untied head over the held columns of the vocabulary."""
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    return jnp.dot(x, params["lm_head"].astype(x.dtype),
                   preferred_element_type=F32)


def _sequence_trunk(params, cfg: "NemotronHConfig", ids, length,
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
        h = _rms(x, layer["norm"], cfg.rms_norm_eps)
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
        elif kind == "full":
            with jax.named_scope("attn_full"):
                q, k, v = _attn.project(h, layer["attn"])
                if cache is not None:
                    cache = paged_write_prompt(cache, i, k, v, slot)
                mix = _attn.attn_out(_attn.sequence_attention(
                    q, k, v, cfg.attn_scale), layer["attn"])
        else:
            mix, row = moe_layer(h, layer["moe"], cfg, valid)
            counts = counts + row
        x = _residual(x, mix)
    return x, cache, counts


def paged_prefill(params, cfg: "NemotronHConfig", input_ids, length,
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


def paged_decode_step(params, cfg: "NemotronHConfig", tokens,
                      cache: PagedKVCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). A Mamba layer shifts every live slot's convolution tail
    and reads, updates and writes back its state once, in place; an
    attention layer appends at ``lengths[s]`` through the block tables
    and attends its live blocks; an expert layer touches no cache. Idle
    slots' states and tails are not touched, their appends land in the
    null block, they route nowhere and are not advanced."""
    live = cache.lengths + 1
    x = _embed(params, cfg, tokens)
    counts = _mamba.routing_zero(cfg)
    for layer, (kind, i) in zip(params["layers"], cfg.layer_map):
        h = _rms(x, layer["norm"], cfg.rms_norm_eps)
        if kind == "state":
            mix, S, tail = _mamba.mixer_token(
                h, layer["mamba"], cfg, active, cache.state[i],
                cache.conv[i])
            cache = with_state_layer(cache, i, S, tail)
        elif kind == "full":
            with jax.named_scope("attn_full"):
                q, k, v = _attn.project(h, layer["attn"])
                cache = paged_append_token(cache, i, k, v)
                mix = _attn.attn_out(_attn.token_attention(
                    q, cache, i, live, cfg.attn_scale), layer["attn"])
        else:
            mix, row = moe_layer(h, layer["moe"], cfg, active)
            counts = counts + row
        x = _residual(x, mix)
    n_live = jnp.sum(active, dtype=jnp.int32)
    cache = _mamba.count(
        cache, "decode", counts, calls=1, live_slots=n_live,
        state_passes=n_live * sum(cfg.state_layers),
        kv_rows_read=jnp.sum(jnp.where(active, live, 0))
        * cfg.hybrid_override_pattern.count(ATTENTION))
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: "NemotronHConfig", input_ids,
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
