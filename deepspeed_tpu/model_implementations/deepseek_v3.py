"""DeepSeek-V3 blocks (``model_type: deepseek_v3``: DeepSeek-V3/R1,
GigaChat3): YaRN-scaled latent attention, leading dense layers, then
expert layers with group-limited sigmoid routing and a shared expert,
served as ONE CHIP'S SHARE of an expert-parallel deployment.

The generic decoder (``transformer.py``) has K/V heads and an expert
layer that runs every expert on every token. This model has neither, so
it is a module of its own that the same serving entry points
(``paged_prefill`` / ``paged_prefill_chunk`` / ``paged_decode_step``,
reached through ``transformer.model_family``) run over
``kv_cache.LatentPagedCache``. One layer (``N`` RMSNorm)::

    h = N_in(x)
    c_q = N_q(h W_qa)          q = c_q W_qb -> [H, Dn + Dr] = (q_nope, q_rope)
    kv = h W_kva [Rkv + Dr]    c_kv = N_kv(kv[:Rkv])   k_rope = RoPE(kv[Rkv:])
    (k_nope, v) = c_kv W_kvb -> [H, Dn + Dv]          cached: [c_kv ; k_rope]
    a = softmax((q_nope k_nope^T + RoPE(q_rope) k_rope^T) s + causal) v
    x <- x + concat_h(a_h) W_o
    u = N_post(x)
    dense (the first ``first_k_dense_replace`` layers):
        x <- x + W_down(silu(W_gate u) * W_up u)
    sparse:
        s = sigmoid(float32(u) W_r)     c = s + b   (b: selection only)
        g_j = sum of the two largest c in group j   (n_group groups of
              consecutive experts)
        keep the topk_group groups of largest g;  P = the top_k largest c
              among their experts
        w_e = f s_e / (sum_{j in P} s_j + 1e-20)
        x <- x + sum_{e in P, e held} w_e E_e(u) + S(u)

then a final RMSNorm and an untied head. RoPE turns interleaved pairs of
the ``Dr`` rotary dims with YaRN-scaled frequencies (``rope.py``); cos
and sin carry ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
mscale_all_dim)`` and the softmax scale is ``(Dn + Dr)^-0.5
yarn_mscale(factor, mscale_all_dim)^2``, ``yarn_mscale(f, m) = 0.1 m
ln f + 1``.

Three programs attend one cache three ways (``latent_attention.py``):
monolithic prefill in the materialised form (flash kernel), a PROMPT
CHUNK against the pool with K and V rebuilt block by block inside the
kernel (``ops/pallas/latent_chunk_attention.py``: chunked prefill and a
prefix-cache hit's tail), decode in the absorbed form
(``ops/pallas/latent_decode_attention.py``). The expert layer holds a
share (``experts_held = [lo, hi)``) through ``held_experts.py``: picks on
absent experts are left out (their holders add those parts) and nothing
stands in for the other chips. The multi-token-prediction module
(``num_nextn_predict_layers``) is not here: it lies on the last
pipeline stage and is a self-drafter, which a latent pool cannot verify
yet (the server refuses ``speculation_tokens`` by name).

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      attn {wq_a q_norm wq_b wkv_a kv_norm wk_b wv_b wo}  (latent_attention.py)
      ffn {w_in [E, 2 F] (gate ; up), w_out [F, E]}          dense layers
      moe {router [E, n_routed], router_bias [n_routed],     sparse layers
           experts {w_in [X, E, 2 Fe], w_out [X, Fe, E]}   X = experts held
           shared {w_in [E, 2 Fs], w_out [Fs, E]}}

``V`` may be a slice of the vocabulary (a vocabulary-parallel share).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (LatentPagedCache,
                                              latent_write_chunk,
                                              latent_write_prompt,
                                              paged_advance)
from deepspeed_tpu.model_implementations import held_experts as _held
from deepspeed_tpu.model_implementations import latent_attention as _mla
from deepspeed_tpu.model_implementations.rope import RopeSpec, rope_table
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32

# what this model keeps in LatentPagedCache.aux, ``[program, column]``:
# the expert layer's routing row (held_experts.COUNTER_TAIL after the
# picks on each held expert), then these
PROGRAMS = ("decode", "prefill")
OWN_COUNTERS = ("latent_rows_read",)


def aux_series(cfg: "DeepseekV3Config", reg) -> list:
    """The registry counter behind each cell of this model's
    ``cache.aux`` (docs/observability.md "Latent attention and the expert
    layer"), ``[program][column]``."""
    out = _held.counter_series(reg, cfg.num_held, PROGRAMS)
    for program, series in zip(PROGRAMS, out):
        series.append(reg.counter(
            "serve_kv_rows_read_total",
            labels={"program": program, "kind": "latent"},
            help="cache rows (one position of one attention) a step had "
                 "to read: a live slot's whole context an attention in "
                 "decode, a chunk's visible context in prefill"))
    return out


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """Sizes under the names the published ``config.json`` gives them
    (its ``rope_scaling`` group as ``rope_*``), and the share this
    process holds (``experts_held``, ``vocab_size`` rows of the
    embedding and the head)."""
    vocab_size: int
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position_embeddings: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 163840
    experts_held: Tuple[int, int] = (0, 256)
    dtype: Any = jnp.bfloat16
    # what InferenceEngine and ContinuousBatchingServer read of any
    # model configuration
    head: str = "lm"
    pre_layer_norm: bool = True
    seq_shard_kv: bool = False
    int8_compute: bool = False
    # not fields: the kind of pool the server builds, and the module
    # whose entry points run this model (``transformer.py`` hands over)
    cache_kind = "latent"
    family = __name__
    q_latent_scale = kv_latent_scale = 1.0

    def __post_init__(self):
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
                f"top {self.topk_group} groups, top "
                f"{self.num_experts_per_tok} experts do not fit together")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {self.first_k_dense_replace} of "
                f"{self.num_hidden_layers} layers")
        if not self.norm_topk_prob or self.n_shared_experts != 1:
            raise NotImplementedError(
                "unnormalised top-k weights / other than one shared expert")

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
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    num_experts = num_held

    @property
    def attentions(self) -> int:
        """Attentions, each with rows of its own in the pool."""
        return self.num_hidden_layers

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def latent_width(self) -> int:
        """Values cached a token an attention: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def yarn_mscale(self, mscale: float) -> float:
        return (0.1 * mscale * math.log(self.rope_factor) + 1.0
                if self.rope_factor > 1.0 else 1.0)

    @property
    def attn_scale(self) -> float:
        return (self.yarn_mscale(self.rope_mscale_all_dim) ** 2
                / math.sqrt(self.qk_head_dim))

    @property
    def rope_spec(self) -> RopeSpec:
        return RopeSpec(
            rope_theta=self.rope_theta, rope_type="yarn",
            factor=self.rope_factor,
            original_max_position_embeddings=(
                self.rope_original_max_position_embeddings),
            beta_fast=self.rope_beta_fast, beta_slow=self.rope_beta_slow,
            attention_factor=(self.yarn_mscale(self.rope_mscale)
                              / self.yarn_mscale(self.rope_mscale_all_dim)))

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), self.num_held + len(_held.COUNTER_TAIL)
                + len(OWN_COUNTERS))


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), embedding rows N(0, 1), norm
# gains 1 but the KV latent's. These depart from that, so that the
# benchmark's check against the float32 reference bites at contexts of
# tens of thousands of rows (each lesson is an earlier model's: PERF.md
# section 6, PRs 29, 34 and 43) while the bfloat16 program stays inside:
# * attention logits: random projections of normed latents give q . k a
#   variance of Dn + Dr, so under ``s`` = (Dn + Dr)^-0.5 m^2 the logits
#   have a standard deviation of m^2 = 2.0 at the published YaRN factor;
#   ``wq_b_x`` 1.75 makes it 3.5 (1.75 without m^2: a missing scale
#   shows). A softmax over n keys with logits of standard deviation s
#   gives ``v_mean + fluctuation``: the mean of the context's values,
#   COMMON to every query that sees the context and 1 / sqrt(n) of a
#   value whatever s, beside a part of the query's own that averages
#   about n / exp(s^2) rows: 610 rows at 33k and s = 2 (a 25th of a
#   value), 64 at 2.5, under one at 3.5 (a few top keys, most of a
#   value). The common part is what has to stay small: layer after layer
#   it grows (queries that share a component favour the same keys), and
#   a residual stream with a common component routes every token to the
#   same few experts. At s = 2 the own part was made visible by ``wo`` x
#   16 (x 4), which scaled the common part with it: a quarter (a ninth)
#   of the router input's energy in the third layer was common at a
#   2048-row context, the held experts' load on the chip read 2.0-2.5 x
#   its mean, which experts were hot and what a step's grouped matmuls
#   cost followed the seed, and with them tokens/s (PERF.md section 6,
#   PR 45). A sharper softmax raises the own part alone;
# * ``attn_out_x``: ``wo`` x 2 makes the attentions about two thirds of
#   the stream's energy. On the chip (PERF.md section 6, PR 45, two
#   seeds) a chunk that sees its own later rows then reads 14-20 x a
#   clean run's largest gap and one wrong table entry 10-13 x; at s =
#   2.5 and ``wo`` x 1.3, where a head was a sixth of a value, they read
#   7 x and 9 x of a gap a third as large, and the first passed the
#   check. Both gaps grow together beyond that (s = 3.0 to 4.0, ``wo`` x
#   1.3 to 2.5 read the same ratios): the program's bfloat16 rounding
#   goes through the same softmaxes as a fault does;
# * the router: logits of standard deviation ``router_std``, and a
#   selection bias of +-``router_bias_spread``, evenly spaced, centred,
#   alike in every aligned group of 16 experts (one chip's share of an
#   EP-16 deployment, half a routing group: every group holds the same
#   set twice) and NOT drawn from the seed;
# * ``expert_out_x``: the routed experts' part about as large as the
#   shared expert's, not more (a held expert that comes or goes at a
#   near tie moves a logit by as much as a missing factor would);
# * ``channel_gain_sd`` / ``ffn_gain_sd``: trained checkpoints have
#   channels of unequal size. The KV latent's norm gains are log-normal
#   and the rows of ``wk_b`` / ``wv_b`` that read them are divided by
#   them; the up half of every SwiGLU carries gains that the down
#   projection's rows undo. In exact arithmetic the model is the one
#   with all gains 1; an 8-bit format with one scale a row (the pool) or
#   a column (weights) loses the small channels.
INIT_SCALES = {"embedding_std": 1.0, "wq_b_x": 1.75, "attn_out_x": 2.0,
               "router_std": 1.5, "router_bias_spread": 0.005,
               "expert_out_x": 0.25, "channel_gain_sd": 1.25,
               "ffn_gain_sd": 2.0}


def router_bias(cfg: "DeepseekV3Config") -> jax.Array:
    """The seeded selection bias ``[n_routed_experts]`` float32: every
    aligned group of 16 experts carries the same evenly spaced, centred
    set."""
    return _held.spread_selection_bias(cfg.n_routed_experts,
                                       INIT_SCALES["router_bias_spread"])


def _gains(key, shape, sd):
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


def _init_layer(key, cfg: "DeepseekV3Config", sparse: bool) -> Dict:
    E, H, dt = cfg.hidden_size, cfg.num_attention_heads, cfg.dtype
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k = jax.random.split(key, 12)
    g = _gains(k[0], (Rkv,), "channel_gain_sd")
    layer = {
        "norm_in": jnp.ones((E,), dt), "norm_post": jnp.ones((E,), dt),
        "attn": {
            "wq_a": _dense(k[1], (E, Rq), E, dt),
            "q_norm": jnp.ones((Rq,), dt),
            "wq_b": _dense(k[2], (Rq, H, Dn + Dr), Rq, dt,
                           INIT_SCALES["wq_b_x"]),
            "wkv_a": _dense(k[3], (E, Rkv + Dr), E, dt),
            "kv_norm": g.astype(dt),
            "wk_b": (_dense(k[4], (Rkv, H, Dn), Rkv, F32)
                     / g[:, None, None]).astype(dt),
            "wv_b": (_dense(k[5], (Rkv, H, Dv), Rkv, F32)
                     / g[:, None, None]).astype(dt),
            "wo": _dense(k[6], (H, Dv, E), H * Dv, dt,
                         INIT_SCALES["attn_out_x"])}}
    if not sparse:
        layer["ffn"] = _swiglu(k[7], (), E, cfg.intermediate_size, dt)
        return layer
    layer["moe"] = {
        "router": _dense(k[8], (E, cfg.n_routed_experts), E, dt,
                         INIT_SCALES["router_std"]),
        "router_bias": router_bias(cfg),
        "experts": _swiglu(k[9], (cfg.num_held,), E,
                           cfg.moe_intermediate_size, dt,
                           INIT_SCALES["expert_out_x"]),
        "shared": _swiglu(k[10], (), E, cfg.moe_intermediate_size
                          * cfg.n_shared_experts, dt)}
    return layer


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: "DeepseekV3Config", sparse: bool):
    return jax.jit(lambda k: _init_layer(k, cfg, sparse))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt, times):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt, times))


def init_params(rng: jax.Array, cfg: "DeepseekV3Config") -> Dict:
    """Seeded weights made on the device, one jitted call a tensor of the
    vocabulary's size and one a layer (layers of one kind share the
    executable): a single program would hold every float32 draw at
    once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    return {
        "wte": _jit_dense((V, E), 1.0, dt,
                          INIT_SCALES["embedding_std"])(keys[0]),
        "lm_head": _jit_dense((E, V), E, dt, 1.0)(keys[1]),
        "norm_f": jnp.ones((E,), dt),
        "layers": [_jit_init_layer(cfg, li >= cfg.first_k_dense_replace)(k)
                   for li, k in enumerate(keys[2:])]}


# ------------------------------------------------------------------ math

_rms = _mla.rms


def _rope(x, positions, cfg: "DeepseekV3Config"):
    """Interleaved pairs ``(x[2i], x[2i + 1])`` over the whole last dim
    of ``x [..., n, Dr]`` at YaRN-scaled frequencies (``positions``
    matches the leading dims)."""
    inv, times = rope_table(cfg.rope_spec, x.shape[-1])
    ang = positions[..., None].astype(F32) * jnp.asarray(inv)
    cos = (jnp.cos(ang) * times)[..., None, :]
    sin = (jnp.sin(ang) * times)[..., None, :]
    xf = x.astype(F32)
    a, b = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _project(h, a, cfg, positions):
    return _mla.project(h, a, cfg, positions,
                        functools.partial(_rope, cfg=cfg))


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
def _route(u, moe, cfg: DeepseekV3Config):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32. Scores are a float32 sigmoid over ALL router outputs; the
    bias moves the selection (of groups and of experts) and never the
    weights; the weights are the picked scores normalised to sum to the
    scaling factor."""
    return _held.sigmoid_route(
        u, moe["router"], moe["router_bias"], cfg.num_experts_per_tok,
        cfg.n_group, cfg.topk_group, cfg.routed_scaling_factor)


def moe_layer(u, moe, cfg: DeepseekV3Config, valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, the counters' row without the rows read): the held experts'
    weighted outputs for the picks that landed on them, and the shared
    expert."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    m, walked = _held.held_experts_part(
        u, order, where, held, weights, group_sizes, moe["experts"],
        fast=_held.expected_rows(u.shape[0], cfg.num_experts_per_tok,
                                 cfg.num_held / cfg.n_routed_experts))
    m = (m + _shared_expert(u, moe["shared"]).astype(F32)).astype(u.dtype)
    counts = _held.routing_counts(picks, held, group_sizes, valid,
                                  cfg.n_routed_experts, walked)
    return m, counts


# ------------------------------------------------------------------ block

def _ffn(x, layer, cfg: DeepseekV3Config, valid, counts):
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


def _counts_zero(cfg: DeepseekV3Config):
    return jnp.zeros((cfg.aux_shape[1] - 1,), jnp.int32)


def _count(cache: LatentPagedCache, program: str, counts, rows_read):
    row = jnp.concatenate([counts,
                           jnp.asarray(rows_read, jnp.int32)[None]])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


def _sequence_trunk(params, cfg: DeepseekV3Config, ids, length, cache=None,
                    slot=None):
    """Embed -> layers over one right-padded sequence ``ids [T]`` with
    ``length`` live tokens, materialised attention; with a cache each
    attention's rows scatter into ``slot``'s blocks. Returns the final
    residual stream ``[T, E]``, the cache and the summed counters."""
    T = ids.shape[0]
    positions = jnp.arange(T)
    valid = positions < length
    x = _embed(params, cfg, ids)
    counts = _counts_zero(cfg)
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q_nope, q_rope, rows = _project(
            _rms(x, layer["norm_in"], cfg.rms_norm_eps), a, cfg, positions)
        if cache is not None:
            cache = latent_write_prompt(cache, li, rows, slot)
        x = x + _mla.attn_out(_mla.materialised_attention(
            q_nope[None], q_rope[None], rows[None], a, cfg)[0], a)
        x, counts = _ffn(x, layer, cfg, valid, counts)
    return x, cache, counts


def paged_prefill(params, cfg: DeepseekV3Config, input_ids, length,
                  cache: LatentPagedCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot`` as ONE program (the
    contract of ``transformer.paged_prefill``): the right-padded ``[1,
    T]`` prompt runs through the trunk, each attention's rows scatter
    into the slot's blocks, ``lengths[slot]`` is pinned. Returns
    (next-token logits ``[1, V]``, cache)."""
    n = length[0].astype(jnp.int32)
    x, cache, counts = _sequence_trunk(params, cfg, input_ids[0], n, cache,
                                       slot)
    cache = _count(cache, "prefill", counts, 0).replace(
        lengths=jax.lax.dynamic_update_index_in_dim(cache.lengths, n, slot,
                                                    0))
    last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
    return _logits(params, cfg, last), cache


def paged_prefill_chunk(params, cfg: DeepseekV3Config, input_ids, start,
                        length, cache: LatentPagedCache, slot, mesh=None):
    """One chunk of an incremental prefill (the contract of
    ``transformer.paged_prefill_chunk``): the ``[1, C]`` chunk at
    positions ``start .. start + C - 1`` (both block-aligned) runs
    through the trunk; each attention writes the chunk's rows into the
    slot's blocks and attends the slot's table up to its own rows:
    earlier chunks' rows and blocks a prefix-cache hit mapped in are read
    where they lie. ``lengths[slot]`` advances to ``min(start + C,
    length)``; the logits are the chunk's last live row's (the next
    token's on the final chunk)."""
    C = input_ids.shape[1]
    n = length[0].astype(jnp.int32)
    positions = start + jnp.arange(C)
    valid = positions < n
    table = jax.lax.dynamic_slice_in_dim(cache.block_tables, slot, 1, 0)[0]
    x = _embed(params, cfg, input_ids[0])
    counts = _counts_zero(cfg)
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q_nope, q_rope, rows = _project(
            _rms(x, layer["norm_in"], cfg.rms_norm_eps), a, cfg, positions)
        cache = latent_write_chunk(cache, li, rows, slot, start)
        x = x + _mla.attn_out(_mla.chunk_attention(
            q_nope, q_rope, cache.rows[li], table, start, a, cfg), a)
        x, counts = _ffn(x, layer, cfg, valid, counts)
    end = jnp.minimum(start + C, n)
    cache = _count(cache, "prefill", counts,
                   end * cfg.attentions).replace(
        lengths=jax.lax.dynamic_update_index_in_dim(cache.lengths, end, slot,
                                                    0))
    last = jax.lax.dynamic_slice_in_dim(x, end - 1 - start, 1, 0)
    return _logits(params, cfg, last), cache


def paged_decode_step(params, cfg: DeepseekV3Config, tokens,
                      cache: LatentPagedCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). Each attention appends its row at ``lengths[s]`` and
    attends the pool in the absorbed form; idle slots write nothing,
    attend nothing, route nowhere and are not advanced."""
    positions = cache.lengths
    live = cache.lengths + 1
    x = _embed(params, cfg, tokens)
    counts = _counts_zero(cfg)
    for li, layer in enumerate(params["layers"]):
        a = layer["attn"]
        q_nope, q_rope, rows = _project(
            _rms(x, layer["norm_in"], cfg.rms_norm_eps), a, cfg, positions)
        cache, o = _mla.absorbed_attention(q_nope, q_rope, rows, cache, li,
                                           active, a, cfg)
        x = x + _mla.attn_out(o, a)
        x, counts = _ffn(x, layer, cfg, active, counts)
    cache = _count(cache, "decode", counts,
                   jnp.sum(jnp.where(active, live, 0)) * cfg.attentions)
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: DeepseekV3Config, input_ids,
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
