"""LongCat-Flash: shortcut-connected MoE with zero-compute experts over
latent attention, served as ONE CHIP'S SHARE of an expert-parallel
deployment.

The generic decoder (``transformer.py``) has K/V heads, one attention and
one FFN a layer, and an expert layer that runs every expert on every
token. This model has none of those, so it is a module of its own that
the same serving entry points (``paged_prefill`` / ``paged_decode_step``,
reached by the kind of cache they are handed) run:

* **a layer is a double block**: two latent attentions ``A0 A1``, two
  dense SwiGLU FFNs ``F0 F1`` and one MoE ``M`` that runs BESIDE the
  dense path (the shortcut: ``M`` reads the first sub-block's normed
  hidden and is added at the end of the layer)::

      h1 = x  + A0(N_in0(x))          u = N_post0(h1)
      m  = M(u)
      h2 = h1 + F0(u)
      h3 = h2 + A1(N_in1(h2))
      y  = h3 + F1(N_post1(h3)) + m

* **latent attention (MLA)**: queries through a normed rank-``q_lora``
  latent, keys and values through a normed rank-``kv_lora`` latent and
  one rotary key shared by all heads. The cache holds ``[c_kv ; k_rope]``
  a token an attention (``kv_cache.LatentPagedCache``). Prefill attends
  in the materialised form (K and V per head, flash kernel on a TPU);
  decode in the absorbed form over the latent pool
  (``ops/pallas/latent_decode_attention.py``); both are
  ``latent_attention.py``'s, shared with every latent family.

* **the expert layer holds a share**: it routes over ALL router outputs
  (real and zero-compute experts) in float32, computes the real experts
  it holds (``experts_held = [lo, hi)``) with a grouped matmul over the
  picks that landed on them, adds the identity experts' term ``(sum w) u``
  for its own tokens with no matmul, and leaves the picks on absent
  experts out: in a deployment their holders add those parts. On one chip
  the layer runs without its exchange; nothing stands in for the other
  chips. With ``experts_held = (0, n_routed_experts)`` it is the whole
  layer.

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of
      norm_in [2, E]  norm_post [2, E]
      attn: 2 x {wq_a [E, Rq], q_norm [Rq], wq_b [Rq, H, Dn + Dr],
                 wkv_a [E, Rkv + Dr], kv_norm [Rkv],
                 wk_b [Rkv, H, Dn], wv_b [Rkv, H, Dv]  (the published
                 W_kvb's key and value columns, kept apart: decode uses
                 them on either side of the kernel), wo [H, Dv, E]}
      ffn:  2 x {w_in [E, 2 F] (gate ; up), w_out [F, E]}
      moe:  {router [E, n_routed + n_zero], router_bias [n_routed + n_zero],
             experts {w_in [X, E, 2 Fe] (gate ; up), w_out [X, Fe, E]}}
             X = experts held

``V`` may be a slice of the vocabulary (a vocabulary-parallel share):
ids, logits and sampling are over the slice.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (LatentPagedCache,
                                              latent_write_prompt,
                                              paged_advance,
                                              with_latent_rows)
from deepspeed_tpu.model_implementations import held_experts as _held
from deepspeed_tpu.model_implementations import latent_attention as _mla
from deepspeed_tpu.profiling.trace import scoped

F32 = jnp.float32

# what this model keeps in LatentPagedCache.aux: routing counters
# ``[program, column]``, the picks on each held expert first, then these.
# ``decode_admit`` has a row of its own, so that ``decode`` keeps meaning
# the pure decode program (its readers divide by its executions)
PROGRAMS = ("decode", "prefill", "decode_admit")
COUNTER_TAIL = _held.COUNTER_TAIL


def aux_series(cfg: "LongcatFlashConfig", reg) -> list:
    """The registry counter behind each cell of this model's
    ``cache.aux`` (docs/observability.md "Latent attention and the expert
    layer"), ``[program][column]``; the server adds each cell's growth
    to its series."""
    return _held.counter_series(reg, cfg.num_held, PROGRAMS)


@dataclasses.dataclass(frozen=True)
class LongcatFlashConfig:
    """Sizes under the names the published ``config.json`` gives them,
    and the share this process holds (``experts_held``, ``vocab_size``
    rows of the embedding and the head)."""
    vocab_size: int
    hidden_size: int = 6144
    num_layers: int = 28
    num_attention_heads: int = 64
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    max_position_embeddings: int = 131072
    experts_held: Tuple[int, int] = (0, 512)
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

    def __post_init__(self):
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
        return self.num_layers

    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def num_experts(self) -> int:
        return self.num_held

    @property
    def num_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + self.zero_expert_num

    @property
    def attentions(self) -> int:
        """Attention sub-blocks, each with rows of its own in the pool."""
        return 2 * self.num_layers

    @property
    def latent_width(self) -> int:
        """Values cached a token an attention: ``[c_kv ; k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.qk_head_dim)

    @property
    def q_latent_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.q_lora_rank)
                if self.mla_scale_q_lora else 1.0)

    @property
    def kv_latent_scale(self) -> float:
        return (math.sqrt(self.hidden_size / self.kv_lora_rank)
                if self.mla_scale_kv_lora else 1.0)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), self.num_held + len(COUNTER_TAIL))


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in); these depart from that, and
# were tuned on the chip (PERF.md section 6, PR 29) so that the bfloat16
# program stays well inside the benchmark's tolerance of the float32
# reference while a wrong layer (no identity term, no scaling factor, no
# latent scales, the MoE fed from the wrong hidden, no correction bias)
# and 8-bit weights or an 8-bit pool each move served tokens past it:
# * the published latent scales (2 and 3.46) multiply what random
#   projections already give unit variance, so attention logits would
#   have a standard deviation near 6 and softmax would pick one key;
#   ``wq_b`` x 0.2 brings them near 1, ``wv_b`` x 0.29 gives unit values,
#   ``wo`` x 2 keeps attention a visible share of the residual stream;
# * router logits of standard deviation 2 for the real experts and 0.8 of
#   that for the zero-compute ones, which by score alone would then take
#   12 % of the picks; the correction bias lifts them back to the
#   published third (LongCat's controller moves the zero-compute
#   experts' bias to hold the average compute: 8 real experts of 12).
#   ``router_bias_identity`` is that lift, found by bisection on Gaussian
#   logits (tests/test_longcat_flash.py holds it to the third). Dropping
#   the bias takes two identity picks of four from every token;
# * on top, each expert's bias carries +-``router_bias_spread`` (an
#   eighth of the 12th score), laid out so that every aligned group of
#   16 experts (one chip's share) has the same evenly spaced, centred
#   set: every chip of the deployment is loaded alike, and each held
#   expert sees 256 x 12 / 768 = 4 +- 0.5 tokens a step. It is NOT drawn
#   from the seed: a seeded +-0.01 made the held experts hit a step, and
#   with them tokens/s, follow the seed (PERF.md section 6);
# * experts' down projection x ``expert_out_x`` (an expert's output
#   about as large as the identity experts'; at x 4 a tie at the 12th
#   place that swaps a held expert moved a served token by 0.036 of its
#   top logit once in 1536 tokens, at x 2 by 0.011);
# * ``channel_gain_sd``: trained checkpoints have channels of unequal
#   size. The KV latent's norm gains are log-normal with this standard
#   deviation and the rows of ``wk_b`` / ``wv_b`` that read them are
#   divided by them; the up half of every SwiGLU (dense and expert) has
#   its columns multiplied by such gains and the down projection's rows
#   divided. In exact arithmetic the model is the one with all gains 1,
#   and a floating-point format does not care; an 8-bit format with one
#   scale a row (the pool) or a column (weights) loses the small
#   channels, as it does on trained weights with outlier channels.
INIT_SCALES = {"embedding_std": 1.0, "router_std_x_sqrt_fan_in": 2.0,
               "router_identity_x": 0.8, "router_bias_identity": 0.009,
               "router_bias_spread": 0.001, "expert_out_x": 2.0,
               "wq_b_x": 0.2, "wv_b_x": 0.29, "attn_out_x": 2.0,
               "channel_gain_sd": 1.25}


def router_bias(cfg: "LongcatFlashConfig") -> jax.Array:
    """The seeded correction bias ``[router outputs]`` float32: the
    zero-compute experts' common lift plus a small spread that every
    aligned group of 16 experts carries alike (evenly spaced, centred)."""
    i = jnp.arange(cfg.router_outputs)
    spread = 2.0 * ((7 * i) % 16 + 0.5) / 16.0 - 1.0
    return (INIT_SCALES["router_bias_spread"] * spread
            + INIT_SCALES["router_bias_identity"]
            * (i >= cfg.n_routed_experts)).astype(F32)


def init_router(key, cfg: "LongcatFlashConfig") -> jax.Array:
    """The seeded router ``[E, router outputs]`` float32: the
    zero-compute experts' columns are ``router_identity_x`` of the real
    ones'."""
    E, R = cfg.hidden_size, cfg.router_outputs
    router = jax.random.normal(key, (E, R), F32) * (
        INIT_SCALES["router_std_x_sqrt_fan_in"] / math.sqrt(E))
    return router * jnp.where(jnp.arange(R) >= cfg.n_routed_experts,
                              INIT_SCALES["router_identity_x"], 1.0)


def _gains(key, shape):
    return jnp.exp(INIT_SCALES["channel_gain_sd"]
                   * jax.random.normal(key, shape, F32))


def _dense(key, shape, fan_in, dt, times=1.0):
    return (jax.random.normal(key, shape, F32)
            * (times / math.sqrt(fan_in))).astype(dt)


def _swiglu(key, lead, d_in, d_hidden, dt, out_x=1.0):
    """``w_in [*lead, d_in, 2 d_hidden]`` (gate ; up) and ``w_out [*lead,
    d_hidden, d_in]`` with per-channel gains on the up half that the
    down projection's rows undo."""
    k0, k1, k2 = jax.random.split(key, 3)
    c = _gains(k2, (*lead, d_hidden))
    w_in = jax.random.normal(k0, (*lead, d_in, 2 * d_hidden), F32)
    w_in = w_in * jnp.concatenate([jnp.ones_like(c), c], -1)[..., None, :]
    w_out = jax.random.normal(k1, (*lead, d_hidden, d_in), F32)
    return {"w_in": (w_in / math.sqrt(d_in)).astype(dt),
            "w_out": (w_out * (out_x / math.sqrt(d_hidden))
                      / c[..., None]).astype(dt)}


def _init_layer(key, cfg: LongcatFlashConfig) -> Dict:
    E, H = cfg.hidden_size, cfg.num_attention_heads
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    F, Fe, X = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size, cfg.num_held
    dt = cfg.dtype
    keys = iter(jax.random.split(key, 32))

    def attn():
        g = _gains(next(keys), (Rkv,))
        return {"wq_a": _dense(next(keys), (E, Rq), E, dt),
                "q_norm": jnp.ones((Rq,), dt),
                "wq_b": _dense(next(keys), (Rq, H, Dn + Dr), Rq, dt,
                               INIT_SCALES["wq_b_x"]),
                "wkv_a": _dense(next(keys), (E, Rkv + Dr), E, dt),
                "kv_norm": g.astype(dt),
                "wk_b": (_dense(next(keys), (Rkv, H, Dn), Rkv, F32)
                         / g[:, None, None]).astype(dt),
                "wv_b": (_dense(next(keys), (Rkv, H, Dv), Rkv, F32,
                                INIT_SCALES["wv_b_x"])
                         / g[:, None, None]).astype(dt),
                "wo": _dense(next(keys), (H, Dv, E), H * Dv, dt,
                             INIT_SCALES["attn_out_x"])}

    return {
        "norm_in": jnp.ones((2, E), dt), "norm_post": jnp.ones((2, E), dt),
        "attn": [attn(), attn()],
        "ffn": [_swiglu(next(keys), (), E, F, dt) for _ in range(2)],
        "moe": {
            "router": init_router(next(keys), cfg).astype(dt),
            "router_bias": router_bias(cfg).astype(dt),
            "experts": _swiglu(next(keys), (X,), E, Fe, dt,
                               INIT_SCALES["expert_out_x"])}}


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: LongcatFlashConfig):
    return jax.jit(lambda k: _init_layer(k, cfg))


@functools.lru_cache(maxsize=None)
def _jit_init_ends(cfg: LongcatFlashConfig):
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype

    def ends(key):
        k0, k1 = jax.random.split(key)
        return {"wte": _dense(k0, (V, E), 1.0, dt,
                              INIT_SCALES["embedding_std"]),
                "lm_head": _dense(k1, (E, V), E, dt),
                "norm_f": jnp.ones((E,), dt)}
    return jax.jit(ends)


def init_params(rng: jax.Array, cfg: LongcatFlashConfig) -> Dict:
    """Seeded weights made on the device, one jitted call a layer (all
    layers share the executable; a single program over every layer would
    hold every tensor's float32 draw at once)."""
    keys = jax.random.split(rng, cfg.num_layers + 1)
    params = _jit_init_ends(cfg)(keys[0])
    params["layers"] = [_jit_init_layer(cfg)(k) for k in keys[1:]]
    return params


# ------------------------------------------------------------------ math

_rms = _mla.rms
_materialised_attention = _mla.materialised_attention
_absorbed_attention = _mla.absorbed_attention
_attn_out = _mla.attn_out


def _rope(x, positions, theta):
    """Interleaved-pair rotary over the whole last dim of ``x [..., n,
    D]`` (``positions`` matches the leading dims)."""
    from deepspeed_tpu.model_implementations.transformer import apply_rotary
    return apply_rotary(x, positions, x.shape[-1], theta, True)


def _mla_project(h, a, cfg: LongcatFlashConfig, positions):
    """Latent attention's projections (``latent_attention.project``)
    under this model's rotary: plain, interleaved pairs."""
    return _mla.project(h, a, cfg, positions,
                        functools.partial(_rope, theta=cfg.rope_theta))


@scoped("dense_ffn")
def _dense_ffn(x, f):
    dt = x.dtype
    gu = x @ f["w_in"].astype(dt)
    F = gu.shape[-1] // 2
    h = jax.nn.silu(gu[..., :F].astype(F32)) * gu[..., F:].astype(F32)
    return h.astype(dt) @ f["w_out"].astype(dt)


# ----------------------------------------------------------- expert layer

@scoped("moe_router")
def _route(u, moe, cfg: LongcatFlashConfig):
    """``u [T, E]`` -> picks ``[T, k]`` and their weights ``[T, k]``
    float32. Scores are a float32 softmax over ALL router outputs; the
    correction bias moves the selection and never the weights; the
    weights are the raw scores times the scaling factor."""
    scores = jax.nn.softmax(jnp.dot(
        u.astype(F32), moe["router"].astype(F32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, picks = jax.lax.top_k(scores + moe["router_bias"].astype(F32),
                             cfg.moe_topk)
    weights = cfg.routed_scaling_factor * jnp.take_along_axis(
        scores, picks, axis=-1)
    return picks, weights


# everything after the picks (sorting the landed ones by expert, the
# grouped matmul over them, the weighted sum, the counters) is the held
# share's and is shared with every family that holds one:
# ``held_experts.py``
_fast_rows = _held.fast_rows


@scoped("moe_combine")
def _identity_part(u, picks, weights, cfg: LongcatFlashConfig):
    """The zero-compute experts' term ``(sum of their weights) u``."""
    w = jnp.sum(jnp.where(picks >= cfg.n_routed_experts, weights, 0.0), -1)
    return w[:, None] * u.astype(F32)


def moe_layer(u, moe, cfg: LongcatFlashConfig, valid):
    """This process's part of the expert layer on ``u [T, E]`` (``valid
    [T]``: rows that are tokens, not padding or idle slots) -> (``[T,
    E]``, counters row)."""
    picks, weights = _route(u, moe, cfg)
    order, where, held, group_sizes = _held.sort_picks(picks, valid,
                                                       cfg.experts_held)
    m, walked = _held.held_experts_part(u, order, where, held, weights,
                                        group_sizes, moe["experts"])
    m = (m + _identity_part(u, picks, weights, cfg)).astype(u.dtype)
    return m, _held.routing_counts(picks, held, group_sizes, valid,
                                   cfg.n_routed_experts, walked)


# ------------------------------------------------------------------ block

def _double_block(x, layer, cfg: LongcatFlashConfig, attend, valid,
                  ffn=_dense_ffn):
    """The shortcut-connected double block on ``x [..., E]``.
    ``attend(h, a, i)`` runs attention sub-block ``i`` (0 or 1) on the
    normed hidden and returns its projected output; ``ffn`` is the dense
    FFN, for a caller that runs it over some of its rows. Returns (y, the
    MoE's counters row)."""
    eps = cfg.rms_norm_eps
    h1 = x + attend(_rms(x, layer["norm_in"][0], eps), layer["attn"][0], 0)
    u = _rms(h1, layer["norm_post"][0], eps)
    m, counts = moe_layer(u.reshape(-1, u.shape[-1]), layer["moe"], cfg,
                          valid.reshape(-1))
    h2 = h1 + ffn(u, layer["ffn"][0])
    h3 = h2 + attend(_rms(h2, layer["norm_in"][1], eps), layer["attn"][1], 1)
    y = h3 + ffn(_rms(h3, layer["norm_post"][1], eps),
                 layer["ffn"][1]) + m.reshape(x.shape)
    return y, counts


@scoped("embed")
def _embed(params, cfg, ids):
    return params["wte"][ids].astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(x.dtype)).astype(F32)


def _count(cache: LatentPagedCache, program: str, counts):
    return cache.replace(aux=cache.aux.at[
        PROGRAMS.index(program)].add(counts))


def _sequence_trunk(params, cfg: LongcatFlashConfig, input_ids, valid,
                    cache=None, slot=None):
    """Embed -> double blocks over whole right-padded sequences
    ``input_ids [B, T]``; with a cache (and ``slot``; B = 1) each
    attention's rows are scattered into the slot's blocks. Returns the
    final residual stream, the cache and the summed counters."""
    B, T = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    x = _embed(params, cfg, input_ids)
    total = jnp.zeros((cfg.aux_shape[1],), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        def attend(h, a, i, li=li):
            nonlocal cache
            q_nope, q_rope, rows = _mla_project(h, a, cfg, positions)
            if cache is not None:
                cache = latent_write_prompt(cache, 2 * li + i, rows[0], slot)
            return _attn_out(_materialised_attention(
                q_nope, q_rope, rows, a, cfg), a)
        x, counts = _double_block(x, layer, cfg, attend, valid)
        total = total + counts
    return x, cache, total


def paged_prefill(params, cfg: LongcatFlashConfig, input_ids, length,
                  cache: LatentPagedCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot`` (the contract of
    ``transformer.paged_prefill``): the right-padded ``[1, T]`` prompt
    runs through the trunk, each attention's rows scatter into the slot's
    blocks, ``lengths[slot]`` is pinned. Returns (next-token logits ``[1,
    V]``, cache)."""
    T = input_ids.shape[1]
    valid = jnp.arange(T)[None] < length[:, None]
    x, cache, counts = _sequence_trunk(params, cfg, input_ids, valid,
                                       cache, slot)
    last = jnp.take_along_axis(x, (length - 1)[:, None, None], axis=1)[:, 0]
    cache = _count(cache, "prefill", counts).replace(
        lengths=jax.lax.dynamic_update_index_in_dim(
            cache.lengths, length[0].astype(jnp.int32), slot, 0))
    return _logits(params, cfg, last), cache


def paged_decode_step(params, cfg: LongcatFlashConfig, tokens,
                      cache: LatentPagedCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). Each attention appends its row at ``lengths[s]`` and
    attends the pool in the absorbed form; idle slots write nothing,
    attend nothing, route nowhere and are not advanced."""
    positions = cache.lengths
    x = _embed(params, cfg, tokens)
    total = jnp.zeros((cfg.aux_shape[1],), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        def attend(h, a, i, li=li):
            nonlocal cache
            q_nope, q_rope, rows = _mla_project(h, a, cfg, positions)
            cache, o = _absorbed_attention(q_nope, q_rope, rows, cache,
                                           2 * li + i, active, a, cfg)
            return _attn_out(o, a)
        x, counts = _double_block(x, layer, cfg, attend, active)
        total = total + counts
    return (_logits(params, cfg, x),
            paged_advance(_count(cache, "decode", total), active))


def paged_decode_admit(params, cfg: LongcatFlashConfig, tokens,
                       cache: LatentPagedCache, active, input_ids, length,
                       slot, mesh=None):
    """One generation step for the resident slots AND one prompt admitted
    into pool slot ``slot``, in one forward over ``S + T`` rows, so the
    weights are read once for both (an optional entry point: the server
    runs it where a family has it, and :func:`paged_prefill` in a program
    of its own where not). ``tokens [S]``, ``active [S]`` as
    :func:`paged_decode_step`; ``input_ids [1, T]``, ``length [1]``,
    ``slot`` as :func:`paged_prefill`. ``slot`` is NOT active in this
    step: its decode row is idle and appends nothing.

    The row-wise layers (norms, projections, dense FFNs, router, held
    experts, head) run once over all rows; each attention scatters the
    prompt's rows and attends them in the materialised form, appends the
    decode rows and attends them in the absorbed form, and projects both
    out together. Returns (logits ``[S, V]``, cache): ``logits[slot]`` is
    the logits of the prompt's last live row, so the sampled vector
    holds the request's first token at ``slot`` and the next step chains
    from it with the slot active. ``lengths[slot]`` is pinned to
    ``length``.

    With NO decode row live (an empty server filling its slots) the
    step is the prompt's alone: an idle row costs a matmul what a live
    one does (512 rows are past where this chip's share turns from
    bandwidth- to compute-bound), so the decode rows' appends and
    attention and their rows of the dense FFNs (three quarters of a
    row's matmul work) are left out on what the program observes
    (``any(active)``), and the admission then costs little more than
    :func:`paged_prefill` does."""
    S, T = tokens.shape[0], input_ids.shape[1]
    positions = jnp.concatenate([cache.lengths, jnp.arange(T)])
    valid = jnp.concatenate([active, jnp.arange(T) < length[0]])
    decoding = jnp.any(active)

    def ffn(u, f):
        """The dense FFN over every row, or over the prompt's alone with
        zeros in the decode rows' place."""
        return jax.lax.cond(
            decoding, lambda u: _dense_ffn(u, f),
            lambda u: jnp.pad(_dense_ffn(u[S:], f), ((S, 0), (0, 0))), u)

    x = _embed(params, cfg, jnp.concatenate([tokens, input_ids[0]]))
    total = jnp.zeros((cfg.aux_shape[1],), jnp.int32)
    for li, layer in enumerate(params["layers"]):
        def attend(h, a, i, li=li):
            nonlocal cache
            idx = 2 * li + i
            q_nope, q_rope, rows = _mla_project(h, a, cfg, positions)
            cache = latent_write_prompt(cache, idx, rows[S:], slot)

            def decode_rows(pool):
                one, o = _absorbed_attention(
                    q_nope[:S], q_rope[:S], rows[:S],
                    cache.replace(rows=(pool,)), 0, active, a, cfg)
                return one.rows[0], o

            def no_rows(pool):
                return pool, jnp.zeros(
                    (S, cfg.num_attention_heads, cfg.v_head_dim), h.dtype)
            pool, decoded = jax.lax.cond(decoding, decode_rows, no_rows,
                                         cache.rows[idx])
            cache = with_latent_rows(cache, idx, pool)
            return _attn_out(jnp.concatenate([
                decoded, _materialised_attention(
                    q_nope[None, S:], q_rope[None, S:], rows[None, S:], a,
                    cfg)[0]]), a)
        x, counts = _double_block(x, layer, cfg, attend, valid, ffn)
        total = total + counts
    last = jax.lax.dynamic_index_in_dim(x, S + length[0] - 1, 0)
    x = jax.lax.dynamic_update_slice_in_dim(x[:S], last, slot, 0)
    cache = paged_advance(_count(cache, "decode_admit", total), active)
    return _logits(params, cfg, x), cache.replace(
        lengths=jax.lax.dynamic_update_index_in_dim(
            cache.lengths, length[0].astype(jnp.int32), slot, 0))


def causal_forward(params, cfg: LongcatFlashConfig, input_ids,
                   attention_mask=None, mesh=None):
    """Full-sequence logits ``[B, T, V]`` (no cache): what
    ``InferenceEngine.forward`` returns."""
    valid = (jnp.ones(input_ids.shape, bool) if attention_mask is None
             else attention_mask.astype(bool))
    x, _, _ = _sequence_trunk(params, cfg, input_ids, valid)
    return _logits(params, cfg, x)
