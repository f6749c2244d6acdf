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
* **Mamba layers** (``mamba``) are Mamba-2 mixers (Dao & Gu 2024,
  arXiv:2405.21060): a short causal depthwise convolution, then a
  recurrence with one scalar decay a head a token over a float32 state
  ``S [heads, head_dim, d_state]`` a slot (``PagedKVCache.state``) and the
  convolution's last inputs (``PagedKVCache.conv``). A state is a fixed
  cost a slot whatever the context: no block, no table entry. Prefill
  runs the chunked (SSD) form inside one program; decode the recurrence.
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
      [x | B | C] = xBC                     x [H, P];  B, C [N], one group
      dt <- softplus(dt + dt_bias)          a = exp(dt A),  A = -exp(A_log)
      S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
      y_t[h] = S_t[h] C_t + D[h] x_t[h]
      Mix = (N_Di(y silu(z)) g) W_out       the gate BEFORE the norm
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
grouped norm, with one group; no bias but the convolution's. Out of
scope: chunked prefill, prefix reuse, speculation, int8 rows and a host
tier (refused by the server by switch name: a state has no rows),
several B/C groups, training.

Parameter schema::

    wte [V, E]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      mamba {w_z [E, Di]  w_xbc [E, Di + 2 N]  w_dt [E, H]     Mamba layers
             conv_w [k, Di + 2 N]  conv_b [Di + 2 N]
             dt_bias [H]  A_log [H]  D [H]  norm [Di]  w_out [Di, E]}
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
from deepspeed_tpu.ops.pallas import decode_attention as _kernels
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.telemetry.registry import ScaledCounter

F32 = jnp.float32
NEG_INF = -1e30
MAMBA, ATTENTION = "mamba", "attention"

# what this model keeps in PagedKVCache.aux, ``[program, column]``: the
# expert layer's routing row (held_experts.COUNTER_TAIL after the picks
# on each held expert), then these. A state PASS is one slot's state and
# convolution tail of one layer, read and written by decode, written by
# prefill; a K/V row is one position of one attention layer, K and V
PROGRAMS = ("decode", "prefill")
COUNTERS = ("calls", "live_slots", "state_passes", "kv_rows_read",
            "prefill_tokens", "prefill_chunks")


def aux_series(cfg: "GraniteHybridConfig", reg) -> list:
    """The registry counter behind each cell of this model's
    ``cache.aux`` (docs/observability.md "State layers beside attention
    layers"), ``[program][column]``. The device counts state PASSES; the
    series is bytes, so a reader need not know the layout."""
    out = _held.counter_series(reg, cfg.num_held, PROGRAMS)
    for program, series in zip(PROGRAMS, out):
        by = {"program": program}
        named = {
            "calls": reg.counter(
                "serve_hybrid_steps_total", labels=by,
                help="executions of a state + attention hybrid's program"),
            "live_slots": reg.counter(
                "serve_hybrid_live_slots_total", labels=by,
                help="live slots summed over decode steps (the sequences "
                     "whose states a step updated)"),
            "state_passes": ScaledCounter(reg.counter(
                "serve_hybrid_state_bytes_total", labels=by,
                help="state layers' bytes moved: live slots x state layers "
                     "x one slot-layer's state and convolution tail, read "
                     "and written by decode, written by prefill"),
                cfg.state_bytes * (2 if program == "decode" else 1)),
            "kv_rows_read": reg.counter(
                "serve_kv_rows_read_total",
                labels={"program": program, "kind": "full"},
                help="cache rows (one position of one layer, K and V) a "
                     "decode step had to read, by layer kind: a live "
                     "slot's whole context a full layer, min(context, "
                     "window) a window layer"),
            "prefill_tokens": reg.counter(
                "serve_hybrid_prefill_tokens_total", labels=by,
                help="live prompt tokens run through the chunked form"),
            "prefill_chunks": reg.counter(
                "serve_hybrid_prefill_chunks_total", labels=by,
                help="chunks of the chunked form that held a live token, "
                     "summed over state layers"),
        }
        series.extend(named[name] for name in COUNTERS)
    return out


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
        if self.mamba_n_groups != 1:
            raise NotImplementedError(
                f"mamba_n_groups {self.mamba_n_groups}: B and C are shared "
                "by all heads here (one group)")
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
        """``[x | B | C]``: what the short convolution runs over."""
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def state_layers(self) -> Tuple[bool, ...]:
        """What the pool is built from: which layers keep a state."""
        return tuple(t == MAMBA for t in self.layer_types)

    @property
    def state_shapes(self) -> Tuple[tuple, tuple]:
        """One slot's state of one layer, and its convolution tail's
        ``(taps, channels)``."""
        return ((self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
                (self.mamba_d_conv - 1, self.conv_channels))

    @property
    def state_bytes(self) -> int:
        """One slot's state and convolution tail of one layer."""
        s_shape, conv_shape = self.state_shapes
        return (math.prod(s_shape) * jnp.dtype(self.state_dtype).itemsize
                + math.prod(conv_shape) * jnp.dtype(self.dtype).itemsize)

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), self.num_held + len(_held.COUNTER_TAIL)
                + len(COUNTERS))

    @property
    def layer_map(self) -> tuple:
        return kind_layer_map("state" if s else "full"
                              for s in self.state_layers)


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), norm gains 1, and the
# Mamba-2 reference initialisation: ``A_log = log U[1, 16]``, ``dt_bias``
# the inverse softplus of a log-uniform ``[dt_min, dt_max]``, ``D = 1``,
# convolution taps U(-1, 1) / sqrt(k). These depart from that, so that
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
# * ``a_global``: a head remembers ``1 / (dt |A|)`` tokens, 0.6 to 1000
#   under the reference initialisation (median 14): every head would be
#   local, and a state kept in bfloat16 would only add unbiased noise
#   that a local head forgets. The second half of a layer's heads are
#   GLOBAL, ``|A|`` log-uniform over ``a_global`` (memories of hundreds to
#   tens of thousands of tokens, as a model served at 131072 positions
#   has): there ``(1 - a) S`` is under half a bfloat16 step, so a
#   bfloat16 state stops decaying and keeps only its largest inputs,
#   which is where the state's precision is decided (the retention
#   family's lesson, PR 34);
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
               "expert_out_x": 2.0, "ffn_gain_sd": 2.0,
               "dt_min": 1e-3, "dt_max": 1e-1, "a_local": (1.0, 16.0),
               "a_global": (2.0 ** -9, 2.0 ** -3)}


def _decay_rates(key, H: int):
    """``|A| [H]``: the first half of the heads uniform over ``a_local``
    (the reference initialisation), the second half log-uniform over
    ``a_global``."""
    k0, k1 = jax.random.split(key)
    lo, hi = INIT_SCALES["a_global"]
    return jnp.concatenate([
        jax.random.uniform(k0, (H // 2,), F32, *INIT_SCALES["a_local"]),
        jnp.exp(jax.random.uniform(k1, (H - H // 2,), F32, math.log(lo),
                                   math.log(hi)))])


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


def _init_mamba(key, cfg: "GraniteHybridConfig") -> Dict:
    E, Di, C, H = (cfg.hidden_size, cfg.d_inner, cfg.conv_channels,
                   cfg.mamba_n_heads)
    dt, s = cfg.dtype, INIT_SCALES
    k = jax.random.split(key, 7)
    step = jnp.exp(jax.random.uniform(k[4], (H,), F32)
                   * (math.log(s["dt_max"]) - math.log(s["dt_min"]))
                   + math.log(s["dt_min"]))
    return {
        "w_z": _dense(k[0], (E, Di), E, dt),
        "w_xbc": _dense(k[1], (E, C), E, dt),
        "w_dt": _dense(k[2], (E, H), E, dt),
        "conv_w": (jax.random.uniform(k[3], (cfg.mamba_d_conv, C), F32, -1.0,
                                      1.0) / math.sqrt(cfg.mamba_d_conv)),
        "conv_b": jnp.zeros((C,), F32),
        # softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(_decay_rates(k[5], H)),
        "D": jnp.ones((H,), F32),
        "norm": jnp.ones((Di,), dt),
        "w_out": _dense(k[6], (Di, E), Di, dt)}


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
        layer["mamba"] = _init_mamba(k[0], cfg)
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


# ------------------------------------------------------------ Mamba mixer

@scoped("mamba_in")
def _mamba_in(h, m):
    """``h [..., E]`` -> ``z [..., Di]``, ``xBC [..., C]`` (the
    activations' type) and the raw ``dt [..., H]`` float32."""
    dt = h.dtype
    return (h @ m["w_z"].astype(dt), h @ m["w_xbc"].astype(dt),
            jnp.dot(h, m["w_dt"].astype(dt), preferred_element_type=F32))


def _split(xbc, raw_dt, m, cfg: "GraniteHybridConfig"):
    """The convolved ``xBC [..., C]`` float32 and the raw ``dt`` -> ``x
    [..., H, P]``, ``B`` / ``C [..., N]``, the step ``dt [..., H]`` and
    ``A [H]`` (negative), all float32."""
    Di, N = cfg.d_inner, cfg.mamba_d_state
    x = xbc[..., :Di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads,
                              cfg.mamba_d_head)
    return (x, xbc[..., Di:Di + N], xbc[..., Di + N:],
            jax.nn.softplus(raw_dt + m["dt_bias"].astype(F32)),
            -jnp.exp(m["A_log"].astype(F32)))


@scoped("mamba_conv")
def _conv_sequence(xbc, m, length):
    """The causal depthwise convolution over one sequence ``xbc [T, C]``
    -> (``silu(conv + b) [T, C]`` float32, the tail ``[k - 1, C]``: the
    inputs at positions ``length - k + 1 .. length - 1``, zeros before
    position 0, so a bucket's padding never reaches it)."""
    w = m["conv_w"].astype(F32)                          # [k, C]
    k, T = w.shape[0], xbc.shape[0]
    xf = xbc.astype(F32)
    padded = jnp.concatenate([jnp.zeros((k - 1, xf.shape[1]), F32), xf])
    out = m["conv_b"].astype(F32) + sum(
        w[j] * padded[j:j + T] for j in range(k))
    # padded row i holds position i - (k - 1): the tail starts at
    # position length - (k - 1), which is padded row ``length``
    tail = jax.lax.dynamic_slice_in_dim(padded, length, k - 1, 0)
    return jax.nn.silu(out), tail.astype(xbc.dtype)


@scoped("mamba_conv")
def _conv_token(xbc, tail, m):
    """One token a slot: ``xbc [S, C]`` after the tail ``[k - 1, S, C]``
    -> (``silu(conv + b) [S, C]`` float32, the shifted tail)."""
    w = m["conv_w"].astype(F32)
    window = jnp.concatenate([tail, xbc[None].astype(tail.dtype)])
    out = m["conv_b"].astype(F32) + jnp.sum(
        w[:, None, :] * window.astype(F32), axis=0)
    return jax.nn.silu(out), window[1:]


@scoped("mamba_scan")
def _scan_sequence(x, B, C, dt, A, D, length, chunk: int, mm):
    """The chunked (SSD) form of the recurrence over one sequence from a
    zero state: ``x [T, H, P]``, ``B`` / ``C [T, N]``, ``dt [T, H]``,
    all float32 -> (``y [T, H, P]``, the state after ``length`` tokens
    ``[H, P, N]``). Positions past ``length`` get ``dt = 0``: they
    neither decay nor feed the state. Decays, their sums and the carried
    state are float32; the matmuls take their operands in ``mm`` (the
    activations' type) and accumulate in float32."""
    T, H, P = x.shape
    L = min(chunk, T)
    nc = -(-T // L)
    dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    if nc * L != T:     # a last chunk of dt = 0 rows (no cell's bucket)
        pad = lambda a: jnp.pad(a, ((0, nc * L - T),) + ((0, 0),)
                                * (a.ndim - 1))
        x_, B, C, dt = pad(x), pad(B), pad(C), pad(dt)
    else:
        x_ = x
    dtx = (dt[..., None] * x_).reshape(nc, L, H, P)
    # the decay's log summed inside a chunk, position ``l`` included
    cum = jnp.cumsum((dt * A).reshape(nc, L, H), axis=1)
    Bc, Cc = B.reshape(nc, L, -1), C.reshape(nc, L, -1)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None]
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)

    def one(S, c):
        dtx_c, cum_c, B_c, C_c = c
        # inside the chunk: (L o (C B^T)) (dt x), L_ij = exp(sum_{j<k<=i})
        decay = jnp.exp(jnp.where(
            causal[None], cum_c.T[:, :, None] - cum_c.T[:, None, :],
            -jnp.inf))                                       # [H, L, L]
        scores = dot("ln,sn->ls", C_c.astype(mm), B_c.astype(mm))
        y = dot("hls,shp->lhp", (decay * scores[None]).astype(mm),
                dtx_c.astype(mm))
        # what the chunks before left: C S_prev, decayed to each position
        y = y + dot("ln,hpn->lhp", C_c.astype(mm),
                    S.astype(mm)) * jnp.exp(cum_c)[..., None]
        # the state at the chunk's end
        keep = jnp.exp(cum_c[-1][None] - cum_c)              # [L, H]
        S = (jnp.exp(cum_c[-1])[:, None, None] * S
             + dot("lhp,ln->hpn", (dtx_c * keep[..., None]).astype(mm),
                   B_c.astype(mm)))
        return S, y

    S, y = jax.lax.scan(one, jnp.zeros((H, P, B.shape[-1]), F32),
                        (dtx, cum, Bc, Cc))
    return y.reshape(nc * L, H, P)[:T] + D[:, None] * x, S


@scoped("mamba_state")
def _state_token(x, B, C, dt, A, D, active, S):
    """The recurrence's one step for every slot: ``x [S, H, P]``, ``B`` /
    ``C [S, N]``, ``dt [S, H]`` float32 over the pool ``S [slots, H, P,
    N]`` -> (``y [S, H, P]``, the pool). An idle slot's ``dt`` is 0: its
    state is neither decayed nor fed."""
    dt = jnp.where(active[:, None], dt, 0.0)
    a = jnp.exp(dt * A)
    S = (a[..., None, None] * S.astype(F32)
         + (dt[..., None] * x)[..., None] * B[:, None, None, :])
    y = jnp.einsum("shpn,sn->shp", S, C) + D[:, None] * x
    return y, S


@scoped("mamba_out")
def _mamba_out(y, z, m, cfg: "GraniteHybridConfig"):
    """``y [..., H, P]`` float32 gated by ``z [..., Di]`` BEFORE the norm
    over all ``Di`` channels (one group), through ``W_out``."""
    g = y.reshape(*y.shape[:-2], -1) * jax.nn.silu(z.astype(F32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    g = (g * m["norm"].astype(F32)).astype(z.dtype)
    return g @ m["w_out"].astype(z.dtype)


def _mamba_sequence(h, m, cfg: "GraniteHybridConfig", length):
    """The mixer over one sequence ``h [T, E]`` -> (``[T, E]``, the final
    state ``[H, P, N]``, the convolution tail ``[k - 1, C]``)."""
    z, xbc, raw_dt = _mamba_in(h, m)
    xbc, tail = _conv_sequence(xbc, m, length)
    x, B, C, dt, A = _split(xbc, raw_dt, m, cfg)
    y, S = _scan_sequence(x, B, C, dt, A, m["D"].astype(F32), length,
                          cfg.mamba_chunk_size, h.dtype)
    return _mamba_out(y, z, m, cfg), S, tail


# -------------------------------------------------------------- attention

def _project(h, a):
    dt = h.dtype
    return (jnp.einsum("...e,ehd->...hd", h, a["wq"].astype(dt)),
            jnp.einsum("...e,ehd->...hd", h, a["wk"].astype(dt)),
            jnp.einsum("...e,ehd->...hd", h, a["wv"].astype(dt)))


def _sequence_attention(q, k, v, scale: float):
    """Causal attention of one sequence against itself, no positional
    encoding: ``q [T, H, d]``, ``k`` / ``v [T, KH, d]`` -> ``[T, H, d]``.
    On a TPU the flash kernel; the masked einsum elsewhere and for a
    prompt the kernel's blocks do not tile."""
    T, H, d = q.shape
    if jax.default_backend() == "tpu" and T >= 128 and T % 128 == 0:
        return flash_attention(q[None], k[None], v[None], causal=True,
                               scale=scale)[0]
    rep = H // k.shape[1]
    s = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, axis=1),
                   preferred_element_type=F32) * scale
    seen = jnp.arange(T)[None] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(seen[None], s, NEG_INF), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype),
                      jnp.repeat(v, rep, axis=1))


def _token_attention(q, cache: PagedKVCache, i: int, live, scale: float):
    """One token a slot against attention layer ``i`` of the pool: ``q
    [S, H, d]`` -> ``[S, H, d]``; ``live [S]`` counts the token just
    appended. The paged kernel on a TPU; its oracle elsewhere (which
    scales by 1 / sqrt(d): the query carries the difference)."""
    if jax.default_backend() == "tpu":
        return _kernels.paged_decode_attention(
            q, cache.k, cache.v, cache.block_tables, live, layer=i,
            scale=scale)
    q = (q.astype(F32) * (scale * math.sqrt(q.shape[-1]))).astype(q.dtype)
    return _kernels.paged_decode_attention_reference(
        q, cache.k[i], cache.v[i], cache.block_tables, live)


def _attn_out(a, attn):
    return jnp.einsum("...hd,hde->...e", a, attn["wo"].astype(a.dtype))


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
    m = (_held.held_experts_part(u, order, where, held, weights,
                                 group_sizes, moe["experts"], fast=fast)
         + _shared_mlp(u, moe["shared"]).astype(F32)).astype(u.dtype)
    return m, _held.routing_counts(picks, held, group_sizes, valid,
                                   cfg.num_local_experts)


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


def _count(cache: PagedKVCache, program: str, routing, **counts):
    row = jnp.concatenate([routing, jnp.stack(
        [jnp.asarray(counts.get(name, 0), jnp.int32) for name in COUNTERS])])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


def _routing_zero(cfg: "GraniteHybridConfig"):
    return jnp.zeros((cfg.num_held + len(_held.COUNTER_TAIL),), jnp.int32)


def _sequence_trunk(params, cfg: "GraniteHybridConfig", ids, length,
                    cache=None, slot=None):
    """Embed -> layers over one right-padded sequence ``ids [T]`` with
    ``length`` live tokens; with a cache, an attention layer's rows
    scatter into ``slot``'s blocks and a Mamba layer's final state and
    convolution tail overwrite the slot's. Returns the final residual
    stream ``[T, E]``, the cache and the summed routing counters."""
    valid = jnp.arange(ids.shape[0]) < length
    x = _embed(params, cfg, ids)
    counts = _routing_zero(cfg)
    for layer, (kind, i) in zip(params["layers"], cfg.layer_map):
        h = _rms(x, layer["norm_in"], cfg.rms_norm_eps)
        if kind == "state":
            mix, S, tail = _mamba_sequence(h, layer["mamba"], cfg, length)
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
                q, k, v = _project(h, layer["attn"])
                if cache is not None:
                    cache = paged_write_prompt(cache, i, k, v, slot)
                mix = _attn_out(_sequence_attention(
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
    cache = _count(cache, "prefill", counts, calls=1, state_passes=n_state,
                   prefill_tokens=n,
                   prefill_chunks=-(-n // chunk) * n_state).replace(
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
    counts = _routing_zero(cfg)
    for layer, (kind, i) in zip(params["layers"], cfg.layer_map):
        h = _rms(x, layer["norm_in"], cfg.rms_norm_eps)
        if kind == "state":
            m = layer["mamba"]
            z, xbc, raw_dt = _mamba_in(h, m)
            xbc, tail = _conv_token(xbc, cache.conv[i], m)
            tail = jnp.where(active[None, :, None], tail, cache.conv[i])
            xs, B, C, dt, A = _split(xbc, raw_dt, m, cfg)
            y, S = _state_token(xs, B, C, dt, A, m["D"].astype(F32), active,
                                cache.state[i])
            cache = with_state_layer(cache, i,
                                     S.astype(cache.state[i].dtype), tail)
            mix = _mamba_out(y, z, m, cfg)
        else:
            with jax.named_scope("attn_full"):
                q, k, v = _project(h, layer["attn"])
                cache = paged_append_token(cache, i, k, v)
                mix = _attn_out(_token_attention(
                    q, cache, i, live, cfg.attention_multiplier),
                    layer["attn"])
        x = _residual(x, mix, cfg)
        x, counts = _ffn(x, layer, cfg, active, counts)
    n_live = jnp.sum(active, dtype=jnp.int32)
    cache = _count(
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
