"""Brumby: a Qwen3-shaped decoder whose every attention is a POWER
RETENTION layer (degree-2 gated linear attention), served over a
recurrent state pool.

The generic decoder (``transformer.py``) and the latent-attention family
(``longcat_flash.py``) both keep rows per token in a paged pool. This
model keeps none: a layer's cache is a state of fixed size a sequence,
``S [KH, R, d, d]`` and ``z [KH, Rz, d]`` in float32
(``kv_cache.RecurrentStateCache``; the layout is
``ops/pallas/power_retention.py``'s), which every decode step reads,
updates and writes back in place, whatever the context length. The same
serving entry points (``paged_prefill`` / ``paged_decode_step``, reached
through ``transformer.model_family``) run it.

One layer, with ``N`` RMSNorm, ``d`` the head size, ``G`` query heads a
key/value head (query head ``n`` reads key/value head ``n // G``)::

    h = N_in(x)
    q = h W_q  [H, d]    k = h W_k  [KH, d]    v = h W_v  [KH, d]
    gamma = h W_g + b_g  [KH]                  log g = logsigmoid(gamma)
    q, k <- N_head(q), N_head(k)               per-head RMSNorm, learned gain
    q, k <- RoPE(q, pos), RoPE(k, pos)         half-rotation pairs, all d dims
    a_tj = (q_t . k_j / sqrt(d))^2 exp(G_t - G_j),  j <= t,  G = cumsum log g
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)
    x <- x + concat_n(y_t[n]) W_o
    x <- x + W_down(silu(N_post(x) W_gate) * (N_post(x) W_up))

then a final RMSNorm and an untied head. A monolithic bucketed prefill
runs the chunked form (``power_retention_prefill``) and leaves the
prompt's final state in the slot; decode is the token recurrence
(``power_retention_decode``). Off the TPU both run as the same forms in
``jax.numpy``.

What the published ``config.json`` does not state, and is assumed here
(the benchmark's configuration file lists each): the degree 2 (the only
one implemented); one gate a key/value head from the normed hidden, with
a learned bias ``b_g`` (a bias-free projection is centred on ``g`` =
0.5, a memory of a few tokens); the head norms; RoPE's form; the
normaliser and its ``eps``; the scale ``1 / sqrt(d)`` inside the power;
a float32 state. Out of scope: keeping K and V until a switch-over
length before folding them into the state, state snapshots (preemption
re-prefills, there is no prefix reuse), chunked prefill, training.

Parameter schema::

    wte [V, E]   lm_head [E, V]   norm_f [E]
    layers: list of
      norm_in [E]  norm_post [E]
      wq [E, H, d]  wk [E, KH, d]  wv [E, KH, d]  wg [E, KH]  bg [KH]
      q_norm [d]  k_norm [d]  wo [H, d, E]
      w_in [E, 2 F] (gate ; up)  w_out [F, E]
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference.kv_cache import (RecurrentStateCache,
                                              paged_advance,
                                              with_layer_state)
from deepspeed_tpu.ops.pallas import power_retention as _ret
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.telemetry.registry import ScaledCounter

F32 = jnp.float32

# what this model keeps in RecurrentStateCache.aux: ``[program, column]``
PROGRAMS = ("decode", "prefill")
COUNTERS = ("calls", "live_slots", "state_passes", "prefill_tokens",
            "prefill_chunks")


def aux_series(cfg: "BrumbyConfig", reg) -> list:
    """The registry counter behind each cell of this model's
    ``cache.aux`` (docs/observability.md "Power retention and the state
    pool"), ``[program][column]``. The device counts state PASSES (one
    slot's state of one layer, read and written by decode, written by
    prefill); the series is bytes, so a reader need not know the
    layout."""
    def series(program: str) -> list:
        by = {"program": program}
        passes = cfg.state_bytes * (2 if program == "decode" else 1)
        named = {
            "calls": reg.counter(
                "serve_retention_steps_total", labels=by,
                help="executions of a retention model's program"),
            "live_slots": reg.counter(
                "serve_retention_live_slots_total", labels=by,
                help="live slots summed over decode steps (the "
                     "sequences whose state a step updated)"),
            "state_passes": ScaledCounter(reg.counter(
                "serve_retention_state_bytes_total", labels=by,
                help="recurrent state bytes moved: live slots x layers x "
                     "one slot-layer's S and z, read and written by "
                     "decode, written by prefill"), passes),
            "prefill_tokens": reg.counter(
                "serve_retention_prefill_tokens_total", labels=by,
                help="live prompt tokens run through the chunked form"),
            "prefill_chunks": reg.counter(
                "serve_retention_prefill_chunks_total", labels=by,
                help="chunks of the chunked form that held a live token, "
                     "summed over layers"),
        }
        return [named[name] for name in COUNTERS]
    return [series(program) for program in PROGRAMS]


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """Sizes under the names the published ``config.json`` gives them,
    then what it does not state (see the module's docstring)."""
    vocab_size: int
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    degree: int = 2
    retention_eps: float = 1e-6
    chunk_size: int = 256
    state_dtype: Any = jnp.float32
    dtype: Any = jnp.bfloat16
    # what InferenceEngine and ContinuousBatchingServer read of any
    # model configuration
    head: str = "lm"
    pre_layer_norm: bool = True
    seq_shard_kv: bool = False
    int8_compute: bool = False
    num_experts: int = 0
    # not fields: the kind of pool the server builds, and the module
    # whose entry points run this model (``transformer.py`` hands over)
    cache_kind = "state"
    family = __name__

    def __post_init__(self):
        if self.degree != 2:
            raise NotImplementedError(
                f"power retention of degree {self.degree}: only degree 2 "
                "(the symmetric embedding of pairs) is implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} key/value heads")
        _ret.pair_rows(self.head_dim)

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
    def group(self) -> int:
        """Query heads a key/value head."""
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def state_shapes(self) -> Tuple[tuple, tuple]:
        """One slot's ``S`` and ``z`` of one layer."""
        d, KH = self.head_dim, self.num_key_value_heads
        return ((KH, _ret.pair_rows(d), d, d), (KH, _ret.z_rows(d), d))

    @property
    def state_bytes(self) -> int:
        """Bytes of one slot's state of one layer, as stored."""
        s, z = self.state_shapes
        return (math.prod(s) + math.prod(z)) * jnp.dtype(
            self.state_dtype).itemsize

    @property
    def aux_shape(self) -> Tuple[int, int]:
        return (len(PROGRAMS), len(COUNTERS))


# ---------------------------------------------------------------- params

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark). Matrices are N(0, 1 / fan_in), embedding rows N(0, 1), norm
# gains 1. These depart from that:
# * the gate: ``wg`` has ``gate_std`` x N(0, 1 / fan_in) and ``bg`` sets
#   each key/value head's memory, ``g = 1 - 2^-e`` at ``gamma = b_g``:
#   the first half of a layer's heads are LOCAL, ``e`` evenly spaced over
#   ``gate_memory_log2["local"]`` (4, 8, 16, 32 tokens with eight heads),
#   the second half GLOBAL over ``gate_memory_log2["global"]`` (4096 to
#   262144 tokens: this model's 32768 positions and beyond; the ladder of
#   powers of two is RetNet's multi-scale decay, Sun et al. 2023). The
#   local heads forget within a prompt and their gates swing from token
#   to token, so a wrong or late decay moves a served token; the global
#   heads keep the whole context, which is where the state's precision
#   is decided: their ``z`` grows by one part in thousands a token, and a
#   bfloat16 state rounds that away (``tests/test_brumby.py``; PERF.md
#   section 6, PR 34);
# * ``attn_out_x`` on ``wo``, and per head ``sqrt(min(memory,
#   attn_out_memory_cap) / shortest memory)`` on top: a normalised
#   average over ``n`` tokens of random values is ``1 / sqrt(n)`` of one
#   value, so at random weights the global heads would vanish from the
#   residual stream (a trained head attends sharply whatever its memory).
#   The mixer has to stay a visible share of the stream, every head of
#   it, for a fault in any of them to move a served token.
INIT_SCALES = {"embedding_std": 1.0, "gate_std": 1.2,
               "gate_memory_log2": {"local": (2.0, 5.0),
                                    "global": (12.0, 18.0)},
               "attn_out_x": 2.0, "attn_out_memory_cap": 1024.0}


def _gate_memory_log2(KH: int):
    """``e [KH]``: key/value head ``m`` remembers ``2^e[m]`` tokens."""
    ladder = INIT_SCALES["gate_memory_log2"]
    return jnp.concatenate([
        jnp.linspace(*ladder["local"], KH // 2, dtype=F32),
        jnp.linspace(*ladder["global"], KH - KH // 2, dtype=F32)])


def _gate_bias(KH: int):
    """``b_g [KH]`` with ``sigmoid(b_g) = 1 - 2^-e``."""
    return jnp.log(jnp.exp2(_gate_memory_log2(KH)) - 1.0)


def _attn_out_scale(KH: int):
    """``[KH]``: what a key/value head's rows of ``wo`` are scaled by."""
    e = _gate_memory_log2(KH)
    cap = math.log2(INIT_SCALES["attn_out_memory_cap"])
    return INIT_SCALES["attn_out_x"] * jnp.exp2(
        0.5 * (jnp.minimum(e, cap) - jnp.minimum(e[0], cap)))


def _dense(key, shape, fan_in, dt, times=1.0):
    return (jax.random.normal(key, shape, F32)
            * (times / math.sqrt(fan_in))).astype(dt)


def _init_layer(key, cfg: BrumbyConfig) -> Dict:
    E, F = cfg.hidden_size, cfg.intermediate_size
    H, KH, d, dt = cfg.n_head, cfg.kv_heads, cfg.head_dim, cfg.dtype
    k = jax.random.split(key, 7)
    return {
        "norm_in": jnp.ones((E,), dt), "norm_post": jnp.ones((E,), dt),
        "wq": _dense(k[0], (E, H, d), E, dt),
        "wk": _dense(k[1], (E, KH, d), E, dt),
        "wv": _dense(k[2], (E, KH, d), E, dt),
        "wg": _dense(k[3], (E, KH), E, dt, INIT_SCALES["gate_std"]),
        "bg": _gate_bias(KH).astype(dt),
        "q_norm": jnp.ones((d,), dt), "k_norm": jnp.ones((d,), dt),
        "wo": (_dense(k[4], (H, d, E), H * d, F32)
               * jnp.repeat(_attn_out_scale(KH), H // KH)[:, None, None]
               ).astype(dt),
        "w_in": _dense(k[5], (E, 2 * F), E, dt),
        "w_out": _dense(k[6], (F, E), F, dt)}


@functools.lru_cache(maxsize=None)
def _jit_init_layer(cfg: BrumbyConfig):
    return jax.jit(lambda k: _init_layer(k, cfg))


@functools.lru_cache(maxsize=None)
def _jit_dense(shape, fan_in, dt, times):
    return jax.jit(lambda k: _dense(k, shape, fan_in, dt, times))


def init_params(rng: jax.Array, cfg: BrumbyConfig) -> Dict:
    """Seeded weights made on the device, one jitted call a tensor of
    the vocabulary's size and one a layer (all layers share the
    executable): a single program would hold every float32 draw at
    once."""
    E, V, dt = cfg.hidden_size, cfg.vocab_size, cfg.dtype
    keys = jax.random.split(rng, cfg.num_hidden_layers + 2)
    return {
        "wte": _jit_dense((V, E), 1.0, dt,
                          INIT_SCALES["embedding_std"])(keys[0]),
        "lm_head": _jit_dense((E, V), E, dt, 1.0)(keys[1]),
        "norm_f": jnp.ones((E,), dt),
        "layers": [_jit_init_layer(cfg)(k) for k in keys[2:]]}


# ------------------------------------------------------------------ math

@scoped("ln")
def _rms(x, g, eps):
    from deepspeed_tpu.model_implementations.transformer import _layer_norm
    return _layer_norm(x, {"scale": g}, eps)


def _rope(x, positions, theta):
    """Half-rotation pairs ``(x[i], x[i + d/2])`` over the whole head."""
    from deepspeed_tpu.model_implementations.transformer import apply_rotary
    return apply_rotary(x, positions, x.shape[-1], theta, False)


@scoped("ret_qkvg")
def _project(h, layer, cfg: BrumbyConfig, positions):
    """``h [..., E]`` -> ``q [..., KH, G, d]``, ``k`` / ``v [..., KH, d]``
    (head norms and rotary applied) and ``log g [..., KH]`` float32."""
    dt, eps = h.dtype, cfg.rms_norm_eps
    q = jnp.einsum("...e,ehd->...hd", h, layer["wq"].astype(dt))
    k = jnp.einsum("...e,ehd->...hd", h, layer["wk"].astype(dt))
    v = jnp.einsum("...e,ehd->...hd", h, layer["wv"].astype(dt))
    gamma = (jnp.einsum("...e,eh->...h", h, layer["wg"].astype(dt),
                        preferred_element_type=F32)
             + layer["bg"].astype(F32))
    q = _rope(_rms(q, layer["q_norm"], eps), positions, cfg.rope_theta)
    k = _rope(_rms(k, layer["k_norm"], eps), positions, cfg.rope_theta)
    q = q.reshape(*q.shape[:-2], cfg.kv_heads, cfg.group, cfg.head_dim)
    return q, k, v, jax.nn.log_sigmoid(gamma)


@scoped("ret_state")
def _retain_prompt(q, k, v, log_g, length, S, z, slot, cfg: BrumbyConfig):
    """The chunked form over one prompt ``[T, ...]``; its final state goes
    into ``slot`` of the layer's pool. Returns ``(y [T, KH, G, d], S,
    z)``."""
    if jax.default_backend() == "tpu":
        return _ret.power_retention_prefill(
            q, k, v, log_g, length, S, z, slot, chunk=cfg.chunk_size,
            eps=cfg.retention_eps)
    y, S1, z1 = _ret.retention_chunked_reference(
        q, k, v, log_g, length, chunk=cfg.chunk_size, eps=cfg.retention_eps)
    z1 = jnp.pad(z1, ((0, 0), (0, z.shape[2] - z1.shape[1]), (0, 0)))
    return (y, S.at[slot].set(S1.astype(S.dtype)),
            z.at[slot].set(z1.astype(z.dtype)))


@scoped("ret_state")
def _retain_token(q, k, v, log_g, active, S, z, cfg: BrumbyConfig):
    """The token recurrence for every live slot, the pool updated in
    place. Returns ``(y [slots, KH, G, d], S, z)``."""
    step = (_ret.power_retention_decode if jax.default_backend() == "tpu"
            else _ret.retention_decode_reference)
    return step(q, k, v, log_g, active, S, z, eps=cfg.retention_eps)


@scoped("ret_out")
def _out(y, layer):
    """``y [..., KH, G, d]`` -> ``[..., E]`` through ``W_o``."""
    y = y.reshape(*y.shape[:-3], -1, y.shape[-1])
    return jnp.einsum("...hd,hde->...e", y, layer["wo"].astype(y.dtype))


@scoped("mlp")
def _mlp(x, layer):
    """The bias-free gated MLP (``transformer._mlp`` wants biases)."""
    dt = x.dtype
    gu = x @ layer["w_in"].astype(dt)
    F = gu.shape[-1] // 2
    h = jax.nn.silu(gu[..., :F].astype(F32)) * gu[..., F:].astype(F32)
    return h.astype(dt) @ layer["w_out"].astype(dt)


@scoped("embed")
def _embed(params, cfg, ids):
    return params["wte"][ids].astype(cfg.dtype)


@scoped("lm_head")
def _logits(params, cfg, x):
    x = _rms(x, params["norm_f"], cfg.rms_norm_eps)
    return (x @ params["lm_head"].astype(x.dtype)).astype(F32)


def _count(cache: RecurrentStateCache, program: str, **counts):
    row = jnp.stack([jnp.asarray(counts.get(name, 0), jnp.int32)
                     for name in COUNTERS])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


# ------------------------------------------------------------------ trunk

def _sequence_trunk(params, cfg: BrumbyConfig, ids, length, states, slot):
    """Embed -> layers over one right-padded sequence ``ids [T]`` with
    ``length`` live tokens. ``states``: per layer ``(S, z)`` pools; each
    layer's final state goes into ``slot`` of its pool. Returns the final
    residual stream ``[T, E]`` and the pools."""
    positions = jnp.arange(ids.shape[0])
    x = _embed(params, cfg, ids)
    eps, out = cfg.rms_norm_eps, []
    for layer, (S, z) in zip(params["layers"], states):
        q, k, v, log_g = _project(_rms(x, layer["norm_in"], eps), layer,
                                  cfg, positions)
        y, S, z = _retain_prompt(q, k, v, log_g, length, S, z, slot, cfg)
        out.append((S, z))
        x = x + _out(y, layer)
        x = x + _mlp(_rms(x, layer["norm_post"], eps), layer)
    return x, out


def paged_prefill(params, cfg: BrumbyConfig, input_ids, length,
                  cache: RecurrentStateCache, slot, mesh=None):
    """Admit one prompt into pool slot ``slot`` (the contract of
    ``transformer.paged_prefill``): the right-padded ``[1, T]`` prompt
    runs through the chunked form, each layer's final state overwrites
    the slot's, ``lengths[slot]`` is pinned. Padding neither decays nor
    feeds the state. Returns (next-token logits ``[1, V]``, cache)."""
    n = length[0].astype(jnp.int32)
    x, states = _sequence_trunk(params, cfg, input_ids[0], n,
                                list(zip(cache.S, cache.z)), slot)
    chunk = min(cfg.chunk_size, input_ids.shape[1])
    cache = _count(
        cache.replace(
            S=tuple(s for s, _ in states), z=tuple(z for _, z in states),
            lengths=jax.lax.dynamic_update_index_in_dim(
                cache.lengths, n, slot, 0)),
        "prefill", calls=1, state_passes=cfg.n_layer, prefill_tokens=n,
        prefill_chunks=-(-n // chunk) * cfg.n_layer)
    last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, 0)
    return _logits(params, cfg, last), cache


def paged_decode_step(params, cfg: BrumbyConfig, tokens,
                      cache: RecurrentStateCache, active, mesh=None):
    """One generation step for all resident slots (the contract of
    ``transformer.paged_decode_step``): ``tokens [S]`` -> (logits ``[S,
    V]``, cache). Every live slot's state of every layer is read, updated
    and written back once; idle slots' states are not touched and their
    lengths not advanced."""
    positions = cache.lengths
    x = _embed(params, cfg, tokens)
    eps = cfg.rms_norm_eps
    for li, layer in enumerate(params["layers"]):
        q, k, v, log_g = _project(_rms(x, layer["norm_in"], eps), layer,
                                  cfg, positions)
        y, S, z = _retain_token(q, k, v, log_g, active, cache.S[li],
                                cache.z[li], cfg)
        cache = with_layer_state(cache, li, S, z)
        x = x + _out(y, layer)
        x = x + _mlp(_rms(x, layer["norm_post"], eps), layer)
    live = jnp.sum(active, dtype=jnp.int32)
    cache = _count(cache, "decode", calls=1, live_slots=live,
                   state_passes=live * cfg.n_layer)
    return _logits(params, cfg, x), paged_advance(cache, active)


def causal_forward(params, cfg: BrumbyConfig, input_ids,
                   attention_mask=None, mesh=None):
    """Full-sequence logits ``[B, T, V]`` (no cache): what
    ``InferenceEngine.forward`` returns. Each row runs the chunked form
    over a one-slot state of its own; a mask has to be a right-padding
    one (the live tokens first)."""
    B, T = input_ids.shape
    lengths = (jnp.full((B,), T, jnp.int32) if attention_mask is None
               else jnp.sum(attention_mask.astype(jnp.int32), axis=1))
    s_shape, z_shape = cfg.state_shapes
    rows = []
    for b in range(B):
        fresh = [(jnp.zeros((1, *s_shape), cfg.state_dtype),
                  jnp.zeros((1, *z_shape), cfg.state_dtype))
                 ] * cfg.n_layer
        x, _ = _sequence_trunk(params, cfg, input_ids[b], lengths[b], fresh,
                               jnp.int32(0))
        rows.append(_logits(params, cfg, x))
    return jnp.stack(rows)
