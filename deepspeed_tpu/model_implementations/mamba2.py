"""A Mamba-2 mixer (Dao & Gu 2024, arXiv:2405.21060) for any family that
keeps its recurrent state a slot in ``kv_cache.PagedKVCache`` (``state``
/ ``conv``): a short causal depthwise convolution, then a recurrence with
one scalar decay a head a token over a float32 state ``S [heads, head_dim,
d_state]``, ``B`` and ``C`` in ``G`` groups (head ``h`` reads group ``h //
(heads / G)``), a gated RMSNorm over each group's channels, ``W_out``::

    z = h W_z [Di]   xBC = h W_xBC [Di + 2 G N]   dt = h W_dt [H]
    xBC <- silu(conv_k(xBC) + b)          causal, depthwise, k taps
    [x | B | C] = xBC                     x [H, P];  B, C [G, N]
    dt <- softplus(dt + dt_bias)          a = exp(dt A),  A = -exp(A_log)
    S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g(h)]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] x_t[h]
    Mix = (N_{Di / G}(y silu(z)) g) W_out     the gate BEFORE the norm,
                                              the mean square a group

Prefill runs the chunked (SSD) form inside one program
(:func:`mixer_sequence`); decode the recurrence's one step over every
slot's state in place (:func:`mixer_token`). The scopes (``mamba_in``,
``mamba_conv``, ``mamba_scan``, ``mamba_state``, ``mamba_out``) are the
names the trace readers know. ``B`` and ``C`` travel as the convolution
leaves them, ``[..., G N]``, and are cut into groups where they are used:
with one group nothing is cut.

A family's configuration gives the sizes under these names (fields or
properties): ``hidden_size``, ``d_inner``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``,
``mamba_d_conv``, ``mamba_chunk_size``, ``rms_norm_eps``, ``dtype``,
``state_dtype``, and for the counters ``num_held``.

Parameter schema of one mixer::

    w_z [E, Di]  w_xbc [E, Di + 2 G N]  w_dt [E, H]
    conv_w [k, Di + 2 G N]  conv_b [Di + 2 G N]
    dt_bias [H]  A_log [H]  D [H]  norm [Di]  w_out [Di, E]

Shared code: it imports no model.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from deepspeed_tpu.model_implementations import held_experts as _held
from deepspeed_tpu.profiling.trace import scoped
from deepspeed_tpu.telemetry.registry import ScaledCounter

F32 = jnp.float32

# What a hybrid of these mixers, attention layers and expert layers keeps
# in ``PagedKVCache.aux``, ``[program, column]``: the expert layer's
# routing row (held_experts.COUNTER_TAIL after the picks on each held
# expert), then these. A state PASS is one slot's state and convolution
# tail of one layer, read and written by decode, written by prefill; a
# K/V row is one position of one attention layer, K and V
PROGRAMS = ("decode", "prefill")
COUNTERS = ("calls", "live_slots", "state_passes", "kv_rows_read",
            "prefill_tokens", "prefill_chunks")


def aux_shape(cfg) -> tuple:
    return (len(PROGRAMS), cfg.num_held + len(_held.COUNTER_TAIL)
            + len(COUNTERS))


def aux_series(cfg, reg) -> list:
    """The registry counter behind each cell of such a model's
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
                state_bytes(cfg) * (2 if program == "decode" else 1)),
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


def count(cache, program: str, routing, **counts):
    """``cache`` with ``program``'s row of ``aux`` grown by the summed
    routing row and the named :data:`COUNTERS`."""
    row = jnp.concatenate([routing, jnp.stack(
        [jnp.asarray(counts.get(name, 0), jnp.int32) for name in COUNTERS])])
    return cache.replace(aux=cache.aux.at[PROGRAMS.index(program)].add(row))


def routing_zero(cfg):
    return jnp.zeros((cfg.num_held + len(_held.COUNTER_TAIL),), jnp.int32)

# Seeded-weight scales (no checkpoint is loaded in tests or the
# benchmark): the Mamba-2 reference initialisation (``A_log = log U[1,
# 16]``, ``dt_bias`` the inverse softplus of a log-uniform ``[dt_min,
# dt_max]``, ``D = 1``, convolution taps U(-1, 1) / sqrt(k)) with one
# departure, ``a_global``: a head remembers ``1 / (dt |A|)`` tokens, 0.6
# to 1000 under the reference initialisation (median 14): every head
# would be local, and a state kept in bfloat16 would only add unbiased
# noise that a local head forgets. The second half of a layer's heads are
# GLOBAL, ``|A|`` log-uniform over ``a_global`` (memories of hundreds to
# tens of thousands of tokens, as a model served at 131072 positions
# has): there ``(1 - a) S`` is under half a bfloat16 step, so a bfloat16
# state stops decaying and keeps only its largest inputs, which is where
# the state's precision is decided (the retention family's lesson,
# PERF.md section 6, PRs 34 and 50).
#
# A family may pass :func:`init_mixer` further departures (none by
# default; PERF.md section 6, PR 58 has why a family would):
# * ``global_dt`` and ``global_memory``: the global heads' step
#   log-uniform over ``global_dt`` and ``|A| = 1 / (step x memory)`` with
#   the memory log-uniform over ``global_memory`` tokens, in place of
#   ``a_global``: every global head then remembers at least
#   ``global_memory[0]`` tokens at a step that is not lost beside ``D x``;
# * ``zero_mean_conv``: the convolution's bias ``sqrt(1 - |w|^2) - 1`` a
#   channel (``|w|^2`` its taps' squares summed), for which ``silu(conv +
#   b)`` of a unit-variance input has no mean to second order. With a
#   zero bias ``x``, ``B`` and ``C`` are positive on average, every
#   sequence's long-memory states fill with the same constant ``mean(x)
#   (x) mean(B)`` times the context, and every token of every sequence
#   gets the same vector from ``W_out``; with it a state holds what ITS
#   sequence put there.
INIT_SCALES = {"dt_min": 1e-3, "dt_max": 1e-1, "a_local": (1.0, 16.0),
               "a_global": (2.0 ** -9, 2.0 ** -3)}


def conv_channels(cfg) -> int:
    """``[x | B | C]``: what the short convolution runs over."""
    return cfg.d_inner + 2 * cfg.mamba_n_groups * cfg.mamba_d_state


def state_shapes(cfg) -> tuple:
    """One slot's state of one layer, and its convolution tail's
    ``(taps, channels)``: what ``init_paged_cache`` builds a state layer
    from."""
    return ((cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
            (cfg.mamba_d_conv - 1, conv_channels(cfg)))


def state_bytes(cfg) -> int:
    """One slot's state and convolution tail of one layer."""
    s_shape, conv_shape = state_shapes(cfg)
    return (math.prod(s_shape) * jnp.dtype(cfg.state_dtype).itemsize
            + math.prod(conv_shape) * jnp.dtype(cfg.dtype).itemsize)


def _log_uniform(key, n: int, lo: float, hi: float):
    return jnp.exp(jax.random.uniform(key, (n,), F32, math.log(lo),
                                      math.log(hi)))


def decay_rates(key, H: int):
    """``|A| [H]``: the first half of the heads uniform over ``a_local``
    (the reference initialisation), the second half log-uniform over
    ``a_global``."""
    k0, k1 = jax.random.split(key)
    return jnp.concatenate([
        jax.random.uniform(k0, (H // 2,), F32, *INIT_SCALES["a_local"]),
        _log_uniform(k1, H - H // 2, *INIT_SCALES["a_global"])])


def _dense(key, shape, fan_in, dt):
    return (jax.random.normal(key, shape, F32)
            * (1.0 / math.sqrt(fan_in))).astype(dt)


def init_mixer(key, cfg, scales=None) -> Dict:
    """One mixer's seeded weights; ``scales`` are a family's departures
    from :data:`INIT_SCALES` (the draws without them stay what they
    were)."""
    E, Di, C, H = (cfg.hidden_size, cfg.d_inner, conv_channels(cfg),
                   cfg.mamba_n_heads)
    dt, s = cfg.dtype, {**INIT_SCALES, **(scales or {})}
    k = jax.random.split(key, 7)
    step = jnp.exp(jax.random.uniform(k[4], (H,), F32)
                   * (math.log(s["dt_max"]) - math.log(s["dt_min"]))
                   + math.log(s["dt_min"]))
    rates = decay_rates(k[5], H)
    if "global_memory" in s:
        far = H - H // 2
        far_step = _log_uniform(jax.random.fold_in(k[4], 1), far,
                                *s["global_dt"])
        memory = _log_uniform(jax.random.fold_in(k[5], 1), far,
                              *s["global_memory"])
        step = step.at[H // 2:].set(far_step)
        rates = rates.at[H // 2:].set(1.0 / (far_step * memory))
    conv_w = (jax.random.uniform(k[3], (cfg.mamba_d_conv, C), F32, -1.0, 1.0)
              / math.sqrt(cfg.mamba_d_conv))
    conv_b = jnp.zeros((C,), F32)
    if s.get("zero_mean_conv"):
        conv_b = jnp.sqrt(jnp.maximum(
            1.0 - jnp.sum(conv_w * conv_w, axis=0), 0.0)) - 1.0
    return {
        "w_z": _dense(k[0], (E, Di), E, dt),
        "w_xbc": _dense(k[1], (E, C), E, dt),
        "w_dt": _dense(k[2], (E, H), E, dt),
        "conv_w": conv_w,
        "conv_b": conv_b,
        # softplus(dt_bias) = step
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "A_log": jnp.log(rates),
        "D": jnp.ones((H,), F32),
        "norm": jnp.ones((Di,), dt),
        "w_out": _dense(k[6], (Di, E), Di, dt)}


@scoped("mamba_in")
def mixer_in(h, m):
    """``h [..., E]`` -> ``z [..., Di]``, ``xBC [..., C]`` (the
    activations' type) and the raw ``dt [..., H]`` float32."""
    dt = h.dtype
    return (h @ m["w_z"].astype(dt), h @ m["w_xbc"].astype(dt),
            jnp.dot(h, m["w_dt"].astype(dt), preferred_element_type=F32))


def split(xbc, raw_dt, m, cfg):
    """The convolved ``xBC [..., C]`` float32 and the raw ``dt`` -> ``x
    [..., H, P]``, ``B`` / ``C [..., G N]``, the step ``dt [..., H]`` and
    ``A [H]`` (negative), all float32."""
    Di, GN = cfg.d_inner, cfg.mamba_n_groups * cfg.mamba_d_state
    x = xbc[..., :Di].reshape(*xbc.shape[:-1], cfg.mamba_n_heads,
                              cfg.mamba_d_head)
    return (x, xbc[..., Di:Di + GN], xbc[..., Di + GN:],
            jax.nn.softplus(raw_dt + m["dt_bias"].astype(F32)),
            -jnp.exp(m["A_log"].astype(F32)))


@scoped("mamba_conv")
def conv_sequence(xbc, m, length):
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
def conv_token(xbc, tail, m):
    """One token a slot: ``xbc [S, C]`` after the tail ``[k - 1, S, C]``
    -> (``silu(conv + b) [S, C]`` float32, the shifted tail)."""
    w = m["conv_w"].astype(F32)
    window = jnp.concatenate([tail, xbc[None].astype(tail.dtype)])
    out = m["conv_b"].astype(F32) + jnp.sum(
        w[:, None, :] * window.astype(F32), axis=0)
    return jax.nn.silu(out), window[1:]


@scoped("mamba_scan")
def scan_sequence(x, B, C, dt, A, D, length, chunk: int, mm,
                  groups: int = 1):
    """The chunked (SSD) form of the recurrence over one sequence from a
    zero state: ``x [T, H, P]``, ``B`` / ``C [T, G N]``, ``dt [T, H]``,
    all float32 -> (``y [T, H, P]``, the state after ``length`` tokens
    ``[H, P, N]``). Positions past ``length`` get ``dt = 0``: they
    neither decay nor feed the state. Decays, their sums and the carried
    state are float32; the matmuls take their operands in ``mm`` (the
    activations' type) and accumulate in float32. A group's ``C B^T`` is
    computed once for the ``H / G`` heads that read it."""
    T, H, P = x.shape
    G, J = groups, H // groups
    N = B.shape[-1] // G
    L = min(chunk, T)
    nc = -(-T // L)
    dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    if nc * L != T:     # a last chunk of dt = 0 rows (no cell's bucket)
        pad = lambda a: jnp.pad(a, ((0, nc * L - T),) + ((0, 0),)
                                * (a.ndim - 1))
        x_, B, C, dt = pad(x), pad(B), pad(C), pad(dt)
    else:
        x_ = x
    dtx = (dt[..., None] * x_).reshape(nc, L, G, J, P)
    # the decay's log summed inside a chunk, position ``l`` included
    cum = jnp.cumsum((dt * A).reshape(nc, L, G, J), axis=1)
    Bc, Cc = B.reshape(nc, L, G, N), C.reshape(nc, L, G, N)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None]
    dot = functools.partial(jnp.einsum, preferred_element_type=F32)

    def one(S, c):
        dtx_c, cum_c, B_c, C_c = c
        # inside the chunk: (L o (C B^T)) (dt x), L_ij = exp(sum_{j<k<=i})
        lg = jnp.moveaxis(cum_c, 0, -1)                      # [G, J, L]
        decay = jnp.exp(jnp.where(
            causal, lg[..., :, None] - lg[..., None, :],
            -jnp.inf))                                       # [G, J, L, L]
        scores = dot("lgn,sgn->gls", C_c.astype(mm), B_c.astype(mm))
        y = dot("gjls,sgjp->lgjp", (decay * scores[:, None]).astype(mm),
                dtx_c.astype(mm))
        # what the chunks before left: C S_prev, decayed to each position
        y = y + dot("lgn,gjpn->lgjp", C_c.astype(mm),
                    S.astype(mm)) * jnp.exp(cum_c)[..., None]
        # the state at the chunk's end
        keep = jnp.exp(cum_c[-1][None] - cum_c)              # [L, G, J]
        S = (jnp.exp(cum_c[-1])[..., None, None] * S
             + dot("lgjp,lgn->gjpn", (dtx_c * keep[..., None]).astype(mm),
                   B_c.astype(mm)))
        return S, y

    S, y = jax.lax.scan(one, jnp.zeros((G, J, P, N), F32),
                        (dtx, cum, Bc, Cc))
    return (y.reshape(nc * L, H, P)[:T] + D[:, None] * x,
            S.reshape(H, P, N))


@scoped("mamba_state")
def state_token(x, B, C, dt, A, D, active, S, groups: int = 1):
    """The recurrence's one step for every slot: ``x [S, H, P]``, ``B`` /
    ``C [S, G N]``, ``dt [S, H]`` float32 over the pool ``S [slots, H, P,
    N]`` -> (``y [S, H, P]``, the pool). An idle slot's ``dt`` is 0: its
    state is neither decayed nor fed. The pool is read as ``[slots, G,
    H / G, P, N]`` (its rows and lanes stay where they are) so that a
    group's ``B`` and ``C`` reach its heads by broadcast."""
    slots, H, P, N = S.shape
    G = groups
    by_group = (slots, G, H // G, P)
    dt = jnp.where(active[:, None], dt, 0.0)
    a = jnp.exp(dt * A).reshape(by_group[:3])
    S = (a[..., None, None] * S.astype(F32).reshape(*by_group, N)
         + (dt[..., None] * x).reshape(by_group)[..., None]
         * B.reshape(slots, G, 1, 1, N))
    y = jnp.einsum("sgjpn,sgn->sgjp", S, C.reshape(slots, G, N))
    return (y.reshape(slots, H, P) + D[:, None] * x,
            S.reshape(slots, H, P, N))


@scoped("mamba_out")
def mixer_out(y, z, m, cfg):
    """``y [..., H, P]`` float32 gated by ``z [..., Di]`` BEFORE the norm,
    whose mean square is taken over each group's ``Di / G`` channels (all
    ``Di`` with one group), through ``W_out``."""
    lead, G = y.shape[:-2], cfg.mamba_n_groups
    g = (y.reshape(*lead, -1) * jax.nn.silu(z.astype(F32))
         ).reshape(*lead, G, -1)
    g = (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                           + cfg.rms_norm_eps)).reshape(*lead, -1)
    g = (g * m["norm"].astype(F32)).astype(z.dtype)
    return g @ m["w_out"].astype(z.dtype)


def mixer_sequence(h, m, cfg, length):
    """The mixer over one sequence ``h [T, E]`` -> (``[T, E]``, the final
    state ``[H, P, N]``, the convolution tail ``[k - 1, C]``)."""
    z, xbc, raw_dt = mixer_in(h, m)
    xbc, tail = conv_sequence(xbc, m, length)
    x, B, C, dt, A = split(xbc, raw_dt, m, cfg)
    y, S = scan_sequence(x, B, C, dt, A, m["D"].astype(F32), length,
                         cfg.mamba_chunk_size, h.dtype, cfg.mamba_n_groups)
    return mixer_out(y, z, m, cfg), S, tail


def mixer_token(h, m, cfg, active, state, conv):
    """The mixer's one step for every slot: ``h [S, E]`` over one state
    layer's pool ``state [slots, H, P, N]`` and tails ``conv [k - 1,
    slots, C]`` -> (``[S, E]``, the pool in its own type, the tails).
    Idle slots' states and tails are not touched."""
    z, xbc, raw_dt = mixer_in(h, m)
    xbc, tail = conv_token(xbc, conv, m)
    tail = jnp.where(active[None, :, None], tail, conv)
    x, B, C, dt, A = split(xbc, raw_dt, m, cfg)
    y, S = state_token(x, B, C, dt, A, m["D"].astype(F32), active, state,
                       cfg.mamba_n_groups)
    return mixer_out(y, z, m, cfg), S.astype(state.dtype), tail
