"""The Nemotron-H family at a small size on the CPU: the program against
the plain float32 reference (prefill, then decode through the one cache
that holds states beside K/V blocks and NOTHING for an expert layer), the
shared Mamba-2 mixer with 1, 2 and 8 B/C groups against a plain scan over
positions, the sigmoid router, ungated relu² experts stored padded, the
two shares adding up to the uncut layer, the pool, the refusals by name,
the configuration file and the cell at a tiny size through the harness.
"""
import argparse
import dataclasses
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import harness  # noqa: E402
from benchmark.lib import reference_nemotron as ref  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference import kv_cache as kc  # noqa: E402
from deepspeed_tpu.model_implementations import held_experts  # noqa: E402
from deepspeed_tpu.model_implementations import (  # noqa: E402
    granite_hybrid as gh, mamba2, nemotron_h as nh)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

BENCH = os.path.join(REPO, "benchmark")
F32 = jnp.float32
BS, CHUNK = 16, 8
PATTERN = "MEMEM*E"
TAIL = len(held_experts.COUNTER_TAIL)


def _load_family():
    return harness.load_family("nemotron_h")


def _cfg(**over):
    """Mamba heads 8 x 8 over a state of 16 in 4 B/C groups, 4 query
    heads over 2 K/V heads of 8, 12 ungated experts 24 wide top-3, layers
    ``MEMEM*E``, chunks of 8 under blocks of 16."""
    base = dict(
        vocab_size=320, hybrid_override_pattern=PATTERN, hidden_size=32,
        num_hidden_layers=len(PATTERN), num_attention_heads=4,
        num_key_value_heads=2, head_dim=8, mamba_num_heads=8,
        mamba_head_dim=8, ssm_state_size=16, n_groups=4, chunk_size=CHUNK,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=40,
        n_routed_experts=12, num_experts_per_tok=3,
        max_position_embeddings=4096, experts_held=(0, 12), dtype=F32)
    base.update(over)
    base["num_hidden_layers"] = len(base["hybrid_override_pattern"])
    return nh.NemotronHConfig(**base)


@functools.lru_cache(maxsize=None)
def _model(held=(0, 12), dtype=F32):
    cfg = _cfg(experts_held=held, dtype=dtype)
    return cfg, nh.init_params(jax.random.PRNGKey(5), cfg)


def _config_of(model: dict):
    box = {}

    def make():
        box["cfg"], params = _load_family().serve_model(model, 0)
        return params
    jax.eval_shape(make)
    return box["cfg"]


def _weights(cfg, params):
    return _load_family().reference_from_serve(cfg, params)


def _pool(cfg, slots, blocks=40, span_blocks=16, dtype=F32):
    return kc.init_paged_cache(
        cfg.n_layer, slots, 1 + blocks, BS, span_blocks, cfg.kv_heads,
        cfg.head_dim, dtype, aux_shape=cfg.aux_shape,
        state_layers=cfg.state_layers, state_shapes=cfg.state_shapes,
        state_dtype=cfg.state_dtype, cacheless_layers=cfg.cacheless_layers)


def _with_tables(cache, slot, positions):
    need = -(-positions // BS)
    tables = np.zeros(cache.block_tables.shape, np.int32)
    tables[slot, :need] = 1 + np.arange(need)
    return cache.replace(block_tables=jnp.asarray(tables))


def _bucket(n):
    return max(BS, 1 << (n - 1).bit_length())


def _prefill(cfg, params, cache, ids, slot, bucket=None, fill=0):
    padded = np.full((1, bucket or _bucket(len(ids))), fill, np.int32)
    padded[0, :len(ids)] = ids
    return jax.jit(functools.partial(nh.paged_prefill, cfg=cfg))(
        params, input_ids=jnp.asarray(padded),
        length=jnp.array([len(ids)]), cache=cache, slot=jnp.int32(slot))


def _teacher_forced(cfg, params, ids, prompt, slots=3, slot=1):
    """Logits of every position from ``prompt - 1`` on: the prompt
    through ``paged_prefill`` into ``slot``, the rest a token a step
    through ``paged_decode_step`` (the other slots idle)."""
    T = len(ids)
    span = max(-(-(T + 1) // BS) + 1, _bucket(prompt) // BS)
    cache = _with_tables(_pool(cfg, slots, span_blocks=span,
                               dtype=cfg.dtype), slot, T + 1)
    decode = jax.jit(functools.partial(nh.paged_decode_step, cfg=cfg))
    logits, cache = _prefill(cfg, params, cache, ids[:prompt], slot)
    out = [logits[0]]
    active = jnp.arange(slots) == slot
    for t in range(prompt, T):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(ids[t]))
        logits, cache = decode(params, tokens=tokens, cache=cache,
                               active=active)
        out.append(logits[slot])
    return jnp.stack(out), cache


def _close(got, want, tol=1e-4):
    return float(jnp.abs(got - want).max()) <= tol * float(
        jnp.abs(want).max())


# ------------------------------------------- the program and the reference

@pytest.mark.parametrize("prompt", [1, 2, 5, CHUNK, BS, 21, 4 * BS, 70],
                         ids=["one-token", "shorter-than-the-conv",
                              "inside-a-chunk", "a-chunk-edge",
                              "a-bucket-edge", "inside-the-padding",
                              "four-blocks", "nine-chunks-padded"])
def test_float32_program_matches_the_reference(prompt):
    """Prefill at a length on each side of a chunk and of a bucket, then
    64 tokens decoded through the states and the block pool: every logit
    within 1e-4 of the recurrence-and-masks float32 reference (the three
    kinds of layer, four B/C groups, the convolution's tail, the sigmoid
    router, relu² experts stored padded and the shared expert are in
    it)."""
    cfg, params = _model()
    T = prompt + 64
    ids = np.random.default_rng(prompt).integers(0, cfg.vocab_size, T)
    got, cache = _teacher_forced(cfg, params, ids, prompt)
    want = ref.logits(_weights(cfg, params), ids[None])[0, prompt - 1:]
    assert _close(got, want)
    # what the programs counted: a pass a live slot a state layer, the
    # attention layer's rows, the prompt's tokens and chunks; an expert
    # layer a routed token
    aux = np.asarray(cache.aux)
    steps, n_state, n_moe = T - prompt, 3, 3
    own = aux[:, 12 + TAIL:]
    assert own[0].tolist() == [steps, steps, n_state * steps,
                               int(np.arange(prompt + 1, T + 1).sum()), 0, 0]
    chunk = min(CHUNK, _bucket(prompt))
    assert own[1].tolist() == [1, 0, n_state, 0, prompt,
                               -(-prompt // chunk) * n_state]
    tail = aux[:, 12:12 + TAIL]
    assert tail[0, 2] == n_moe * steps and tail[1, 2] == n_moe * prompt
    assert aux[:, :12].sum() == 3 * n_moe * (steps + prompt)


def test_bfloat16_program_stays_with_the_reference():
    """bfloat16 weights, activations, rows and tails with a float32 state
    and float32 routing, 64 decode steps behind a prefill: the served
    logits' median distance to the float32 reference over the same
    (bfloat16-stored) weights is a few thousandths of their size, and
    nearly every position's top choice is the reference's (a logit's
    LARGEST distance is a pick that flipped at a near tie among 12
    experts: up to a quarter of a logit's size at this toy width)."""
    cfg, params = _model(dtype=jnp.bfloat16)
    ids = np.random.default_rng(12).integers(0, cfg.vocab_size, 21 + 64)
    got, cache = _teacher_forced(cfg, params, ids, 21)
    assert cache.state[0].dtype == F32 and cache.k.dtype == jnp.bfloat16
    assert cache.conv[0].dtype == jnp.bfloat16
    want = ref.logits(_weights(cfg, params), ids[None])[0, 20:]
    top = want.max(-1)
    chosen = jnp.take_along_axis(want, got.argmax(-1)[:, None], -1)[:, 0]
    gap = (top - chosen) / jnp.maximum(1.0, jnp.abs(top))
    assert float(gap.max()) <= 0.05
    assert float((gap == 0).mean()) >= 0.9
    assert float(jnp.median(jnp.abs(got - want))) <= 0.02


def test_causal_forward_matches_the_reference():
    cfg, params = _model()
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 37))
    got = nh.causal_forward(params, cfg, jnp.asarray(ids))
    assert _close(got, ref.logits(_weights(cfg, params), ids))


# --------------------------------------- the shared mixer, by B/C groups

def _mixer_inputs(T, G, H=8, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.silu(jax.random.normal(k[1], (T, G * N))),
            jax.nn.silu(jax.random.normal(k[2], (T, G * N))),
            jnp.exp(jax.random.uniform(k[3], (T, H), F32, -7.0, -1.0)),
            -jnp.exp(jax.random.uniform(k[4], (H,), F32, -5.0, 3.0)),
            jax.random.normal(k[5], (H,)))


def _plain_scan(x, B, C, dt, A, D, length, G):
    """The recurrence a position and a head at a time, in numpy float64:
    head ``h`` reads group ``h // (H / G)``."""
    x, B, C, dt, A, D = (np.asarray(a, np.float64)
                         for a in (x, B, C, dt, A, D))
    T, H, P = x.shape
    N = B.shape[1] // G
    S = np.zeros((H, P, N))
    ys = np.zeros((length, H, P))
    for t in range(length):
        for h in range(H):
            g = h // (H // G)
            b, c = B[t, g * N:(g + 1) * N], C[t, g * N:(g + 1) * N]
            S[h] = np.exp(dt[t, h] * A[h]) * S[h] + dt[t, h] * np.outer(
                x[t, h], b)
            ys[t, h] = S[h] @ c + D[h] * x[t, h]
    return ys, S


@pytest.mark.parametrize("G", [1, 2, 8])
@pytest.mark.parametrize("T,length,chunk", [
    (64, 64, 8), (64, 37, 8), (16, 16, 256), (70, 70, 8)],
    ids=["whole", "padded-tail", "shorter-than-a-chunk",
         "ragged-last-chunk"])
def test_the_chunked_form_and_the_one_token_update_equal_the_plain_scan(
        G, T, length, chunk):
    """``mamba2.scan_sequence`` (the chunked form) and
    ``mamba2.state_token`` a position at a time, with 1, 2 and 8 groups of
    B and C, against a plain scan over positions and heads: the live
    positions' outputs and the state after ``length`` tokens."""
    x, B, C, dt, A, D = _mixer_inputs(T, G)
    want_y, want_S = _plain_scan(x, B, C, dt, A, D, length, G)
    y, S = mamba2.scan_sequence(x, B, C, dt, A, D, jnp.int32(length), chunk,
                                F32, groups=G)
    assert _close(y[:length], want_y, 1e-5) and _close(S, want_S, 1e-5)
    S1 = jnp.zeros((2, 8, 8, 16), F32)           # slot 0 idle, slot 1 live
    active = jnp.array([False, True])
    got = []
    for t in range(length):
        two = lambda a: jnp.stack([a[t] * 0 + 1, a[t]])   # noqa: E731
        y_t, S1 = mamba2.state_token(two(x), two(B), two(C), two(dt), A, D,
                                     active, S1, groups=G)
        got.append(y_t[1])
    assert _close(jnp.stack(got), want_y, 1e-5)
    assert _close(S1[1], want_S, 1e-5)
    assert float(jnp.abs(S1[0]).max()) == 0


def test_groups_are_not_one_group():
    """With 8 groups a head reads ITS group's B and C: the same inputs
    read as one group (every head the first 16 channels) give another
    output."""
    x, B, C, dt, A, D = _mixer_inputs(32, 8)
    y8, _ = mamba2.scan_sequence(x, B, C, dt, A, D, jnp.int32(32), 8, F32,
                                 groups=8)
    y1, _ = mamba2.scan_sequence(x, B[:, :16], C[:, :16], dt, A, D,
                                 jnp.int32(32), 8, F32, groups=1)
    assert _close(y8[:, 0], y1[:, 0], 1e-5)       # head 0 is in group 0
    assert not _close(y8[:, 7], y1[:, 7], 1e-2)   # head 7 is in group 7


@pytest.mark.parametrize("G", [1, 2, 8])
def test_the_gated_norm_is_over_each_groups_channels(G):
    """``mixer_out``: the gate BEFORE the norm, the mean square over each
    group's ``Di / G`` channels (all 64 only with one group)."""
    cfg = _cfg(n_groups=G)
    m = mamba2.init_mixer(jax.random.PRNGKey(1), cfg)
    m["norm"] = jax.random.normal(jax.random.PRNGKey(2), (64,))
    y = jax.random.normal(jax.random.PRNGKey(3), (5, 8, 8))
    z = jax.random.normal(jax.random.PRNGKey(4), (5, 64))
    v = np.asarray(y.reshape(5, 64) * jax.nn.silu(z), np.float64)
    per = 64 // G
    want = np.concatenate([
        v[:, g * per:(g + 1) * per] / np.sqrt(
            (v[:, g * per:(g + 1) * per] ** 2).mean(-1, keepdims=True)
            + 1e-5) for g in range(G)], -1) * np.asarray(m["norm"])
    got = mamba2.mixer_out(y, z, m, cfg)
    assert _close(got, want @ np.asarray(m["w_out"], np.float64), 1e-5)
    if G > 1:
        over_all = v / np.sqrt((v ** 2).mean(-1, keepdims=True) + 1e-5)
        assert not _close(got, (over_all * np.asarray(m["norm"]))
                          @ np.asarray(m["w_out"], np.float64), 1e-2)


def test_padding_never_reaches_a_state():
    """The same prompt in its own bucket and in two larger ones whose
    padding is other tokens: each Mamba layer's state and convolution
    tail and the next-token logits are the same."""
    cfg, params = _model()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, 21)
    outs = []
    for bucket, fill in ((32, 0), (64, 7), (128, 311)):
        cache = _with_tables(_pool(cfg, 2), 1, 128)
        logits, cache = _prefill(cfg, params, cache, ids, 1, bucket, fill)
        outs.append((logits, cache))
    for logits, cache in outs[1:]:
        assert _close(logits, outs[0][0], 1e-5)
        for a, b in zip(cache.state + cache.conv,
                        outs[0][1].state + outs[0][1].conv):
            assert _close(a[..., 1, :] if a.ndim == 3 else a[1],
                          b[..., 1, :] if b.ndim == 3 else b[1], 1e-5)
    cache = outs[0][1]
    assert float(jnp.abs(cache.conv[0][:, 1]).min()) > 0
    assert float(jnp.abs(cache.conv[0][:, 0]).max()) == 0   # slot 0: idle


def test_the_seeded_convolution_bias_leaves_x_b_and_c_without_a_mean():
    """``zero_mean_conv``: under the bias ``sqrt(1 - |w|^2) - 1`` a
    channel, ``silu(conv + b)`` of a unit-variance input keeps under a
    fifth of the mean a zero bias leaves (a quarter of its own deviation
    a channel then, positive on EVERY channel: the constant that filled
    every sequence's states), and the family's mixers are built so."""
    cfg = _cfg(hidden_size=64)
    key = jax.random.PRNGKey(5)
    plain = mamba2.init_mixer(key, cfg)
    ours = mamba2.init_mixer(key, cfg, nh.MIXER_SCALES)
    assert float(jnp.abs(plain["conv_b"]).max()) == 0
    np.testing.assert_array_equal(np.asarray(ours["conv_w"]),
                                  np.asarray(plain["conv_w"]))
    xbc = jax.random.normal(jax.random.PRNGKey(6),
                            (8192, mamba2.conv_channels(cfg)), F32)
    means = {name: np.asarray(jnp.mean(mamba2.conv_sequence(
        xbc, m, jnp.int32(8192))[0], axis=0)) for name, m in
        (("plain", plain), ("ours", ours))}
    assert (means["plain"] > 0).all()
    assert np.abs(means["ours"]).mean() < 0.2 * means["plain"].mean()
    layer = nh.init_params(jax.random.PRNGKey(0), _cfg())["layers"][0]
    assert float(jnp.abs(layer["mamba"]["conv_b"]).max()) > 0


def test_the_familys_global_heads_remember_thousands_of_tokens():
    """``global_dt`` / ``global_memory``: the second half of a mixer's
    heads step in the upper decade of the reference range and remember
    ``1 / (dt |A|)`` = 1000-30000 tokens; the first half keep the
    reference initialisation, and a mixer built without a family's
    departures keeps every draw it had."""
    cfg = _cfg()
    key = jax.random.PRNGKey(7)
    m = mamba2.init_mixer(key, cfg, nh.MIXER_SCALES)
    plain = mamba2.init_mixer(key, cfg)
    H = cfg.mamba_n_heads
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    rate = np.exp(np.asarray(m["A_log"]))
    memory = 1.0 / (step * rate)
    assert (step[H // 2:] >= 1e-2 * 0.999).all()
    assert (step[H // 2:] <= 1e-1 * 1.001).all()
    assert (memory[H // 2:] >= 1e3 * 0.999).all()
    assert (memory[H // 2:] <= 3e4 * 1.001).all()
    assert (rate[:H // 2] >= 1).all() and (rate[:H // 2] <= 16).all()
    for name in ("dt_bias", "A_log"):
        np.testing.assert_array_equal(np.asarray(m[name])[:H // 2],
                                      np.asarray(plain[name])[:H // 2])
    for name in ("w_z", "w_xbc", "w_dt", "conv_w", "D", "w_out"):
        np.testing.assert_array_equal(np.asarray(m[name]),
                                      np.asarray(plain[name]))


def test_granite_builds_and_serves_with_eight_groups():
    """The other hybrid on the shared mixer with ``mamba_n_groups`` 8 (it
    used to refuse any but 1): the chunked prefill and the one-token
    decode agree with its own full forward."""
    cfg = gh.GraniteHybridConfig(
        vocab_size=320, layer_types=(gh.MAMBA, gh.ATTENTION, gh.MAMBA),
        hidden_size=32, intermediate_size=24, shared_intermediate_size=40,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=12, num_experts_per_tok=3, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_n_groups=8,
        mamba_chunk_size=CHUNK, max_position_embeddings=4096,
        experts_held=(0, 12), dtype=F32)
    assert cfg.conv_channels == 64 + 2 * 8 * 16
    params = gh.init_params(jax.random.PRNGKey(2), cfg)
    ids = np.random.default_rng(2).integers(0, 320, 40)
    want = gh.causal_forward(params, cfg, jnp.asarray(ids[None]))[0]
    cache = kc.init_paged_cache(
        3, 2, 21, BS, 8, 2, 8, F32, aux_shape=cfg.aux_shape,
        state_layers=cfg.state_layers, state_shapes=cfg.state_shapes)
    cache = _with_tables(cache, 1, 41)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = ids[:21]
    logits, cache = jax.jit(functools.partial(gh.paged_prefill, cfg=cfg))(
        params, input_ids=jnp.asarray(padded), length=jnp.array([21]),
        cache=cache, slot=jnp.int32(1))
    got = [logits[0]]
    step = jax.jit(functools.partial(gh.paged_decode_step, cfg=cfg))
    for t in range(21, 40):
        logits, cache = step(
            params, tokens=jnp.array([0, int(ids[t])], jnp.int32),
            cache=cache, active=jnp.array([False, True]))
        got.append(logits[1])
    assert _close(jnp.stack(got), want[20:], 1e-4)


# ----------------------------------------------------- the expert layer

def test_routing_is_a_float32_sigmoid_with_a_bias_that_moves_picks_only():
    """Scores are a float32 sigmoid over all experts whatever the
    activations' type; the selection bias changes WHICH experts are
    picked and never a weight; the weights are the picked scores over
    their sum times 2.5; the reference routes alike."""
    cfg, params = _model()
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.hidden_size))
    picks, w = nh._route(u, moe, cfg)
    s = 1 / (1 + np.exp(-np.asarray(u @ moe["router"], np.float64)))
    b = np.asarray(moe["router_bias"], np.float64)
    want = np.argsort(-(s + b), axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(picks, -1), np.sort(want, -1))
    picked = np.take_along_axis(s, np.asarray(picks), -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the bias moves picks ...
    unbiased = np.argsort(-s, axis=-1)[:, :3]
    assert (np.sort(unbiased, -1) != np.sort(want, -1)).any()
    # ... and not weights: where both pick the same experts, the weights
    # are those of the scores alone
    no_bias = dict(moe, router_bias=jnp.zeros_like(moe["router_bias"]))
    picks0, w0 = nh._route(u, no_bias, cfg)
    same = (np.sort(picks, -1) == np.sort(picks0, -1)).all(-1)
    assert same.any() and not same.all()
    np.testing.assert_allclose(np.sort(np.asarray(w)[same], -1),
                               np.sort(np.asarray(w0)[same], -1), rtol=1e-6)
    # bfloat16 activations and router weights: the float32 sigmoid's
    # picks and weights of the SAME (rounded) inputs
    ub, rb = u.astype(jnp.bfloat16), moe["router"].astype(jnp.bfloat16)
    picks_b, w_b = nh._route(ub, dict(moe, router=rb), cfg)
    picks_f, w_f = nh._route(ub.astype(F32), dict(moe, router=rb.astype(F32)),
                             cfg)
    np.testing.assert_array_equal(picks_b, picks_f)
    np.testing.assert_allclose(w_b, w_f, rtol=1e-6)
    r_picks, r_w = ref.route(u, moe["router"], moe["router_bias"], top_k=3,
                             scaling=2.5)
    np.testing.assert_array_equal(picks, r_picks)
    np.testing.assert_allclose(w, r_w, rtol=1e-6)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """An EP-2 deployment's two chips hold experts [0, 6) and [6, 12):
    their parts of an expert layer, with the shared expert (which both
    compute alike) counted once, are the uncut reference's whole
    layer."""
    whole_cfg, params = _model()
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (40, whole_cfg.hidden_size))
    valid = jnp.ones((40,), bool)
    parts = []
    for lo, hi in ((0, 6), (6, 12)):
        cfg = _cfg(experts_held=(lo, hi))
        share = dict(moe, experts=jax.tree.map(lambda a: a[lo:hi],
                                               moe["experts"]))
        m, counts = nh.moe_layer(u, share, cfg, valid)
        parts.append(m)
        assert int(counts[:6].sum() + counts[6 + 1]) == 40 * 3
    shared = nh._shared_expert(u, moe["shared"])
    weights = _weights(whole_cfg, params)
    want = ref.expert_layer(u, weights["layers"][1], weights["sizes"])
    assert _close(parts[0] + parts[1] - shared, want, 1e-5)
    assert not _close(parts[0], want, 1e-2)


def test_experts_are_stored_padded_with_zeros_and_the_padding_is_inert():
    """24 wide as published, 128 as stored: the columns of ``w_in`` and
    the rows of ``w_out`` past 24 are zeros, and the layer over the
    stored weights is the layer over the published ones."""
    cfg, params = _model()
    assert (cfg.moe_intermediate_size, cfg.expert_stored_width) == (24, 128)
    full = dataclasses.replace(cfg, moe_intermediate_size=1856)
    assert full.expert_stored_width == 1920
    ex = params["layers"][1]["moe"]["experts"]
    assert ex["w_in"].shape == (12, 32, 128)
    assert ex["w_out"].shape == (12, 128, 32)
    assert float(jnp.abs(ex["w_in"][..., 24:]).max()) == 0
    assert float(jnp.abs(ex["w_out"][:, 24:]).max()) == 0
    assert float(jnp.abs(ex["w_in"][..., :24]).min()) > 0
    # a down projection's rows sum to nothing: positive activations'
    # mean reaches no token as a fixed vector
    assert float(jnp.abs(ex["w_out"][:, :24].sum(1)).max()) < 1e-5
    cut = {"w_in": ex["w_in"][..., :24], "w_out": ex["w_out"][:, :24]}
    gs = jnp.asarray([3, 0, 9, 1, 0, 0, 5, 2, 0, 4, 0, 6], jnp.int32)
    xs, at = _on_tiles(jax.random.normal(jax.random.PRNGKey(0), (30, 32)),
                       gs.tolist())
    np.testing.assert_allclose(
        held_experts._experts(xs, gs, ex, "relu2", tm=16)[at],
        held_experts._experts(xs, gs, cut, "relu2", tm=16)[at], rtol=1e-5,
        atol=1e-6)


def _on_tiles(packed, sizes, tm=16):
    """Rows packed end to end by group, laid out as the grouped matmul
    wants them (every group on a boundary of ``tm``; zeros between), and
    each packed row's place."""
    at, row = [], 0
    for n in sizes:
        at += range(row, row + n)
        row += -(-n // tm) * tm
    at = np.asarray(at)
    return jnp.zeros((row, packed.shape[1]), packed.dtype).at[at].set(
        packed), at


@pytest.mark.parametrize("act", ["swiglu", "relu2"])
def test_both_expert_forms_run_through_the_same_grouped_matmuls(act):
    """``held_experts._experts`` under either form equals the form written
    out an expert at a time; the site counter names the form."""
    X, E, Fe = 3, 64, 48
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    wide = 2 * Fe if act == "swiglu" else Fe
    ex = {"w_in": jax.random.normal(k[0], (X, E, wide)) / 8,
          "w_out": jax.random.normal(k[1], (X, Fe, E)) / 7}
    gs = jnp.asarray([5, 0, 11], jnp.int32)
    packed = jax.random.normal(k[2], (16, E))
    xs, at = _on_tiles(packed, [5, 0, 11])
    reg = MetricRegistry()
    was = set_registry(reg)
    try:
        got = held_experts._experts(xs, gs, ex, act, tm=16)
    finally:
        set_registry(was)
    want, row = [], 0
    for x, n in enumerate([5, 0, 11]):
        u = packed[row:row + n] @ ex["w_in"][x]
        h = (jax.nn.silu(u[:, :Fe]) * u[:, Fe:] if act == "swiglu"
             else jnp.square(jnp.maximum(u, 0)))
        want.append(h @ ex["w_out"][x])
        row += n
    np.testing.assert_allclose(got[at], jnp.concatenate(want), rtol=1e-4,
                               atol=1e-5)
    series = reg.snapshot()["serve_moe_expert_matmul_sites_total"]["series"]
    assert [(s["labels"]["form"], s["labels"]["act"]) for s in series] == [
        ("tiled", act)]


# ----------------------------------------------------------------- the pool

def test_a_cache_for_this_pattern_holds_one_layer_of_rows_and_three_states():
    """``MEMEM*E``: three states, ONE slab of the block pool (the
    attention layer's) and nothing for the three expert layers, which a
    pool over every stateless layer would have given a slab each."""
    cfg = _cfg()
    cache = _pool(cfg, 3)
    assert cache.layer_map == (
        ("state", 0), ("none", 0), ("state", 1), ("none", 1), ("state", 2),
        ("full", 0), ("none", 2))
    assert cache.layer_map == cfg.layer_map
    assert cache.k.shape == (1, 41, BS, 16) == cache.v.shape
    assert [a.shape for a in cache.state] == [(3, 8, 8, 16)] * 3
    assert [a.shape for a in cache.conv] == [(3, 3, 64 + 2 * 4 * 16)] * 3
    assert len(kc.pool_arrays(cache)) == 2 + 6
    without = kc.init_paged_cache(
        cfg.n_layer, 3, 41, BS, 16, cfg.kv_heads, cfg.head_dim, F32,
        state_layers=cfg.state_layers, state_shapes=cfg.state_shapes)
    assert without.k.shape[0] == 4


def test_cacheless_layers_alone_and_the_maps_refusals():
    cache = kc.init_paged_cache(3, 2, 5, BS, 4, 2, 16,
                                cacheless_layers=(False, True, False))
    assert cache.k.shape[0] == 2 and cache.state is None
    assert cache.layer_map == (("full", 0), ("none", 0), ("full", 1))
    assert kc.kind_layer_map(["none", "state", "none", "full"]) == (
        ("none", 0), ("state", 0), ("none", 1), ("full", 0))
    with pytest.raises(ValueError, match="cacheless"):
        kc.init_paged_cache(3, 2, 5, BS, 4, 2, 16,
                            cacheless_layers=(False, True))
    with pytest.raises(ValueError, match="cacheless"):
        kc.init_paged_cache(2, 2, 5, BS, 4, 2, 16,
                            state_layers=(True, False),
                            state_shapes=((2, 4, 4), (3, 16)),
                            cacheless_layers=(True, False))


def test_the_config_refuses_what_it_cannot_be():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        nh.NemotronHConfig(vocab_size=8, hybrid_override_pattern="ME",
                           num_hidden_layers=3)
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        _cfg(hybrid_override_pattern="MXE")
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(8, 20))
    with pytest.raises(ValueError, match="n_groups"):
        _cfg(n_groups=3)
    with pytest.raises(ValueError, match="key/value"):
        _cfg(num_key_value_heads=3)


# ------------------------------------------------------------ the server

def _server(num_slots=3, pool=None, span=256, **knobs):
    cfg, params = _model()
    engine = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=span, block_size=BS,
        num_slots=num_slots, max_queued_requests=32, kv_pool_blocks=pool,
        **knobs))
    return cfg, params, engine


@pytest.mark.parametrize("async_loop", [False, True])
def test_served_through_the_server_with_slots_reused(async_loop):
    """Seven requests of different lengths through three slots, 64 tokens
    each: every slot is reused after a longer or shorter request and every
    served token is the reference's choice. ONE cache holds the states
    beside the attention layer's blocks, the gauge says what the states
    cost, and every block is back at the end."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        cfg, params, engine = _server(pool=30, async_loop=async_loop)
        server = ContinuousBatchingServer(engine)
        cache = server._cache
        assert isinstance(cache, kc.PagedKVCache)
        assert cache.k.shape == (1, 31, BS, 16)
        assert cache.layer_map == cfg.layer_map
        gauge = get_registry().snapshot()["serve_kv_state_bytes"]["series"]
        assert gauge[0]["value"] == 3 * 3 * cfg.state_bytes
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (3, CHUNK, 70, 17, BS, 33, 150)]
        ids = [server.submit(p, max_new_tokens=64, eos_token_id=None)
               for p in prompts]
        while not server.scheduler.idle:
            server.step()
        served = [server.result(i)[len(p):] for i, p in zip(ids, prompts)]
        alloc = server.scheduler.allocator
        assert alloc.live_blocks == 0 and alloc.free_blocks == 30
        server.close()
    finally:
        set_registry(prev)
    weights = _weights(cfg, params)
    for p, out in zip(prompts, served):
        row = np.asarray(ref.logits(weights, [p + out[:-1]])[0])
        at = row[len(p) - 1:]
        top = at.max(-1)
        assert (top - at[np.arange(len(out)), out]
                <= 1e-4 * np.abs(top)).all()


@pytest.mark.parametrize("switch,value", [
    ("kv_cache_dtype", "int8"),
    ("enable_prefix_caching", True),
    ("prefill_chunk_tokens", BS),
    ("speculation_tokens", 4),
])
def test_server_switches_a_state_cannot_honour_are_refused(switch, value):
    _, _, engine = _server(**{switch: value})
    with pytest.raises(NotImplementedError, match=switch) as e:
        ContinuousBatchingServer(engine)
    assert "state layers" in str(e.value)


# ------------------------------------------------ the benchmark's new cell

CELL = "serve-nemotron3-nano-ep2-reasoning-batch"
CONFIG = "nemotron3-nano-30b-ep2-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_file_states_the_published_sizes_once():
    """The top level holds the published keys whole (the reduced ones at
    their reduced values); the ``model`` block is what runs: the first
    13 layers, the router's 128 outputs, 64 experts and half the
    vocabulary held; the pool holds 2 layers' rows, not 7."""
    conf = harness.load_json(os.path.join(BENCH, "configs",
                                          CONFIG + ".json"))
    contract = harness.load_contract()
    entry = next(c for c in contract["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert set(conf["reduced"]) == set(entry["reduced"])
    assert entry["source"] == conf["source"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (13, 64, 65536)
    model = conf["model"]
    assert model["n_routed_experts"] == 128
    assert model["experts_held"] == [0, 64]
    assert model["hybrid_override_pattern"] == "MEMEM*EMEMEM*"
    assert conf["hybrid_override_pattern"].startswith(
        model["hybrid_override_pattern"])
    assert len(conf["hybrid_override_pattern"]) == 52
    assert model["state_dtype"] == "float32"
    same = {"mamba_num_heads": "mamba_num_heads",
            "mamba_head_dim": "mamba_head_dim",
            "ssm_state_size": "ssm_state_size", "n_groups": "n_groups",
            "conv_kernel": "conv_kernel", "chunk_size": "chunk_size",
            "moe_intermediate_size": "moe_intermediate_size",
            "moe_shared_expert_intermediate_size":
                "moe_shared_expert_intermediate_size",
            "num_experts_per_tok": "num_experts_per_tok",
            "n_group": "n_group", "topk_group": "topk_group",
            "routed_scaling_factor": "routed_scaling_factor",
            "layer_norm_epsilon": "layer_norm_epsilon",
            "hidden_size": "hidden_size", "head_dim": "head_dim",
            "num_attention_heads": "num_attention_heads",
            "num_key_value_heads": "num_key_value_heads",
            "max_position_embeddings": "max_position_embeddings"}
    for key, published in same.items():
        assert model[key] == conf[published], key
    for key in ("source", "deployment", "reduced", "not_served", "assumed",
                "engine", "why"):
        assert conf[key], key
    assert set(conf["not_served"]) == {"layers", "experts", "vocabulary"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
        assert row["source_url"] == conf["source"] == entry["source"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert conf[key] == value, key
    cfg = _config_of(model)
    assert cfg.state_bytes == 64 * 64 * 128 * 4 + 3 * 6144 * 2
    assert cfg.state_shapes == ((64, 64, 128), (3, 6144))
    assert (sum(cfg.state_layers), sum(cfg.cacheless_layers)) == (6, 5)
    assert [k for k, _ in cfg.layer_map].count("full") == 2
    assert cfg.expert_stored_width == 1920
    assert cfg.d_inner == 4096
    assert abs(cfg.attn_scale - 128 ** -0.5) < 1e-12
    eng = conf["engine"]
    pool = (2 * eng["kv_pool_blocks"] * eng["block_size"]
            * 2 * 2 * 128 * 2)
    assert 3.4e9 < pool < 3.6e9
    assert eng["max_out_tokens"] // eng["block_size"] == 80


def test_the_cell_reports_its_own_metrics_and_not_the_three_matrix_roofline():
    contract = harness.load_contract()
    cell = harness.resolve_cell(contract, CELL)
    mine = set(cell["per_layer"])
    assert "moe_experts_roofline" not in mine
    assert "mamba_state_update_roofline" not in mine   # pinned to Granite
    # no share of the held experts' roofline yet: the routing counters are
    # the whole process's and the device time the traced window's
    assert "nemotron_experts_roofline" not in mine
    assert {"nemotron_state_update_roofline", "nemotron_decode_mamba_ms",
            "nemotron_decode_attn_ms", "nemotron_decode_moe_ms", "nemotron_state_gb_per_step",
            "nemotron_kv_gb_per_step", "decode_program_ms",
            "moe_tokens_per_held_expert"} <= mine
    assert cell["end_to_end"] == ["serve_out_tokens_per_s", "setup_s"]
    for m in contract["per_layer"]:
        if m["name"].startswith("nemotron_"):
            assert m["workloads"] == [CELL]
            assert m["moves"] == "serve_out_tokens_per_s"


def test_the_cell_runs_at_a_tiny_size_through_the_harness():
    """The harness's own runner, the real readers and family, the tiny
    twins of the configuration and the traffic: the backlog stays full,
    nothing compiles in the window, the served tokens pass the check, and
    the counter-based metrics read what the program counted."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        contract = harness.load_contract()
        cell = harness.resolve_cell(contract, CELL)
        twin = {k: harness.load_json(os.path.join(
            BENCH, "testdata", d, name + ".json"))
            for k, d, name in (("config", "configs", "tiny-nemotron-serve"),
                               ("traffic", "traffic",
                                "tiny-nemotron-reasoning-decode-batch"))}
        assert twin["config"]["twin_of"] == cell["cell"]["config"]
        assert twin["traffic"]["twin_of"] == cell["cell"]["traffic"]
        cell.update(twin)
        args = argparse.Namespace(seed=2 ** 31 + 11, seconds=1.0, trace=0)
        run, _ = harness.run_cell(cell, args, time.time(),
                                  jax.devices()[:1], "TPU v5 lite")
        assert all(run["checks"].values()), run["checks"]
        assert run["failed"] == 0 and run["compiles_in_window"] == 0
        assert run["reference_check"]["max_gap"] <= 1e-3
        assert run["reference_check"]["tokens"] >= 60
        metrics = harness.read_metrics(
            cell["end_to_end"] + cell["per_layer"], run, None,
            harness.units_of(contract), cell["root"])
        assert set(cell["end_to_end"]) <= set(metrics)
        state_gb = metrics["nemotron_state_gb_per_step"]["value"]
        slots = twin["config"]["engine"]["num_slots"]
        cfg = _config_of(twin["config"]["model"])
        most = 2 * slots * sum(cfg.state_layers) * cfg.state_bytes / 1e9
        assert 0 < state_gb <= most * (1 + 1e-6)
        assert metrics["nemotron_kv_gb_per_step"]["value"] > 0
        assert 0 <= metrics["serve_refill_share_pct"]["value"] <= 100
        held = metrics["moe_tokens_per_held_expert"]["value"]
        assert 0 < held <= slots * 3
        assert run["shapes"]["layers"] == 2            # expert layers
        assert run["shapes"]["state_layers"] == 2
    finally:
        set_registry(prev)
