"""MiMo-V2 at a small size on the CPU: the program against the plain
float32 reference (prefill at lengths around a block's edge, the
window's edge, the ring's wrap and inside a bucket's padding, then more
than nine laps of the ring decoded through ONE cache whose pool rows
carry 2 key/value heads and whose ring rows 4, keys 24 and values 16
wide), the sink present, absent and zero, the irregular first group of
the layer pattern, the 32 shares of 8 experts adding up to the uncut
layer, the allocator over a pool smaller than slots x span on both
loops, the refusals by name, the configuration file against the catalog
row, and the cell at a tiny size through the harness.
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

from benchmark.lib import flops_mimo, harness  # noqa: E402
from benchmark.lib import reference_mimo as ref  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference import kv_cache as kc  # noqa: E402
from deepspeed_tpu.model_implementations import held_experts  # noqa: E402
from deepspeed_tpu.model_implementations import mimo_v2 as mm  # noqa: E402
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

BENCH = os.path.join(REPO, "benchmark")
F32 = jnp.float32
# the published geometry in small: the window is ONE block, so a ring is
# two (the window and a block of slack)
BS = WINDOW = 16
RING = kc.ring_blocks_for(WINDOW, BS) * BS          # 32 rows
PATTERN = (0, 1, 1, 1, 1, 0)        # the irregular first group
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _load_family():
    return harness.load_family("mimo_v2")


def _cfg(**over):
    """8 query heads over 2 (full) / 4 (window) key/value heads, keys 24
    and values 16 wide (rotary over int(0.334 x 24) = 8 dims), window 16
    (a ring of 32 rows), 16 experts top-2, layers ``full, w, w, w, w,
    full`` behind a dense layer 0."""
    base = dict(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        num_hidden_layers=6, hybrid_layer_pattern=PATTERN,
        moe_layer_freq=(0, 1, 1, 1, 1, 1), num_attention_heads=8,
        num_key_value_heads=2, swa_num_attention_heads=8,
        swa_num_key_value_heads=4, head_dim=24, v_head_dim=16,
        swa_head_dim=24, swa_v_head_dim=16, sliding_window=WINDOW,
        moe_intermediate_size=32, n_routed_experts=16,
        num_experts_per_tok=2, max_position_embeddings=4096,
        experts_held=(0, 16), dtype=F32)
    return mm.MiMoV2Config(**{**base, **over})


@functools.lru_cache(maxsize=None)
def _model(**over):
    cfg = _cfg(**over)
    return cfg, mm.init_params(jax.random.PRNGKey(5), cfg)


def _weights(cfg, params):
    return _load_family().reference_from_serve(cfg, params)


def _pool(cfg, slots, blocks=40, span_blocks=16):
    return kc.init_paged_cache(
        cfg.n_layer, slots, 1 + blocks, BS, span_blocks, cfg.kv_heads,
        cfg.head_dim, F32, window_layers=cfg.window_layers,
        window=cfg.sliding_window, aux_shape=cfg.aux_shape,
        v_head_dim=cfg.v_head_dim, ring_kv_heads=cfg.ring_kv_heads)


@functools.lru_cache(maxsize=None)
def _programs(cfg):
    return (jax.jit(functools.partial(mm.paged_prefill, cfg=cfg)),
            jax.jit(functools.partial(mm.paged_decode_step, cfg=cfg)))


def _teacher_forced(cfg, params, ids, prompt, slots=3, slot=1):
    """Logits of every position from ``prompt - 1`` on: the prompt
    through ``paged_prefill`` into ``slot``, the rest a token a step
    through ``paged_decode_step`` (the other slots idle)."""
    T = len(ids)
    bucket = max(BS, 1 << (prompt - 1).bit_length())
    need = -(-(T + 1) // BS)
    cache = _pool(cfg, slots, blocks=need + 2, span_blocks=need + 1)
    tables = np.zeros(cache.block_tables.shape, np.int32)
    tables[slot, :need] = 1 + np.arange(need)
    cache = cache.replace(block_tables=jnp.asarray(tables))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt] = ids[:prompt]
    prefill, decode = _programs(cfg)
    logits, cache = prefill(params, input_ids=jnp.asarray(padded),
                            length=jnp.array([prompt]), cache=cache,
                            slot=jnp.int32(slot))
    out = [logits[0]]
    active = jnp.arange(slots) == slot
    for t in range(prompt, T):
        tokens = jnp.zeros((slots,), jnp.int32).at[slot].set(int(ids[t]))
        logits, cache = decode(params, tokens=tokens, cache=cache,
                               active=active)
        out.append(logits[slot])
    return jnp.stack(out), cache


def _close(got, want, tol=1e-4):
    assert float(jnp.abs(got - want).max()) <= tol * float(
        jnp.abs(want).max())


# ------------------------------------------- the program vs the reference

@pytest.mark.parametrize("prompt", [
    1, 2, WINDOW - 1, WINDOW, WINDOW + 1, 21, RING - 1, RING, RING + 1],
    ids=["one", "two", "window-1", "window=block-edge", "window+1",
         "inside-padding", "ring-1", "ring", "ring+1"])
def test_float32_program_matches_the_reference_through_ring_laps(prompt):
    """Prefill at a length on each side of a block's edge (which is the
    window's), of the ring's wrap and inside a bucket's padding, then
    300 tokens (more than nine laps of the 32-row ring) decoded through
    the pool and the rings: every logit within 1e-4 of the masked
    float32 reference (both head counts, both rotary bases, keys wider
    than values, the value scale, the sink, dense layer 0 and the
    expert layer without a shared expert are in it)."""
    cfg, params = _model()
    T = prompt + 300
    ids = np.random.default_rng(prompt).integers(0, cfg.vocab_size, T)
    got, cache = _teacher_forced(cfg, params, ids, prompt)
    want = ref.logits(_weights(cfg, params), ids[None])[0, prompt - 1:]
    _close(got, want)
    # four row widths in ONE cache: 2 heads in the pool, 4 in the rings,
    # keys 24 and values 16 wide; the ring never grew
    assert cache.k.shape[2:] == (BS, 2 * 24) and cache.v.shape[2:] == (
        BS, 2 * 16)
    assert cache.ring_k.shape == (4, 3 * RING // BS, BS, 4 * 24)
    assert cache.ring_v.shape == (4, 3 * RING // BS, BS, 4 * 16)
    assert (cache.num_kv_heads, cache.ring_heads, cache.head_dim,
            cache.v_head_dim) == (2, 4, 24, 16)
    # the counters say what a step had to read
    aux = np.asarray(cache.aux)
    steps = T - prompt
    seen = np.arange(prompt + 1, T + 1)
    assert aux[0, -2] == 2 * seen.sum()                       # full rows
    assert aux[0, -1] == 4 * np.minimum(seen, WINDOW).sum()   # window rows
    tail = aux[:, 16:16 + len(held_experts.COUNTER_TAIL)]
    # one token's two picks are under a row tile: ``ragged_dot`` walks none
    assert tail[0].tolist() == [0, 0, 5 * steps, 5 * steps, tail[0, 4],
                                0]
    assert tail[1, 2] == 5 * prompt and aux[:, :16].sum() == 2 * (
        5 * steps + 5 * prompt)


@pytest.mark.parametrize("sink", ["absent", "zero", "large"])
def test_the_sink_joins_the_denominator_and_carries_no_value(sink):
    """A model without sinks, with sinks of 0 (each takes exp(0) of the
    denominator: NOT the same as none) and with sinks that take nearly
    everything, each against the reference through prefill and a ring
    lap; and the three are different models."""
    if sink == "absent":
        cfg, params = _model(add_swa_attention_sink_bias=False)
        assert not any("sink" in layer for layer in params["layers"])
    else:
        cfg, params = _model()
        assert [("sink" in layer) for layer in params["layers"]] == [
            bool(p) for p in PATTERN]
        params = dict(params, layers=[
            dict(layer, sink=jnp.full_like(layer["sink"],
                                           0.0 if sink == "zero" else 9.0))
            if "sink" in layer else layer for layer in params["layers"]])
    prompt, T = 19, 19 + RING + 7
    ids = np.random.default_rng(7).integers(0, cfg.vocab_size, T)
    got, _ = _teacher_forced(cfg, params, ids, prompt)
    want = ref.logits(_weights(cfg, params), ids[None])[0, prompt - 1:]
    _close(got, want)
    other_cfg, other = _model(add_swa_attention_sink_bias=(sink == "absent"))
    moved = ref.logits(_weights(other_cfg, other), ids[None])[0, prompt - 1:]
    assert float(jnp.abs(moved - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


def test_causal_forward_over_the_irregular_first_group_and_a_period():
    """Twelve layers, the published pattern's first group ``0,1,1,1,1,0``
    and one whole period ``1,1,1,1,1,0``: full layers at 0, 5 and 11.
    ``causal_forward`` (what prefill runs) against the reference, with a
    right-padding mask."""
    pattern = (0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0)
    cfg, params = _model(num_hidden_layers=12, hybrid_layer_pattern=pattern,
                         moe_layer_freq=(0,) + (1,) * 11)
    assert [k for k, _ in kc.window_layer_map(cfg.window_layers)].count(
        "full") == 3
    assert [li for li, w in enumerate(cfg.window_layers) if not w] == [
        0, 5, 11]
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 70))
    got = mm.causal_forward(params, cfg, jnp.asarray(ids))
    want = ref.logits(_weights(cfg, params), ids)
    _close(got, want)


@pytest.mark.parametrize("what,edit", [
    ("window-a-row-short", dict(sliding_window=WINDOW - 1)),
    ("window-a-row-long", dict(sliding_window=WINDOW + 1)),
    ("value-scale-dropped", dict(attention_value_scale=1.0)),
    ("bases-swapped", dict(rope_theta=10000.0, swa_rope_theta=5000000.0)),
    ("full-rotation", dict(partial_rotary_factor=1.0)),
    ("unnormalised-top-k", dict(norm_topk_prob=False)),
])
def test_what_the_chip_check_may_not_part_is_held_here(what, edit):
    """Departures whose effect the chip's largest-gap statistic may not
    part from bfloat16 rounding move the float32 logits by far more than
    the 1e-4 the program is held to."""
    cfg, params = _model()
    off = dataclasses.replace(cfg, **edit)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 90))
    want = ref.logits(_weights(cfg, params), ids)
    got = mm.causal_forward(params, off, jnp.asarray(ids))
    assert float(jnp.abs(got - want).max()) > 1e-3 * float(
        jnp.abs(want).max())


def test_query_groups_read_their_own_kv_head_by_kind():
    """A full layer's 8 query heads read 2 key/value heads in groups of
    4, a window layer's read 4 in groups of 2: swapping the kinds' head
    counts is another model (and another parameter tree)."""
    cfg, params = _model()
    full, window = params["layers"][0], params["layers"][1]
    assert full["wk"].shape == (64, 2, 24) and full["wv"].shape == (64, 2, 16)
    assert window["wk"].shape == (64, 4, 24) and window["wv"].shape == (
        64, 4, 16)
    assert full["wq"].shape == window["wq"].shape == (64, 8, 24)
    assert full["wo"].shape == window["wo"].shape == (8, 16, 64)
    # rotary: the first int(0.334 x 24) = 8 dims turn, 16 pass
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 24), F32)
    y = mm._rope(x, jnp.array([0, 5, 900]), cfg.rope("window"))
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(y[0], x[0], rtol=1e-6)
    ang = 900 * 10000.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(
        y[2, 0, :4], x[2, 0, :4] * np.cos(ang) - x[2, 0, 4:8] * np.sin(ang),
        atol=2e-4)
    assert cfg.rope("full").rope_theta == 5000000.0
    # the published sizes: r = int(0.334 x 192) = 64
    assert mm.rope_table(mm.MiMoV2Config(
        vocab_size=8, hybrid_layer_pattern=(0,) * 48,
        moe_layer_freq=(0,) * 48).rope("full"), 192)[0].shape == (32,)


# ---------------------------------------------------------- expert layer

def test_routing_is_sigmoid_normalised_and_the_bias_only_selects():
    cfg, params = _model()
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.hidden_size), F32)
    picks, w = mm._route(u, moe, cfg)
    s = jax.nn.sigmoid(u @ moe["router"])
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)   # no factor
    picked = jnp.take_along_axis(s, picks, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    pushed = dict(moe, router_bias=moe["router_bias"].at[3].add(10.0))
    picks2, w2 = mm._route(u, pushed, cfg)
    assert bool((picks2 == 3).any(-1).all())
    picked2 = jnp.take_along_axis(s, picks2, -1)
    np.testing.assert_allclose(w2, picked2 / picked2.sum(-1, keepdims=True),
                               rtol=1e-5)
    # routing is float32 whatever the activations' type: with bfloat16
    # activations and router weights the scores are the float32 sigmoid
    # of THOSE values (a bfloat16 product rounds the scores to three
    # digits and moves picks and weights: the chip's largest-gap check
    # cannot part that from a clean run, PERF.md section 7; this does)
    ub, rb = u.astype(jnp.bfloat16), moe["router"].astype(jnp.bfloat16)
    picks_b, w_b = mm._route(ub, dict(moe, router=rb), cfg)
    s_b = jax.nn.sigmoid(jnp.dot(ub.astype(F32), rb.astype(F32),
                                 precision=jax.lax.Precision.HIGHEST))
    want_picks = jax.lax.top_k(s_b + moe["router_bias"], 2)[1]
    np.testing.assert_array_equal(picks_b, want_picks)
    picked_b = jnp.take_along_axis(s_b, want_picks, -1)
    np.testing.assert_allclose(
        w_b, picked_b / picked_b.sum(-1, keepdims=True), rtol=1e-6)
    rough = jax.nn.sigmoid(jnp.dot(ub, rb).astype(F32))
    assert float(jnp.abs(rough - s_b).max()) > 1e-4
    # the seeded bias: alike in every share of 8, centred, not from the seed
    b = np.asarray(mm.router_bias(_cfg(n_routed_experts=256,
                                       experts_held=(0, 8)))).reshape(32, 8)
    assert (b == b[0]).all() and abs(b[0].sum()) < 1e-6
    assert len(set(np.round(b[0], 6))) == 8
    # the held share's matmul rows at the cell's sizes
    published = _cfg(n_routed_experts=256, num_experts_per_tok=8,
                     experts_held=(0, 8))
    assert mm._expert_rows(96, published) == 128
    assert mm._expert_rows(4096, published) == 1536


def test_the_32_shares_of_8_experts_add_up_to_the_uncut_layer():
    """The 32 shares' routed parts, plus what every chip computes alike
    (attention) counted once, are the uncut reference's layer: there is
    no shared part to count once."""
    whole_cfg, params = _model(n_routed_experts=256, num_experts_per_tok=8,
                               experts_held=(0, 256))
    layer = params["layers"][2]
    weights = _weights(whole_cfg, params)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, whole_cfg.hidden_size),
                          F32)
    rl, z = weights["layers"][2], weights["sizes"]
    want = ref.layer_forward(x, rl, z)
    q, k, v = ref._project(x, rl["g_in"], rl["w_q"], rl["w_k"], rl["w_v"],
                           theta=rl["theta"], r=z["rotary_dim"],
                           scale=z["value_scale"], eps=z["eps"])
    h = ref._attn_out(x, ref._attention(q, k, v, rl["sink"],
                                        window=z["window"], block=40),
                      rl["w_o"])
    u = ref._norm(h, rl["g_post"], eps=z["eps"])
    valid = jnp.ones((40,), bool)
    total, hit = h, 0
    for share in range(32):
        held = (8 * share, 8 * share + 8)
        cfg = dataclasses.replace(whole_cfg, experts_held=held)
        moe = dict(layer["moe"], experts=jax.tree.map(
            lambda w: w[held[0]:held[1]], layer["moe"]["experts"]))
        m, counts = mm.moe_layer(u, moe, cfg, valid)
        total = total + m
        hit += int(counts[:8].sum())
        assert int(counts[8 + 1]) == 40 * 8 - int(counts[:8].sum())  # absent
    assert hit == 40 * 8                      # every pick landed once
    _close(total, want)


# ------------------------------------------------ allocator and scheduler

def _server(num_slots=3, pool=None, span=512, **knobs):
    cfg, params = _model()
    engine = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=span, block_size=BS,
        num_slots=num_slots, max_queued_requests=32, kv_pool_blocks=pool,
        **knobs))
    return cfg, params, engine


def _serve(server, prompts, n_out, watch=None):
    ids = [server.submit(p, max_new_tokens=n_out, eos_token_id=None)
           for p in prompts]
    while not server.scheduler.idle:
        server.step()
        if watch is not None:
            watch(server)
    return [server.result(i)[len(p):] for i, p in zip(ids, prompts)]


def _held_to_reference(cfg, params, prompts, served):
    weights = _weights(cfg, params)
    for p, out in zip(prompts, served):
        row = np.asarray(ref.logits(weights, [p + out[:-1]])[0])
        at = row[len(p) - 1:]
        top = at.max(-1)
        assert (top - at[np.arange(len(out)), out]
                <= 1e-4 * np.abs(top)).all()


@pytest.mark.parametrize("async_loop", [False, True])
def test_served_through_the_server_over_a_pool_smaller_than_slots_x_span(
        async_loop):
    """Seven requests through three slots over a pool of 40 blocks
    (slots x span would be 96): prompts on each side of the window and
    the ring, 100 tokens each (three laps of the ring), slots reused;
    every served token is the reference's choice. The pool and the rings
    are what the model's head counts and widths make them, every block
    comes back, and the gauges count the real bytes."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        cfg, params, engine = _server(pool=40, async_loop=async_loop)
        server = ContinuousBatchingServer(engine)
        cache = server._cache
        assert cache.k.shape == (2, 41, BS, 2 * 24)        # 2 full layers
        assert cache.v.shape == (2, 41, BS, 2 * 16)
        assert cache.ring_k.shape == (4, 3 * RING // BS, BS, 4 * 24)
        assert cache.ring_v.shape == (4, 3 * RING // BS, BS, 4 * 16)
        assert cache.layer_map == (
            ("full", 0), ("window", 0), ("window", 1), ("window", 2),
            ("window", 3), ("full", 1))
        assert sum(a.nbytes for a in kc.pool_arrays(cache)) == 4 * (
            2 * 41 * BS * 2 * (24 + 16) + 4 * 3 * RING * 4 * (24 + 16))
        snap = get_registry().snapshot()
        assert snap["serve_kv_ring_bytes"]["series"][0]["value"] == (
            4 * 3 * RING * 4 * (24 + 16) * 4)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
                   for n in (1, WINDOW, 17, RING - 1, RING, 45, 90)]
        waited = []
        sched = server.scheduler

        def watch(server):
            if sched.waits_on_blocks(server._tick):
                waited.append(len(sched.slots))
        served = _serve(server, prompts, 100, watch)
        alloc = sched.allocator
        assert alloc.live_blocks == 0 and alloc.free_blocks == 40
        assert server._cache.ring_k.shape == cache.ring_k.shape
        server.close()
        _held_to_reference(cfg, params, prompts, served)
    finally:
        set_registry(prev)


def test_admission_waits_on_blocks_with_a_slot_free():
    """A pool of 12 blocks for 3 slots: two requests of 5 blocks fit,
    the third waits with a slot free (admission counts the FULL layers'
    blocks only: a ring takes none)."""
    cfg, params, engine = _server(pool=12)
    server = ContinuousBatchingServer(engine)
    sched = server.scheduler
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, 40).tolist()
               for _ in range(4)]
    waited = []

    def watch(server):
        if sched.waits_on_blocks(server._tick):
            waited.append((len(sched.slots), sched.pending_requests,
                           sched.allocator.free_blocks))
    served = _serve(server, prompts, 30, watch)           # 70 -> 5 blocks
    assert waited and all(live == 2 and queued >= 1 and free < 5
                          for live, queued, free in waited)
    server.close()
    _held_to_reference(cfg, params, prompts, served)


def _scopes_and_kernels(jaxpr, scopes, kernels):
    for eqn in jaxpr.eqns:
        scopes.add(str(eqn.source_info.name_stack))
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scopes_and_kernels(sub, scopes, kernels)


def test_on_the_tpu_path_each_layer_is_one_kernel_call_of_its_kind(
        monkeypatch):
    """Neither kind of layer carries the ``kv_read`` scope: a window
    layer reads its ring through the kernel (with its sink), a full
    layer its blocks, one call a layer; no shared expert's scope."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = _model()
    traced = jax.make_jaxpr(functools.partial(
        mm.paged_decode_step, cfg=cfg))(
            params, tokens=jnp.zeros((2,), jnp.int32), cache=_pool(cfg, 2),
            active=jnp.ones((2,), bool))
    scopes, kernels = set(), []
    _scopes_and_kernels(traced.jaxpr, scopes, kernels)
    attention = [k for k in kernels if "attention" in k]
    assert sorted(attention) == ["paged_decode_attention"] * 2 + [
        "paged_window_decode_attention"] * 4
    assert not any("kv_read" in s or "moe_shared" in s for s in scopes)
    for name in ("attn_full", "attn_window/kv_write", "moe_router",
                 "moe_experts", "dense_ffn", "lm_head"):
        assert any(name in s for s in scopes), name


# --------------------------------------------------------------- refusals

@pytest.mark.parametrize("switch,value", [
    ("kv_cache_dtype", "int8"),
    ("enable_prefix_caching", True),
    ("prefill_chunk_tokens", BS),
    ("speculation_tokens", 4),
])
def test_server_switches_a_ring_cannot_honour_are_refused(switch, value):
    _, _, engine = _server(**{switch: value})
    with pytest.raises(NotImplementedError, match=switch) as e:
        ContinuousBatchingServer(engine)
    assert "window layers" in str(e.value)


@pytest.mark.parametrize("name,knobs,kwargs", [
    ("kv_host_offload", dict(kv_host_offload=True,
                             enable_prefix_caching=True), {}),
    ("handoff_import", {}, dict(handoff_import=True)),
    ("draft_engine", {}, dict(draft_engine="a draft")),
])
def test_tier_handoff_and_draft_are_refused_by_name(name, knobs, kwargs):
    _, _, engine = _server(**knobs)
    with pytest.raises(NotImplementedError, match=name):
        ContinuousBatchingServer(engine, **kwargs)


@pytest.mark.parametrize("switch,conf", [
    ("int8", dict(dtype="int8")),
    ("tp_size", dict(tensor_parallel={"tp_size": 2})),
])
def test_engine_switches_are_refused_by_name(switch, conf):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=switch):
        InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
            **{"max_out_tokens": 64, **conf}))


def test_the_config_checks_what_one_cache_cannot_hold():
    with pytest.raises(ValueError, match="hybrid_layer_pattern"):
        _cfg(hybrid_layer_pattern=PATTERN[:5])
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(8, 20))
    with pytest.raises(NotImplementedError, match="swa_head_dim"):
        _cfg(swa_head_dim=32)
    with pytest.raises(NotImplementedError,
                       match="add_full_attention_sink_bias"):
        _cfg(add_full_attention_sink_bias=True)
    with pytest.raises(ValueError, match="group"):
        _cfg(swa_num_key_value_heads=3)


# ------------------------------------------------ the benchmark's new cell

CELL = "serve-mimo-v2-flash-ep32-reasoning-batch"
CONFIG = "mimo-v2-flash-ep32-serve"
REDUCED = {"num_hidden_layers": 12, "n_routed_experts": 8,
           "vocab_size": 19072}


def test_configuration_file_holds_the_catalog_row_key_by_key():
    """Every key of the catalog row's ``config`` is in the file under
    the same name with the same value, but for the three in ``reduced``;
    the ``model`` block is what runs: the first twelve entries of both
    per-layer lists, the router's 256 outputs, the held experts and
    vocabulary rows."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "MiMo-V2-Flash")
    contract = harness.load_contract()
    entry = harness.find(contract["configs"], CONFIG, "config")
    conf = harness.load_json(os.path.join(REPO, entry["file"]))
    assert entry["source"] == conf["source"] == row["source_url"]
    assert entry["reduced"] == list(conf["reduced"]) == list(REDUCED)
    for key, value in row["config"].items():
        assert conf[key] == REDUCED.get(key, value), key
    model = conf["model"]
    assert (model["num_hidden_layers"], model["n_routed_experts"],
            model["experts_held"], model["vocab_size"]) == (
                12, 256, [0, 8], 19072)
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "swa_num_key_value_heads",
                "head_dim", "v_head_dim", "sliding_window", "rope_theta",
                "swa_rope_theta", "partial_rotary_factor",
                "attention_value_scale", "moe_intermediate_size",
                "num_experts_per_tok", "layernorm_epsilon",
                "add_swa_attention_sink_bias"):
        assert model[key] == row["config"][key], key
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        assert len(conf[key]) == 48 and model[key] == conf[key][:12], key
    assert model["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0,
                                             1, 1, 1, 1, 1, 0]
    for key in ("rotation", "value_scale", "sink", "router", "activation",
                "attention_chunk_size", "final_norm", "seeded_init"):
        assert key in conf["assumed"], key
    assert "multi_token_prediction" in conf["not_served"]
    for key in ("deployment", "why"):
        assert conf[key]


def test_the_cell_is_the_traffic_the_issue_names():
    contract = harness.load_contract()
    cell = harness.resolve_cell(contract, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["config"]["engine"] == {
        "dtype": "bfloat16", "max_out_tokens": 12288, "block_size": 128,
        "num_slots": 96, "kv_pool_blocks": 5400, "max_queued_requests": 512}
    assert set(cell["end_to_end"]) == {"serve_out_tokens_per_s", "setup_s"}
    new = [m for m in contract["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in new} == {
        "mimo_decode_full_attn_ms", "mimo_decode_window_attn_ms",
        "mimo_decode_moe_ms", "mimo_full_decode_roofline",
        "mimo_window_decode_roofline", "mimo_kv_gb_per_step"}
    listed = {m["name"] for m in contract["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert {"serve_goodput_pct", "serve_pipelined_steps_pct",
            "trace_lower_s", "compile_cache_misses", "decode_program_ms",
            "decode_dispatch_gap_ms", "batch_device_idle_pct",
            "batch_peak_hbm_gb", "moe_tokens_per_held_expert",
            "moe_held_load_max_over_mean", "compile_s",
            "compiles_in_window"} <= listed
    assert sum(w["chips"] == 4 for w in contract["workloads"]) == 1
    assert len(contract["workloads"]) >= 10     # PR 58 added the eleventh
    traffic = cell["traffic"]
    assert (traffic["kind"], traffic["requests"],
            traffic["max_total_tokens"]) == ("backlog", 256, 12288)
    assert traffic["prompt_len"] == {"dist": "loguniform", "lo": 256,
                                     "hi": 4096}
    assert traffic["output_len"] == {"dist": "loguniform", "lo": 2048,
                                     "hi": 8192}
    assert (traffic["order"], traffic["stratify_block"]) == ("rotation", 8)
    assert traffic["check"]["output_tokens"] >= 320
    # the deployment's arithmetic: a ring, a row of each kind
    assert kc.ring_blocks_for(128, 128) * 128 == 256
    assert flops_mimo.row_bytes(4, 192, 128) == 2560
    assert flops_mimo.row_bytes(8, 192, 128) == 5120
    shapes = _load_family().shapes(cell["config"]["model"])
    assert (shapes["layers"], shapes["full_layers"], shapes["window_layers"],
            shapes["expert_ffn"], shapes["full_kv_heads"],
            shapes["window_kv_heads"]) == (11, 3, 9, 2048, 4, 8)


def test_the_cells_order_is_the_ring_that_scatters_least_under_its_model():
    """``benchmark/tools/backlog_order.py`` replays the backlog in steps
    under the traffic file's fitted ``order_model`` (a pipelined step's
    cost by live tokens, an admitting step's by prefill bucket): it
    orders a lap as the generator does, and the committed ring's 256
    rotations scatter less in tokens/s than Laguna's ring and than
    seeded permutations of the same multiset (what ``order_seed`` was
    chosen for; PERF.md section 6, PR 53)."""
    from benchmark.lib import traffic as traffic_lib
    from benchmark.tools import backlog_order
    tr = harness.load_json(os.path.join(
        BENCH, "traffic", "mimo-reasoning-decode-batch.json"))
    seed = 2 ** 31 + 5
    made = traffic_lib.build_requests(tr, 50.0, seed, 2)["requests"]
    lap = backlog_order.laps(tr, seed, 1)
    assert [(len(r["prompt"]), r["out"]) for r in made] == [
        tuple(int(x) for x in pair) for pair in lap]
    assert min(r["out"] for r in made) >= 2048      # nobody ends early
    model = tr["order_model"]
    assert set(model["admission_ms"]) == {"512", "1024", "2048", "4096"}
    ring = backlog_order.scatter(tr, model, 96, 50.0)
    lagunas = backlog_order.scatter(dict(tr, order_seed=796), model, 96,
                                    50.0)
    perm = backlog_order.scatter(dict(tr, order="permutation"), model, 96,
                                 50.0, seeds=64)
    assert ring < 0.22 < perm < lagunas, (ring, perm, lagunas)


def test_the_cell_runs_at_a_tiny_size_through_the_harness():
    """The harness's own runner, the real readers and family, the tiny
    twins of the configuration and the traffic: the backlog stays full
    over a pool smaller than slots x span, nothing compiles in the
    window, the served tokens pass the check, and the counter-based
    metrics read what the program counted at the real row bytes."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        contract = harness.load_contract()
        cell = harness.resolve_cell(contract, CELL)
        twin = {k: harness.load_json(os.path.join(
            BENCH, "testdata", d, name + ".json"))
            for k, d, name in (("config", "configs", "tiny-mimo-serve"),
                               ("traffic", "traffic",
                                "tiny-mimo-reasoning-decode-batch"))}
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
        assert metrics["mimo_kv_gb_per_step"]["value"] > 0
        held = metrics["moe_tokens_per_held_expert"]["value"]
        assert 0 < held <= 4 * 2           # 4 slots x top-2 over the share
    finally:
        set_registry(prev)
