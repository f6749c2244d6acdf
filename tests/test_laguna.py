"""Laguna at a small size on the CPU: the program against the plain
float32 reference (prefill at lengths around the window and the ring,
then ring laps of decode), the windowed kernels in interpret mode against
their ``jax.numpy`` forms, the rotary tables against a direct formula,
the eight shares adding up to the uncut layer, the allocator with a ring
beside a pool smaller than slots x span, and the refusals by name.
"""
import argparse
import dataclasses
import functools
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import flops_laguna, harness  # noqa: E402
from benchmark.lib import reference_laguna as ref  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference import kv_cache as kc  # noqa: E402
from deepspeed_tpu.model_implementations import held_experts  # noqa: E402
from deepspeed_tpu.model_implementations import laguna as lg  # noqa: E402
from deepspeed_tpu.ops.pallas import decode_attention as da  # noqa: E402
from deepspeed_tpu.ops.pallas import flash_attention as fa  # noqa: E402
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

BENCH = os.path.join(REPO, "benchmark")
F32 = jnp.float32
BS, WINDOW = 16, 24
RING = kc.ring_blocks_for(WINDOW, BS) * BS          # 48 rows
FULL_ROPE = lg.RopeSpec(
    rope_theta=500000, rope_type="yarn", factor=64,
    original_max_position_embeddings=4096, beta_slow=1, beta_fast=64,
    attention_factor=1.4158883083359672, partial_rotary_factor=0.5)
SLIDING_ROPE = lg.RopeSpec(rope_theta=10000)
LAYERS = (lg.FULL, lg.WINDOW, lg.WINDOW, lg.WINDOW, lg.FULL)


def _load_family():
    return harness.load_family("laguna")


def _cfg(**over):
    """Heads 6 / 8 over 2 KV heads, window 24 (a ring of 48 rows),
    16 experts top-2, layers ``full, s, s, s, full``; YaRN with an
    original context of 16 positions so that its ramp is inside the
    tiny head."""
    base = dict(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        num_hidden_layers=5, num_key_value_heads=2, head_dim=16,
        layer_types=LAYERS, sliding_window=WINDOW,
        mlp_layer_types=("dense",) + ("sparse",) * 4,
        num_attention_heads_per_layer=(6, 8, 8, 8, 6),
        rope_full=dataclasses.replace(
            FULL_ROPE, original_max_position_embeddings=16),
        rope_sliding=SLIDING_ROPE, num_experts=16, num_experts_per_tok=2,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        max_position_embeddings=4096, experts_held=(0, 16), dtype=F32)
    return lg.LagunaConfig(**{**base, **over})


@functools.lru_cache(maxsize=None)
def _model(held=(0, 16)):
    cfg = _cfg(experts_held=held)
    return cfg, lg.init_params(jax.random.PRNGKey(5), cfg)


def _weights(cfg, params):
    return _load_family().reference_from_serve(cfg, params)


def _pool(cfg, slots, blocks=40, span_blocks=16):
    return kc.init_paged_cache(
        cfg.n_layer, slots, 1 + blocks, BS, span_blocks, cfg.kv_heads,
        cfg.head_dim, F32, window_layers=cfg.window_layers,
        window=cfg.sliding_window, aux_shape=cfg.aux_shape)


# ------------------------------------------------------------ rotary tables

@pytest.mark.parametrize("spec,rotated", [(FULL_ROPE, 64),
                                          (SLIDING_ROPE, 128)],
                         ids=["full-yarn-partial", "sliding-default"])
def test_rope_tables_match_the_direct_formula(spec, rotated):
    """The published parameters at the published head size, against the
    formula written out: partial rotation, YaRN's blended frequencies
    and its factor on cos and sin."""
    inv, times = lg.rope_table(spec, 128)
    i = np.arange(rotated // 2)
    plain = spec.rope_theta ** (-2.0 * i / rotated)
    if spec.rope_type == "default":
        want, want_times = plain, 1.0
    else:
        def dim_of(turns):
            return rotated * math.log(4096 / (turns * 2 * math.pi)) / (
                2 * math.log(spec.rope_theta))
        low, high = math.floor(dim_of(64)), math.ceil(dim_of(1))
        ramp = np.clip((i - low) / (high - low), 0, 1)
        want = plain * (1 - ramp) + plain / 64 * ramp
        want_times = 0.1 * math.log(64) + 1
        # the fastest dimensions keep their frequency, the slowest are
        # divided by the factor
        assert want[0] == plain[0] and np.isclose(want[-1], plain[-1] / 64)
    assert inv.shape == (rotated // 2,)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert times == pytest.approx(want_times, rel=1e-7)
    # the reference computes its own table from the same parameters
    r_inv, r_times = ref.rope_frequencies(
        {k: v for k, v in dataclasses.asdict(spec).items()
         if v is not None}, 128)
    np.testing.assert_allclose(r_inv, want, rtol=1e-12)
    assert r_times == pytest.approx(want_times, rel=1e-12)
    # rotation: position 0 only scales the rotated dims; the rest pass
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 128), F32)
    y = lg._rope(x, jnp.array([0, 5, 900]), spec)
    np.testing.assert_allclose(y[0, :, :rotated], x[0, :, :rotated] * times,
                               rtol=1e-6)
    np.testing.assert_array_equal(y[..., rotated:], x[..., rotated:])
    a, b = x[2, 0, :rotated // 2], x[2, 0, rotated // 2:rotated]
    ang = 900 * np.asarray(want, np.float32)
    np.testing.assert_allclose(
        y[2, 0, :rotated // 2],
        (a * np.cos(ang) - b * np.sin(ang)) * want_times, atol=2e-4)


# --------------------------------------------------------------- kernels

def _dense_window_truth(q, ks, vs, lengths, window):
    """Each slot's query against its last ``window`` positions, from the
    whole history ``ks / vs [S, T, KH, D]``."""
    out = []
    H, KH = q.shape[1], ks.shape[2]
    for s, n in enumerate(np.asarray(lengths)):
        lo = max(0, n - window)
        k = jnp.repeat(ks[s, lo:n], H // KH, 1)
        v = jnp.repeat(vs[s, lo:n], H // KH, 1)
        sc = jnp.einsum("hd,shd->hs", q[s], k) / math.sqrt(q.shape[-1])
        out.append(jnp.einsum("hs,shd->hd", jax.nn.softmax(sc, -1), v))
    return jnp.stack(out)


@pytest.mark.parametrize("H", [12, 16], ids=["groups-of-6", "groups-of-8"])
def test_window_walk_kernel_matches_its_oracles_over_ring_laps(H):
    """The paged kernel over the rings (interpret mode) and the
    ``jax.numpy`` oracle against attention over the true last ``window``
    positions: prompts shorter than the window, inside the ring's slack
    and longer than the ring, then more than three laps of appends; a
    slot that stays idle reads nothing."""
    S, KH, D, steps = 4, 2, 128, 3 * RING + 9
    cache = kc.init_paged_cache(
        2, S, 9, BS, 8, KH, D, F32, window_layers=(False, True),
        window=WINDOW)
    assert cache.ring_k.shape == (1, S * RING // BS, BS, KH * D)
    keys = jax.random.split(jax.random.PRNGKey(H), 3)
    T = 128
    ks = jax.random.normal(keys[0], (S, T + steps, KH, D), F32)
    vs = jax.random.normal(keys[1], (S, T + steps, KH, D), F32)
    lens = np.array([5, WINDOW + 3, 127, 0])
    for s, n in enumerate(lens[:3]):
        cache = kc.ring_write_prompt(cache, 0, ks[s, :T], vs[s, :T],
                                     jnp.int32(s), jnp.int32(n))
    cache = cache.replace(lengths=jnp.asarray(lens, jnp.int32))
    active = jnp.asarray(lens > 0)
    rows = jnp.arange(S)
    for step in range(steps):
        n = cache.lengths
        cache = kc.ring_append_token(cache, 0, ks[rows, n], vs[rows, n])
        q = jax.random.normal(jax.random.fold_in(keys[2], step), (S, H, D))
        live = jnp.where(active, n + 1, 0)
        want = _dense_window_truth(q[:3], ks, vs, live[:3], WINDOW)
        oracle = da.paged_window_decode_attention_reference(
            q, cache.ring_k[0], cache.ring_v[0], live, WINDOW)
        np.testing.assert_allclose(oracle[:3], want, atol=2e-6)
        if step % 7 == 0 or step >= steps - 3:
            got = da.paged_window_decode_attention(
                q, cache.ring_k, cache.ring_v, live, WINDOW, layer=0,
                interpret=True)
            np.testing.assert_allclose(got[:3], want, atol=3e-6)
            assert not np.asarray(got[3]).any()
        cache = kc.paged_advance(cache, active)
    assert cache.ring_k.shape == (1, S * RING // BS, BS, KH * D)


def test_window_walk_refuses_what_a_ring_cannot_be():
    q = jnp.zeros((2, 4, 128), F32)
    ring = jnp.zeros((1, 2 * 3, BS, 2 * 128), F32)
    with pytest.raises(ValueError, match="ring"):
        da.paged_window_decode_attention(q, ring, ring, jnp.ones(2), 64,
                                         interpret=True)


def _masked_softmax(q, k, v, window):
    T, H = q.shape[1], q.shape[2]
    rep = H // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)) / math.sqrt(
        q.shape[-1])
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    s = jnp.where((j <= i) & (i - j < window), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      jnp.repeat(v, rep, 2))


@pytest.mark.parametrize("window", [1, 100, 128, 200, 256, 511, 4096])
@pytest.mark.parametrize("H,blocks", [(12, (128, 128)), (16, (256, 128)),
                                      (12, (128, 256))],
                         ids=["6x-128-128", "8x-256-128", "6x-128-256"])
def test_windowed_flash_forward_matches_the_masked_softmax(window, H,
                                                           blocks):
    """Windows below, at and across the block edges, and one wider than
    the sequence (plain causal), groups of 6 and of 8 (interpret
    mode)."""
    keys = jax.random.split(jax.random.PRNGKey(window), 3)
    q = jax.random.normal(keys[0], (1, 512, H, 128), F32)
    k = jax.random.normal(keys[1], (1, 512, 2, 128), F32)
    v = jax.random.normal(keys[2], (1, 512, 2, 128), F32)
    got = fa.flash_attention(q, k, v, window=window, block_q=blocks[0],
                             block_k=blocks[1])
    np.testing.assert_allclose(got, _masked_softmax(q, k, v, window),
                               atol=5e-6)


def test_windowed_flash_is_forward_only_and_says_so():
    x = jnp.ones((1, 128, 2, 128), F32)
    with pytest.raises(NotImplementedError, match="window=64"):
        jax.grad(lambda q: fa.flash_attention(q, x, x, window=64).sum())(x)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(x, x, x, causal=False, window=64)
    # no window: the kernel and its backward as they were
    g = jax.grad(lambda q: fa.flash_attention(q, x, x).sum())(x)
    assert g.shape == x.shape


# ------------------------------------------- the program vs the reference

def _teacher_forced(cfg, params, ids, prompt, slots=3, slot=1):
    """Logits of every position from ``prompt - 1`` on: the prompt
    through ``paged_prefill`` into ``slot``, the rest a token a step
    through ``paged_decode_step`` (the other slots idle)."""
    T = len(ids)
    bucket = max(BS, 1 << (prompt - 1).bit_length())
    cache = _pool(cfg, slots, span_blocks=-(-(T + 1) // BS) + 1)
    need = -(-(T + 1) // BS)
    tables = np.zeros(cache.block_tables.shape, np.int32)
    tables[slot, :need] = 1 + np.arange(need)
    cache = cache.replace(block_tables=jnp.asarray(tables))
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :prompt] = ids[:prompt]
    prefill = jax.jit(functools.partial(lg.paged_prefill, cfg=cfg))
    decode = jax.jit(functools.partial(lg.paged_decode_step, cfg=cfg))
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


@pytest.mark.parametrize("prompt", [5, WINDOW, WINDOW + 7, RING + 9],
                         ids=["shorter-than-window", "the-window",
                              "inside-the-slack", "longer-than-ring"])
def test_float32_program_matches_the_reference_through_ring_laps(prompt):
    """Prefill at a length on each side of the window and of the ring,
    then more than three laps of the ring decoded through the pool and
    the rings: every logit within 1e-4 of the masked float32 reference
    (both head counts, both rotary tables, the gate, dense layer 0, the
    expert layer and the shared expert are in it)."""
    cfg, params = _model()
    T = prompt + 3 * RING + 5
    ids = np.random.default_rng(prompt).integers(0, cfg.vocab_size, T)
    got, cache = _teacher_forced(cfg, params, ids, prompt)
    want = ref.logits(_weights(cfg, params), ids[None])[0, prompt - 1:]
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-4 * scale
    # the ring never grew, and the counters say what a step had to read
    assert cache.ring_k.shape == (3, 3 * RING // BS, BS, 32)
    aux = np.asarray(cache.aux)
    steps = T - prompt
    seen = np.arange(prompt + 1, T + 1)
    assert aux[0, -2] == 2 * seen.sum()                       # full rows
    assert aux[0, -1] == 3 * np.minimum(seen, WINDOW).sum()   # window rows
    tail = aux[:, 16:16 + len(held_experts.COUNTER_TAIL)]
    # one token's two picks are under a row tile: ``ragged_dot`` walks none
    assert tail[0].tolist() == [0, 0, 4 * steps, 4 * steps, tail[0, 4],
                                0]
    assert tail[1, 2] == 4 * prompt and aux[:, :16].sum() == 2 * (
        4 * steps + 4 * prompt)


def test_causal_forward_matches_the_reference_and_is_what_prefill_gives():
    cfg, params = _model()
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 70))
    got = lg.causal_forward(params, cfg, jnp.asarray(ids))
    want = ref.logits(_weights(cfg, params), ids)
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())


@pytest.mark.parametrize("edge", [-1, 1], ids=["a-row-short", "a-row-long"])
def test_a_window_one_row_off_is_not_the_model(edge):
    """What the chip's largest-gap check cannot part from rounding (PERF.md
    section 7) is held here: a window one row short or long moves the
    float32 logits by far more than the 1e-4 the program is held to."""
    cfg, params = _model()
    off = dataclasses.replace(cfg, sliding_window=WINDOW + edge)
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (1, 90))
    want = ref.logits(_weights(cfg, params), ids)
    got = lg.causal_forward(params, off, jnp.asarray(ids))
    assert float(jnp.abs(got - want).max()) > 1e-2 * float(
        jnp.abs(want).max())


# ---------------------------------------------------------- expert layer

def test_routing_is_sigmoid_normalised_and_the_bias_only_selects():
    cfg, params = _model()
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.hidden_size), F32)
    picks, w = lg._route(u, moe, cfg)
    s = jax.nn.sigmoid(u @ moe["router"])
    np.testing.assert_allclose(w.sum(-1), cfg.moe_routed_scaling_factor,
                               rtol=1e-6)
    picked = jnp.take_along_axis(s, picks, -1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    # a large bias on one expert puts it into every token's picks and
    # leaves the weights a function of the scores alone
    pushed = dict(moe, router_bias=moe["router_bias"].at[3].add(10.0))
    picks2, w2 = lg._route(u, pushed, cfg)
    assert bool((picks2 == 3).any(-1).all())
    picked2 = jnp.take_along_axis(s, picks2, -1)
    np.testing.assert_allclose(
        w2, 2.5 * picked2 / picked2.sum(-1, keepdims=True), rtol=1e-5)


def test_seeded_bias_is_alike_in_every_share_and_not_from_the_seed():
    cfg = _cfg(num_experts=256, experts_held=(0, 32))
    b = np.asarray(lg.router_bias(cfg)).reshape(8, 32)
    assert (b == b[0]).all() and abs(b[0].sum()) < 1e-6
    steps = np.diff(np.sort(b[0]))
    np.testing.assert_allclose(steps, steps[0], rtol=1e-4)
    one = lg.init_params(jax.random.PRNGKey(1), _cfg())
    two = lg.init_params(jax.random.PRNGKey(2), _cfg())
    np.testing.assert_array_equal(one["layers"][1]["moe"]["router_bias"],
                                  two["layers"][1]["moe"]["router_bias"])
    # the held share's matmul rows at the cell's sizes: a decode batch
    # and the longest prompt (its landed picks + six deviations)
    published = _cfg(num_experts=256, num_experts_per_tok=8,
                     experts_held=(0, 32))
    assert lg._expected_rows(96, published) == 256
    assert lg._expected_rows(8192, published) == 8704


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The 8 shares' routed parts, plus what every chip computes alike
    (attention, the shared expert) counted once, are the uncut
    reference's layer."""
    whole_cfg, params = _model()
    layer = params["layers"][2]
    weights = _weights(whole_cfg, params)
    x = jax.random.normal(jax.random.PRNGKey(4), (40, whole_cfg.hidden_size),
                          F32)
    want = ref.layer_forward(x, weights["layers"][2], weights["sizes"])
    # what every chip computes alike, by the reference's own functions
    rl, z = weights["layers"][2], weights["sizes"]
    q, k, v, gate = ref._project(x, rl["g_in"], rl["w_q"], rl["w_k"],
                                 rl["w_v"], rl["w_g"], rope=rl["rope"],
                                 eps=z["eps"])
    h = ref._attn_out(x, ref._attention(q, k, v, window=z["window"],
                                        block=64), gate, rl["w_o"])
    u = ref._norm(h, rl["g_post"], eps=z["eps"])
    shared = lg._shared_expert(u, layer["moe"]["shared"])
    valid = jnp.ones((40,), bool)
    total = h + shared
    hit = 0
    for share in range(8):
        held = (2 * share, 2 * share + 2)
        cfg = _cfg(experts_held=held)
        moe = dict(layer["moe"], experts=jax.tree.map(
            lambda w: w[held[0]:held[1]], layer["moe"]["experts"]))
        m, counts = lg.moe_layer(u, moe, cfg, valid)
        total = total + (m - shared)
        hit += int(counts[:2].sum())
        assert int(counts[2 + 1]) == 40 * 2 - int(counts[:2].sum())  # absent
    assert hit == 40 * 2                      # every pick landed once
    assert float(jnp.abs(total - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())


def test_long_prompts_combine_by_gather_and_agree_with_the_assignment():
    """Above ``ASSIGN_ROWS_A_PICK`` rows a pick the held part gathers each
    pick's row; the two forms of the weighted sum are the same
    function."""
    out = jax.random.normal(jax.random.PRNGKey(0), (64, 8), F32)
    where = jax.random.permutation(jax.random.PRNGKey(1), 96).reshape(48, 2)
    held = where < 40
    w = jax.random.uniform(jax.random.PRNGKey(2), (48, 2))
    landed = jnp.arange(64) < 40
    a = held_experts._combine_landed(out, landed, where, held, w)
    b = held_experts._combine_gathered(out, where, held, w)
    np.testing.assert_allclose(a, b, atol=1e-6)


# ------------------------------------------------ allocator and scheduler

def _server(num_slots=3, pool=None, span=512, registry=None, **knobs):
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
def test_served_through_the_server_over_three_ring_laps(async_loop):
    """Seven requests through three slots: prompts on each side of the
    window and the ring, 150 tokens each (three laps of a 48-row ring),
    slots reused; every served token is the reference's choice. The
    rings are what they were built as, every block is back, and the
    pool is the size asked for."""
    cfg, params, engine = _server(pool=40, async_loop=async_loop)
    server = ContinuousBatchingServer(engine)
    cache = server._cache
    assert cache.k.shape == (2, 41, BS, 32)             # 2 full layers
    assert cache.ring_k.shape == (3, 3 * RING // BS, BS, 32)
    assert cache.layer_map == (("full", 0), ("window", 0), ("window", 1),
                               ("window", 2), ("full", 1))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (3, WINDOW, 30, RING - 1, RING, 70, 150)]
    served = _serve(server, prompts, 150)
    alloc = server.scheduler.allocator
    assert alloc.live_blocks == 0 and alloc.free_blocks == 40
    assert server._cache.ring_k.shape == cache.ring_k.shape
    server.close()
    _held_to_reference(cfg, params, prompts, served)


def test_admission_waits_on_blocks_with_a_slot_free():
    """A pool of 12 blocks for 3 slots of span 32 blocks: two requests
    of 5 blocks fit, the third waits with a slot free and the queue
    non-empty (which is not a dry backlog), is admitted when blocks come
    back, and every request is served right."""
    prev = get_registry()
    set_registry(MetricRegistry())
    reg = get_registry()
    try:
        cfg, params, engine = _server(pool=12)
        server = ContinuousBatchingServer(engine)
        assert server._cache.k.shape[1] == 13
        sched = server.scheduler
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, cfg.vocab_size, 40).tolist()
                   for _ in range(4)]
        waited = []

        def watch(server):
            if sched.waits_on_blocks(server._tick):
                waited.append((len(sched.slots), sched.pending_requests,
                               sched.allocator.free_blocks))
        served = _serve(server, prompts, 30, watch)       # 70 -> 5 blocks
        assert waited and all(live == 2 and queued >= 1 and free < 5
                              for live, queued, free in waited)
        snap = reg.snapshot()
        blocked = snap["serve_kv_admission_blocked_steps_total"]["series"]
        assert blocked[0]["value"] > 0
        used = snap["serve_kv_used_block_steps_total"]["series"][0]["value"]
        steps = snap["serve_decode_steps_total"]["series"][0]["value"]
        assert 5 <= used / steps <= 10
        assert snap["serve_kv_ring_bytes"]["series"][0]["value"] == (
            2 * 3 * 3 * RING * 32 * 4)
        server.close()
        _held_to_reference(cfg, params, prompts, served)
        with pytest.raises(ValueError, match="whole pool holds 12"):
            ContinuousBatchingServer(engine).submit([1] * 200, 100)
    finally:
        set_registry(prev)


def test_defaults_build_the_pool_they_always_built():
    """No ``kv_pool_blocks``, no window layers: ``slots x span`` blocks
    and the null block, no ring, no layer map, nothing of the model's."""
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    cfg = InferenceTransformerConfig(vocab_size=64, n_positions=128,
                                     n_embd=32, n_layer=2, n_head=2,
                                     dtype=F32)
    engine = InferenceEngine(
        (cfg, init_params(jax.random.PRNGKey(0), cfg)),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=128,
                                 block_size=BS, num_slots=3))
    server = ContinuousBatchingServer(engine)
    cache = server._cache
    assert cache.k.shape[:2] == (2, 1 + 3 * 8)
    assert (cache.ring_k, cache.ring_v, cache.aux, cache.layer_map) == (
        None,) * 4
    assert len(kc.pool_arrays(cache)) == 2
    server.close()
    with pytest.raises(ValueError, match="kv_pool_blocks"):
        DeepSpeedInferenceConfig(kv_pool_blocks=0)


def _scopes_and_kernels(jaxpr, scopes, kernels):
    """Every equation's scope path and every Pallas call's name, through
    the nested jaxprs."""
    for eqn in jaxpr.eqns:
        scopes.add(str(eqn.source_info.name_stack))
        if eqn.primitive.name == "pallas_call":
            kernels.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scopes_and_kernels(sub, scopes, kernels)


def test_a_window_layer_never_gathers_the_pool(monkeypatch):
    """On the TPU path neither kind of layer carries the ``kv_read``
    scope (the XLA gathers of the dense fallbacks): a window layer reads
    its ring through the kernel, a full layer its blocks, one call a
    layer."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, params = _model()
    traced = jax.make_jaxpr(functools.partial(
        lg.paged_decode_step, cfg=cfg))(
            params, tokens=jnp.zeros((2,), jnp.int32), cache=_pool(cfg, 2),
            active=jnp.ones((2,), bool))
    scopes, kernels = set(), []
    _scopes_and_kernels(traced.jaxpr, scopes, kernels)
    assert sorted(kernels) == ["paged_decode_attention"] * 2 + [
        "paged_window_decode_attention"] * 3
    assert not any("kv_read" in s for s in scopes)
    for name in ("attn_full", "attn_window/kv_write", "moe_shared",
                 "moe_router", "dense_ffn", "lm_head"):
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


def test_a_ring_has_no_int8_rows_and_the_config_checks_its_lists():
    with pytest.raises(NotImplementedError, match="int8"):
        kc.init_paged_cache(2, 2, 5, BS, 4, 2, 16, quantized=True,
                            window_layers=(False, True), window=WINDOW)
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=LAYERS[:4])
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(8, 20))
    with pytest.raises(NotImplementedError, match="rope_type"):
        lg.RopeSpec(rope_theta=1e4, rope_type="llama3")


# ------------------------------------------------ the benchmark's new cell

CELL = "serve-laguna-xs2-ep8-mixed-context-batch"
CONFIG = "laguna-xs2-ep8-serve"


def test_configuration_file_states_the_published_sizes_once():
    """The top level holds the catalog's keys whole (the reduced ones at
    their reduced values); the ``model`` block is what runs: the first
    twelve layers of every per-layer list, the router's 256 outputs."""
    contract = harness.load_contract()
    entry = harness.find(contract["configs"], CONFIG, "config")
    conf = harness.load_json(os.path.join(REPO, entry["file"]))
    model = conf["model"]
    assert entry["reduced"] == list(conf["reduced"]) == [
        "num_hidden_layers", "num_experts"]
    assert (conf["num_hidden_layers"], conf["num_experts"]) == (12, 32)
    assert (model["num_hidden_layers"], model["num_experts"],
            model["experts_held"]) == (12, 256, [0, 32])
    for key in ("vocab_size", "hidden_size", "intermediate_size",
                "num_key_value_heads", "head_dim", "sliding_window",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "rms_norm_eps",
                "moe_routed_scaling_factor", "max_position_embeddings"):
        assert model[key] == conf[key], key
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(conf[key]) == 40 and model[key] == conf[key][:12], key
    assert model["layer_types"].count("full_attention") == 3
    assert model["num_attention_heads_per_layer"][:2] == [48, 64]
    assert model["rope_parameters"]["full_attention"] == conf[
        "rope_parameters"]["full_attention"]
    assert model["rope_parameters"]["sliding_attention"] == conf[
        "rope_parameters"]["sliding_attention"]
    for key in ("activation", "rotation", "gate", "router", "head_norm",
                "final_norm", "seeded_init"):
        assert key in conf["assumed"], key
    cell = harness.resolve_cell(contract, CELL)
    assert cell["cell"]["chips"] == 1
    assert cell["config"]["engine"] == {
        "dtype": "bfloat16", "max_out_tokens": 10240, "block_size": 128,
        "num_slots": 96, "kv_pool_blocks": 3200, "max_queued_requests": 512}
    assert set(cell["end_to_end"]) == {"serve_out_tokens_per_s", "setup_s"}
    new = [m for m in contract["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in new} == {
        "laguna_decode_full_attn_ms", "laguna_decode_window_attn_ms",
        "laguna_decode_moe_ms", "laguna_full_decode_roofline",
        "laguna_window_decode_roofline", "laguna_kv_gb_per_step",
        "laguna_full_pool_used_pct"}
    listed = {m["name"] for m in contract["per_layer"]
              if CELL in m.get("workloads", ())}
    for name in listed:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    assert {"serve_goodput_pct", "serve_pipelined_steps_pct",
            "trace_lower_s", "compile_cache_misses", "decode_program_ms",
            "decode_dispatch_gap_ms", "batch_device_idle_pct",
            "batch_peak_hbm_gb", "moe_experts_roofline",
            "moe_tokens_per_held_expert", "moe_held_load_max_over_mean",
            "compile_s", "compiles_in_window"} <= listed
    traffic = cell["traffic"]
    assert (traffic["kind"], traffic["requests"],
            traffic["max_total_tokens"]) == ("backlog", 256, 10240)
    assert traffic["prompt_len"] == {"dist": "loguniform", "lo": 256,
                                     "hi": 8192}
    assert traffic["output_len"] == {"dist": "loguniform", "lo": 512,
                                     "hi": 2048}
    assert traffic["check"]["output_tokens"] == 160
    # the deployment's arithmetic: a ring, a block, a step's reads
    assert kc.ring_blocks_for(512, 128) * 128 == 640
    assert flops_laguna.row_bytes(8, 128) == 4096
    assert flops_laguna.seen_pairs(8192, 512) == 512 * 513 / 2 + 7680 * 512
    shapes = _load_family().shapes(model)
    assert (shapes["layers"], shapes["full_layers"],
            shapes["window_layers"], shapes["expert_ffn"]) == (11, 3, 9, 512)


def test_the_cells_order_is_a_ring_that_scatters_less_than_a_permutation():
    """``benchmark/tools/backlog_order.py`` replays the backlog in steps
    under the traffic file's fitted ``order_model``: it orders a lap as
    the generator does, and the committed ring's 256 rotations scatter
    less in tokens/s than seeded permutations of the same multiset (what
    ``order_seed`` was chosen for; PERF.md section 6, PR 43)."""
    from benchmark.lib import traffic as traffic_lib
    from benchmark.tools import backlog_order
    tr = harness.load_json(os.path.join(
        BENCH, "traffic", "laguna-mixed-context-batch.json"))
    assert (tr["order"], tr["stratify_block"]) == ("rotation", 8)
    seed = 2 ** 31 + 5
    made = traffic_lib.build_requests(tr, 50.0, seed, 2)["requests"]
    lap = backlog_order.laps(tr, seed, 1)
    assert [(len(r["prompt"]), r["out"]) for r in made] == [
        tuple(int(x) for x in pair) for pair in lap]
    model = tr["order_model"]
    ring = backlog_order.scatter(tr, model, 96, 50.0)
    perm = backlog_order.scatter(dict(tr, order="permutation"), model, 96,
                                 50.0, seeds=64)
    assert ring < 0.35 < perm, (ring, perm)


def test_the_cell_runs_at_a_tiny_size_through_the_harness(tmp_path):
    """The harness's own runner, the real readers and family, the tiny
    twins of the configuration and the traffic: the backlog stays full
    over a pool smaller than slots x span, nothing compiles in the
    window, the served tokens pass the check, and the counter-based
    metrics read what the program counted."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        contract = harness.load_contract()
        cell = harness.resolve_cell(contract, CELL)
        twin = {k: harness.load_json(os.path.join(
            BENCH, "testdata", d, name + ".json"))
            for k, d, name in (("config", "configs", "tiny-laguna-serve"),
                               ("traffic", "traffic",
                                "tiny-laguna-mixed-context-batch"))}
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
        assert 0 < metrics["laguna_full_pool_used_pct"]["value"] <= 100
        assert metrics["laguna_kv_gb_per_step"]["value"] > 0
        held = metrics["moe_tokens_per_held_expert"]["value"]
        assert 0 < held <= 4 * 2           # 4 slots x top-2 over the share
    finally:
        set_registry(prev)
