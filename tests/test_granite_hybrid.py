"""The Granite hybrid family at a small size on the CPU: the program
against the plain float32 reference (prefill at lengths inside a chunk,
on a chunk's edge and inside a bucket's padding, then decode through the
one cache that holds states beside K/V blocks), the chunked form against
the recurrence, padding and idle slots kept out of every state, the four
multipliers, the softmax over the picks, the two shares adding up to the
uncut layer, the pool and the refusals by name.
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
from benchmark.lib import reference_granite as ref  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference import kv_cache as kc  # noqa: E402
from deepspeed_tpu.inference import server as srv  # noqa: E402
from deepspeed_tpu.model_implementations import held_experts  # noqa: E402
from deepspeed_tpu.model_implementations import (  # noqa: E402
    granite_hybrid as gh)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

BENCH = os.path.join(REPO, "benchmark")
F32 = jnp.float32
BS, CHUNK = 16, 8
LAYERS = (gh.MAMBA, gh.MAMBA, gh.ATTENTION, gh.MAMBA)
TAIL = len(held_experts.COUNTER_TAIL)


def _load_family():
    return harness.load_family("granite_hybrid")


def _cfg(**over):
    """Mamba heads 8 x 8 over a state of 16, 4 query heads over 2 K/V
    heads of 8, 12 experts top-3, layers ``m, m, a, m``, chunks of 8 under
    blocks of 16."""
    base = dict(
        vocab_size=320, layer_types=LAYERS, hidden_size=32,
        intermediate_size=24, shared_intermediate_size=40,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=12, num_experts_per_tok=3, mamba_n_heads=8,
        mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=CHUNK,
        max_position_embeddings=4096, experts_held=(0, 12), dtype=F32)
    return gh.GraniteHybridConfig(**{**base, **over})


@functools.lru_cache(maxsize=None)
def _model(held=(0, 12)):
    cfg = _cfg(experts_held=held)
    return cfg, gh.init_params(jax.random.PRNGKey(5), cfg)


def _config_of(model: dict):
    """The program's configuration of a file's ``model`` block, with no
    weight made."""
    box = {}

    def make():
        box["cfg"], params = _load_family().serve_model(model, 0)
        return params
    jax.eval_shape(make)
    return box["cfg"]


def _weights(cfg, params):
    return _load_family().reference_from_serve(cfg, params)


def _pool(cfg, slots, blocks=40, span_blocks=16):
    return kc.init_paged_cache(
        cfg.n_layer, slots, 1 + blocks, BS, span_blocks, cfg.kv_heads,
        cfg.head_dim, F32, aux_shape=cfg.aux_shape,
        state_layers=cfg.state_layers, state_shapes=cfg.state_shapes,
        state_dtype=cfg.state_dtype)


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
    return jax.jit(functools.partial(gh.paged_prefill, cfg=cfg))(
        params, input_ids=jnp.asarray(padded),
        length=jnp.array([len(ids)]), cache=cache, slot=jnp.int32(slot))


def _teacher_forced(cfg, params, ids, prompt, slots=3, slot=1):
    """Logits of every position from ``prompt - 1`` on: the prompt
    through ``paged_prefill`` into ``slot``, the rest a token a step
    through ``paged_decode_step`` (the other slots idle)."""
    T = len(ids)
    span = max(-(-(T + 1) // BS) + 1, _bucket(prompt) // BS)
    cache = _with_tables(_pool(cfg, slots, span_blocks=span), slot, T + 1)
    decode = jax.jit(functools.partial(gh.paged_decode_step, cfg=cfg))
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
    40 tokens decoded through the states and the block pool: every logit
    within 1e-4 of the recurrence-and-masks float32 reference (both layer
    kinds, the convolution's tail, the expert layer, the shared MLP and
    the four multipliers are in it)."""
    cfg, params = _model()
    T = prompt + 40
    ids = np.random.default_rng(prompt).integers(0, cfg.vocab_size, T)
    got, cache = _teacher_forced(cfg, params, ids, prompt)
    want = ref.logits(_weights(cfg, params), ids[None])[0, prompt - 1:]
    assert _close(got, want)
    # what the programs counted: a pass a live slot a state layer, the
    # attention layer's rows, the prompt's tokens and chunks
    aux = np.asarray(cache.aux)
    steps, n_state = T - prompt, 3
    own = aux[:, 12 + TAIL:]
    assert own[0].tolist() == [steps, steps, n_state * steps,
                               int(np.arange(prompt + 1, T + 1).sum()), 0, 0]
    chunk = min(CHUNK, _bucket(prompt))
    assert own[1].tolist() == [1, 0, n_state, 0, prompt,
                               -(-prompt // chunk) * n_state]
    tail = aux[:, 12:12 + TAIL]
    assert tail[0, 2] == 4 * steps and tail[1, 2] == 4 * prompt
    assert aux[:, :12].sum() == 3 * (4 * steps + 4 * prompt)


def test_causal_forward_matches_the_reference():
    cfg, params = _model()
    ids = np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 37))
    got = gh.causal_forward(params, cfg, jnp.asarray(ids))
    assert _close(got, ref.logits(_weights(cfg, params), ids))


def _mixer_inputs(T, H=8, P=8, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (T, H, P)),
            jax.nn.silu(jax.random.normal(k[1], (T, N))),
            jax.nn.silu(jax.random.normal(k[2], (T, N))),
            jnp.exp(jax.random.uniform(k[3], (T, H), F32, -7.0, -1.0)),
            -jnp.exp(jax.random.uniform(k[4], (H,), F32, -5.0, 3.0)),
            jax.random.normal(k[5], (H,)))


@pytest.mark.parametrize("T,length,chunk", [
    (64, 64, 8), (64, 37, 8), (64, 3, 16), (16, 16, 256), (70, 70, 8)],
    ids=["whole", "padded-tail", "inside-the-first-chunk",
         "shorter-than-a-chunk", "ragged-last-chunk"])
def test_the_chunked_form_equals_the_recurrence(T, length, chunk):
    """``_scan_sequence`` against ``_state_token`` a position at a time:
    the outputs of the live positions and the state after ``length``
    tokens, over decays from a step's half-life to thousands."""
    x, B, C, dt, A, D = _mixer_inputs(T)
    y, S = gh._scan_sequence(x, B, C, dt, A, D, jnp.int32(length), chunk,
                             F32)
    S1 = jnp.zeros((1, 8, 8, 16), F32)
    want = []
    for t in range(length):
        y_t, S1 = gh._state_token(x[t][None], B[t][None], C[t][None],
                                  dt[t][None], A, D, jnp.array([True]), S1)
        want.append(y_t[0])
    assert _close(y[:length], jnp.stack(want), 1e-5)
    assert _close(S, S1[0], 1e-5)


def test_padding_never_reaches_a_state():
    """The same prompt in its own bucket and in a larger one whose
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
    # the tail is the prompt's last three inputs, not the bucket's
    cache = outs[0][1]
    assert float(jnp.abs(cache.conv[0][:, 1]).min()) > 0
    assert float(jnp.abs(cache.conv[0][:, 0]).max()) == 0   # slot 0: idle


def test_idle_slots_and_a_reused_slot_carry_nothing():
    """Decode steps leave an idle slot's state and tail bit for bit as
    they were; a slot that served a long request and is prefilled again
    reads exactly as a fresh pool's."""
    cfg, params = _model()
    rng = np.random.default_rng(2)
    long_ids = rng.integers(0, cfg.vocab_size, 90)
    short = rng.integers(0, cfg.vocab_size, 9)
    got, cache = _teacher_forced(cfg, params, long_ids, 70)
    for a in cache.state:                       # slots 0 and 2 were idle
        assert float(jnp.abs(a[0]).max()) == 0 == float(jnp.abs(a[2]).max())
    for a in cache.conv:
        assert float(jnp.abs(a[:, 0]).max()) == 0
    again, reused = _prefill(cfg, params, cache, short, 1)
    fresh_logits, fresh = _prefill(
        cfg, params, _with_tables(_pool(cfg, 3, span_blocks=8), 1, 91),
        short, 1)
    np.testing.assert_array_equal(again, fresh_logits)
    for a, b in zip(reused.state + reused.conv, fresh.state + fresh.conv):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------- the model's parts

@pytest.mark.parametrize("name,other", [
    ("embedding_multiplier", 5.0), ("attention_multiplier", 8 ** -0.5),
    ("residual_multiplier", 1.0), ("logits_scaling", 3.0)])
def test_each_multiplier_is_where_the_reference_has_it(name, other):
    """The program under another value of one multiplier equals the
    reference under that value and not the reference under the published
    one (for the attention's: 1 / sqrt(head size) is not the model)."""
    cfg, params = _model()
    moved = dataclasses.replace(cfg, **{name: other})
    ids = np.random.default_rng(4).integers(0, cfg.vocab_size, (1, 40))
    got = gh.causal_forward(params, moved, jnp.asarray(ids))
    assert _close(got, ref.logits(_weights(moved, params), ids))
    assert not _close(got, ref.logits(_weights(cfg, params), ids), 1e-2)


def test_routing_is_a_softmax_over_the_picks_and_not_over_all():
    cfg, params = _model()
    moe = params["layers"][0]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(1), (50, cfg.hidden_size))
    picks, w = gh._route(u, moe, cfg)
    logits = np.asarray(u @ moe["router"], np.float64)
    order = np.argsort(-logits, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(picks, -1), np.sort(order, -1))
    top = np.take_along_axis(logits, np.asarray(picks), -1)
    over_picks = np.exp(top) / np.exp(top).sum(-1, keepdims=True)
    over_all = np.exp(top) / np.exp(logits).sum(-1, keepdims=True)
    np.testing.assert_allclose(w, over_picks, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    assert np.abs(np.asarray(w) - over_all).max() > 0.05
    r_picks, r_w = ref.route(u, moe["router"], top_k=3)
    np.testing.assert_array_equal(picks, r_picks)
    np.testing.assert_allclose(w, r_w, rtol=1e-6)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """An EP-2 deployment's two chips hold experts [0, 6) and [6, 12):
    their parts of an expert layer, with the shared MLP (which both
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
        m, counts = gh.moe_layer(u, share, cfg, valid)
        parts.append(m)
        assert int(counts[:6].sum() + counts[6 + 1]) == 40 * 3
    shared = gh._shared_mlp(u, moe["shared"])
    weights = _weights(whole_cfg, params)
    want = ref.expert_layer(u, weights["layers"][1], weights["sizes"])
    assert _close(parts[0] + parts[1] - shared, want, 1e-5)
    # and one share alone is not the layer
    assert not _close(parts[0], want, 1e-2)


def test_the_gate_comes_before_the_norm_and_d_is_in_the_output():
    cfg, params = _model()
    m = params["layers"][0]["mamba"]
    y = jax.random.normal(jax.random.PRNGKey(3), (5, 8, 8))
    z = jax.random.normal(jax.random.PRNGKey(4), (5, 64))
    g = y.reshape(5, 64) * jax.nn.silu(z)
    want = (g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
            ) @ m["w_out"]
    assert _close(gh._mamba_out(y, z, m, cfg), want, 1e-5)
    x, B, C, dt, A, D = _mixer_inputs(16)
    y0, _ = gh._scan_sequence(x, B, C, dt, A, D * 0, jnp.int32(16), 8, F32)
    y1, _ = gh._scan_sequence(x, B, C, dt, A, D, jnp.int32(16), 8, F32)
    assert _close(y1 - y0, D[:, None] * x, 1e-5)


def test_seeded_heads_are_local_and_global():
    """Half a layer's heads keep the reference initialisation's decay
    rates, half remember hundreds of tokens and more (where a state's
    precision is decided)."""
    A = np.asarray(gh._decay_rates(jax.random.PRNGKey(0), 128))
    assert (A[:64] >= 1).all() and (A[:64] <= 16).all()
    assert (A[64:] >= 2.0 ** -9).all() and (A[64:] <= 2.0 ** -3).all()
    cfg, params = _model()
    m = params["layers"][0]["mamba"]
    step = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert (step >= 1e-3 * 0.999).all() and (step <= 1e-1 * 1.001).all()
    assert float(jnp.abs(m["D"] - 1).max()) == 0


# ------------------------------------------------------------ the server

def _server(num_slots=3, pool=None, span=256, **knobs):
    cfg, params = _model()
    engine = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=span, block_size=BS,
        num_slots=num_slots, max_queued_requests=32, kv_pool_blocks=pool,
        **knobs))
    return cfg, params, engine


def _serve(server, prompts, n_out):
    ids = [server.submit(p, max_new_tokens=n_out, eos_token_id=None)
           for p in prompts]
    while not server.scheduler.idle:
        server.step()
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
def test_served_through_the_server_with_slots_reused(async_loop):
    """Seven requests of different lengths through three slots, 60 tokens
    each: requests of different length decode side by side, every slot is
    reused after a longer or shorter request, and every served token is
    the reference's choice. ONE cache holds the states beside the
    attention layer's blocks; every block is back at the end."""
    cfg, params, engine = _server(pool=30, async_loop=async_loop)
    server = ContinuousBatchingServer(engine)
    cache = server._cache
    assert isinstance(cache, kc.PagedKVCache)
    assert cache.k.shape == (1, 31, BS, 16)            # the attention layer
    assert cache.layer_map == (("state", 0), ("state", 1), ("full", 0),
                               ("state", 2))
    assert [a.shape for a in cache.state] == [(3, 8, 8, 16)] * 3
    assert [a.shape for a in cache.conv] == [(3, 3, 96)] * 3
    assert all(a.dtype == F32 for a in cache.state)
    assert len(kc.pool_arrays(cache)) == 2 + 6
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (3, CHUNK, 70, 17, BS, 33, 150)]
    served = _serve(server, prompts, 60)
    alloc = server.scheduler.allocator
    assert alloc.live_blocks == 0 and alloc.free_blocks == 30
    server.close()
    _held_to_reference(cfg, params, prompts, served)


def test_admission_counts_a_slot_and_the_attention_layers_blocks():
    """A pool smaller than slots x span: a request reserves the blocks of
    its prompt and output for the ONE layer that has rows, and a state
    costs it nothing there."""
    cfg, params, engine = _server(num_slots=4, pool=12)
    server = ContinuousBatchingServer(engine)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in (40, 40, 40, 40)]
    ids = [server.submit(p, max_new_tokens=40, eos_token_id=None)
           for p in prompts]
    most = 0
    while not server.scheduler.idle:
        server.step()
        most = max(most, len(server.scheduler.slots))
    # 80 positions = 5 blocks a request: two fit 12 blocks, not three
    assert most == 2
    assert all(len(server.result(i)) == 80 for i in ids)
    server.close()


def test_defaults_build_the_pool_they_always_built():
    cache = kc.init_paged_cache(2, 2, 5, BS, 4, 2, 16)
    assert cache.state is None and cache.conv is None
    assert cache.layer_map is None and len(kc.pool_arrays(cache)) == 2
    leaves = jax.tree_util.tree_leaves(cache)
    assert len(leaves) == 4                     # k, v, tables, lengths


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


@pytest.mark.parametrize("name,knobs,kwargs", [
    ("kv_host_offload", dict(kv_host_offload=True,
                             enable_prefix_caching=True), {}),
    ("prefill_chain", dict(prefill_chain=True, prefill_chunk_tokens=BS),
     {}),
    ("handoff_import", {}, dict(handoff_import=True)),
    ("draft_engine", {}, dict(draft_engine="a draft")),
])
def test_tier_chain_handoff_and_draft_are_refused_by_name(name, knobs,
                                                          kwargs):
    _, _, engine = _server(**knobs)
    with pytest.raises(NotImplementedError, match=name):
        ContinuousBatchingServer(engine, **kwargs)


def test_the_kind_refuses_every_rows_switch_with_its_own_reason():
    kind = srv._POOL_KINDS["kv_state"]
    assert kind.make_pool == "_make_kv_pool" and kind.block_tables
    names = [name for name, _, _ in kind.refuses]
    assert names == [name for name, _, _ in
                     srv._POOL_KINDS["kv_window"].refuses]
    why = dict((name, why) for name, _, why in kind.refuses)
    assert "state" in why["kv_cache_dtype"]
    assert "snapshot" in why["enable_prefix_caching"]
    assert "chunk" in why["prefill_chunk_tokens"]
    assert "rejected draft" in why["speculation_tokens"]


@pytest.mark.parametrize("switch,conf", [
    ("int8", dict(dtype="int8")),
    ("tp_size", dict(tensor_parallel={"tp_size": 2})),
])
def test_engine_switches_are_refused_by_name(switch, conf):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=switch):
        InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
            **{"max_out_tokens": 64, **conf}))


def test_the_pool_and_the_config_refuse_what_they_cannot_be():
    with pytest.raises(NotImplementedError, match="int8"):
        kc.init_paged_cache(2, 2, 5, BS, 4, 2, 16, quantized=True,
                            state_layers=(True, False),
                            state_shapes=((2, 4, 4), (3, 16)))
    with pytest.raises(NotImplementedError, match="window"):
        kc.init_paged_cache(2, 2, 5, BS, 4, 2, 16, window=8,
                            window_layers=(False, True),
                            state_layers=(True, False),
                            state_shapes=((2, 4, 4), (3, 16)))
    with pytest.raises(ValueError, match="layer_types"):
        _cfg(layer_types=LAYERS[:3])
    with pytest.raises(ValueError, match="experts_held"):
        _cfg(experts_held=(8, 20))
    with pytest.raises(ValueError, match="mamba_n_groups"):
        _cfg(mamba_n_groups=3)      # 8 heads: 1, 2, 4 and 8 groups run
    with pytest.raises(ValueError, match="mamba_expand"):
        _cfg(mamba_n_heads=4)


# ------------------------------------------------ the benchmark's new cell

CELL = "serve-granite4-h-small-ep2-decode-batch"
CONFIG = "granite4-h-small-ep2-serve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_configuration_file_states_the_published_sizes_once():
    """The top level holds the published keys whole (the reduced ones at
    their reduced values); the ``model`` block is what runs: the first
    ten layers, the router's 72 outputs, 36 experts and half the
    vocabulary held."""
    conf = harness.load_json(os.path.join(BENCH, "configs",
                                          CONFIG + ".json"))
    contract = harness.load_contract()
    entry = next(c for c in contract["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts",
                                "vocab_size"]
    assert set(conf["reduced"]) == set(entry["reduced"])
    assert entry["source"] == conf["source"]
    assert (conf["num_hidden_layers"], conf["num_local_experts"],
            conf["vocab_size"]) == (10, 36, 50176)
    model = conf["model"]
    assert model["num_local_experts"] == 72
    assert model["experts_held"] == [0, 36]
    assert model["layer_types"] == conf["layer_types"][:10]
    assert model["layer_types"].count("attention") == 1
    assert model["state_dtype"] == "float32"
    for key in ("hidden_size", "intermediate_size",
                "shared_intermediate_size", "num_experts_per_tok",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "mamba_expand", "mamba_chunk_size",
                "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling", "rms_norm_eps"):
        assert model[key] == conf[key], key
    assert set(conf["not_served"]) == {"layers", "experts", "vocabulary"}
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-small")
        assert row["source_url"] == conf["source"]
        for key, value in row["config"].items():
            if key not in entry["reduced"]:
                assert conf[key] == value, key
    cfg = _config_of(model)
    assert cfg.state_bytes == 128 * 64 * 128 * 4 + 3 * 8448 * 2
    assert cfg.state_shapes == ((128, 64, 128), (3, 8448))


def test_the_cell_runs_at_a_tiny_size_through_the_harness():
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
            for k, d, name in (("config", "configs", "tiny-granite-serve"),
                               ("traffic", "traffic",
                                "tiny-granite-docs-decode-batch"))}
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
        state_gb = metrics["granite_state_gb_per_step"]["value"]
        slots = twin["config"]["engine"]["num_slots"]
        cfg = _config_of(twin["config"]["model"])
        most = 2 * slots * sum(cfg.state_layers) * cfg.state_bytes / 1e9
        assert 0 < state_gb <= most * (1 + 1e-6)
        assert metrics["granite_kv_gb_per_step"]["value"] > 0
        assert 0 <= metrics["granite_refill_share_pct"]["value"] <= 100
        held = metrics["moe_tokens_per_held_expert"]["value"]
        assert 0 < held <= slots * 3
    finally:
        set_registry(prev)
