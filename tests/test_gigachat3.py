"""GigaChat3 (DeepSeek-V3 blocks) at a small size on the CPU: the program
against the plain float32 reference (monolithic prefill, chunked prefill
at several chunk sizes and prompt lengths, a prefix-cache hit on another
request's blocks, decode through the pool, all through the server too),
the chunk kernel in interpret mode against its ``jax.numpy`` form, the
YaRN table and the softmax scale against a direct formula, group-limited
selection against a direct formula, the shares adding up to the uncut
layer, and the refusals by switch name.
"""
import argparse
import functools
import json
import math
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.lib import flops_gigachat, harness  # noqa: E402
from benchmark.lib import reference_gigachat as ref  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference import kv_cache as kc  # noqa: E402
from deepspeed_tpu.model_implementations import deepseek_v3 as dv  # noqa: E402
from deepspeed_tpu.model_implementations import held_experts  # noqa: E402
from deepspeed_tpu.model_implementations import (  # noqa: E402
    longcat_flash as lf)
from deepspeed_tpu.ops.pallas import (  # noqa: E402
    latent_chunk_attention as lca)
from deepspeed_tpu.telemetry import (MetricRegistry,  # noqa: E402
                                     get_registry, set_registry)

BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-gigachat3-ep16-shared-context-batch"
F32 = jnp.float32
BS = 16
ref.ROW_BLOCK = 24      # several query and key blocks at these lengths


def _family():
    return harness.load_family("deepseek_v3")


def _cfg(**over):
    """Hidden 64, 4 heads, ranks 24 / 16, nope 8 / rope 4 / v 12, 16
    experts in 4 groups, top-2 groups, top-4, one dense and three expert
    layers; YaRN with an original context of 16 positions so that its
    ramp is inside the tiny head."""
    base = dict(
        vocab_size=320, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=4,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=12, n_routed_experts=16, num_experts_per_tok=4,
        n_group=4, topk_group=2, rope_theta=1e5, rope_factor=64.0,
        rope_original_max_position_embeddings=16,
        max_position_embeddings=4096, experts_held=(0, 16), dtype=F32)
    return dv.DeepseekV3Config(**{**base, **over})


@functools.lru_cache(maxsize=None)
def _model(held=(4, 8)):
    cfg = _cfg(experts_held=held)
    return cfg, dv.init_params(jax.random.PRNGKey(5), cfg)


def _weights(cfg, params):
    return _family().reference_from_serve(cfg, params)


def _pool(cfg, slots=3, blocks=40, span_blocks=16):
    return kc.init_latent_paged_cache(
        cfg.attentions, slots, 1 + blocks, BS, span_blocks,
        cfg.latent_width, aux_shape=cfg.aux_shape, dtype=F32)


def _ids(n, seed=0, vocab=320):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def _table(cache, slot, blocks):
    row = np.zeros((cache.block_tables.shape[1],), np.int32)
    row[:len(blocks)] = blocks
    return cache.replace(block_tables=cache.block_tables.at[slot].set(row))


def _chunked(params, cfg, cache, slot, prompt, C, start=0):
    """The prompt from ``start`` on through ``paged_prefill_chunk``, as
    the server's chunk loop runs it; the last chunk's logits."""
    logits = None
    while start < len(prompt):
        ids = np.zeros((1, C), np.int32)
        part = prompt[start:start + C]
        ids[0, :len(part)] = part
        logits, cache = dv.paged_prefill_chunk(
            params, cfg, jnp.asarray(ids), jnp.int32(start),
            jnp.asarray([len(prompt)], jnp.int32), cache, jnp.int32(slot))
        start += C
    return logits, cache


# ---------------------------------------------------- program vs reference

def test_full_sequence_logits_match_the_reference():
    cfg, params = _model()
    ids = np.stack([_ids(53, 1), _ids(53, 2)])
    got = dv.causal_forward(params, cfg, jnp.asarray(ids))
    want = ref.logits(_weights(cfg, params), ids)
    assert _rel(got, want) < 1e-4


def test_monolithic_prefill_then_decode_match_the_reference():
    """One program for the prompt (its bucket padded), then eleven decode
    steps through the pool beside an idle and a second live slot."""
    cfg, params = _model()
    cache = _table(_table(_pool(cfg), 0, [3, 9, 4, 11]), 2, [7, 1, 2])
    prompts = {0: _ids(37, 3), 2: _ids(18, 4)}
    last = {}
    for slot, p in prompts.items():
        ids = np.zeros((1, 48), np.int32)
        ids[0, :len(p)] = p
        lg, cache = dv.paged_prefill(
            params, cfg, jnp.asarray(ids), jnp.asarray([len(p)], jnp.int32),
            cache, jnp.int32(slot))
        last[slot] = lg[0]
    seqs = {s: list(p) for s, p in prompts.items()}
    got = {s: [last[s]] for s in seqs}
    active = jnp.asarray([True, False, True])
    for _ in range(11):
        tokens = [int(jnp.argmax(got[s][-1])) if s in seqs else 0
                  for s in range(3)]
        for s in seqs:
            seqs[s].append(tokens[s])
        lg, cache = dv.paged_decode_step(params, cfg, jnp.asarray(tokens),
                                         cache, active)
        for s in seqs:
            got[s].append(lg[s])
    w = _weights(cfg, params)
    for s, p in prompts.items():
        want = ref.logits(w, [seqs[s]])[0][len(p) - 1:]
        assert _rel(jnp.stack(got[s]), want) < 1e-4
    assert [int(n) for n in cache.lengths] == [48, 0, 29]


def test_the_tpu_decode_path_leaves_what_the_cpu_path_leaves(monkeypatch):
    """Three decode steps over two live slots and an idle one between
    them (one slot's rows cross into a fresh block), through the TPU path
    (the latent kernel appends and attends; it and the grouped matmul in
    interpret mode) against the CPU path (scatter, then the
    ``jax.numpy`` attention): logits, lengths and every pool, the null
    block included."""
    from deepspeed_tpu.ops.pallas import grouped_matmul
    from deepspeed_tpu.ops.pallas import latent_decode_attention as lda
    cfg, params = _model()
    cache = _table(_table(_pool(cfg), 0, [3, 9, 4, 11]), 2, [7, 8, 2])
    for slot, p in {0: _ids(37, 3), 2: _ids(15, 4)}.items():
        ids = np.zeros((1, 48), np.int32)
        ids[0, :len(p)] = p
        _, cache = dv.paged_prefill(
            params, cfg, jnp.asarray(ids), jnp.asarray([len(p)], jnp.int32),
            cache, jnp.int32(slot))
    active = jnp.asarray([True, False, True])

    def run():
        step = jax.jit(lambda *a: dv.paged_decode_step(a[0], cfg, *a[1:]))
        after, logits = cache, []
        for tokens in ([5, 0, 9], [11, 0, 2], [4, 0, 8]):
            lg, after = step(params, jnp.asarray(tokens), after, active)
            logits.append(lg)
        return jnp.stack(logits), after
    want, pools = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_should_interpret", lambda: True)
    monkeypatch.setattr(lda, "paged_latent_decode_attention",
                        functools.partial(lda.paged_latent_decode_attention,
                                          interpret=True))
    got, after = run()
    assert _rel(got, want) < 1e-5
    assert [int(n) for n in after.lengths] == [40, 0, 18]
    assert (np.asarray(after.aux) == np.asarray(pools.aux)).all()
    for a, b, c in zip(after.rows, pools.rows, cache.rows):
        assert (np.asarray(a[0]) == np.asarray(c[0])).all()
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())
    # the first attention's first row a slot is the scatter's to the bit
    differ = np.asarray(after.rows[0]) != np.asarray(pools.rows[0])
    assert differ.sum() <= 2 * 2 * cfg.latent_width


@pytest.mark.parametrize("chunk,length", [
    (16, 16), (16, 41), (32, 64), (32, 33), (48, 100), (64, 9)])
def test_chunked_prefill_matches_the_reference(chunk, length):
    """Chunks of one to four blocks; prompts that end on a block edge,
    on a chunk edge, one row into a chunk, and inside the first chunk.
    The blocks of the slot are scattered over the pool."""
    cfg, params = _model()
    blocks = [13, 2, 30, 8, 21, 5, 17, 9]
    cache = _table(_pool(cfg), 1, blocks[:-(-(length + 8) // BS)])
    prompt = _ids(length, length)
    logits, cache = _chunked(params, cfg, cache, 1, prompt, chunk)
    w = _weights(cfg, params)
    assert _rel(logits[0], ref.logits(w, [prompt])[0][-1]) < 1e-4
    assert int(cache.lengths[1]) == length
    # and the rows it left decode on
    tok = int(jnp.argmax(logits[0]))
    lg, cache = dv.paged_decode_step(
        params, cfg, jnp.asarray([0, tok, 0]), cache,
        jnp.asarray([False, True, False]))
    assert _rel(lg[1], ref.logits(w, [prompt + [tok]])[0][-1]) < 1e-4


def test_a_hit_on_another_slots_blocks_gives_the_cold_logits():
    """Slot 0 prefills a prompt cold, by chunks. Slot 1's table names
    slot 0's first four blocks (what a prefix-cache hit maps) and blocks
    of its own after them; it prefills only its tail, from position 64.
    Its logits are the cold request's to 1e-4, and those of the
    reference; one wrong entry among the shared blocks breaks them."""
    cfg, params = _model()
    shared = _ids(64, 7)
    first, second = shared + _ids(20, 8), shared + _ids(37, 9)
    cache = _table(_pool(cfg), 0, [5, 6, 7, 8, 9, 10])
    _, cache = _chunked(params, cfg, cache, 0, first, 32)
    cold, _ = _chunked(params, cfg, _table(cache, 2, [20, 21, 22, 23, 24,
                                                      25, 26]),
                       2, second, 32)
    hit = _table(cache, 1, [5, 6, 7, 8, 30, 31, 32]).replace(
        lengths=cache.lengths.at[1].set(64))
    warm, after = _chunked(params, cfg, hit, 1, second, 32, start=64)
    assert _rel(warm[0], cold[0]) < 1e-4
    w = _weights(cfg, params)
    assert _rel(warm[0], ref.logits(w, [second])[0][-1]) < 1e-4
    assert int(after.lengths[1]) == len(second)
    wrong = _table(cache, 1, [5, 6, 9, 8, 30, 31, 32])
    off, _ = _chunked(params, cfg, wrong, 1, second, 32, start=64)
    assert _rel(off[0], cold[0]) > 1e-2


# ------------------------------------------------------ through the server

def _server(num_slots=3, pool=60, registry=None, **knobs):
    cfg, params = _model()
    engine = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=BS,
        num_slots=num_slots, max_queued_requests=32, kv_pool_blocks=pool,
        **knobs))
    return cfg, params, ContinuousBatchingServer(engine, registry=registry)


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
def test_served_with_chunks_and_prefix_reuse(async_loop):
    """Seven requests over two shared contexts through three slots, all
    queued at once: each context is prefilled cold ONCE (nothing is
    admitted while a prefill is in flight, so the next request of a
    context finds its blocks published), every
    other request hits them and prefills only its turn, every
    served token is the reference's choice, and every block comes back."""
    reg = MetricRegistry()
    cfg, params, server = _server(prefill_chunk_tokens=32,
                                  enable_prefix_caching=True,
                                  async_loop=async_loop, registry=reg)
    a, b = _ids(96, 11), _ids(96, 12)
    turns = [(a, 5), (a, 40), (b, 17), (a, 1), (b, 33), (b, 64), (a, 16)]
    prompts = [ctx + _ids(n, 20 + i) for i, (ctx, n) in enumerate(turns)]
    served = _serve(server, prompts, 20)
    stats = server.stats
    # five hits of six shared blocks; nothing but the two contexts and
    # the turns was prefilled
    assert stats["prefix_cache_hits"] == 5 * 6
    assert stats["prefix_tokens_skipped"] == 5 * 96
    snap = reg.snapshot()
    tokens = {s["labels"]["source"]: s["value"]
              for s in snap["serve_prompt_tokens_total"]["series"]}
    assert tokens == {"cached": 5 * 96,
                      "prefilled": sum(len(p) for p in prompts) - 5 * 96}
    assert (snap["serve_prefill_chunk_rows_total"]["series"][0]["value"]
            == 32 * stats["prefill_chunks"])
    rows = {s["labels"]["program"]: s["value"]
            for s in snap["serve_kv_rows_read_total"]["series"]
            if s["labels"]["kind"] == "latent"}
    assert rows["decode"] > 0 and rows["prefill"] > 0
    alloc = server.scheduler.allocator
    assert alloc.live_blocks == 0
    server.close()
    _held_to_reference(cfg, params, prompts, served)


def test_chunk_phase_spans_name_the_program_and_the_position():
    """A step that ran chunks says so on its ``serve:prefill_chunk``
    span: the program, the first chunk's position, how many, their
    rows."""
    from deepspeed_tpu.telemetry.spans import get_span_log
    cfg, params, server = _server(prefill_chunk_tokens=32,
                                  enable_prefix_caching=True)
    shared = _ids(64, 31)
    _serve(server, [shared + _ids(30, 32), shared + _ids(9, 33)], 4)
    server.close()
    notes = [r[6] for r in get_span_log().snapshot(prefix="serve:")
             if r[0] == "serve:prefill_chunk" and (r[6] or {}).get("program")]
    assert [(n["start"], n["chunks"], n["rows"]) for n in notes[-4:]] == [
        (0, 1, 32), (32, 1, 32), (64, 1, 32), (64, 1, 32)]
    assert {n["program"] for n in notes} == {"serve_prefill_chunk"}


# ----------------------------------------------------------------- kernels

@pytest.mark.parametrize("start", [0, 16, 48, 80])
def test_chunk_kernel_matches_its_oracle(start):
    """Interpret mode over a POISONED pool: the blocks the table does not
    name, and the table entries past the chunk's last row, hold NaN."""
    H, C, Dn, Dr, Dv, R, bs = 4, 32, 8, 4, 12, 16, 16
    k = jax.random.split(jax.random.PRNGKey(start), 4)
    q = jax.random.normal(k[0], (H, C, Dn + Dr))
    wk = jax.random.normal(k[1], (H, Dn, R))
    wv = jax.random.normal(k[2], (H, Dv, R))
    table = np.array([4, 9, 2, 11, 6, 13, 1, 0], np.int32)
    live = -(-(start + C) // bs)
    pool = np.full((16, R + Dr, bs), np.nan, np.float32)
    pool[table[:live]] = jax.random.normal(k[3], (live, R + Dr, bs))
    got = lca.latent_chunk_attention(
        q, jnp.asarray(pool), jnp.asarray(table), jnp.int32(start), wk, wv,
        scale=0.3, interpret=True)
    clean = jnp.nan_to_num(jnp.asarray(pool))
    want = lca.latent_chunk_attention_reference(
        q, clean, jnp.asarray(table), jnp.int32(start), wk, wv, scale=0.3)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_chunk_kernel_sees_neither_later_rows_nor_other_tables():
    """Row ``i`` of a chunk attends positions ``<= start + i``: changing
    a later row of the chunk's own block, or a block past it, moves no
    earlier row's output."""
    H, C, Dn, Dr, Dv, R, bs = 2, 16, 8, 4, 12, 16, 16
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(k[0], (H, C, Dn + Dr))
    wk, wv = (jax.random.normal(k[1], (H, Dn, R)),
              jax.random.normal(k[2], (H, Dv, R)))
    pool = jax.random.normal(k[3], (6, R + Dr, bs))
    table = jnp.array([1, 2, 3, 4], jnp.int32)
    run = functools.partial(lca.latent_chunk_attention, q, table=table,
                            start=jnp.int32(16), wk=wk, wv=wv, scale=0.3,
                            interpret=True)
    base = run(pool=pool)
    later = run(pool=pool.at[2, :, 9:].add(5.0).at[3].add(5.0))
    assert float(jnp.max(jnp.abs(later[:, :9] - base[:, :9]))) == 0.0
    assert float(jnp.max(jnp.abs(later[:, 9:] - base[:, 9:]))) > 1e-3


def test_operation_counts_of_the_two_forms():
    """At the cell's shapes a chunk of 1024 rows over a 32768-row context
    costs 1.42 x its attention in the materialised form (the rebuild) and
    2.83 x in the absorbed form."""
    s = dict(heads=64, qk_dim=192, v_dim=192, kv_rank=512, nope_dim=128)
    attn = flops_gigachat.chunk_attention_flops(32768, 1024, 64, 192, 192)
    built = flops_gigachat.chunk_kernel_flops(32768, 1024, s, 128)
    absorbed = flops_gigachat.absorbed_chunk_flops(32768, 1024, 64, 512, 64)
    assert attn == 2 * 384 * 64 * (1024 * 32768 + 1024 * 1025 / 2)
    assert 1.41 < built / attn < 1.43
    assert 2.82 < absorbed / attn < 2.84


# ------------------------------------------------- rotary table and scale

def test_yarn_table_and_softmax_scale_match_a_direct_formula():
    """The published values: theta 1e5, factor 64 over 4096 positions,
    beta 32 / 1, mscale = mscale_all_dim = 1, 64 rope dims."""
    cfg = dv.DeepseekV3Config(vocab_size=8, rope_theta=100000.0,
                              rope_factor=64.0, v_head_dim=192,
                              num_attention_heads=64)
    inv, times = dv.rope_table(cfg.rope_spec, 64)
    dim, base, orig = 64, 1e5, 4096

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))
    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    for j in range(32):
        plain = base ** (-2 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        want = plain * (1 - ramp) + plain / 64 * ramp
        assert abs(inv[j] - want) <= 1e-6 * want
    assert inv[0] == 1.0 and abs(inv[31] * 64 - base ** (-62 / 64)) < 1e-9
    assert times == 1.0
    m = 0.1 * math.log(64) + 1
    assert abs(m - 1.41589) < 1e-5
    assert abs(cfg.attn_scale - m * m / math.sqrt(192)) < 1e-12
    w = {"nope": 128, "rope": 64, "yarn": tuple(sorted(dict(
        factor=64.0, original_max_position_embeddings=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0).items()))}
    assert abs(ref.softmax_scale(w) - cfg.attn_scale) < 1e-12
    inv_ref, times_ref = ref.rope_frequencies(1e5, dict(w["yarn"]), 64)
    assert np.allclose(inv_ref, inv, rtol=1e-6) and times_ref == 1.0


def test_rope_turns_interleaved_pairs():
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 2, 4))
    pos = jnp.array([0, 5, 900])
    y = dv._rope(x, pos, cfg)
    inv, _ = dv.rope_table(cfg.rope_spec, 4)
    for t, p in enumerate([0, 5, 900]):
        for j in range(2):
            c, s = math.cos(p * inv[j]), math.sin(p * inv[j])
            a, b = x[t, :, 2 * j], x[t, :, 2 * j + 1]
            assert np.allclose(y[t, :, 2 * j], a * c - b * s, atol=1e-5)
            assert np.allclose(y[t, :, 2 * j + 1], b * c + a * s, atol=1e-5)


# ------------------------------------------------------------------ routing

def _direct_picks(choice, k, n_group, topk_group):
    """Group-limited selection written out a token at a time; ties to the
    lower index (a stable sort of the negated values)."""
    out = []
    per = choice.shape[1] // n_group
    for row in np.asarray(choice, np.float64):
        score = [np.sort(row[g * per:(g + 1) * per])[-2:].sum()
                 for g in range(n_group)]
        kept = np.argsort(-np.asarray(score), kind="stable")[:topk_group]
        allowed = [e for g in sorted(kept) for e in range(g * per,
                                                          (g + 1) * per)]
        order = np.argsort(-row[allowed], kind="stable")[:k]
        out.append([allowed[i] for i in order])
    return np.asarray(out)


def test_group_limited_selection_matches_a_direct_formula():
    rng = np.random.default_rng(0)
    choice = rng.random((200, 16)).astype(np.float32)
    # ties: between groups' scores, and between experts at the k-th place
    choice[0] = [.9, .1, 0, 0, .5, .5, 0, 0, .9, .1, 0, 0, .2, .2, 0, 0]
    choice[1] = [.5, .5, .5, .5, .5, .5, .5, .5, .5, .5, .5, .5, .1, 0, 0, 0]
    choice[2] = 0.25
    got = held_experts.group_limited_top_k(jnp.asarray(choice), 4, 4, 2)
    want = _direct_picks(choice, 4, 4, 2)
    assert (np.asarray(got) == want).all()
    # groups 0, 1 and 2 tie at 1.0: the lower two are kept
    assert want[0].tolist() == [0, 4, 5, 1]
    assert want[2].tolist() == [0, 1, 2, 3]
    # the reference's own selection is the same rule: with a zero router
    # every score is 0.5, and ``choice`` comes in as the bias
    for row in (0, 1, 2, 50):
        picks, weights = ref.route(
            jnp.zeros((1, 8)), jnp.zeros((8, 16)),
            jnp.asarray(choice[row] - 0.5), top_k=4, n_group=4,
            topk_group=2, factor=2.5)
        assert np.asarray(picks)[0].tolist() == want[row].tolist()
        assert np.allclose(weights, 2.5 / 4)


def test_router_weights_and_the_group_limit():
    """Weights are the picked scores normalised to the factor; the bias
    moves the selection and not the weights; picks stay inside the kept
    groups, which an ungrouped top-k of the same scores would leave."""
    cfg, params = _model()
    moe = params["layers"][1]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.hidden_size))
    picks, weights = dv._route(u, moe, cfg)
    scores = jax.nn.sigmoid(u @ moe["router"])
    picked = jnp.take_along_axis(scores, picks, -1)
    assert np.allclose(weights, 2.5 * picked / picked.sum(-1, keepdims=True),
                       rtol=1e-5)
    assert np.allclose(weights.sum(-1), 2.5, rtol=1e-5)
    assert (np.asarray(picks) == _direct_picks(
        np.asarray(scores + moe["router_bias"]), 4, 4, 2)).all()
    groups = np.asarray(picks) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    free = jax.lax.top_k(scores + moe["router_bias"], 4)[1]
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(picks))).any()
    big = dict(moe, router_bias=moe["router_bias"].at[3].add(10.0))
    p2, w2 = dv._route(u, big, cfg)
    assert (np.asarray(p2) == 3).any(-1).all()
    assert np.allclose(w2.sum(-1), 2.5, rtol=1e-5)


def test_the_seeded_bias_loads_every_share_and_group_alike():
    cfg = dv.DeepseekV3Config(vocab_size=8, v_head_dim=192,
                              num_attention_heads=64)
    b = np.asarray(dv.router_bias(cfg)).reshape(16, 16)
    assert (np.sort(b, axis=1) == np.sort(b[0])).all()
    assert abs(b.sum()) < 1e-6 and np.unique(b[0]).size == 16


def test_the_shares_of_one_expert_layer_sum_to_the_uncut_layer():
    """Four chips hold four experts each: their routed parts, plus what
    every chip computes alike (the shared expert) counted once, are the
    uncut layer, which is the reference's with every expert held."""
    cfg, params = _model(held=(0, 16))
    moe = params["layers"][2]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden_size))
    valid = jnp.ones((40,), bool)
    whole, _ = dv.moe_layer(u, moe, cfg, valid)
    shared = dv._shared_expert(u, moe["shared"])
    parts = []
    for lo in range(0, 16, 4):
        c = _cfg(experts_held=(lo, lo + 4))
        share = dict(moe, experts=jax.tree.map(lambda w: w[lo:lo + 4],
                                               moe["experts"]))
        part, counts = dv.moe_layer(u, share, c, valid)
        parts.append(part - shared)
        assert int(counts[:4].sum()) + int(counts[5]) == 40 * 4
    assert _rel(sum(parts) + shared, whole) < 1e-5
    layer = _weights(cfg, params)["layers"][2]
    z = _weights(cfg, params)["sizes"]
    want = ref._sparse(jnp.zeros_like(u), u, layer, z)
    assert _rel(whole, want) < 1e-4


@pytest.mark.parametrize("cancel_first", [False, True])
def test_nothing_is_admitted_behind_a_prefill_in_flight(cancel_first):
    """Three requests of one context queued together, three free slots:
    one is admitted, the others wait in the queue and hit what it
    published (6 blocks each). A job that leaves mid-prefill publishes
    nothing and holds nobody back: the second prefills cold, the third
    hits."""
    shared = _ids(96, 41)
    prompts = [shared + _ids(n, 42 + n) for n in (5, 9, 13)]
    cfg, params, server = _server(prefill_chunk_tokens=32,
                                  enable_prefix_caching=True)
    ids = [server.submit(p, max_new_tokens=6, eos_token_id=None)
           for p in prompts]
    server.step()
    assert server.scheduler.active_slots == 1
    if cancel_first:
        server.cancel(ids[0])
        server.step()
        assert server.scheduler.active_slots == 1
        assert server.scheduler.find_slot(ids[1]) is not None
    while not server.scheduler.idle:
        server.step()
    assert server.stats["prefix_cache_hits"] == (6 if cancel_first else 12)
    keep = slice(1, None) if cancel_first else slice(None)
    served = [server.result(i)[len(p):]
              for i, p in zip(ids[keep], prompts[keep])]
    server.close()
    _held_to_reference(cfg, params, prompts[keep], served)


# ---------------------------------------------------------------- refusals

@pytest.mark.parametrize("switch,value", [
    ("prefill_chunk_tokens", 32), ("enable_prefix_caching", True)])
def test_a_latent_family_without_a_chunk_entry_point_is_refused(switch,
                                                                value):
    assert not hasattr(lf, "paged_prefill_chunk")
    cfg = lf.LongcatFlashConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_attention_heads=2,
        ffn_hidden_size=48, expert_ffn_hidden_size=16, q_lora_rank=12,
        kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, n_routed_experts=4, zero_expert_num=2, moe_topk=2,
        experts_held=(0, 4), dtype=F32)
    engine = InferenceEngine(
        (cfg, lf.init_params(jax.random.PRNGKey(0), cfg)),
        DeepSpeedInferenceConfig(dtype="float32", max_out_tokens=64,
                                 block_size=BS, num_slots=2,
                                 **{switch: value}))
    with pytest.raises(NotImplementedError) as e:
        ContinuousBatchingServer(engine)
    assert switch in str(e.value) and "paged_prefill_chunk" in str(e.value)


@pytest.mark.parametrize("switch,conf", [
    ("kv_cache_dtype", {"kv_cache_dtype": "int8"}),
    ("kv_host_offload", {"kv_host_offload": True,
                         "enable_prefix_caching": True}),
    ("prefill_chain", {"prefill_chain": True, "prefill_chunk_tokens": 32}),
    ("speculation_tokens", {"speculation_tokens": 4}),
    ("speculation_draft / draft_engine",
     {"speculation_tokens": 4, "draft": True}),
    ("handoff_import", {"handoff": True, "enable_prefix_caching": True})])
def test_switches_the_latent_cache_still_cannot_honour_are_refused(switch,
                                                                   conf):
    cfg, params = _model()
    conf = dict(conf)
    draft, handoff = conf.pop("draft", False), conf.pop("handoff", False)
    engine = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=64, block_size=BS, num_slots=2,
        **conf))
    with pytest.raises(NotImplementedError) as e:
        ContinuousBatchingServer(engine, handoff_import=handoff,
                                 draft_engine=engine if draft else None)
    assert switch in str(e.value)
    assert "prefill_chunk_tokens" not in str(e.value).split(" — ")[0]


@pytest.mark.parametrize("switch,conf", [
    ("int8", dict(dtype="int8")),
    ("tp_size", dict(tensor_parallel={"tp_size": 2}))])
def test_engine_switches_are_refused_by_name(switch, conf):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=switch) as e:
        InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
            **{"max_out_tokens": 64, **conf}))
    assert "DeepseekV3Config" in str(e.value)


# ------------------------------------------------------- the configuration

def test_configuration_file_states_the_published_sizes():
    """The top level holds every number of the catalog entry under its
    key, the three reduced ones at their reduced values; the ``model``
    block is what runs and differs only where the cut is stated another
    way (the router's width, the leading dense layers)."""
    contract = harness.load_contract()
    entry = harness.find(contract["configs"], "gigachat3-702b-ep16-serve",
                         "config")
    conf = harness.load_json(os.path.join(REPO, entry["file"]))
    assert entry["source"] == conf["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        rows = [json.loads(line) for line in open(catalog)]
        row = next(r for r in rows if r["name"] == "GigaChat3.1-702B-A36B")
        assert conf["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == value, key
    assert sorted(conf["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (6, 16, 16032)
    model = conf["model"]
    for key, value in model.items():
        if key in conf and key not in ("n_routed_experts",
                                       "first_k_dense_replace"):
            assert conf[key] == value, key
    lo, hi = model["experts_held"]
    assert hi - lo == conf["n_routed_experts"]
    assert model["n_routed_experts"] == 256 and conf["v_head_dim"] == 192
    assert model["first_k_dense_replace"] == 1
    assert "num_nextn_predict_layers" in conf["not_served"]
    assert {"rope", "group_score", "router", "final_norm",
            "seeded_init"} <= set(conf["assumed"])
    engine = conf["engine"]
    assert (engine["num_slots"], engine["prefill_chunk_tokens"],
            engine["enable_prefix_caching"],
            engine["kv_pool_blocks"]) == (48, 1024, True, 2560)
    cell = harness.resolve_cell(contract, CELL)
    traffic = cell["traffic"]
    assert (traffic["requests"], traffic["shared_prefix_tokens"],
            traffic["shared_prefix_groups"]) == (192, 32768, 4)
    assert traffic["prompt_len"] == {"dist": "loguniform", "lo": 32896,
                                     "hi": 33792}
    assert traffic["check"]["per_bucket"] == 2
    assert {"serve_out_tokens_per_s", "setup_s"} <= set(cell["end_to_end"])
    new = [m for m in contract["per_layer"]
           if m.get("workloads") == [CELL]]
    assert len(new) == 6 and all(m["name"].startswith("gigachat_")
                                 for m in new)
    for m in contract["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               m["name"] + ".py"))


def test_the_cell_runs_at_a_tiny_size_through_the_harness(tmp_path):
    """The harness's own runner, the real readers and family, the tiny
    twins: correct, nothing failed, the backlog never dry, every
    counter-fed and span-fed metric prints a number, and nearly every
    prompt token after the fill came from the cache."""
    prev = get_registry()
    set_registry(MetricRegistry())
    try:
        root = tmp_path / "bench"
        for d in ("metrics", "models"):
            shutil.copytree(os.path.join(BENCH, d), root / d)
        os.makedirs(root / "configs")
        os.makedirs(root / "traffic")
        shutil.copy(os.path.join(BENCH, "testdata", "configs",
                                 "tiny-gigachat-serve.json"),
                    root / "configs")
        shutil.copy(os.path.join(BENCH, "testdata", "traffic",
                                 "tiny-gigachat-shared-context-batch.json"),
                    root / "traffic")
        contract = json.loads(json.dumps(harness.load_contract()))
        contract["configs"] = [{
            "name": "tiny-gigachat-serve",
            "file": "bench/configs/tiny-gigachat-serve.json"}]
        contract["workloads"] = [{
            "name": CELL, "config": "tiny-gigachat-serve",
            "traffic": "tiny-gigachat-shared-context-batch", "chips": 1}]
        cell = harness.resolve_cell(contract, CELL, repo=str(tmp_path))
        args = argparse.Namespace(seed=2 ** 31 + 5, seconds=1.0, trace=0)
        run, _ = harness.run_cell(cell, args, time.time(),
                                  jax.devices()[:1], "TPU v5 lite")
        assert all(run["checks"].values()), (run["checks"],
                                             run["reference_check"])
        assert run["failed"] == 0 and run["attempted"] > 0
        metrics = harness.read_metrics(
            cell["end_to_end"] + cell["per_layer"], run, None,
            harness.units_of(contract), cell["root"])
        for name in ("serve_out_tokens_per_s", "setup_s",
                     "moe_tokens_per_held_expert",
                     "moe_held_load_max_over_mean", "batch_peak_hbm_gb",
                     "gigachat_prefix_hit_pct", "gigachat_refill_share_pct",
                     "gigachat_latent_gb_per_step",
                     "serve_pipelined_steps_pct"):
            assert name in metrics, name
        assert 40 < metrics["gigachat_prefix_hit_pct"]["value"] < 100
        assert 0 < metrics["gigachat_refill_share_pct"]["value"] < 100
    finally:
        set_registry(prev)
