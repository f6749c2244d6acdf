"""LongCat-Flash on the normal serving path, at a small size on the CPU:
widths cut, structure whole (2 double-block layers, 2 latent attentions
each, real and identity experts, top-k > 1, a non-zero correction bias,
a share of the experts held).

The program (``model_implementations/longcat_flash.py`` through
``InferenceEngine`` + ``ContinuousBatchingServer`` + the latent paged
cache) is held to the plain reference (``benchmark/lib/
reference_longcat.py``), and the benchmark's cell is rehearsed at the
tiny size through the harness's own runner and readers.
"""
import argparse
import json
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

from benchmark.lib import harness  # noqa: E402
from deepspeed_tpu.inference import (ContinuousBatchingServer,  # noqa: E402
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference.kv_cache import (  # noqa: E402
    init_latent_paged_cache)
from deepspeed_tpu.model_implementations import (  # noqa: E402
    longcat_flash as lf)
from deepspeed_tpu.model_implementations import transformer  # noqa: E402
from deepspeed_tpu.ops.pallas import grouped_matmul  # noqa: E402
from deepspeed_tpu.ops.pallas import latent_decode_attention as lda  # noqa: E402

BENCH = os.path.join(REPO, "benchmark")
family = harness.load_family("longcat_flash")
ref = family.reference

MODEL = dict(
    family="longcat_flash", dtype="float32", vocab_size=256, hidden_size=64,
    num_layers=2, num_attention_heads=4, ffn_hidden_size=128,
    expert_ffn_hidden_size=32, q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    mla_scale_q_lora=True, mla_scale_kv_lora=True, n_routed_experts=8,
    zero_expert_num=4, moe_topk=3, routed_scaling_factor=6,
    rms_norm_eps=1e-5, rope_theta=1e7, max_position_embeddings=1024,
    experts_held=[2, 6])
BS, MB, SLOTS = 16, 4, 3


def _model(dtype="float32", held=(2, 6), seed=3):
    return family.serve_model(dict(MODEL, dtype=dtype,
                                   experts_held=list(held)), seed)


@pytest.fixture(scope="module")
def f32():
    cfg, params = _model()
    assert float(jnp.abs(params["layers"][0]["moe"]["router_bias"]).max()) > 0
    return cfg, params, family.reference_from_serve(cfg, params)


def _ids(n, t, seed=0):
    return np.random.default_rng(seed).integers(1, MODEL["vocab_size"],
                                                size=(n, t))


def _rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


def _pool(cfg, slots=SLOTS):
    """A latent pool whose slot ``s`` owns blocks ``1 + s MB ..``."""
    cache = init_latent_paged_cache(
        cfg.attentions, slots, 1 + slots * MB, BS, MB, cfg.latent_width,
        aux_shape=cfg.aux_shape, dtype=cfg.dtype)
    return cache.replace(block_tables=jnp.asarray(
        1 + np.arange(slots * MB).reshape(slots, MB), jnp.int32))


# ------------------------------------------- float32 program = reference

def test_full_sequence_logits_match_the_reference(f32):
    cfg, params, weights = f32
    ids = _ids(2, 24)
    got = transformer.causal_forward(params, cfg, jnp.asarray(ids))
    assert _rel(got, ref.logits(weights, ids)) < 1e-4


def test_paged_prefill_and_decode_match_the_reference(f32):
    """Prompts of two lengths prefilled into two slots (the third stays
    idle), then five decode steps over the pool: the logits of every
    step are the reference's at that position of the full sequence."""
    cfg, params, weights = f32
    lens, steps = (11, 27), 5
    seqs = _ids(2, max(lens) + steps, seed=1)
    cache = _pool(cfg)
    prefill = jax.jit(lambda *a: transformer.paged_prefill(a[0], cfg, *a[1:]))
    decode = jax.jit(lambda *a: transformer.paged_decode_step(a[0], cfg,
                                                              *a[1:]))
    logits = {}
    for s, n in enumerate(lens):
        ids = np.zeros((1, 2 * BS), np.int32)
        ids[0, :n] = seqs[s, :n]
        lg, cache = prefill(
            params, jnp.asarray(ids), jnp.asarray([n], jnp.int32),
            cache, jnp.int32(s))
        logits[s, n - 1] = lg[0]
    active = jnp.asarray([True, True, False])
    for k in range(steps):
        toks = [seqs[s, n + k] for s, n in enumerate(lens)] + [0]
        lg, cache = decode(params, jnp.asarray(toks, jnp.int32), cache,
                           active)
        for s, n in enumerate(lens):
            logits[s, n + k] = lg[s]
    assert [int(x) for x in cache.lengths] == [n + steps for n in lens] + [0]
    for s, n in enumerate(lens):
        want = ref.logits(weights, seqs[s:s + 1, :n + steps])[0]
        for (slot, pos), got in logits.items():
            if slot == s:
                assert _rel(got, want[pos]) < 1e-4, (slot, pos)
    # the routing counters: every valid token routed once a layer, each
    # with top-k picks that are held, absent or identity
    c = np.asarray(cache.aux)
    held = cfg.num_held
    tail = dict(zip(lf.COUNTER_TAIL, c[:, held:].T))
    # (rows: decode, prefill, decode_admit, which did not run)
    assert list(tail["tokens_routed"]) == [2 * steps * cfg.num_layers,
                                           sum(lens) * cfg.num_layers, 0]
    assert list(tail["layer_calls"]) == [steps * cfg.num_layers,
                                         2 * cfg.num_layers, 0]
    assert (c[:, :held].sum(1) + tail["identity_picks"]
            + tail["absent_picks"] == tail["tokens_routed"] * cfg.moe_topk
            ).all()


@pytest.mark.parametrize("n,bucket,decoding", [
    (20, 2 * BS, True), (9, BS, True), (20, 2 * BS, False)],
    ids=["shorter-than-its-bucket", "one-block-bucket", "nothing-decoding"])
def test_decode_admit_is_the_decode_step_and_the_prefill_in_one_forward(
        f32, n, bucket, decoding):
    """``paged_decode_admit`` over two decoding slots and one prompt
    admitted into the third: the decode rows' logits are
    ``paged_decode_step``'s, the rider's are ``paged_prefill``'s last
    live row, and pool rows, lengths and tables are those of the two
    programs run one after the other. Its routing counters have a row
    of their own, the sum of what the two programs count, and leave
    ``decode`` alone. With no slot decoding (an empty server filling up)
    it is the prefill alone: the decode rows append nothing and are left
    out of the attention and the dense FFNs."""
    cfg, params, _ = f32
    seqs = _ids(3, 40, seed=1)
    cache = _pool(cfg)
    prefill = jax.jit(lambda *a: lf.paged_prefill(a[0], cfg, *a[1:]))
    decode = jax.jit(lambda *a: lf.paged_decode_step(a[0], cfg, *a[1:]))
    admit = jax.jit(lambda *a: lf.paged_decode_admit(a[0], cfg, *a[1:]))
    for s, m in enumerate((11, 27)):
        ids = np.zeros((1, 2 * BS), np.int32)
        ids[0, :m] = seqs[s, :m]
        _, cache = prefill(params, jnp.asarray(ids),
                           jnp.asarray([m], jnp.int32), cache, jnp.int32(s))
    before = np.asarray(cache.aux)
    tokens = jnp.asarray([5, 7, 0] if decoding else [0, 0, 0], jnp.int32)
    active = jnp.asarray([decoding, decoding, False])
    ids = np.zeros((1, bucket), np.int32)
    ids[0, :n] = seqs[2, :n]
    rider = (jnp.asarray(ids), jnp.asarray([n], jnp.int32), jnp.int32(2))
    got, both = admit(params, tokens, cache, active, *rider)
    stepped, one = decode(params, tokens, cache, active)
    first, two = prefill(params, rider[0], rider[1], one, rider[2])
    if decoding:
        assert _rel(got[:2], stepped[:2]) < 1e-6
    assert _rel(got[2], first[0]) < 1e-6
    assert [int(x) for x in both.lengths] == [11 + decoding, 27 + decoding,
                                              n]
    assert (np.asarray(both.lengths) == np.asarray(two.lengths)).all()
    assert (np.asarray(both.block_tables)
            == np.asarray(two.block_tables)).all()
    # (an idle row appends nothing: the null block, block 0, stays as it
    # was; with nothing decoding no row is appended anywhere)
    for a, b, c in zip(both.rows, two.rows, cache.rows):
        assert (np.asarray(a[0]) == np.asarray(c[0])).all()
        if decoding:
            assert float(jnp.abs(a - b).max()) < 1e-6
        else:
            blocks = np.asarray(cache.block_tables)[2, :bucket // BS]
            assert float(jnp.abs(a[blocks] - b[blocks]).max()) < 1e-6
            rest = np.setdiff1d(np.arange(a.shape[0]), blocks)
            assert (np.asarray(a[rest]) == np.asarray(c[rest])).all()
    grew = np.asarray(both.aux) - before
    apart = np.asarray(two.aux) - before
    d, p, da = (lf.PROGRAMS.index(x) for x in ("decode", "prefill",
                                               "decode_admit"))
    assert not grew[d].any() and not grew[p].any()
    tail = {name: cfg.num_held + i for i, name in enumerate(lf.COUNTER_TAIL)}
    # what a token or a pick adds is the two programs' sum ...
    additive = list(range(cfg.num_held)) + [
        tail[x] for x in ("identity_picks", "absent_picks", "tokens_routed")]
    assert (grew[da][additive] == (apart[d] + apart[p])[additive]).all()
    assert grew[da][tail["tokens_routed"]] == (2 * decoding + n
                                               ) * cfg.num_layers
    # ... and the layer ran once, over the union of their rows
    assert grew[da][tail["layer_calls"]] == cfg.num_layers
    hit = tail["held_experts_hit"]
    assert max(apart[d][hit], apart[p][hit]) <= grew[da][hit] <= (
        apart[d][hit] + apart[p][hit])
    if not decoding:
        assert (grew[da] == apart[p]).all()


# --------------------------------------- absorbed = materialised attention

def test_absorbed_decode_equals_materialised_attention_on_one_cache(f32):
    """The same cached rows, attended both ways: K and V built per head
    from the latent (what prefill does) against queries carried into the
    latent space (what decode does)."""
    cfg, params, _ = f32
    a = params["layers"][0]["attn"][1]
    rng = np.random.default_rng(5)
    T = 23
    h = jnp.asarray(rng.normal(size=(1, T, cfg.hidden_size)), jnp.float32)
    positions = jnp.arange(T)[None]
    q_nope, q_rope, rows = lf._mla_project(h, a, cfg, positions)
    want = lf._materialised_attention(q_nope, q_rope, rows, a, cfg)[0, -1]
    cache = _pool(cfg, slots=1)
    padded = jnp.zeros((2 * BS, cfg.latent_width)).at[:T].set(rows[0])
    from deepspeed_tpu.inference.kv_cache import latent_write_prompt
    cache = latent_write_prompt(cache, 1, padded, jnp.int32(0)).replace(
        lengths=jnp.asarray([T - 1], jnp.int32))
    # (the decode step appends the last row itself, over its own copy)
    after, got = lf._absorbed_attention(
        q_nope[0, -1:], q_rope[0, -1:], rows[0, -1:], cache, 1,
        jnp.asarray([True]), a, cfg)
    assert _rel(got[0], want) < 1e-5
    assert (np.asarray(after.rows[1]) == np.asarray(cache.rows[1])).all()


def _reread(q, pool, tables, live, *, value_dim, scale):
    """The kernel over a pool that holds every live row already: each
    live slot appends the row its last position holds. -> (out, pool)"""
    bs = pool.shape[2]
    pos = jnp.maximum(live - 1, 0)
    blk = jnp.take_along_axis(tables, (pos // bs)[:, None], axis=1)[:, 0]
    return lda.paged_latent_decode_attention(
        q[..., :value_dim], q[..., value_dim:], pool[blk, :, pos % bs],
        pool, tables, live - 1, scale=scale, interpret=True)


def _attend(q, pool, tables, live, **static):
    """The kernel as a reader: the pool must come back as it went in, to
    the bit (NaN for NaN)."""
    out, after = _reread(q, pool, tables, live, **static)
    np.testing.assert_array_equal(np.asarray(after, np.float32),
                                  np.asarray(pool, np.float32))
    return out


@pytest.mark.parametrize("lengths", [(0, 5, 16, 41), (48, 1, 17, 32)])
def test_latent_decode_kernel_matches_its_oracle(lengths):
    """The Pallas kernel in interpret mode against the XLA formulation:
    idle slots, partial blocks, whole blocks, a full table."""
    rng = np.random.default_rng(0)
    S, H, W, V, bs, mb = 4, 8, 40, 32, 16, 3
    q = jnp.asarray(rng.normal(size=(S, H, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(1 + S * mb, W, bs)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(S * mb).reshape(S, mb),
                         jnp.int32)
    live = jnp.asarray(lengths, jnp.int32)
    got = _attend(q, pool, tables, live, value_dim=V, scale=0.2)
    want = lda.paged_latent_decode_attention_reference(
        q, pool, tables, live, value_dim=V, scale=0.2)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got[np.asarray(lengths) == 0]).max(initial=0)) == 0


# the walk's own cases over an eight-entry table: every length around a
# block's edge, every number of live blocks (the kernel attends the
# table in groups of ``lda.ENTRIES``: a group short of entries, a full
# one, several), and every way a slot can be idle beside a live one (an
# idle slot passes the start of the next slot's first blocks on)
_WALK_BS, _WALK_MB = 16, 8
_WALKS = {
    "ragged": (0, 1, _WALK_BS - 1, _WALK_BS, _WALK_BS + 1,
               _WALK_MB * _WALK_BS),
    "every-count": tuple(n * _WALK_BS - 3 for n in range(1, _WALK_MB + 1)),
    "full-tables": (_WALK_MB * _WALK_BS,) * 4,
    "all-idle": (0,) * 6,
    "first-idle": (0, 5, 17, 33, 50, 100),
    "last-idle": (5, 17, 33, 50, 100, 0),
    "idle-run": (7, 0, 0, 0, 70, 16),
}


def _walk_case(lengths, H=8, W=40, bs=_WALK_BS, mb=_WALK_MB,
               dtype=jnp.float32, seed=0):
    """``(q, pool, poisoned pool, tables, lengths)``: a shuffled table
    whose DEAD entries name blocks no walk reaches, and a second pool in
    which every value a right walk never uses is lethal: the columns of
    a slot's last live block at or past its length hold large finite
    garbage (a missing bound shows), every block no walk reaches, the
    null block among them, holds NaN (one block too many shows however
    it is masked)."""
    rng = np.random.default_rng(seed)
    S = len(lengths)
    NB = 1 + 2 * S * mb
    q = jnp.asarray(rng.normal(size=(S, H, W)), dtype)
    pool = jnp.asarray(rng.normal(size=(NB, W, bs)), dtype)
    tables = 1 + rng.permutation(NB - 1)[:S * mb].reshape(S, mb)
    live = np.zeros((NB, bs), bool)
    reached = np.zeros(NB, bool)
    for row, n in zip(tables, lengths):
        for pos in range(n):
            live[row[pos // bs], pos % bs] = True
        reached[row[:-(-n // bs)]] = True
    tail = jnp.asarray(reached[:, None] & ~live)[:, None, :]
    bad = jnp.where(jnp.asarray(~reached)[:, None, None], jnp.nan,
                    jnp.where(tail, 3e4, pool)).astype(dtype)
    return (q, pool, bad, jnp.asarray(tables, jnp.int32),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("pool", ["clean", "poisoned"])
@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_latent_decode_kernel_walks_live_blocks_only(walk, pool):
    """The kernel in interpret mode against the oracle on the clean
    pool, to 1e-5 in float32; over the poisoned pool its output is
    finite and the clean pool's to the bit, and an idle slot's is
    zeros."""
    lengths = _WALKS[walk]
    q, clean, bad, tables, live = _walk_case(lengths)
    kernel = lambda rows: _attend(q, rows, tables, live, value_dim=32,
                                  scale=0.2)
    want = lda.paged_latent_decode_attention_reference(
        q, clean, tables, live, value_dim=32, scale=0.2)
    got = np.asarray(kernel(clean if pool == "clean" else bad))
    assert np.isfinite(got).all()
    assert float(np.abs(got - np.asarray(want)).max()) < 1e-5
    assert not got[np.asarray(lengths) == 0].any()
    if pool == "poisoned":
        np.testing.assert_array_equal(got, np.asarray(kernel(clean)))


def test_latent_decode_kernel_at_the_cell_widths_in_bfloat16():
    """64 heads over rows of 512 + 64 values, blocks of 128, tables of
    8, bfloat16 (the LongCat cell's kernel signature but for the slot
    count), over a poisoned pool: an idle slot, a partial block, a block
    and a row, a full table."""
    lengths = (0, 77, 129, 1024)
    q, clean, bad, tables, live = _walk_case(
        lengths, H=64, W=576, bs=128, mb=8, dtype=jnp.bfloat16, seed=1)
    got = _attend(q, bad, tables, live, value_dim=512, scale=192 ** -0.5)
    want = lda.paged_latent_decode_attention_reference(
        q.astype(jnp.float32), clean.astype(jnp.float32), tables, live,
        value_dim=512, scale=192 ** -0.5)
    got = np.asarray(got.astype(jnp.float32))
    assert got.shape == (4, 64, 512) and np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-2, atol=2e-2)
    assert not got[0].any()


@pytest.mark.parametrize("family", ["latent", "kv"])
def test_eight_calls_of_one_signature_trace_the_kernel_once(family,
                                                            monkeypatch):
    """A decode program's attentions have one static signature (each its
    own pool buffer; a K/V layer is data, a block offset), and the
    ``pallas_call`` is kept per signature: eight calls under one
    ``jax.jit`` trace the kernel BODY once, not eight times."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    module, body, kept = {"latent": (lda, "_kernel", lda._latent_call),
                          "kv": (da, "_paged_kernel", da._paged_call)}[family]
    traced = []
    real = getattr(module, body)

    def counting(*refs, **static):
        traced.append(1)
        return real(*refs, **static)
    monkeypatch.setattr(module, body, counting)
    kept.cache_clear()          # a kept call holds the body it was built on
    rng = np.random.default_rng(2)
    tables = jnp.asarray(1 + rng.permutation(8).reshape(2, 4), jnp.int32)
    live = jnp.asarray([19, 64], jnp.int32)
    try:
        if family == "latent":
            q = jnp.asarray(rng.normal(size=(2, 8, 40)), jnp.float32)
            pools = [jnp.asarray(rng.normal(size=(9, 40, 16)), jnp.float32)
                     for _ in range(8)]
            calls = [lambda q, pool=pool: _reread(
                q, pool, tables, live, value_dim=32, scale=0.2)[0]
                for pool in pools]
            oracles = [lda.paged_latent_decode_attention_reference(
                q, pool, tables, live, value_dim=32, scale=0.2)
                for pool in pools]
        else:
            q = jnp.asarray(rng.normal(size=(2, 4, 16)), jnp.float32)
            k, v = (jnp.asarray(rng.normal(size=(8, 9, 16, 64)), jnp.float32)
                    for _ in range(2))
            calls = [lambda q, layer=layer: da.paged_decode_attention(
                q, k, v, tables, live, interpret=True, layer=layer)
                for layer in range(8)]
            oracles = [da.paged_decode_attention_reference(
                q, k[layer], v[layer], tables, live) for layer in range(8)]
        outs = jax.jit(lambda q: [call(q) for call in calls])(q)
        info = kept.cache_info()
    finally:
        kept.cache_clear()
    assert len(traced) == 1
    assert (info.misses, info.hits) == (1, 7)
    for got, want in zip(outs, oracles):
        assert float(jnp.abs(got - want).max()) < 1e-5


# where the step's new rows go (a slot's length before the step; -1 an
# idle slot), over an eight-entry table of 16-position blocks: the tail
# block as the only, second and third entry of a first group and of later
# ones, new rows at a fresh block's first column, its second and a
# block's last, idle slots beside live ones and alone
_APPENDS = {
    "every-count": tuple(n * _WALK_BS - 3 for n in range(1, 8)),
    "block-edges": (0, 1, _WALK_BS - 1, _WALK_BS, _WALK_BS + 1,
                    3 * _WALK_BS, 4 * _WALK_BS - 1, _WALK_MB * _WALK_BS - 1),
    "idle-between": (-1, 5, -1, -1, 70, -1, 16, -1),
    "first-and-last-idle": (-1, 33, 50, -1),
    "all-idle": (-1,) * 5,
    # a second resident slab of new rows (``lda.LANES`` slots a slab):
    # the first slab's last slots and the second's first
    "two-slabs": (-1,) * (lda.LANES - 3) + tuple(
        -1 if i % 4 == 1 else (i * 29) % 127 for i in range(9)),
}


def _append_case(positions, neighbours=False, dtype=jnp.float32, **sizes):
    """``_walk_case`` for a step that appends: the pool holds each live
    slot's rows BEFORE the step (the new row's column holds large
    garbage, every block no live slot owns holds NaN, the null block
    among them). With ``neighbours`` the tail blocks of slots 0 and 1 lie
    side by side in the pool. -> (q, rows, pool, tables, positions)"""
    lengths = tuple(max(p, 0) for p in positions)
    q, _, bad, tables, _ = _walk_case(
        tuple(n + 1 if p >= 0 else 0 for n, p in zip(lengths, positions)),
        dtype=dtype, **sizes)
    bs = bad.shape[2]
    if neighbours:
        tables = np.array(tables)
        (i, j) = (positions[0] // bs, positions[1] // bs)
        a, b = int(tables[0, i]), int(tables[1, j])
        other = np.argwhere(tables == a + 1)
        if len(other):          # whoever held the block after a takes b
            tables[tuple(other[0])] = b
        tables[1, j] = a + 1
        bad = bad.at[a + 1].set(bad[b]).at[b].set(bad[a + 1])
        tables = jnp.asarray(tables)
    rng = np.random.default_rng(11)
    rows = jnp.asarray(rng.normal(size=(len(positions), q.shape[-1])), dtype)
    for s, p in enumerate(positions):   # the new row's place: garbage yet
        if p >= 0:
            bad = bad.at[tables[s, p // bs], :, p % bs].set(3e4)
    return q, rows, bad, tables, jnp.asarray(positions, jnp.int32)


def _appended(pool, rows, tables, positions):
    """What the XLA scatter leaves (``kv_cache.latent_append_token``)."""
    from deepspeed_tpu.inference.kv_cache import (LatentPagedCache,
                                                  latent_append_token)
    cache = LatentPagedCache(rows=(pool,), block_tables=tables,
                             lengths=jnp.maximum(positions, 0),
                             aux=jnp.zeros((1, 1), jnp.int32))
    return latent_append_token(cache, 0, rows, positions >= 0).rows[0]


@pytest.mark.parametrize("case", sorted(_APPENDS) + ["neighbours"])
def test_latent_decode_kernel_appends_what_the_scatter_writes(case):
    """The kernel in interpret mode over a poisoned pool: the pool it
    returns is the scatter's to the bit (every block no live slot owns,
    block 0 among them, as it was), and its output is, to the bit, what
    the kernel reads from that pool (the row attended in VMEM is the row
    the pool ends up holding, where it holds it) and the oracle's over
    it to 1e-5; an idle slot's is zeros."""
    positions = _APPENDS.get(case, (2 * _WALK_BS - 1, 40, -1, 7))
    q, rows, pool, tables, pos = _append_case(
        positions, neighbours=case == "neighbours")
    if case == "neighbours":
        tails = [int(tables[s, positions[s] // _WALK_BS]) for s in (0, 1)]
        assert tails[1] == tails[0] + 1
    out, after = lda.paged_latent_decode_attention(
        q[..., :32], q[..., 32:], rows, pool, tables, pos, scale=0.2,
        interpret=True)
    want = _appended(pool, rows, tables, pos)
    np.testing.assert_array_equal(np.asarray(after), np.asarray(want))
    changed = np.asarray(after != pool) & ~np.isnan(np.asarray(pool))
    assert changed.sum() == sum(p >= 0 for p in positions) * q.shape[-1]
    out = np.asarray(out)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, np.asarray(_attend(
        q, want, tables, pos + 1, value_dim=32, scale=0.2)))
    clean = jnp.nan_to_num(want)
    oracle = lda.paged_latent_decode_attention_reference(
        q, clean, tables, pos + 1, value_dim=32, scale=0.2)
    assert float(np.abs(out - np.asarray(oracle)).max()) < 1e-5
    assert not out[np.asarray(positions) < 0].any()


def test_latent_decode_kernel_appends_at_the_cell_widths_in_bfloat16():
    """Rows of 512 + 64 values in blocks of 128, bfloat16 (a packed
    dtype: the slab of new rows is turned as 32-bit words), over sixteen
    slots and over three: pool and output to the bit."""
    for positions in ((-1, 76, 128, 1023) * 4, (300, -1, 127)):
        q, rows, pool, tables, pos = _append_case(
            positions, dtype=jnp.bfloat16, H=64, W=576, bs=128, mb=8)
        out, after = lda.paged_latent_decode_attention(
            q[..., :512], q[..., 512:], rows, pool, tables, pos,
            scale=192 ** -0.5, interpret=True)
        want = _appended(pool, rows, tables, pos)
        np.testing.assert_array_equal(np.asarray(after, np.float32),
                                      np.asarray(want, np.float32))
        np.testing.assert_array_equal(
            np.asarray(out, np.float32), np.asarray(_attend(
                q, want, tables, pos + 1, value_dim=512,
                scale=192 ** -0.5), np.float32))


@pytest.mark.parametrize("program", ["step", "admit"])
def test_the_tpu_decode_path_leaves_what_the_cpu_path_leaves(
        f32, monkeypatch, program):
    """``paged_decode_step`` and ``paged_decode_admit`` (one bucket) over
    two decoding slots and an idle one, through the TPU path (the latent
    kernel appends and attends; it and the grouped matmul in interpret
    mode) against the CPU path (scatter, then the ``jax.numpy``
    attention): logits, lengths and every pool, the null block
    included. The first attention's pool is the scatter's to the bit;
    the later ones follow attentions that differ in their last bits."""
    import functools
    cfg, params, _ = f32
    seqs = _ids(3, 40, seed=1)
    cache = _pool(cfg)
    prefill = jax.jit(lambda *a: lf.paged_prefill(a[0], cfg, *a[1:]))
    for s, m in enumerate((11, 2 * BS - 1)):
        ids = np.zeros((1, 2 * BS), np.int32)
        ids[0, :m] = seqs[s, :m]
        _, cache = prefill(params, jnp.asarray(ids),
                           jnp.asarray([m], jnp.int32), cache, jnp.int32(s))
    args = [jnp.asarray([5, 7, 0], jnp.int32), cache,
            jnp.asarray([True, True, False])]
    if program == "admit":
        ids = np.zeros((1, BS), np.int32)
        ids[0, :9] = seqs[2, :9]
        args += [jnp.asarray(ids), jnp.asarray([9], jnp.int32), jnp.int32(2)]
    fn = {"step": lf.paged_decode_step, "admit": lf.paged_decode_admit}[
        program]

    def run():
        # two steps: the second appends to a fresh tail block of slot 1
        logits, after = jax.jit(lambda *a: fn(a[0], cfg, *a[1:]))(
            params, *args)
        again, after = jax.jit(lambda *a: lf.paged_decode_step(
            a[0], cfg, *a[1:]))(params, args[0], after, args[2])
        return logits, again, after
    want = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(grouped_matmul, "_should_interpret", lambda: True)
    monkeypatch.setattr(lda, "paged_latent_decode_attention",
                        functools.partial(lda.paged_latent_decode_attention,
                                          interpret=True))
    got = run()
    for a, b in zip(got[:2], want[:2]):
        assert _rel(a, b) < 1e-5
    assert (np.asarray(got[2].lengths) == np.asarray(want[2].lengths)).all()
    # (after the second step: the first step's row of the first
    # attention is the scatter's, the second's follows the first's logits)
    for i, (a, b, c) in enumerate(zip(got[2].rows, want[2].rows,
                                      cache.rows)):
        assert (np.asarray(a[0]) == np.asarray(c[0])).all()
        assert float(jnp.abs(a - b).max()) < 1e-5 * float(jnp.abs(b).max())
    first = (np.asarray(got[2].rows[0]) != np.asarray(want[2].rows[0]))
    assert first.sum() <= 2 * cfg.latent_width     # the second step's rows


# ------------------------------------------------- the share and the model

def _moe_input(cfg, n=40, seed=7):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=(n, cfg.hidden_size)), jnp.float32)


def test_the_shares_of_one_expert_layer_sum_to_the_uncut_layer():
    """Four shares of two experts each, run by the PROGRAM: the real
    experts' parts (each by its holder) and the identity term (once)
    sum to the uncut layer of the REFERENCE."""
    cfg_all, params = _model(held=(0, 8))
    moe = params["layers"][1]["moe"]
    u = _moe_input(cfg_all)
    weights = family.reference_from_serve(cfg_all, params)
    whole = ref._moe(u, weights["layers"][1], ref._sizes(weights))
    valid = jnp.ones((u.shape[0],), bool)
    picks, w = lf._route(u, moe, cfg_all)
    identity = jnp.sum(jnp.where(picks >= cfg_all.n_routed_experts, w, 0.0),
                       -1)[:, None] * u
    total = identity
    for lo in range(0, 8, 2):
        cfg = lf.LongcatFlashConfig(**{
            **{f.name: getattr(cfg_all, f.name)
               for f in cfg_all.__dataclass_fields__.values()},
            "experts_held": (lo, lo + 2)})
        part = dict(moe, experts=jax.tree.map(lambda a: a[lo:lo + 2],
                                              moe["experts"]))
        m, counts = lf.moe_layer(u, part, cfg, valid)
        total = total + (m - identity)       # a share's real experts
        assert int(counts[:2].sum()) == int(
            ((picks >= lo) & (picks < lo + 2)).sum())
    assert _rel(total, whole) < 1e-5


@pytest.mark.parametrize("held,branch", [((2, 4), "fast"), ((0, 8), "all")])
def test_expert_layer_is_exact_on_both_row_buffers(held, branch):
    """200 tokens x top-3 = 600 picks, 128 fast rows: a share of two
    experts lands ~100 picks (the small buffer), all eight experts land
    ~400 (the fallback over every pick). Both equal the reference."""
    cfg, params = _model(held=held)
    moe = params["layers"][0]["moe"]
    u = _moe_input(cfg, n=200)
    m, counts = lf.moe_layer(u, moe, cfg, jnp.ones((200,), bool))
    landed = int(counts[:cfg.num_held].sum())
    assert (landed <= lf._fast_rows(200, cfg.moe_topk)) == (branch == "fast")
    weights = family.reference_from_serve(cfg, params)
    want = ref._moe(u, weights["layers"][0], ref._sizes(weights))
    assert _rel(m, want) < 1e-5


def test_identity_experts_cost_no_matmul_and_bias_moves_only_selection():
    cfg, params = _model()
    moe = dict(params["layers"][0]["moe"])
    u = _moe_input(cfg, n=12)
    valid = jnp.ones((12,), bool)
    free_picks, free_w = lf._route(u, moe, cfg)
    # a correction bias that puts every identity expert first
    bias = jnp.where(jnp.arange(cfg.router_outputs)
                     >= cfg.n_routed_experts, 10.0, 0.0)
    moe["router_bias"] = bias
    picks, w = lf._route(u, moe, cfg)
    assert bool((picks >= cfg.n_routed_experts).all())
    assert not bool((free_picks >= cfg.n_routed_experts).all())
    # the weights are the raw scores times the factor, bias or no bias
    scores = jax.nn.softmax(u @ moe["router"], axis=-1)
    np.testing.assert_allclose(
        w, 6.0 * jnp.take_along_axis(scores, picks, -1), rtol=1e-5)
    np.testing.assert_allclose(
        free_w, 6.0 * jnp.take_along_axis(scores, free_picks, -1), rtol=1e-5)
    m, counts = lf.moe_layer(u, moe, cfg, valid)
    # no pick landed on an expert: the grouped matmul has no row to visit
    assert int(counts[:cfg.num_held].sum()) == 0
    assert int(counts[cfg.num_held]) == 12 * cfg.moe_topk
    np.testing.assert_allclose(m, w.sum(-1)[:, None] * u, rtol=1e-5)


def test_the_seeded_bias_holds_the_identity_share_and_loads_experts_alike():
    """At the published router sizes the seeded correction bias lifts the
    zero-compute experts, which the seeded router scores lower, back to
    a third of the picks (8 real experts of 12 a token); every expert of
    a chip's share sees 256 x 12 / 768 = 4 of 256 tokens, give or take
    one; without the bias the identity share falls by more than a third
    of itself."""
    cfg = lf.LongcatFlashConfig(vocab_size=256, experts_held=(0, 16))
    n = 16384
    u = jax.random.normal(jax.random.PRNGKey(1), (n, cfg.hidden_size))
    u = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True))
    moe = {"router": lf.init_router(jax.random.PRNGKey(2), cfg),
           "router_bias": lf.router_bias(cfg)}
    picks, _ = lf._route(u, moe, cfg)
    picks = np.asarray(picks)
    assert 0.31 < (picks >= 512).mean() < 0.355
    per_256 = np.bincount(picks.reshape(-1), minlength=768)[:16] * 256.0 / n
    assert per_256.min() > 2.8 and per_256.max() < 5.2, per_256
    free, _ = lf._route(u, dict(moe, router_bias=jnp.zeros(768)), cfg)
    assert (np.asarray(free) >= 512).mean() < 0.2


def _shapes_with_experts_first(text, cfg, tokens):
    """Tensors ``[experts, tokens, ...]`` named in a program's text."""
    import re
    found = []
    for experts in (cfg.num_held, cfg.n_routed_experts, cfg.router_outputs):
        found += re.findall(rf"[\[<]{experts}[,x] ?{tokens}[,x]", text)
    return found


def test_no_dense_expert_tensor_in_the_decode_program(f32):
    """The held-experts layer gathers the landed picks and runs a grouped
    matmul over them, in either of its forms (the small-tile Pallas
    kernel or ``ragged_dot``: ``held_experts.matmul_form``): the decode
    program's jaxpr holds no ``[experts, tokens, ...]`` tensor. (What a
    backend makes of ``ragged_dot`` is its own: the CPU expands it, a TPU
    runs a Mosaic kernel; the next test compiles for one.)"""
    cfg, params, _ = f32
    cache = _pool(cfg)
    jaxpr = str(jax.make_jaxpr(lambda p, t, c, a: transformer.paged_decode_step(
        p, cfg, t, c, a))(params, jnp.zeros((SLOTS,), jnp.int32), cache,
                          jnp.ones((SLOTS,), bool)))
    assert "ragged_dot" in jaxpr or grouped_matmul.NAME in jaxpr
    assert not _shapes_with_experts_first(jaxpr, cfg, SLOTS)


def test_expert_layer_compiles_for_v5e_as_a_grouped_matmul_kernel(
        monkeypatch):
    """Compiled for a described (not attached) TPU v5e, at toy widths
    and at the published ones: the expert layer is the small-tile Pallas
    kernel (``held_experts_grouped_matmul``) in both branches of
    ``held_experts_part``, and the optimized module has no ``[experts,
    tokens, ...]`` tensor. A bare ``ragged_dot`` is the chip's
    ``ragged-dot`` Mosaic custom call, whose row tiling is pinned here:
    it is what the kernel was measured against."""
    # the process is on the CPU (the kernel would pick interpret mode);
    # the compile target is not
    monkeypatch.setattr(grouped_matmul, "_should_interpret", lambda: False)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this box
        pytest.skip(f"cannot describe a v5e topology: {e}")
    cfg = lf.LongcatFlashConfig(
        vocab_size=256, hidden_size=256, expert_ffn_hidden_size=128,
        n_routed_experts=8, zero_expert_num=4, moe_topk=3,
        experts_held=(2, 6), num_layers=1)
    tokens = 64
    one = SingleDeviceSharding(topo.devices[0])
    moe = jax.eval_shape(lambda k: lf._init_layer(k, cfg)["moe"],
                         jax.random.PRNGKey(0))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda u, m, v: lf.moe_layer(u, m, cfg, v)).lower(
            on_chip(jax.ShapeDtypeStruct((tokens, 256), jnp.bfloat16)),
            on_chip(moe),
            on_chip(jax.ShapeDtypeStruct((tokens,), bool))
        ).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    assert f'{grouped_matmul.NAME}/pallas_call"' in text
    assert "ragged-dot" not in text and "tpu_custom_call" in text
    assert not _shapes_with_experts_first(text, cfg, tokens)
    assert not _shapes_with_experts_first(text, cfg, tokens * cfg.moe_topk)
    # what the choice of the small-tile kernel was measured against
    # (PERF.md section 6, PR 52): the compiler's own kernel for a
    # ``ragged_dot`` tiles the rows by the largest power of two up to
    # 512 that divides them and computes whole tiles per group, so a
    # 128- or 640-row buffer costs 128 rows an expert, 768 rows cost 256
    # (PR 50 read that step as +1.7 ms) and all 3072 picks cost 512. If
    # this changes, measure the two forms again
    # (``scripts/grouped_matmul_micro.py``).
    import re
    full = lf.LongcatFlashConfig(vocab_size=256, experts_held=(0, 16))
    E, Fe, X = full.hidden_size, full.expert_ffn_hidden_size, full.num_held
    assert lf._fast_rows(256, full.moe_topk) == 128
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for rows, tile in ((128, 128), (640, 128), (768, 256),
                           (256 * full.moe_topk, 512)):
            text = jax.jit(jax.lax.ragged_dot).lower(
                on_chip(jax.ShapeDtypeStruct((rows, E), jnp.bfloat16)),
                on_chip(jax.ShapeDtypeStruct((X, E, 2 * Fe), jnp.bfloat16)),
                on_chip(jax.ShapeDtypeStruct((X,), jnp.int32))
            ).compile().as_text()
            assert "ragged-dot" in text and re.findall(
                r'ragged_dot_tiling="(\d+),', text) == [str(tile)], (
                    rows, tile)
        # the decode step's expert layer at the published widths: the
        # small-tile kernel in both branches, none of the compiler's
        slots = 256
        moe = jax.eval_shape(lambda k: lf._init_layer(k, full)["moe"],
                             jax.random.PRNGKey(0))
        text = jax.jit(lambda u, m, v: lf.moe_layer(u, m, full, v)).lower(
            on_chip(jax.ShapeDtypeStruct((slots, E), jnp.bfloat16)),
            on_chip(moe),
            on_chip(jax.ShapeDtypeStruct((slots,), bool))
        ).compile().as_text()
        assert text.count(
            f'{grouped_matmul.NAME}/pallas_call"') == 4, "w_in, w_out x 2"
        assert "ragged-dot" not in text
        assert not _shapes_with_experts_first(text, full, slots)
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


# ----------------------------------------- bfloat16 program, float32 reference

TIE_EPS = 5e-3        # biased-score margin under which a pick may flip


def test_bfloat16_routing_flips_only_at_ties_and_logits_agree():
    """Every routing disagreement between the bfloat16 program and the
    float32 reference sits where the reference's margin between its last
    pick and its first loser is under ``TIE_EPS``; told to break exactly
    those ties the program's way, the reference's logits agree with the
    program's to a bfloat16 tolerance."""
    cfg, params = _model(dtype="bfloat16")
    weights = family.reference_from_serve(cfg, params)
    ids = _ids(2, 40, seed=11)
    seen = []
    route = lf._route

    def spy(u, moe, c):
        picks, w = route(u, moe, c)
        seen.append(np.asarray(picks))
        return picks, w
    lf._route = spy
    try:
        got = lf.causal_forward(params, cfg, jnp.asarray(ids))
    finally:
        lf._route = route
    # the reference routed as the program routed; ``record`` holds what it
    # would have picked itself, layer by layer, from the same (tied)
    # hidden: where that differs, it must have been a tie. (Layer by
    # layer matters: breaking a tie in one layer moves the next layer's
    # hidden, and with it that layer's own ties.)
    record = []
    tied = ref.logits(weights, ids, route_as=seen, record=record)
    flips = 0
    for mine, theirs in zip(seen, record):
        same = (np.sort(mine, -1) == np.sort(np.asarray(theirs["picks"]),
                                             -1)).all(-1)
        assert (np.asarray(theirs["margin"])[~same] < TIE_EPS).all()
        flips += int((~same).sum())
    scale = float(jnp.abs(tied).max())
    assert float(jnp.abs(got - tied).max()) < 0.05 * scale
    # free routing is the default (what ``logits_at`` uses), and the
    # override is what moved the reference where picks differed
    if flips:
        assert float(jnp.abs(ref.logits(weights, ids) - tied).max()) > 0


# ------------------------------------- the latent cache under the allocator

def _server(**engine):
    cfg, params = _model()
    conf = dict(dtype="float32", max_out_tokens=BS * MB, block_size=BS,
                num_slots=2, max_queued_requests=32)
    conf.update(engine)
    return cfg, params, InferenceEngine(
        (cfg, params), DeepSpeedInferenceConfig(**conf))


def test_slots_retire_and_blocks_are_reused_without_cross_talk(f32):
    """Seven requests through two slots: blocks go back to the allocator
    and out again, and every served token is the float32 reference's
    choice for that request's own sequence (to 1e-4 of the top logit)."""
    cfg, params, weights = f32
    _, _, engine = _server()
    server = ContinuousBatchingServer(engine)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).tolist()
               for n in (5, 17, 30, 9, 33, 16, 21)]
    rids = [server.submit(p, max_new_tokens=7, eos_token_id=None)
            for p in prompts]
    server.drain()
    assert (server.scheduler.allocator.free_blocks
            == 2 * server.max_blocks_per_slot)
    pool = server._cache
    assert len(pool.rows) == 2 * cfg.num_layers
    assert pool.rows[0].shape == (1 + 2 * server.max_blocks_per_slot,
                                  cfg.latent_width, BS)
    served = [server.result(rid) for rid in rids]
    batch = np.zeros((len(served), max(map(len, served)) - 1), np.int32)
    for i, full in enumerate(served):            # causal: padding is inert
        batch[i, :len(full) - 1] = full[:-1]
    logits = np.asarray(ref.logits(weights, batch))
    for rid, p, full, lg in zip(rids, prompts, served, logits):
        assert server.finish_reason(rid) == "length" and len(full) == len(p) + 7
        for pos in range(len(p) - 1, len(full) - 1):
            top = lg[pos].max()
            assert top - lg[pos, full[pos + 1]] <= 1e-4 * max(1.0, abs(top))
    stats = server.stats
    assert stats["kv_tier"]["pool_bytes"] == sum(r.nbytes for r in pool.rows)
    snap = server.telemetry.snapshot()
    assert snap["serve_moe_tokens_routed_total"]["series"]
    server.close()


@pytest.mark.parametrize("async_loop", [False, True])
def test_routing_series_follow_the_device_counters(async_loop):
    """The routing series are the pool's ``aux`` array, cell for cell.
    A step at lag 0 brings the array over with its tokens, so with
    ``async_loop`` off the two agree after every step; a lagged commit
    cannot (its pool has been donated into the next program), and
    ``stats`` catches the series up. The totals of the two servers may
    differ: a chained step runs a finished slot's row once more."""
    from deepspeed_tpu.telemetry import MetricRegistry, set_registry
    prev = set_registry(MetricRegistry())
    try:
        cfg, _, engine = _server(async_loop=async_loop)
        server = ContinuousBatchingServer(engine)

        def series():
            return np.array([[c.value for c in row]
                             for row in server._aux_series])

        rng = np.random.default_rng(4)
        for n in (5, 17, 9):        # three requests, two slots: a queue
            server.submit(rng.integers(1, cfg.vocab_size, size=n).tolist(),
                          max_new_tokens=6, eos_token_id=None)
        while not server.scheduler.idle:
            server.step()
            if not async_loop:
                assert (series() == np.asarray(server._cache.aux)).all()
        loop = server.stats["async_loop"]       # catches the series up
        assert (loop["pipelined_steps"] > 0) == async_loop
        assert series().sum() > 0
        assert (series() == np.asarray(server._cache.aux)).all()
        server.close()
    finally:
        set_registry(prev)


@pytest.mark.parametrize("switch,value", [
    ("kv_cache_dtype", "int8"),
    ("enable_prefix_caching", True),
    ("prefill_chunk_tokens", BS),
    ("speculation_tokens", 4),
])
def test_server_switches_the_latent_cache_cannot_honour_are_refused(
        switch, value):
    _, _, engine = _server(**{switch: value})
    with pytest.raises(NotImplementedError, match=switch):
        ContinuousBatchingServer(engine)


def test_host_offload_is_refused_by_name():
    _, _, engine = _server(kv_host_offload=True, enable_prefix_caching=True)
    with pytest.raises(NotImplementedError, match="kv_host_offload"):
        ContinuousBatchingServer(engine)


@pytest.mark.parametrize("switch,conf", [
    ("int8", dict(dtype="int8")),
    ("tp_size", dict(tensor_parallel={"tp_size": 2})),
])
def test_engine_switches_are_refused_by_name(switch, conf):
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match=switch):
        InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
            **{"max_out_tokens": 64, **conf}))


def test_generate_over_a_dense_cache_is_refused():
    cfg, params = _model()
    with pytest.raises(NotImplementedError, match="ContinuousBatching"):
        transformer.decode_step(params, cfg, jnp.zeros((1,), jnp.int32), None)


# ------------------------------------------------ the benchmark's new cell

CELL = "serve-longcat-flash-ep32-decode-batch"


def test_configuration_file_states_the_published_sizes_once():
    """The top level holds the catalog's keys (the three reduced ones at
    their reduced values); the ``model`` block is what runs and may
    differ only where the share is stated another way."""
    contract = harness.load_contract()
    entry = harness.find(contract["configs"], "longcat-flash-ep32-serve",
                         "config")
    conf = harness.load_json(os.path.join(REPO, entry["file"]))
    model = conf["model"]
    for key, value in conf.items():
        if key in model and key != "n_routed_experts":
            assert model[key] == value, key
    lo, hi = model["experts_held"]
    assert hi - lo == conf["n_routed_experts"] == 16
    assert model["n_routed_experts"] + model["zero_expert_num"] == 768
    assert sorted(entry["reduced"]) == sorted(conf["reduced"])
    cell = harness.resolve_cell(contract, CELL)
    assert cell["config"]["engine"]["num_slots"] == 256
    assert "serve_out_tokens_per_s" in cell["end_to_end"]
    # the readers this cell brought: it leads their lists (a later cell
    # of another family that holds experts reports three of them too);
    # 12 with the cell, 2 of the host loop's with the rider round (PR 51)
    new = [m for m in contract["per_layer"]
           if m.get("workloads", [])[:1] == [CELL]]
    assert len(new) == 14
    assert [m["name"] for m in new[-2:]] == [
        "longcat_admission_step_ms", "longcat_rider_admissions_pct"]
    for m in new + [m for m in contract["per_layer"]
                    if CELL in m.get("workloads", ())]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    # the accepted readers of the layers this cell runs too
    assert {"serve_goodput_pct", "trace_lower_s", "compile_cache_misses"} <= {
        m["name"] for m in contract["per_layer"]
        if CELL in m.get("workloads", ())}
    traffic = harness.load_json(os.path.join(
        BENCH, "traffic", "longcat-decode-batch.json"))
    assert traffic["trace_seconds"] == 4


def test_the_cell_runs_at_a_tiny_size_through_the_harness(tmp_path):
    """The harness's own runner, the real readers and family, the tiny
    configuration under a backlog of 24: correct, nothing failed, the
    backlog never dry, and every counter-fed metric prints a number (the
    device-trace ones need a chip and are left out without a trace)."""
    root = tmp_path / "bench"
    for d in ("metrics", "models"):
        shutil.copytree(os.path.join(BENCH, d), root / d)
    os.makedirs(root / "configs")
    os.makedirs(root / "traffic")
    shutil.copy(os.path.join(BENCH, "testdata", "configs",
                             "tiny-longcat-serve.json"), root / "configs")
    shutil.copy(os.path.join(BENCH, "testdata", "traffic",
                             "tiny-longcat-decode-batch.json"),
                root / "traffic")
    contract = json.loads(json.dumps(harness.load_contract()))
    contract["configs"] = [{"name": "tiny-longcat-serve",
                            "file": "bench/configs/tiny-longcat-serve.json"}]
    contract["workloads"] = [{"name": CELL, "config": "tiny-longcat-serve",
                              "traffic": "tiny-longcat-decode-batch",
                              "chips": 1}]
    cell = harness.resolve_cell(contract, CELL, repo=str(tmp_path))
    args = argparse.Namespace(seed=2 ** 31 + 5, seconds=1.0, trace=0)
    run, _ = harness.run_cell(cell, args, time.time(), jax.devices()[:1],
                              "TPU v5 lite")
    assert all(run["checks"].values()), (run["checks"],
                                         run["reference_check"])
    assert run["failed"] == 0 and run["attempted"] > 0
    metrics = harness.read_metrics(
        cell["end_to_end"] + cell["per_layer"], run, None,
        harness.units_of(contract), cell["root"])
    for name in ("serve_out_tokens_per_s", "setup_s",
                 "moe_tokens_per_held_expert", "moe_held_load_max_over_mean",
                 "moe_identity_pick_pct", "longcat_peak_hbm_gb"):
        assert name in metrics, name
    assert 0 < metrics["moe_identity_pick_pct"]["value"] < 100
    assert metrics["moe_held_load_max_over_mean"]["value"] >= 1.0


def test_the_programs_alone_script_holds_the_rider_to_the_reference(capsys):
    """``scripts/longcat_admit_programs.py`` at its toy size (float32):
    with every slot but one decoding and with none, both buckets,
    ``paged_decode_admit`` serves the tokens of the two programs run
    apart from the same pool, leaves the pool they leave, and both are
    the float32 reference's choice; every program is timed."""
    script = harness.load_module(
        os.path.join(REPO, "scripts", "longcat_admit_programs.py"),
        "longcat_admit_programs")
    assert script.main(["--tiny", "--calls", "1", "--ref-rows", "4"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    parity = [x["parity"] for x in lines if "parity" in x]
    assert [(p["bucket"], p["form"]) for p in parity] == [
        (128, "rider"), (128, "alone"), (256, "rider"), (256, "alone")]
    for p in parity:
        apart, to_ref = [p["rider_row"]], [p["rider_row_to_reference"]]
        if p["form"] == "rider":
            assert p["decode_rows"]["rows"] == 7
            apart.append(p["decode_rows"])
            to_ref.append(p["decode_rows_to_reference"])
        for rows in apart:
            assert rows["tokens_equal"] == rows["rows"]
            assert rows["logit_gap_p50_p99_max"][-1] < 1e-4
        for rows in to_ref:
            assert rows["decode_admit"]["exact"] == rows["rows"]
            assert rows["decode_admit"]["max_logit_gap"] < 1e-4
        assert p["pool"]["beyond_a_64th_of_row_max"] == 0
        assert p["pool"]["lengths_equal"] and p["pool"]["tables_equal"]
    assert [x["program"] for x in lines if "program" in x] == [
        "decode", "prefill_128", "decode_admit_128_rider",
        "decode_admit_128_alone", "prefill_256", "decode_admit_256_rider",
        "decode_admit_256_alone"]


def test_the_kernel_alone_script_runs_both_geometries(capsys):
    """``scripts/latent_decode_micro.py`` at its toy sizes (float32,
    interpret mode): a timed reading for each geometry, idle slots among
    the live ones, several live blocks a slot (GigaChat's under a shared
    context)."""
    script = harness.load_module(
        os.path.join(REPO, "scripts", "latent_decode_micro.py"),
        "latent_decode_micro")
    script.main(["--tiny", "--calls", "2", "--reps", "2"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    timed = {x["geometry"]: x for x in lines if x.get("form") == "fused"}
    assert sorted(timed) == ["gigachat", "longcat"]
    assert all(x["ms_per_call"] > 0 for x in timed.values())
    assert timed["gigachat"]["live_blocks_mean"] > 12 > (
        timed["longcat"]["live_blocks_mean"]) > 1
