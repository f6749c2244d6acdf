"""KV-cache primitive contracts — dense and paged.

The invariants every decode path leans on: (1) the chunked writer at
K=1 is EXACTLY the single-token appender, (2) cache content beyond the
live ``lengths`` is dead memory — any garbage there must be invisible
to attention, (3) the paged pool + block table reproduces the dense
cache bit-for-bit through the gather, and the null block isolates idle
slots from live ones.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (
    BlockAllocator, advance, append_token, init_cache, init_paged_cache,
    paged_append_token, paged_gather_kv, paged_write_prompt,
    paged_write_tokens, pool_arrays, write_chunk, write_prompt)


def _rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


def _heads(cache, layer=0):
    """One layer of the K pool, rows split into heads:
    ``[NB, BS, KH, D]`` out of the stored ``[L, NB, BS, KH*D]``."""
    pool = np.asarray(cache.k[layer])
    return pool.reshape(*pool.shape[:2], cache.num_kv_heads, cache.head_dim)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_write_chunk_k1_equals_append_token(seed):
    """write_chunk with a K=1 chunk must be byte-identical to
    append_token at every layer — the speculative verify path and the
    decode path share the cache layout only if this holds."""
    L, B, S, H, D = 2, 3, 32, 2, 4
    cache_a = init_cache(L, B, S, H, D, jnp.float32)
    cache_b = init_cache(L, B, S, H, D, jnp.float32)
    lengths = jnp.asarray([0, 5, 17], jnp.int32)
    cache_a = cache_a.replace(lengths=lengths)
    cache_b = cache_b.replace(lengths=lengths)
    for layer in range(L):
        k = _rand(seed * 10 + layer, (B, H, D))
        v = _rand(seed * 10 + layer + 100, (B, H, D))
        cache_a = append_token(cache_a, layer, k, v)
        cache_b = write_chunk(cache_b, layer, k[:, None], v[:, None])
    np.testing.assert_array_equal(np.asarray(cache_a.k),
                                  np.asarray(cache_b.k))
    np.testing.assert_array_equal(np.asarray(cache_a.v),
                                  np.asarray(cache_b.v))


def test_garbage_beyond_lengths_never_leaks():
    """Mask invariance: filling every cache position >= lengths with
    random garbage must not move decode logits by a single bit — that
    dead tail is what speculative rollback and right-padding both rely
    on being invisible."""
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, decode_step, init_params, prefill)
    V, E, L, H, T, S = 64, 32, 2, 4, 8, 64
    cfg = InferenceTransformerConfig(vocab_size=V, n_positions=128,
                                     n_embd=E, n_layer=L, n_head=H,
                                     dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0, V)
    lengths = jnp.asarray([T, T - 3], jnp.int32)
    cache = init_cache(L, 2, S, cfg.kv_heads, cfg.head_dim, jnp.float32)
    _, cache = prefill(params, cfg, ids, lengths, cache)

    tok = jnp.asarray([5, 9], jnp.int32)
    logits_clean, _ = decode_step(params, cfg, tok, cache)

    pos = jnp.arange(S)[None, None, :, None, None]
    dead = pos >= cache.lengths[None, :, None, None, None]
    garbage = _rand(7, cache.k.shape) * 100.0
    cache_dirty = cache.replace(k=jnp.where(dead, garbage, cache.k),
                                v=jnp.where(dead, garbage * 2, cache.v))
    logits_dirty, _ = decode_step(params, cfg, tok, cache_dirty)
    np.testing.assert_array_equal(np.asarray(logits_clean),
                                  np.asarray(logits_dirty))


def test_paged_write_prompt_matches_dense_through_gather():
    """Scatter a prompt into pool blocks, gather it back through the
    block table: logical positions must reproduce the dense
    write_prompt layout exactly."""
    L, T, H, D, BS = 2, 64, 2, 4, 16
    k = _rand(0, (T, H, D))
    v = _rand(1, (T, H, D))
    cache = init_paged_cache(L, 2, 10, BS, 4, H, D, jnp.float32)
    bt = np.zeros((2, 4), np.int32)
    bt[1] = [3, 7, 2, 9]           # non-contiguous, out-of-order blocks
    cache = cache.replace(block_tables=jnp.asarray(bt),
                          lengths=jnp.asarray([0, 50], jnp.int32))
    for layer in range(L):
        cache = paged_write_prompt(cache, layer, k, v, jnp.int32(1))
    for layer in range(L):
        gk, gv = paged_gather_kv(cache, layer)
        np.testing.assert_array_equal(np.asarray(gk[1]), np.asarray(k))
        np.testing.assert_array_equal(np.asarray(gv[1]), np.asarray(v))


def test_paged_append_isolates_idle_slots():
    """Appends for an idle slot (length 0, all-zero table) land in the
    reserved null block 0 and can never touch a live slot's blocks."""
    L, H, D, BS = 1, 2, 4, 16
    cache = init_paged_cache(L, 2, 6, BS, 2, H, D, jnp.float32)
    bt = np.zeros((2, 2), np.int32)
    bt[0] = [2, 4]                  # slot 0 live, slot 1 idle
    cache = cache.replace(block_tables=jnp.asarray(bt),
                          lengths=jnp.asarray([5, 0], jnp.int32))
    k = _rand(3, (2, H, D))
    cache = paged_append_token(cache, 0, k, k)
    pool = _heads(cache)
    # slot 0's token landed at block 2, offset 5
    np.testing.assert_array_equal(pool[2, 5], np.asarray(k[0]))
    # slot 1's (discarded) token landed in null block 0, nowhere else
    np.testing.assert_array_equal(pool[0, 0], np.asarray(k[1]))
    assert np.all(pool[[1, 3, 4, 5]] == 0)


def test_paged_write_tokens_k1_equals_append_token():
    """The multi-token speculative writer at K=1 must be byte-identical
    to paged_append_token — the verify path and the decode path share
    the pool layout only if this holds (the paged mirror of the dense
    write_chunk(K=1) ≡ append_token pin)."""
    L, H, D, BS = 2, 2, 4, 16
    bt = np.zeros((3, 3), np.int32)
    bt[0] = [2, 5, 1]
    bt[1] = [4, 3, 0]               # slot 2 idle (null table, length 0)
    lengths = jnp.asarray([5, 17, 0], jnp.int32)
    cache_a = init_paged_cache(L, 3, 8, BS, 3, H, D, jnp.float32)
    cache_a = cache_a.replace(block_tables=jnp.asarray(bt),
                              lengths=lengths)
    cache_b = cache_a
    for layer in range(L):
        k = _rand(layer, (3, H, D))
        v = _rand(layer + 50, (3, H, D))
        cache_a = paged_append_token(cache_a, layer, k, v)
        cache_b = paged_write_tokens(cache_b, layer, k[:, None],
                                     v[:, None])
    np.testing.assert_array_equal(np.asarray(cache_a.k),
                                  np.asarray(cache_b.k))
    np.testing.assert_array_equal(np.asarray(cache_a.v),
                                  np.asarray(cache_b.v))


def test_paged_write_tokens_commit_rollback_across_block_edges():
    """THE speculative-rollback property: write K candidate positions
    at ``lengths``, advance only the accepted prefix, repeat — whatever
    the per-round acceptance (0..K-1 proposals, crossing block edges
    mid-chunk or not), the live span gathered through the table is
    byte-identical to appending exactly the committed stream one token
    at a time. Rejected garbage beyond ``lengths`` never survives a
    later round's overwrite, and the blocks the table maps stay the
    RIGHT blocks (out-of-order ids pin the indirection)."""
    H, D, BS, MB = 2, 3, 4, 4
    K = 3
    rng = np.random.default_rng(0)
    for trial in range(5):
        bt = np.zeros((1, MB), np.int32)
        bt[0] = rng.permutation([3, 7, 2, 9])[:MB]   # out-of-order
        start = int(rng.integers(0, BS))             # mid-block start
        cache = init_paged_cache(1, 1, 12, BS, MB, H, D, jnp.float32)
        cache = cache.replace(block_tables=jnp.asarray(bt),
                              lengths=jnp.asarray([start], jnp.int32))
        committed_ref = []
        for rnd in range(6):
            k = rng.normal(size=(1, K, H, D)).astype(np.float32)
            v = rng.normal(size=(1, K, H, D)).astype(np.float32)
            cache = paged_write_tokens(cache, 0, jnp.asarray(k),
                                       jnp.asarray(v))
            adv = int(rng.integers(1, K + 1))        # accept 1..K
            live = int(cache.lengths[0])
            if live + adv > MB * BS:
                break
            committed_ref.extend((k[0, i], v[0, i]) for i in range(adv))
            cache = cache.replace(lengths=cache.lengths + adv)
        gk, gv = paged_gather_kv(cache, 0)
        live = int(cache.lengths[0])
        assert live == start + len(committed_ref)
        for i, (k_ref, v_ref) in enumerate(committed_ref):
            np.testing.assert_array_equal(np.asarray(gk[0, start + i]),
                                          k_ref, err_msg=f"t{trial} p{i}")
            np.testing.assert_array_equal(np.asarray(gv[0, start + i]),
                                          v_ref)


def test_paged_write_tokens_overshoot_spills_to_null_block():
    """A write window running past the block table (a wedged slot
    decoding beyond its budget) must land in the reserved null block —
    NOT clamp onto the table's last live entry and clobber it."""
    H, D, BS, MB = 2, 3, 4, 2
    cache = init_paged_cache(1, 1, 6, BS, MB, H, D, jnp.float32)
    cache = cache.replace(
        block_tables=jnp.asarray([[3, 5]], jnp.int32),
        lengths=jnp.asarray([BS * MB - 1], jnp.int32))  # one slot left
    k = _rand(1, (1, 3, H, D))
    cache = paged_write_tokens(cache, 0, k, k)
    pool = _heads(cache)
    # position 7 (last live) landed in block 5 offset 3; the two
    # overshooting positions landed in null block 0 offsets 0..1
    np.testing.assert_array_equal(pool[5, 3], np.asarray(k[0, 0]))
    np.testing.assert_array_equal(pool[0, 0], np.asarray(k[0, 1]))
    np.testing.assert_array_equal(pool[0, 1], np.asarray(k[0, 2]))
    assert np.all(pool[3] == 0)     # the OTHER live block is untouched


def test_paged_garbage_beyond_lengths_invisible_with_k_gt_1():
    """Mask invariance, paged + multi-token: random garbage at every
    position >= lengths (exactly where rejected speculative writes
    land) must not move paged_verify_step logits by a single bit — the
    invariant that makes advance-only-the-accepted-prefix a correct
    rollback."""
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params, paged_prefill,
        paged_verify_step)
    V, E, L, H, BS, MB = 64, 32, 2, 4, 16, 4
    cfg = InferenceTransformerConfig(vocab_size=V, n_positions=128,
                                     n_embd=E, n_layer=L, n_head=H,
                                     dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    cache = init_paged_cache(L, 2, 10, BS, MB, cfg.kv_heads,
                             cfg.head_dim, jnp.float32)
    bt = np.zeros((2, MB), np.int32)
    bt[0], bt[1] = [2, 5, 1, 0], [4, 3, 0, 0]
    cache = cache.replace(block_tables=jnp.asarray(bt))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, V)
    for slot, plen in ((0, 16), (1, 9)):
        _, cache = paged_prefill(params, cfg, ids,
                                 jnp.asarray([plen], jnp.int32), cache,
                                 jnp.int32(slot))
    toks = jnp.asarray([[5, 9, 3], [7, 2, 8]], jnp.int32)
    logits_clean, _ = paged_verify_step(params, cfg, toks, cache)

    # poison EVERY pool position that is not live content for its slot
    # (per-slot live spans mapped through the tables)
    live = np.zeros((10, BS), bool)
    for slot, plen in ((0, 16), (1, 9)):
        for p in range(plen):
            live[bt[slot][p // BS], p % BS] = True
    garbage = np.asarray(_rand(7, cache.k.shape)) * 100.0
    mask = live[None, :, :, None]
    cache_dirty = cache.replace(
        k=jnp.where(mask, cache.k, garbage),
        v=jnp.where(mask, cache.v, garbage * 2))
    logits_dirty, _ = paged_verify_step(params, cfg, toks, cache_dirty)
    np.testing.assert_array_equal(np.asarray(logits_clean),
                                  np.asarray(logits_dirty))


# ------------------------------------------------- a layer is an offset
# The paged kernels take the whole stacked pool and find a layer by a
# block offset in their index map: reading the wrong layer is the new
# way to be wrong, so every test below gives every layer its own data.

def _layered_pools(L, NB, BS, KH, D, quantized, dtype=jnp.float32):
    """``(k, v, scales)``: pools ``[L, NB, BS, KH*D]`` as PagedKVCache
    stores them (int8 + ``[L, NB, KH, BS]`` scale tiles when
    ``quantized``), every layer and the null block 0 holding their own
    random rows."""
    from deepspeed_tpu.ops.quant_core import quantize_int8
    out, scales = [], {}
    for name, seed in (("k", 11), ("v", 12)):
        pool = _rand(seed, (L, NB, BS, KH, D))
        if quantized:
            pool, s = quantize_int8(pool, -1)
            scales[f"{name}_scale"] = s[..., 0].transpose(0, 1, 3, 2)
        else:
            pool = pool.astype(dtype)
        out.append(pool.reshape(L, NB, BS, KH * D))
    return out[0], out[1], scales


def _poisoned(k, v, scales, tables, seen):
    """The pools with every byte a right walk never uses made lethal:
    rows at or beyond ``seen[slot]`` positions of a slot's last live
    block hold large finite garbage (a missing bound shows), and every
    block no slot's walk reaches, the null block among them, holds NaN
    (an int8 pool: NaN scales), in every layer, so one block too many
    shows however it is masked."""
    L, NB, BS, W = k.shape
    live = np.zeros((NB, BS), bool)       # rows some slot attends
    reached = np.zeros(NB, bool)          # blocks some walk fetches
    for row, n in zip(tables, seen):
        for pos in range(n):
            live[row[pos // BS], pos % BS] = True
        reached[row[:-(-n // BS)]] = True
    tail = jnp.asarray(reached[:, None] & ~live)[None, :, :, None]
    dead = jnp.asarray(~reached)
    if scales:
        fill = lambda pool, big: jnp.where(tail, jnp.int8(big), pool)
        kill = lambda tile: jnp.where(dead[None, :, None, None], jnp.nan,
                                      tile)
        return fill(k, 127), fill(v, -127), {n: kill(t)
                                             for n, t in scales.items()}
    fill = lambda pool, big: jnp.where(
        dead[None, :, None, None], jnp.nan,
        jnp.where(tail, big, pool)).astype(pool.dtype)
    return fill(k, 3e4), fill(v, -3e4), {}


# what the one kernel body is asked for: one query a slot, a verify
# window, a prefill chunk of one and of several row blocks
_KINDS = {"decode": None, "verify": 4, "chunk128": 128, "chunk256": 256}
# pool: (dtype of q and a float pool, int8 pool + scales, tolerance)
_POOLS = {"fp32": (jnp.float32, False, 2e-5),
          "bf16": (jnp.bfloat16, False, 3e-2),
          "int8": (jnp.float32, True, 2e-5)}


@pytest.mark.parametrize("pool", sorted(_POOLS))
@pytest.mark.parametrize("heads", ["mha", "gqa4"])
@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_paged_kernels_attend_their_own_layer(kind, heads, pool):
    """Each paged kernel (interpret mode) over a three-layer pool whose
    layers hold different data, first and last layer against the
    per-layer float32 oracle: a shuffled block table, ragged bounds (an
    idle slot, 1, one short of a block, a block, one past it, the whole
    table), heads batched into one product (decode; GQA verify) and a
    product a head (MHA verify, chunks; a 256-token chunk is several
    row blocks), and a pool POISONED wherever a right walk does not
    look (:func:`_poisoned`): the oracle reads the clean pool."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    dtype, quantized, tol = _POOLS[pool]
    H, KH = {"mha": (16, 16), "gqa4": (8, 2)}[heads]
    L, D, NB, BS, MB, T = 3, 16, 24, 32, 12, _KINDS[kind]
    k, v, scales = _layered_pools(L, NB, BS, KH, D, quantized, dtype)
    ids = iter(np.random.default_rng(5).permutation(np.arange(1, NB)))
    both = (0, L - 1)
    if kind.startswith("chunk"):
        # one slot's table; the chunk at the table's start (both layers)
        # and at its end (one will do)
        calls = []
        for start, layers in ((0, both), (MB * BS - T, both[1:])):
            row = np.zeros(MB, np.int32)
            blocks = (start + T) // BS
            row[:blocks] = [next(ids) for _ in range(blocks)]
            calls.append((_rand(start, (T, H, D)).astype(dtype), row,
                          jnp.int32(start), [start + T], layers))
        kernel, oracle = (da.paged_chunk_attention,
                          da.paged_chunk_attention_reference)
    else:
        # live lengths; a verify window sees its own K tokens beyond them
        K = T or 0
        lens = np.asarray([0, 1, BS - 1, BS, BS + 1, MB * BS - K])
        seen = [int(n) + K for n in lens]
        bt = np.zeros((len(lens), MB), np.int32)
        for slot, n in enumerate(seen):
            bt[slot, :-(-n // BS)] = [next(ids) for _ in range(-(-n // BS))]
        q = _rand(0, (len(lens), K, H, D) if T else (len(lens), H, D))
        calls = [(q.astype(dtype), bt, jnp.asarray(lens, jnp.int32), seen,
                  both)]
        kernel, oracle = ((da.paged_verify_attention,
                           da.paged_verify_attention_reference) if T else
                          (da.paged_decode_attention,
                           da.paged_decode_attention_reference))
    outs = []
    for q, table, bound, seen, layers in calls:
        bad_k, bad_v, bad_scales = _poisoned(k, v, scales,
                                             np.atleast_2d(table), seen)
        table = jnp.asarray(table)
        for layer in layers:
            got = kernel(q, bad_k, bad_v, table, bound, interpret=True,
                         layer=layer, **bad_scales)
            want = oracle(q.astype(jnp.float32), k[layer], v[layer], table,
                          bound,
                          **{n: s[layer] for n, s in scales.items()})
            got = np.asarray(got.astype(jnp.float32))
            live = np.asarray(seen) > 0 if T is None else slice(None)
            np.testing.assert_allclose(got[live], np.asarray(want)[live],
                                       rtol=tol, atol=tol,
                                       err_msg=f"layer {layer}")
            if T is None:                  # an idle slot reads nothing
                assert not got[~live].any()
            outs.append(got)
    # the layers' answers differ, so a wrong offset cannot pass above
    assert not np.allclose(outs[0], outs[1], atol=5e-2)
    with pytest.raises(ValueError, match="3-layer pool"):
        kernel(q, k, v, table, bound, interpret=True, layer=L, **scales)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("kind", ["decode", "verify", "chunk"])
def test_paged_steps_read_the_layer_they_wrote(kind, quantized, monkeypatch):
    """Writer and reader agree on where a layer lies: a three-layer
    model's paged steps with attention through the Pallas kernels
    (interpret mode; the whole pool and a layer offset) against the same
    steps through the XLA gathers (one layer cut out, as on the CPU),
    over a pool the paged writers filled; decode also against the dense
    cache."""
    from deepspeed_tpu.model_implementations import transformer as tf
    V, E, L, H, BS, MB = 64, 32, 3, 4, 16, 4
    cfg = tf.InferenceTransformerConfig(
        vocab_size=V, n_positions=128, n_embd=E, n_layer=L, n_head=H,
        dtype=jnp.float32)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    cache = init_paged_cache(L, 2, 10, BS, MB, cfg.kv_heads, cfg.head_dim,
                             jnp.float32, quantized=quantized)
    bt = np.zeros((2, MB), np.int32)
    bt[0], bt[1] = [2, 5, 1, 0], [4, 3, 0, 0]
    cache = cache.replace(block_tables=jnp.asarray(bt))
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, V)
    plens = (32, 9)
    for slot, plen in enumerate(plens):
        _, cache = tf.paged_prefill(params, cfg, ids[slot:slot + 1],
                                    jnp.asarray([plen], jnp.int32), cache,
                                    jnp.int32(slot))
    tok = jnp.asarray([5, 9], jnp.int32)

    def step(cache):
        if kind == "decode":
            return tf.paged_decode_step(params, cfg, tok, cache,
                                        jnp.asarray([True, True]))[0]
        if kind == "verify":
            return tf.paged_verify_step(
                params, cfg, jnp.stack([tok, tok + 1, tok + 2], 1), cache)[0]
        return tf.paged_prefill_chunk(      # slot 0's second block again
            params, cfg, ids[:1, BS:2 * BS], jnp.int32(BS),
            jnp.asarray([2 * BS], jnp.int32), cache, jnp.int32(0))[0]

    by_gather = np.asarray(step(cache))
    monkeypatch.setattr(tf, "_use_decode_kernel", lambda *a: True)
    by_kernel = np.asarray(step(cache))
    np.testing.assert_allclose(by_kernel, by_gather, rtol=2e-4, atol=2e-4)
    if kind == "decode" and not quantized:
        monkeypatch.undo()
        dense = init_cache(L, 2, 64, cfg.kv_heads, cfg.head_dim, jnp.float32)
        _, dense = tf.prefill(params, cfg, ids,
                              jnp.asarray(plens, jnp.int32), dense)
        want, _ = tf.decode_step(params, cfg, tok, dense)
        np.testing.assert_allclose(by_kernel, np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_pool_arrays_are_the_stored_pool(quantized):
    """Memory accounting reads ``pool_arrays``: K and V as stored, one
    ``[L, NB, BS, KH*D]`` array each, and an int8 pool's two scale
    tiles."""
    L, NB, BS, KH, D = 3, 5, 16, 2, 8
    cache = init_paged_cache(L, 2, NB, BS, 2, KH, D, jnp.bfloat16,
                             quantized=quantized)
    arrays = pool_arrays(cache)
    assert [a.shape for a in arrays[:2]] == [(L, NB, BS, KH * D)] * 2
    assert (cache.num_kv_heads, cache.head_dim) == (KH, D)
    assert (cache.num_layers, cache.num_blocks, cache.block_size) == (
        L, NB, BS)
    rows = L * NB * BS * KH
    assert sum(a.nbytes for a in arrays) == (
        2 * rows * (D + 4) if quantized else 2 * rows * D * 2)


# ------------------------------ places with their own heads and widths

def test_one_cache_keeps_four_row_widths_and_defaults_mean_what_they_did():
    """``v_head_dim`` and ``ring_kv_heads``: a pool of 2-head rows beside
    rings of 4-head rows, keys 24 and values 16 wide, in ONE cache
    object over one set of block tables; writers reshape the new rows
    only; ``pool_arrays`` counts the real bytes. Without the new
    arguments the cache is the one it always was."""
    from deepspeed_tpu.inference.kv_cache import (paged_append_token,
                                                  ring_append_token,
                                                  ring_write_prompt)
    L, S, NB, BS, MB = 3, 2, 7, 16, 3
    cache = init_paged_cache(
        L, S, NB, BS, MB, 2, 24, jnp.float32,
        window_layers=(False, True, True), window=16, v_head_dim=16,
        ring_kv_heads=4)
    assert cache.k.shape == (1, NB, BS, 2 * 24)
    assert cache.v.shape == (1, NB, BS, 2 * 16)
    assert cache.ring_k.shape == (2, S * 2, BS, 4 * 24)
    assert cache.ring_v.shape == (2, S * 2, BS, 4 * 16)
    assert (cache.num_kv_heads, cache.ring_heads, cache.head_dim,
            cache.v_head_dim, cache.ring_rows) == (2, 4, 24, 16, 32)
    assert sum(a.nbytes for a in pool_arrays(cache)) == 4 * (
        NB * BS * 2 * 40 + 2 * S * 2 * BS * 4 * 40)
    cache = cache.replace(
        block_tables=jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        lengths=jnp.asarray([17, 3], jnp.int32))
    k, v = _rand(1, (S, 2, 24)), _rand(2, (S, 2, 16))
    out = paged_append_token(cache, 0, k, v)
    np.testing.assert_array_equal(out.k[0, 2, 1], k[0].reshape(-1))
    np.testing.assert_array_equal(out.v[0, 4, 3], v[1].reshape(-1))
    rk, rv = _rand(3, (S, 4, 24)), _rand(4, (S, 4, 16))
    out = ring_append_token(out, 1, rk, rv)
    np.testing.assert_array_equal(out.ring_k[1, 1, 1], rk[0].reshape(-1))
    np.testing.assert_array_equal(out.ring_v[1, 2, 3], rv[1].reshape(-1))
    pk, pv = _rand(5, (48, 4, 24)), _rand(6, (48, 4, 16))
    out = ring_write_prompt(out, 0, pk, pv, jnp.int32(1), jnp.int32(40))
    np.testing.assert_array_equal(      # position 39 at row 39 mod 32 = 7
        out.ring_v[0, 2, 7], pv[39].reshape(-1))
    assert out.k.shape == cache.k.shape and out.ring_v.shape == (
        cache.ring_v.shape)
    # the defaults: one head count, one width, everywhere
    old = init_paged_cache(L, S, NB, BS, MB, 2, 24, jnp.float32,
                           window_layers=(False, True, True), window=16)
    assert old.k.shape == old.v.shape == (1, NB, BS, 48)
    assert old.ring_k.shape == old.ring_v.shape == (2, S * 2, BS, 48)
    assert (old.ring_kv_heads, old.ring_heads, old.v_head_dim) == (
        None, 2, 24)
    with pytest.raises(NotImplementedError, match="two widths"):
        init_paged_cache(L, S, NB, BS, MB, 2, 24, quantized=True,
                         v_head_dim=16)
    with pytest.raises(ValueError, match="ring_kv_heads"):
        init_paged_cache(L, S, NB, BS, MB, 2, 24, ring_kv_heads=4)


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("shape", ["d24-v16", "d192-v128", "d128-v64"])
def test_decode_kernel_takes_narrow_values_and_a_sink(shape, sink, kind):
    """The one decode kernel body (interpret mode) with values narrower
    than keys, a sink a query head and heads whose lanes do not tile
    (192: the heads of a block share ONE block-diagonal product up to 64
    query rows), over the pool and over rings, against the float32
    oracles and against the softmax written out; an idle slot still
    writes zeros."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    D, Dv, H, KH = {"d24-v16": (24, 16, 8, 2), "d192-v128": (192, 128, 64, 4),
                    "d128-v64": (128, 64, 8, 8)}[shape]
    if kind == "window":
        KH = min(2 * KH, H)
    S, BS, NB, MB = 4, 16, 13, 3
    q = _rand(1, (S, H, D))
    b = 2.0 + _rand(2, (H,)) if sink else None
    assert da._ragged(192) and not da._ragged(128) and not da._ragged(64)
    if kind == "full":
        k = _rand(3, (2, NB, BS, KH * D))
        v = _rand(4, (2, NB, BS, KH * Dv))
        tables = jnp.asarray(np.arange(1, 13).reshape(4, 3), jnp.int32)
        lengths = jnp.asarray([1, BS + 1, 0, MB * BS], jnp.int32)
        got = da.paged_decode_attention(q, k, v, tables, lengths, layer=1,
                                        sink=b, interpret=True)
        want = da.paged_decode_attention_reference(q, k[1], v[1], tables,
                                                   lengths, sink=b)
        rows = lambda s: (k[1][tables[s]].reshape(-1, KH, D),
                          v[1][tables[s]].reshape(-1, KH, Dv),
                          np.arange(MB * BS) < int(lengths[s]))
    else:
        window, RB = BS, 2
        k = _rand(3, (2, S * RB, BS, KH * D))
        v = _rand(4, (2, S * RB, BS, KH * Dv))
        lengths = jnp.asarray([1, BS + 1, 0, 5 * BS + 3], jnp.int32)
        got = da.paged_window_decode_attention(q, k, v, lengths, window,
                                               layer=1, sink=b,
                                               interpret=True)
        want = da.paged_window_decode_attention_reference(
            q, k[1], v[1], lengths, window, sink=b)

        def rows(s):
            from deepspeed_tpu.inference.kv_cache import ring_newest_position
            n = int(lengths[s]) - 1
            pos = np.asarray(ring_newest_position(jnp.int32(n), RB * BS))
            return (k[1][s * RB:(s + 1) * RB].reshape(-1, KH, D),
                    v[1][s * RB:(s + 1) * RB].reshape(-1, KH, Dv),
                    (pos >= 0) & (pos > n - window))
    assert got.shape == (S, H, Dv)
    assert not np.asarray(got[2]).any()                 # the idle slot
    live = np.array([0, 1, 3])
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    for s in live:                  # the softmax written out, a slot
        ks, vs, seen = rows(s)
        sc = np.einsum("hd,shd->hs", np.asarray(q[s], np.float64),
                       np.repeat(np.asarray(ks, np.float64), H // KH, 1)
                       ) / np.sqrt(D)
        e = np.where(seen[None], np.exp(sc), 0.0)
        den = e.sum(-1) + (np.exp(np.asarray(b, np.float64)) if sink
                           else 0.0)
        out = np.einsum("hs,shd->hd", e / den[:, None],
                        np.repeat(np.asarray(vs, np.float64), H // KH, 1))
        np.testing.assert_allclose(got[s], out, atol=2e-5)


# the one-token walk at several table entries an iteration: kv heads,
# query rows a head, key / value lanes, ring blocks (0: a pool), window,
# sink, and lengths whose live-block counts hit every remainder mod 2, 3
# and 4 (a slot of one row, a ring not yet wrapped, idle slots first,
# last and between)
_WALKS = {
    # 4 x 16 rows over heads of 192 lanes: one block-diagonal product
    "ragged-batched": (4, 16, 192, 128, 0, 0, False,
                       [0, 1, 16, 17, 40, 48, 0, 64, 70, 96, 144, 0]),
    # 8 x 6 rows: a product a head
    "per-head": (8, 6, 128, 128, 0, 0, False,
                 [0, 1, 16, 17, 40, 48, 0, 64, 70, 96, 144, 0]),
    "ring2-sink": (8, 8, 192, 128, 2, 16, True,
                   [0, 1, 15, 16, 17, 0, 33, 500, 0]),
    "ring5": (8, 8, 128, 128, 5, 64, False,
              [0, 3, 16, 47, 64, 65, 0, 80, 81, 1000, 0]),
}


@functools.lru_cache(maxsize=None)
def _walk_case(case):
    """One ``_WALKS`` case over a two-layer POISONED pool (blocks of 16
    rows; :func:`_poisoned`; a ring's unwritten rows likewise: NaN in
    the blocks its walk does not reach, large garbage behind the newest
    row of the block it does): the kernel as a function of the entries
    an iteration, the float32 oracle over the clean second layer, the
    live slots, and the walk of one entry an iteration (once a case)."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    KH, rep, D, Dv, RB, window, sink, lengths = _WALKS[case]
    S, BS, L = len(lengths), 16, 2
    q = _rand(1, (S, KH * rep, D))
    b = 2.0 + _rand(2, (KH * rep,)) if sink else None
    seen = np.asarray(lengths)
    if RB:
        MB, NB = RB, S * RB
        tables = np.asarray(da.ring_tables(S, RB))
    else:
        MB = -(-max(lengths) // BS)
        NB = 1 + S * MB
        tables = 1 + np.random.default_rng(5).permutation(S * MB).reshape(
            S, MB)
    k, v = _rand(3, (L, NB, BS, KH * D)), _rand(4, (L, NB, BS, KH * Dv))
    pk, pv, _ = _poisoned(k, v, {}, tables, np.minimum(seen, MB * BS))
    if RB:
        want = da.paged_window_decode_attention_reference(
            q, k[1], v[1], jnp.asarray(seen), window, sink=b)
    else:
        want = da.paged_decode_attention_reference(
            q, k[1], v[1], jnp.asarray(tables), jnp.asarray(seen), sink=b)

    def run(entries):
        return np.asarray(da._paged_attention(
            q.reshape(S, KH, rep, D), pk, pv, jnp.asarray(tables, jnp.int32),
            jnp.asarray(seen - 1, jnp.int32), rep=rep, scale=None,
            interpret=True, name="x", layer=1, window=window, sink=b,
            entries=entries)).reshape(S, KH * rep, Dv)
    return run, np.asarray(want), seen > 0, run(1)


@pytest.mark.parametrize("case,entries", [
    (case, entries) for case in sorted(_WALKS) for entries in (1, 2, 3, 4)
    if entries <= (_WALKS[case][4] or 4)])      # at most the ring
def test_decode_walk_of_several_entries_is_the_walk_of_one(case, entries):
    """The one-token kernel (interpret mode) attending ``entries`` table
    entries a loop iteration, pinned through ``_paged_attention``'s
    private keyword: equal TO THE BIT to the walk of one entry an
    iteration (the float32 sums and their order are the same), which is
    the float32 oracle's to tolerance; over a poisoned pool, so a copy
    of a dead entry or a row past a slot's bound shows."""
    run, want, live, one = _walk_case(case)
    np.testing.assert_allclose(one[live], want[live], atol=2e-5)
    assert not one[~live].any()                     # idle slots: zeros
    if entries > 1:
        np.testing.assert_array_equal(run(entries), one)


def test_entries_an_iteration_follow_the_bytes_of_a_block():
    """The rule at the five cells' shapes (blocks of 128 bfloat16 rows;
    K + V bytes a table entry, table entries): about a mebibyte an
    iteration, at most four entries (two where each head has a product
    of its own) and at most the table; GPT-2 1.3B's block is a mebibyte,
    so its walk is the one it was. Verify windows,
    prefill chunks and int8 pools walk an entry an iteration whatever
    their blocks weigh, and the gauge says what each signature got."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    from deepspeed_tpu.telemetry.registry import get_registry
    rule = da._entries_per_iteration

    def slab(kv_heads, D, Dv):
        return 128 * kv_heads * (D + Dv) * 2
    one, each = dict(batched=True), dict(batched=False)   # product(s) a block
    assert rule(slab(4, 192, 128), 96, True, False, **one) == 3   # MiMo full
    assert rule(slab(8, 192, 128), 2, True, False, **one) == 2    # MiMo ring
    assert rule(slab(8, 128, 128), 80, True, False, **each) == 2  # Laguna full
    assert rule(slab(8, 128, 128), 5, True, False, **each) == 2   # Laguna ring
    assert rule(slab(8, 128, 128), 48, True, False, **one) == 2   # Granite
    assert rule(slab(16, 128, 128), 8, True, False, **one) == 1   # GPT-2 1.3B
    assert rule(slab(1, 64, 64), 96, True, False, **one) == 4     # at most 4
    assert rule(slab(1, 64, 64), 3, True, False, **one) == 3      # ... the table
    assert rule(slab(4, 128, 128), 80, True, False, **each) == 2  # ... 2 a head
    assert rule(slab(4, 192, 128), 96, False, False, **one) == 1  # tokens
    assert rule(slab(4, 192, 128), 96, True, True, **one) == 1    # int8

    # through the entry points, on toy blocks (a rule that looked at the
    # bytes alone would give each of these four)
    S, H, KH, D, NB, BS, MB, T = 2, 4, 2, 16, 9, 16, 4, 4
    k, v, scales = _layered_pools(1, NB, BS, KH, D, False)
    k8, v8, scales8 = _layered_pools(1, NB, BS, KH, D, True)
    tables = jnp.arange(1, 1 + S * MB, dtype=jnp.int32).reshape(S, MB)
    lengths = jnp.asarray([5, 40], jnp.int32)
    da._paged_call.cache_clear()
    da.paged_decode_attention(_rand(1, (S, H, D)), k, v, tables, lengths,
                              interpret=True)
    da.paged_decode_attention(_rand(1, (S, H, D)), k8, v8, tables, lengths,
                              interpret=True, **scales8)
    da.paged_verify_attention(_rand(1, (S, T, H, D)), k, v, tables, lengths,
                              interpret=True)
    da.paged_chunk_attention(_rand(1, (BS, H, D)), k, v, tables[1],
                             jnp.int32(BS), interpret=True)

    def got(kernel, nbytes):
        return get_registry().gauge(
            "paged_decode_entries_per_iteration", labels={
                "kernel": kernel, "slab_bytes": str(nbytes)}).value
    fp, int8 = BS * KH * 2 * D * 4, BS * KH * 2 * D
    assert got("paged_decode_attention", fp) == 4
    assert got("paged_decode_attention", int8) == 1
    assert got("paged_verify_attention", fp) == 1
    assert got("paged_chunk_attention", fp) == 1
    with pytest.raises(ValueError, match="table entries an iteration"):
        da._paged_attention(
            _rand(1, (S, KH, T * H // KH, D)), k, v, tables, lengths,
            rep=H // KH, scale=None, interpret=True, name="x", entries=2)


def test_decode_kernel_refuses_what_it_cannot_carry():
    from deepspeed_tpu.ops.pallas import decode_attention as da
    q = _rand(1, (2, 8, 16))
    k = _rand(2, (1, 5, 16, 2 * 16))
    tables = jnp.ones((2, 2), jnp.int32)
    with pytest.raises(ValueError, match="sink"):       # one a query head
        da.paged_decode_attention(q, k, k, tables, jnp.ones(2), sink=_rand(
            3, (4,)), interpret=True)
    with pytest.raises(ValueError, match="sink"):       # one token a slot
        da._paged_attention(
            q.reshape(2, 2, 4, 16).repeat(2, 2), k, k, tables,
            jnp.ones(2, jnp.int32), rep=4, scale=None, interpret=True,
            name="x", sink=_rand(3, (8,)))
    with pytest.raises(ValueError, match="multiple of 2 of V"):
        da.paged_decode_attention(q, k, k[..., :31], tables, jnp.ones(2),
                                  interpret=True)
    # heads of 192 lanes and more query rows than one product carries
    q = _rand(1, (1, 4, 8 * 16, 192)).reshape(1, 4, 128, 192)
    wide = _rand(2, (1, 3, 16, 4 * 192))
    with pytest.raises(ValueError, match="do not tile"):
        da._paged_attention(q, wide, wide, jnp.ones((1, 2), jnp.int32),
                            jnp.ones(1, jnp.int32), rep=16, scale=None,
                            interpret=True, name="x")


def test_block_allocator_free_list():
    alloc = BlockAllocator(8)       # 7 usable, block 0 reserved
    assert alloc.free_blocks == 7
    got = alloc.allocate(3)
    assert got is not None and 0 not in got and len(set(got)) == 3
    assert alloc.allocate(5) is None          # 4 left
    alloc.release(got)
    assert alloc.free_blocks == 7
    with pytest.raises(ValueError, match="double free"):
        alloc.release([alloc.allocate(1)[0] + 0] * 2)
    with pytest.raises(ValueError, match="null block"):
        alloc.release([0])
    with pytest.raises(ValueError, match="2 pool blocks"):
        BlockAllocator(1)


def test_dense_advance_and_prompt_roundtrip():
    """write_prompt + advance bookkeeping sanity (the dense invariants
    the paged tests mirror)."""
    L, B, S, H, D = 1, 2, 32, 2, 4
    cache = init_cache(L, B, S, H, D, jnp.float32)
    k = _rand(0, (B, 8, H, D))
    cache = write_prompt(cache, 0, k, k, jnp.asarray([8, 3], jnp.int32))
    np.testing.assert_array_equal(np.asarray(cache.lengths), [8, 3])
    cache = advance(cache)
    np.testing.assert_array_equal(np.asarray(cache.lengths), [9, 4])
    np.testing.assert_array_equal(np.asarray(cache.k[0, 0, :8]),
                                  np.asarray(k[0]))
