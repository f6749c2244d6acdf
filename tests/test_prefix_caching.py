"""Automatic prefix caching + chunked prefill — the serving contracts.

The acceptance oracle stays one-shot ``generate()``: greedy output with
prefix caching ON (blocks reused across requests, prefill skipping the
cached span) must be token-for-token identical to cold prefill, across
rotary/GQA and TP=2. The allocator contracts: refcounts never go
negative, a double free is loud, an evicted block's hash is forgotten
(a later identical prefix re-prefills), and the free list's set shadow
keeps release O(n). The trace contract: chunked prefill is ONE traced
signature per (chunk, num_slots, block_size) config, and one step()
never runs more than one chunk — resident decoders are stalled at most
one chunk per step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference.kv_cache import (BlockAllocator,
                                              prefix_block_hashes)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)


def make_engine(seed=0, max_out_tokens=256, block_size=32, num_slots=4,
                tp_size=1, **knobs):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    base.update(knobs.pop("model", {}))
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens,
        block_size=block_size, num_slots=num_slots,
        tensor_parallel={"tp_size": tp_size}, **knobs))


PREFIX = [1 + (i % 100) for i in range(64)]          # 2 full 32-blocks
PROMPTS = [PREFIX + [10 + j, 11 + j, 12 + j] for j in range(6)]


def _serve(eng, prompts, max_new_tokens=6):
    srv = ContinuousBatchingServer(eng)
    ids = [srv.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    out = srv.drain()
    return [out[i] for i in ids], srv


# ------------------------------------------------------------ parity

def test_prefix_cached_output_identical_to_cold():
    """THE acceptance criterion: warm the cache with one request, then
    serve shared-prefix requests — greedy outputs must equal one-shot
    generate() (== caching-off) token for token, with real hits and
    real prefill compute skipped."""
    ref = make_engine().generate(PROMPTS, max_new_tokens=6)
    eng = make_engine(enable_prefix_caching=True)
    srv = ContinuousBatchingServer(eng)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=6)
    srv.drain()                                      # warm: cold miss
    ids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS[1:]]
    out = srv.drain()
    assert srv.result(r0) == ref[0]
    assert [out[i] for i in ids] == ref[1:]
    st = srv.stats
    # 5 warm requests x 2 reusable prefix blocks, warm request misses 2
    assert st["prefix_cache_hits"] == 10
    assert st["prefix_cache_misses"] == 2
    assert st["prefix_tokens_skipped"] == 10 * 32
    # hit rate >= 50% of prefix-block lookups (acceptance floor)
    hits, misses = st["prefix_cache_hits"], st["prefix_cache_misses"]
    assert hits / (hits + misses) >= 0.5
    # pool fully recovers: shared blocks park in the evictable LRU but
    # stay allocatable
    assert st["free_blocks"] == srv.scheduler.allocator.usable_blocks


@pytest.mark.parametrize("knobs", [
    dict(model=dict(positional="rotary", norm_type="rmsnorm",
                    gated_mlp=True, activation="silu", n_kv_head=2,
                    tied_lm_head=False)),            # llama/GQA
    dict(tp_size=2),                                 # tensor parallel
    dict(model=dict(positional="alibi")),            # bloom (XLA path)
    dict(model=dict(local_windows=(None, 8))),       # windowed layers
])
def test_prefix_cached_parity_across_architectures(knobs):
    ref = make_engine(seed=1, **knobs).generate(PROMPTS[:4],
                                                max_new_tokens=5)
    eng = make_engine(seed=1, enable_prefix_caching=True, **knobs)
    srv = ContinuousBatchingServer(eng)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=5)
    srv.drain()                              # warm the cache
    ids = [srv.submit(p, max_new_tokens=5) for p in PROMPTS[1:4]]
    out = srv.drain()
    assert [srv.result(r0)] + [out[i] for i in ids] == ref
    assert srv.stats["prefix_cache_hits"] > 0


def test_chunked_prefill_parity_without_caching():
    """Sarathi-style chunking alone (caching off) must also match the
    one-shot oracle — chunk boundaries are invisible to the math."""
    ref = make_engine().generate(PROMPTS, max_new_tokens=6)
    eng = make_engine(prefill_chunk_tokens=32)
    out, srv = _serve(eng, PROMPTS)
    assert out == ref
    assert srv.stats["prefix_cache_hits"] == 0
    assert srv.stats["prefill_chunks"] >= len(PROMPTS) * 3  # 67 tok / 32


# ------------------------------------------------------------ traces

def test_chunked_prefill_traced_once():
    """ONE chunk signature per (chunk, num_slots, block_size) config:
    prompts of every length and cached depth replay the same trace."""
    eng = make_engine(enable_prefix_caching=True,
                      prefill_chunk_tokens=32)
    srv = ContinuousBatchingServer(eng)
    srv.submit(PROMPTS[0], max_new_tokens=4)
    srv.drain()
    srv.submit(PROMPTS[1], max_new_tokens=4)         # cached prefix
    srv.submit([7, 8, 9], max_new_tokens=3)          # sub-chunk prompt
    srv.submit(list(range(1, 100)), max_new_tokens=4)  # multi-chunk
    srv.drain()
    assert srv._chunk_jit._cache_size() == 1
    assert srv.stats["chunk_traces"] == 1
    assert srv.stats["decode_traces"] == 1
    assert srv.stats["retraces"] == 0
    # the monolithic prefill program was never traced in chunked mode
    assert srv.stats["prefill_traces"] == 0


def test_decode_never_stalls_more_than_one_chunk_per_step():
    """While a long prompt prefills chunk by chunk, an already-resident
    sequence keeps committing one token per step() — the monolithic
    stall this feature removes."""
    eng = make_engine(prefill_chunk_tokens=32)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=30)
    # let A prefill (its single chunk) and start decoding
    srv.step()
    base = len(srv.scheduler.slots[
        next(iter(srv.scheduler.slots))].generated)
    b = srv.submit(list(range(1, 120)), max_new_tokens=4)  # 4 chunks
    chunks_before = srv.stats["prefill_chunks"]
    for i in range(4):
        srv.step()
        st = srv.stats
        # at most one chunk per step, and A advanced every step
        assert st["prefill_chunks"] - chunks_before <= i + 1
    slot_a = [s for s, st_ in srv.scheduler.slots.items()
              if st_.request.request_id == a]
    assert slot_a, "A must still be decoding"
    assert len(srv.scheduler.slots[slot_a[0]].generated) >= base + 4
    out = srv.drain()
    assert out[b] == make_engine().generate(
        [list(range(1, 120))], max_new_tokens=4)[0]


# ------------------------------------------------------------ allocator

def test_allocator_refcount_sharing_and_double_free():
    alloc = BlockAllocator(8, enable_prefix_caching=True)
    blocks = alloc.allocate(2)
    h = prefix_block_hashes(list(range(64)), 32)
    assert alloc.register_prefix(blocks[0], h[0])
    assert alloc.register_prefix(blocks[1], h[1])
    # a second holder acquires by refcount — no new blocks consumed
    free0 = alloc.free_blocks
    hits = alloc.match_prefix(h)
    assert hits == blocks and alloc.free_blocks == free0
    alloc.release(blocks)                  # first holder done: ref 2->1
    alloc.release(blocks)                  # second done: ref 1->0 -> LRU
    assert alloc.cached_blocks == 2
    assert alloc.free_blocks == 7          # LRU blocks stay allocatable
    with pytest.raises(ValueError, match="double free"):
        alloc.release([blocks[0]])         # refcount would go negative
    # duplicate registration is first-writer-wins
    other = alloc.allocate(1)
    assert alloc.register_prefix(other[0], h[0]) is False
    with pytest.raises(ValueError, match="non-live"):
        alloc.register_prefix(blocks[0], b"x")   # evictable, not live


def test_allocator_eviction_forgets_hash():
    """When the free list dries up, the oldest evictable cached block is
    evicted and its hash forgotten — a later identical prefix MISSES
    (and re-prefills) instead of silently reading recycled memory."""
    alloc = BlockAllocator(4, enable_prefix_caching=True)   # 3 usable
    h = prefix_block_hashes(list(range(96)), 32)
    blocks = alloc.allocate(3)
    for b, hh in zip(blocks, h):
        alloc.register_prefix(b, hh)
    alloc.release(blocks)                  # all three evictable
    got = alloc.allocate(2)                # evicts the two oldest
    assert set(got) == set(blocks[:2])
    assert alloc.match_prefix(h) == []     # chain broken at block 0
    assert alloc.block_hash(blocks[0]) is None
    assert alloc.cached_blocks == 1        # deepest block still indexed
    # the survivor is unreachable (its parent is gone) but evictable
    assert alloc.allocate(1) == [blocks[2]]
    alloc.release(got)
    alloc.release([blocks[2]])


def test_allocator_match_stops_at_first_miss():
    alloc = BlockAllocator(8, enable_prefix_caching=True)
    h = prefix_block_hashes(list(range(96)), 32)
    blocks = alloc.allocate(3)
    alloc.register_prefix(blocks[0], h[0])
    alloc.register_prefix(blocks[2], h[2])   # hole at depth 1
    assert alloc.match_prefix(h) == [blocks[0]]
    alloc.release([blocks[0]])               # roll the hit back
    alloc.release(blocks)


def test_free_list_set_membership_large_release():
    """The double-free check must be O(1) per block (set shadow), not a
    linear scan of the free list — releasing N blocks into a mostly-free
    pool stays O(N). Pinned behaviorally: interleaved allocate/release
    keeps the set and list views consistent at scale."""
    n = 4097
    alloc = BlockAllocator(n)
    got = alloc.allocate(n - 1)
    alloc.release(got[2000:])
    alloc.release(got[:2000])
    assert alloc.free_blocks == n - 1
    assert sorted(alloc._free) == sorted(alloc._free_set)
    assert len(alloc._free_set) == n - 1
    with pytest.raises(ValueError, match="double free"):
        alloc.release([got[0]])
    # nothing hashed without prefix caching
    assert alloc.cached_blocks == 0


def test_chain_hashes_are_prefix_sensitive():
    a = prefix_block_hashes(list(range(64)), 32)
    b = prefix_block_hashes(list(range(1, 65)), 32)
    assert a[0] != b[0]
    # identical second block under a different first block hashes
    # differently (the chain pins absolute position)
    c = prefix_block_hashes(list(range(32, 96)), 32)
    assert a[1] != c[0] and len(a) == 2


def test_chain_hashes_read_ids_not_their_digits():
    # ids go in as int64 bytes: [1, 23] and [12, 3] must not collide,
    # and a list and an array of the same ids hash alike
    a = prefix_block_hashes([1, 23], 2)
    assert a != prefix_block_hashes([12, 3], 2)
    assert a != prefix_block_hashes([123, 0], 2)
    assert a == prefix_block_hashes(np.array([1, 23], np.int32), 2)
    assert prefix_block_hashes([1, 23, 5], 2) == a      # full blocks only


@pytest.mark.parametrize("caching", [True, False])
def test_submit_hashes_the_prompt_where_it_arrives(caching):
    from deepspeed_tpu.inference.scheduler import Request, Scheduler
    sched = Scheduler(num_slots=2, num_blocks=16, block_size=4,
                      max_blocks_per_slot=8, max_queued_requests=4,
                      enable_prefix_caching=caching)
    req = Request(request_id=1, prompt=list(range(10)), max_new_tokens=4)
    sched.submit(req)
    # the step loop's admission only looks the chain up
    assert (req._hashes is not None) == caching
    if caching:
        assert req._hashes == prefix_block_hashes(list(range(10)), 4)
        slot, state = sched.admit_next()
        assert state.prompt_hashes is req._hashes


# ------------------------------------------------------------ server

def test_fully_aligned_prompt_still_prefills_last_token():
    """A prompt that is exactly block-aligned caches all but its last
    block on lookup (the prefill must score the final token), and still
    matches the oracle."""
    prompt = PREFIX                                   # exactly 2 blocks
    ref = make_engine().generate([prompt, prompt], max_new_tokens=5)
    eng = make_engine(enable_prefix_caching=True)
    srv = ContinuousBatchingServer(eng)
    r0 = srv.submit(prompt, max_new_tokens=5)
    srv.drain()
    r1 = srv.submit(prompt, max_new_tokens=5)
    out = srv.drain()
    assert out[r0] == ref[0] and out[r1] == ref[1]
    # only ONE of the two full blocks is reusable; block 2 registers
    # but can never be looked up for this prompt length
    assert srv.stats["prefix_cache_hits"] == 1
    assert srv.stats["prefix_tokens_skipped"] == 32


def test_tail_blocks_reclaimed_on_early_eos():
    """A sequence that EOSes far below its budget returns its reserved
    never-written tail blocks at retirement, counted."""
    eng = make_engine()
    ref = eng.generate([PROMPTS[0]], max_new_tokens=60)[0]
    eos = ref[69]                    # third generated token
    srv = ContinuousBatchingServer(make_engine())
    rid = srv.submit(PROMPTS[0], max_new_tokens=60, eos_token_id=eos)
    out = srv.drain()
    assert out[rid][-1] == eos and len(out[rid]) < 67 + 60
    # span reserved ceil((67+60)/32)=4 blocks; cache ever held
    # 67+(g-1) tokens -> 3 blocks used
    assert srv.stats["tail_blocks_reclaimed"] >= 1
    assert srv.stats["free_blocks"] == srv.scheduler.allocator.usable_blocks


def test_prefix_cache_hits_share_memory_under_pressure():
    """More concurrent shared-prefix requests than private blocks could
    cover: sharing makes them fit (refcount > 1 on prefix blocks)."""
    # pool: 4 slots x 4 blocks = 16 usable; 6 requests x 4 blocks = 24
    # private blocks, but 2 shared prefix blocks bring residency down
    eng = make_engine(max_out_tokens=128, enable_prefix_caching=True)
    srv = ContinuousBatchingServer(eng)
    ref = make_engine(max_out_tokens=128).generate(
        PROMPTS, max_new_tokens=6)
    r0 = srv.submit(PROMPTS[0], max_new_tokens=6)
    srv.drain()
    ids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS[1:]]
    out = srv.drain()
    assert [out[i] for i in ids] == ref[1:] and srv.result(r0) == ref[0]
    alloc = srv.scheduler.allocator
    assert srv.stats["prefix_cache_hits"] >= 5
    assert alloc.free_blocks == alloc.usable_blocks


def test_config_validation():
    with pytest.raises(ValueError, match="multiple of block_size"):
        DeepSpeedInferenceConfig(block_size=128, prefill_chunk_tokens=96)
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        DeepSpeedInferenceConfig(prefill_chunk_tokens=-128)
    cfg = DeepSpeedInferenceConfig(enable_prefix_caching=True)
    assert cfg.prefill_chunk_tokens == 0      # server derives block_size
    eng = make_engine(enable_prefix_caching=True)
    assert ContinuousBatchingServer(eng).chunk_tokens == 32


def test_paged_chunk_kernel_interpret_matches_reference():
    """The Pallas chunked-prefill kernel (interpret mode) against the
    gather oracle — table indirection, nonzero start, GQA grouping,
    the second layer of a two-layer pool."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_chunk_attention, paged_chunk_attention_reference)
    C, H, KH, D, NB, BS = 32, 8, 2, 16, 12, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (C, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    vp = jax.random.normal(jax.random.PRNGKey(2), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    bt = jnp.asarray([3, 5, 7, 2, 9, 0], jnp.int32)
    for start in (0, 16, 48):
        got = paged_chunk_attention(q, kp, vp, bt, jnp.int32(start),
                                    interpret=True, layer=1)
        want = paged_chunk_attention_reference(q, kp[1], vp[1], bt, start)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
