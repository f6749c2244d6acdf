"""int8 paged KV cache + host offload of cold blocks (docs/serving.md
"KV quantization & host tiering").

Three layers of pins:

* **quant core** (ops/quant_core.py): round-trip error bounds of the
  shared per-axis int8 idiom — the contract both SwitchBack training
  and the KV writers lean on.
* **int8 writers / kernels** (inference/kv_cache.py, ops/pallas/
  decode_attention.py): the PR-1 cache invariants survive quantization
  — K=1 verify-write ≡ append (same int8 bytes AND scales), writes
  across block edges, garbage-beyond-lengths invisibility — and the
  Pallas kernels' VMEM dequant matches the XLA oracle.
* **host tier** (BlockAllocator + HostKVTier + server): demote → hit →
  swap-in reproduces never-evicted content exactly, double demotes are
  loud, famine demotes BEFORE the preemption ladder fires, and the
  serving A/B stays greedy-token-identical with zero retraces. Fake
  clock everywhere; no sleeps.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.kv_cache import (
    BlockAllocator, HostKVTier, init_paged_cache, paged_append_token,
    paged_gather_kv, paged_read_block, paged_swap_in, paged_write_prompt,
    paged_write_tokens, prefix_block_hashes)
from deepspeed_tpu.ops.quant_core import (INT8_QMAX, dequantize_int8,
                                          quantize_int8)


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


# ------------------------------------------------------------ quant core


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("axis", [-1, 0, None])
def test_quant_roundtrip_error_bound(seed, axis):
    """|dequant(quant(x)) - x| <= scale/2 elementwise — round-to-nearest
    of an in-range value; the bound every consumer (KV parity, fake-
    quant training noise) is sized against."""
    x = _rand(seed, (6, 8, 16), scale=3.0)
    q, s = quantize_int8(x, axis)
    assert q.dtype == jnp.int8
    deq = dequantize_int8(q, s)
    err = np.abs(np.asarray(deq) - np.asarray(x))
    bound = np.broadcast_to(np.asarray(s) / 2, x.shape)
    assert np.all(err <= bound + 1e-7)
    # relative to the slice amax the error never exceeds 1/254
    assert np.max(err) <= np.max(np.abs(np.asarray(x))) / (2 * INT8_QMAX) \
        + 1e-7


def test_quant_zero_slice_and_extremes():
    """All-zero slices take scale 1.0 (dequant = exact 0, never 0/0);
    the amax element always round-trips exactly (it maps to ±127)."""
    x = jnp.asarray([[0.0, 0.0, 0.0], [1.0, -2.0, 0.5]], jnp.float32)
    q, s = quantize_int8(x, -1)
    np.testing.assert_array_equal(np.asarray(q[0]), 0)
    np.testing.assert_array_equal(np.asarray(s[0]), 1.0)
    deq = np.asarray(dequantize_int8(q, s))
    np.testing.assert_allclose(deq[1, 1], -2.0, rtol=1e-6)  # the amax
    np.testing.assert_array_equal(deq[0], 0.0)


def test_quant_training_alias_unchanged():
    """ops/int8_training's _quant is now THE shared definition — same
    function object, so the two paths cannot drift."""
    from deepspeed_tpu.ops import int8_training
    assert int8_training._quant is quantize_int8


# ----------------------------------------------------- int8 pool writers


def _quant_pool(seed, NB, BS, KH, D, L=2):
    """A random int8 pool of ``L`` layers as PagedKVCache stores it,
    ``[L, NB, BS, KH*D]``, + matching ``[L, NB, KH, BS]`` scale tiles."""
    kp = _rand(seed, (L, NB, BS, KH, D))
    q, s = quantize_int8(kp, -1)
    return (kp, q.reshape(L, NB, BS, KH * D),
            s[..., 0].transpose(0, 1, 3, 2))


def test_int8_write_tokens_k1_equals_append():
    """paged_write_tokens with K=1 must produce byte-identical int8
    payloads AND scale tiles to paged_append_token — the verify and
    decode paths share the quantized layout only if this holds."""
    L, H, D, BS = 2, 2, 8, 16
    cache = init_paged_cache(L, 2, 6, BS, 2, H, D, jnp.float32,
                             quantized=True)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.asarray([5, 17], jnp.int32)
    a = cache.replace(block_tables=bt, lengths=lengths)
    b = cache.replace(block_tables=bt, lengths=lengths)
    for layer in range(L):
        k = _rand(10 + layer, (2, H, D))
        v = _rand(20 + layer, (2, H, D))
        a = paged_append_token(a, layer, k, v)
        b = paged_write_tokens(b, layer, k[:, None], v[:, None])
    for field in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)),
                                      err_msg=field)


def test_int8_write_across_block_edges():
    """A K-token verify write straddling a block boundary resolves each
    position's (block, offset, scale-tile slot) independently — the
    gathered dequantized cache equals per-token dequantized appends."""
    L, H, D, BS, K = 1, 2, 8, 16, 6
    cache = init_paged_cache(L, 1, 6, BS, 3, H, D, jnp.float32,
                             quantized=True)
    cache = cache.replace(
        block_tables=jnp.asarray([[2, 5, 1]], jnp.int32),
        lengths=jnp.asarray([BS - 3], jnp.int32))     # straddles 2->5
    k = _rand(0, (1, K, H, D))
    v = _rand(1, (1, K, H, D))
    chunked = paged_write_tokens(cache, 0, k, v)
    stepwise = cache
    for i in range(K):
        stepwise = paged_append_token(stepwise, 0, k[:, i], v[:, i])
        stepwise = stepwise.replace(lengths=stepwise.lengths + 1)
    stepwise = stepwise.replace(lengths=cache.lengths)
    for field in ("k", "v", "k_scale", "v_scale"):
        np.testing.assert_array_equal(
            np.asarray(getattr(chunked, field)),
            np.asarray(getattr(stepwise, field)), err_msg=field)
    gk, _ = paged_gather_kv(chunked, 0)
    want = np.asarray(k[0])
    got = np.asarray(gk[0])[BS - 3:BS - 3 + K]
    assert np.max(np.abs(got - want)) <= np.max(np.abs(want)) / 254 + 1e-7


def test_int8_garbage_beyond_lengths_invisible():
    """Random garbage written beyond ``lengths`` — int8 payload AND
    scale tiles both scribbled — must not move decode logits by a bit:
    the dead-tail invariant survives quantization because masking
    happens after dequant, scale garbage included."""
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, paged_decode_step)
    from deepspeed_tpu.model_implementations.transformer import \
        init_params as tf_init
    V, E, L, H, BS = 64, 32, 2, 4, 16
    cfg = InferenceTransformerConfig(vocab_size=V, n_positions=128,
                                     n_embd=E, n_layer=L, n_head=H,
                                     dtype=jnp.float32)
    params = tf_init(jax.random.PRNGKey(0), cfg)
    cache = init_paged_cache(L, 2, 8, BS, 3, cfg.kv_heads, cfg.head_dim,
                             jnp.float32, quantized=True)
    cache = cache.replace(
        block_tables=jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        lengths=jnp.asarray([10, 20], jnp.int32))
    k = _rand(1, (BS * 3, cfg.kv_heads, cfg.head_dim))
    v = _rand(2, (BS * 3, cfg.kv_heads, cfg.head_dim))
    for layer in range(L):
        for slot in (0, 1):
            cache = paged_write_prompt(cache, layer, k, v,
                                       jnp.int32(slot))
    tok = jnp.asarray([5, 9], jnp.int32)
    active = jnp.asarray([True, True])
    logits_clean, _ = paged_decode_step(params, cfg, tok, cache, active)

    # scribble payload + scales beyond lengths (positions >= lengths
    # within each slot's table)
    dead_k = np.array(cache.k)
    dead_scale = np.array(cache.k_scale)
    rng = np.random.default_rng(0)
    bt = np.asarray(cache.block_tables)
    lens = np.asarray(cache.lengths)
    for s in range(2):
        for j, blk in enumerate(bt[s]):
            for o in range(BS):
                if j * BS + o >= lens[s]:
                    dead_k[:, blk, o] = rng.integers(
                        -127, 127, dead_k[:, blk, o].shape)
                    dead_scale[:, blk, :, o] = rng.uniform(
                        0.5, 50.0, dead_scale[:, blk, :, o].shape)
    dirty = cache.replace(k=jnp.asarray(dead_k),
                          v=jnp.asarray(dead_k),
                          k_scale=jnp.asarray(dead_scale),
                          v_scale=jnp.asarray(dead_scale))
    # v payload garbage too — reuse k's scribble for both
    dirty = dirty.replace(v=jnp.asarray(dead_k))
    # restore the LIVE v content (only dead positions may differ)
    vv = np.asarray(cache.v)
    dv = np.array(dirty.v)
    vs = np.asarray(cache.v_scale)
    dvs = np.array(dirty.v_scale)
    for s in range(2):
        for j, blk in enumerate(bt[s]):
            for o in range(BS):
                if j * BS + o < lens[s]:
                    dv[:, blk, o] = vv[:, blk, o]
                    dvs[:, blk, :, o] = vs[:, blk, :, o]
    dirty = dirty.replace(v=jnp.asarray(dv), v_scale=jnp.asarray(dvs))
    logits_dirty, _ = paged_decode_step(params, cfg, tok, dirty, active)
    np.testing.assert_array_equal(np.asarray(logits_clean),
                                  np.asarray(logits_dirty))


def test_int8_paged_kernels_match_reference():
    """The three Pallas paged kernels (interpret mode) with VMEM
    dequant against the dequantize-then-dense oracle — block-table
    indirection, partial tails, idle slot; the second layer of a
    two-layer pool against the reference over that layer."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_chunk_attention, paged_chunk_attention_reference,
        paged_decode_attention, paged_decode_attention_reference,
        paged_verify_attention, paged_verify_attention_reference)
    S, H, KH, D, NB, BS, MB = 3, 8, 2, 16, 12, 32, 4
    _, qk, ks = _quant_pool(1, NB, BS, KH, D)
    _, qv, vs = _quant_pool(2, NB, BS, KH, D)
    bt = jnp.asarray([[3, 5, 0, 0], [1, 2, 7, 9], [11, 0, 0, 0]],
                     jnp.int32)
    lens = jnp.asarray([40, 100, 17], jnp.int32)
    q = _rand(0, (S, H, D))
    LAYER = 1
    one = dict(k_scale=ks[LAYER], v_scale=vs[LAYER])   # the oracle's
    got = paged_decode_attention(q, qk, qv, bt, lens, interpret=True,
                                 k_scale=ks, v_scale=vs, layer=LAYER)
    want = paged_decode_attention_reference(q, qk[LAYER], qv[LAYER], bt,
                                            lens, **one)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # an idle slot (length 0) must produce zeros, not NaN
    got0 = paged_decode_attention(q, qk, qv, bt,
                                  jnp.asarray([0, 100, 17], jnp.int32),
                                  interpret=True, k_scale=ks,
                                  v_scale=vs, layer=LAYER)
    assert not np.any(np.isnan(np.asarray(got0)))
    np.testing.assert_array_equal(np.asarray(got0[0]), 0.0)
    qv_q = _rand(3, (S, 3, H, D))
    gotv = paged_verify_attention(qv_q, qk, qv, bt, lens, layer=LAYER,
                                  interpret=True, k_scale=ks, v_scale=vs)
    wantv = paged_verify_attention_reference(qv_q, qk[LAYER], qv[LAYER],
                                             bt, lens, **one)
    np.testing.assert_allclose(np.asarray(gotv), np.asarray(wantv),
                               rtol=2e-5, atol=2e-5)
    qc = _rand(4, (BS, H, D))
    gotc = paged_chunk_attention(qc, qk, qv, bt[1], jnp.int32(BS),
                                 interpret=True, k_scale=ks, v_scale=vs,
                                 layer=LAYER)
    wantc = paged_chunk_attention_reference(qc, qk[LAYER], qv[LAYER],
                                            bt[1], BS, **one)
    np.testing.assert_allclose(np.asarray(gotc), np.asarray(wantc),
                               rtol=2e-5, atol=2e-5)


def test_scale_mismatch_is_loud():
    """An int8 pool without scales (or an fp pool with them) must raise
    at the kernel boundary, not silently attend over raw int8."""
    from deepspeed_tpu.ops.pallas.decode_attention import \
        paged_decode_attention
    S, H, KH, D, NB, BS, MB = 1, 2, 2, 8, 4, 16, 2
    q = _rand(0, (S, H, D))
    bt = jnp.zeros((S, MB), jnp.int32)
    lens = jnp.zeros((S,), jnp.int32)
    _, qk, ks = _quant_pool(1, NB, BS, KH, D)
    with pytest.raises(ValueError, match="require k_scale"):
        paged_decode_attention(q, qk, qk, bt, lens, interpret=True)
    fp = _rand(2, (2, NB, BS, KH * D))
    with pytest.raises(ValueError, match="must not pass"):
        paged_decode_attention(q, fp, fp, bt, lens, interpret=True,
                               k_scale=ks, v_scale=ks)


# -------------------------------------------------------- allocator tier


def _fake_device(num_blocks):
    """A dict standing in for the device pool: block id -> payload."""
    return {b: {"k": np.full((2, 2), float(b))} for b in
            range(num_blocks)}


def _wire(alloc, tier, device):
    """Bind demote/swap-in callbacks that copy through the fake
    device — the same protocol the server implements with real
    arrays (the allocator pops the payload from the tier BEFORE the
    staging allocation and hands it to on_swap_in)."""
    def demote(b, h):
        tier.put(h, {k: v.copy() for k, v in device[b].items()})

    def swap_in(b, payload):
        device[b] = payload

    alloc.on_demote = demote
    alloc.on_swap_in = swap_in


def test_demote_hit_swap_in_content_parity():
    """demote → prefix hit → swap-in hands back EXACTLY the bytes the
    block held when it parked — tiering must be invisible to content,
    matching a pool big enough to never evict."""
    tier = HostKVTier()
    alloc = BlockAllocator(6, enable_prefix_caching=True,
                           host_tier=tier)
    device = _fake_device(6)
    _wire(alloc, tier, device)
    hashes = prefix_block_hashes(list(range(8)), 4)  # 2 block hashes
    blocks = alloc.allocate(2)
    golden = {}
    for b, h in zip(blocks, hashes):
        device[b]["k"][:] = b * 10.0 + 1.0
        golden[h] = device[b]["k"].copy()
        assert alloc.register_prefix(b, h)
    alloc.release(blocks)          # park both
    # churn the pool so both parked blocks demote
    churn = alloc.allocate(5)
    assert alloc.demotions == 2 and tier.swap_outs == 2
    assert len(tier) == 2
    alloc.release(churn)
    # the prefix walk now hits the HOST tier and swaps both back in
    hit = alloc.match_prefix(hashes)
    assert len(hit) == 2
    assert alloc.swap_ins == 2 and tier.swap_ins == 2
    assert len(tier) == 0
    for b, h in zip(hit, hashes):
        np.testing.assert_array_equal(device[b]["k"], golden[h])
        assert alloc.block_hash(b) == h


def test_double_demote_is_loud():
    """Two device blocks demoting under the same chain hash means the
    refcount story broke — HostKVTier.put must raise, not overwrite."""
    tier = HostKVTier()
    tier.put(b"h1", {"k": np.zeros(2)})
    with pytest.raises(ValueError, match="double demote"):
        tier.put(b"h1", {"k": np.ones(2)})


def test_host_tier_capacity_drops_oldest():
    """Past max_blocks the OLDEST payload drops for good (host-LRU),
    and the drop is counted."""
    tier = HostKVTier(max_blocks=2)
    for i in range(3):
        tier.put(bytes([i]), {"k": np.zeros(1)})
    assert len(tier) == 2 and tier.dropped == 1
    assert not tier.has(bytes([0])) and tier.has(bytes([2]))


def test_bounded_tier_swap_in_survives_its_own_staging_drop():
    """A swap-in whose staging allocation demotes another block must
    not lose its own payload to the bounded tier's capacity drop: the
    allocator reserves the payload BEFORE popping the free list. With
    max_blocks=1, swapping h1 in forces h2's demotion, whose put()
    would otherwise evict h1 from the store mid-swap."""
    tier = HostKVTier(max_blocks=1)
    alloc = BlockAllocator(3, enable_prefix_caching=True,
                           host_tier=tier)
    device = _fake_device(3)
    _wire(alloc, tier, device)
    h1, h2 = prefix_block_hashes(list(range(8)), 4)
    b1 = alloc.allocate(1)
    device[b1[0]]["k"][:] = 11.0
    alloc.register_prefix(b1[0], h1)
    alloc.release(b1)
    churn = alloc.allocate(2)      # demotes h1 to host
    assert tier.has(h1)
    alloc.release(churn[1:])
    # park h2 and drain the free list so the swap-in's staging pop
    # MUST demote h2 (free list empty, LRU = {h2's block})
    alloc.register_prefix(churn[0], h2)
    alloc.release(churn[:1])
    alloc.allocate(1)              # held live: free list now empty
    hit = alloc.match_prefix([h1])
    assert len(hit) == 1
    np.testing.assert_array_equal(device[hit[0]]["k"],
                                  np.full((2, 2), 11.0))
    # h2's demotion landed (and is the tier's sole resident)
    assert tier.has(h2) and len(tier) == 1


def test_reregistered_hash_purges_stale_host_copy():
    """Bounded-tier stranding: after the tier drops a chain ANCESTOR,
    a descendant hash can sit host-resident while the re-prefilled
    chain re-registers it device-side. register_prefix must purge the
    stale host copy so the block's next demotion is not a (spurious)
    double demote."""
    tier = HostKVTier()
    alloc = BlockAllocator(4, enable_prefix_caching=True,
                           host_tier=tier)
    device = _fake_device(4)
    _wire(alloc, tier, device)
    h = prefix_block_hashes([1, 2, 3, 4], 4)[0]
    # simulate the stranded state: h host-resident but unknown to the
    # device index (its ancestor dropped, so match_prefix broke early
    # and the chain re-prefilled)
    tier.put(h, {"k": np.zeros((2, 2))})
    b = alloc.allocate(1)
    assert alloc.register_prefix(b[0], h)
    assert not tier.has(h)          # stale copy purged
    assert tier.superseded == 1
    alloc.release(b)
    alloc.allocate(3)               # forces the demotion — must not raise
    assert alloc.demotions == 1 and tier.has(h)


def test_tier_requires_prefix_caching():
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        BlockAllocator(4, enable_prefix_caching=False,
                       host_tier=HostKVTier())


def test_unwired_tier_falls_back_to_eviction():
    """Until the owner binds the copy callbacks, an LRU pop is a plain
    eviction — never silent data teleportation."""
    tier = HostKVTier()
    alloc = BlockAllocator(3, enable_prefix_caching=True,
                           host_tier=tier)
    b = alloc.allocate(1)
    h = prefix_block_hashes([1, 2, 3, 4], 4)[0]
    alloc.register_prefix(b[0], h)
    alloc.release(b)
    alloc.allocate(2)              # forces the LRU pop
    assert alloc.evictions == 1 and alloc.demotions == 0
    assert len(tier) == 0


def test_rolled_back_swap_in_parks_device_side():
    """A match_prefix whose tail allocation fails rolls back — a
    swapped-in block re-parks DEVICE-side with its hash (content
    intact), not back to the host tier."""
    tier = HostKVTier()
    alloc = BlockAllocator(4, enable_prefix_caching=True,
                           host_tier=tier)
    device = _fake_device(4)
    _wire(alloc, tier, device)
    h = prefix_block_hashes([1, 2, 3, 4], 4)[0]
    b = alloc.allocate(1)
    alloc.register_prefix(b[0], h)
    alloc.release(b)
    churn = alloc.allocate(3)      # demotes the parked block
    assert alloc.demotions == 1
    alloc.release(churn)
    hit = alloc.match_prefix([h])
    assert len(hit) == 1
    alloc.rollback_match(hit)      # tail allocation failed upstream
    assert len(tier) == 0          # content stays device-side...
    hit2 = alloc.match_prefix([h])  # ...and hits WITHOUT a swap
    assert hit2 == hit
    assert alloc.swap_ins == 1


# --------------------------------------------------------- server-level


def _smoke_server(**kw):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.server import ContinuousBatchingServer
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    from deepspeed_tpu.telemetry import MetricRegistry
    mcfg = InferenceTransformerConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=2, n_head=4,
        dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    cfg = DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=kw.pop("max_out_tokens", 256),
        block_size=32, num_slots=kw.pop("num_slots", 4), **kw)
    eng = InferenceEngine((mcfg, params), cfg)
    return ContinuousBatchingServer(eng, registry=MetricRegistry())


def test_server_int8_greedy_parity_and_no_retrace():
    """The int8 server's greedy tokens are identical to the fp
    server's on the smoke model, with ONE decode executable and zero
    retraces — quantization is data, not signature."""
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [4, 4, 6, 6, 1, 2, 3]]
    outs = []
    for dtype in ("fp", "int8"):
        srv = _smoke_server(kv_cache_dtype=dtype)
        ids = [srv.submit(p, max_new_tokens=8) for p in prompts]
        res = srv.drain()
        outs.append([res[i] for i in ids])
        st = srv.stats
        assert st["retraces"] == 0
        assert st["decode_traces"] == 1
        if dtype == "int8":
            assert st["kv_tier"]["kv_dtype"] == "int8"
            # int8 payload + f32 scale tiles vs the f32 smoke pool:
            # comfortably past the 2x capacity bar
            assert fp_bytes >= 2 * st["kv_tier"]["pool_bytes"]
        else:
            fp_bytes = st["kv_tier"]["pool_bytes"]
        srv.close()
    assert outs[0] == outs[1]


def test_server_famine_demotes_before_preempt():
    """Under pool famine with the tier armed, admission demotes the
    coldest parked blocks (device→host) and the request is served —
    the preemption rung never fires and nothing is evicted. Fake
    clock: zero real sleeps."""
    t = [0.0]
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.server import ContinuousBatchingServer
    from deepspeed_tpu.model_implementations.transformer import (
        InferenceTransformerConfig, init_params)
    from deepspeed_tpu.telemetry import MetricRegistry
    mcfg = InferenceTransformerConfig(
        vocab_size=256, n_positions=512, n_embd=64, n_layer=2, n_head=4,
        dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    cfg = DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=128, block_size=32, num_slots=2,
        enable_prefix_caching=True, kv_host_offload=True)
    srv = ContinuousBatchingServer(
        InferenceEngine((mcfg, params), cfg),
        registry=MetricRegistry(),
        clock=lambda: t.__setitem__(0, t[0] + 0.001) or t[0])
    prefixes = [[1 + (s * 7 + i) % 250 for i in range(96)]
                for s in range(3)]
    for i in range(6):
        rid = srv.submit(prefixes[i % 3] + [7 + i, 9], max_new_tokens=4)
        srv.drain()
    st = srv.stats
    assert st["kv_tier"]["demotions"] > 0
    assert st["kv_tier"]["swap_ins"] > 0
    assert st["preempted"] == 0
    assert st["prefix_cache_evictions"] == 0
    assert st["kv_pool"]["swap_outs"] == st["kv_tier"]["demotions"]
    assert st["kv_pool"]["host_blocks"] == st["kv_tier"]["host_blocks"]
    srv.close()


def test_server_offload_parity_with_never_evicted():
    """demote → hit → swap-in through the real device pool reproduces
    the never-evicted server's greedy tokens exactly."""
    prefixes = [[1 + (s * 7 + i) % 250 for i in range(96)]
                for s in range(3)]

    def leg(**kw):
        kw.setdefault("max_out_tokens", 128)
        kw.setdefault("num_slots", 2)
        srv = _smoke_server(enable_prefix_caching=True, **kw)
        outs = []
        for i in range(6):
            rid = srv.submit(prefixes[i % 3] + [7 + i, 9],
                             max_new_tokens=4)
            outs.append(srv.drain()[rid])
        st = srv.stats
        srv.close()
        return outs, st

    # golden: same int8 storage, pool big enough that nothing ever
    # demotes — the comparison isolates TIERING (structurally
    # byte-invisible), not quantization (pinned by the parity test
    # above)
    golden, _ = leg(max_out_tokens=256, num_slots=4,
                    kv_cache_dtype="int8")
    tiered, st = leg(kv_host_offload=True, kv_cache_dtype="int8")
    assert st["kv_tier"]["swap_ins"] > 0
    assert tiered == golden


def test_server_host_bytes_visible_in_memory_snapshot():
    """/debug/memory accounts the tier: after a demotion the
    kv_host_tier host component reports nonzero bytes; close()
    unregisters it."""
    from deepspeed_tpu.telemetry import MetricRegistry
    from deepspeed_tpu.telemetry.memory import get_memory_monitor
    prefixes = [[1 + (s * 7 + i) % 250 for i in range(96)]
                for s in range(3)]
    srv = _smoke_server(max_out_tokens=128, num_slots=2,
                        enable_prefix_caching=True, kv_host_offload=True)
    for i in range(4):
        srv.submit(prefixes[i % 3] + [7 + i], max_new_tokens=4)
        srv.drain()
    snap = get_memory_monitor().snapshot(MetricRegistry())
    host = snap["host_components"]
    assert host["kv_host_tier"]["bytes"] > 0
    assert snap["host_bytes_total"] >= host["kv_host_tier"]["bytes"]
    srv.close()
    snap2 = get_memory_monitor().snapshot(MetricRegistry())
    assert "kv_host_tier" not in snap2["host_components"]


def test_swap_thrash_event_fires_once_per_episode():
    """A sustained swap-in storm (every admission cycles blocks through
    the tier) fires ONE kv_swap_thrash ring event."""
    from deepspeed_tpu.telemetry.events import (KV_SWAP_THRASH, EventRing,
                                                set_event_ring)
    ring = EventRing(256)
    prev = set_event_ring(ring)
    try:
        srv = _smoke_server(max_out_tokens=128, num_slots=2,
                            enable_prefix_caching=True,
                            kv_host_offload=True)
        # tighten the window so the smoke trace can fill it
        srv._SWAP_WINDOW_STEPS = 4
        srv._swap_window = type(srv._swap_window)(maxlen=4)
        prefixes = [[1 + (s * 7 + i) % 250 for i in range(96)]
                    for s in range(3)]
        for i in range(12):
            srv.submit(prefixes[i % 3] + [7 + i], max_new_tokens=4)
            srv.drain()
        events = [e for e in ring.snapshot()
                  if e["kind"] == KV_SWAP_THRASH]
        assert len(events) == 1
        assert events[0]["data"]["swap_ins_per_step"] > 0
        assert srv.stats["kv_tier"]["thrash_alarm"] is True
        srv.close()
    finally:
        set_event_ring(prev)


def test_config_validation():
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    with pytest.raises(ValueError, match="enable_prefix_caching"):
        DeepSpeedInferenceConfig(kv_host_offload=True)
    with pytest.raises(ValueError, match="kv_host_offload"):
        DeepSpeedInferenceConfig(kv_host_blocks=4)
    with pytest.raises(ValueError):
        DeepSpeedInferenceConfig(kv_cache_dtype="int4")
    cfg = DeepSpeedInferenceConfig(kv_cache_dtype="int8",
                                   kv_host_offload=True,
                                   enable_prefix_caching=True,
                                   kv_host_blocks=64)
    assert cfg.kv_host_blocks == 64


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
def test_swap_in_roundtrip_preserves_bytes(quantized):
    """paged_read_block → HostKVTier → paged_swap_in is byte-exact
    (payload and, for an int8 pool, scale tiles), and the host payload
    holds, per (layer, position, head), the row that was written there:
    the pool's layout is the payload's, a position's heads side by
    side."""
    L, BS, KH, D = 2, 16, 2, 8
    cache = init_paged_cache(L, 1, 5, BS, 2, KH, D, jnp.float32,
                             quantized=quantized)
    rows = [_rand(layer, (2 * BS, KH, D)) for layer in range(L)]
    cache = cache.replace(
        block_tables=jnp.asarray([[1, 3]], jnp.int32))
    for layer, k in enumerate(rows):      # every layer its own data
        cache = paged_write_prompt(cache, layer, k, 2 * k, jnp.int32(0))
    payload = paged_read_block(cache, 3)  # positions BS..2*BS-1
    for layer, k in enumerate(rows):
        want, scale = k[BS:], None
        if quantized:
            want, scale = quantize_int8(want, -1)
        np.testing.assert_array_equal(
            payload["k"][layer].reshape(BS, KH, D), np.asarray(want))
        if quantized:
            np.testing.assert_array_equal(
                payload["k_scale"][layer].T, np.asarray(scale[..., 0]))
    assert payload["k"].nbytes == L * BS * KH * D * (1 if quantized else 4)
    # snapshot before the swap-in DONATES the cache buffers
    golden = {f: np.asarray(getattr(cache, f)[:, 3]) for f in payload}
    assert set(golden) == ({"k", "v", "k_scale", "v_scale"} if quantized
                           else {"k", "v"})
    tier = HostKVTier()
    tier.put(b"h", payload)
    out = paged_swap_in(cache, 4, tier.take(b"h"))
    for field, want in golden.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(out, field)[:, 4]), want, err_msg=field)
    for layer, k in enumerate(rows):      # and reads back through a table
        out = out.replace(block_tables=jnp.asarray([[1, 4]], jnp.int32))
        gk, gv = paged_gather_kv(out, layer)
        np.testing.assert_allclose(np.asarray(gk[0]), np.asarray(k),
                                   atol=0.05 if quantized else 0)


def test_block_transfer_traces_once_per_geometry():
    """Both tier-copy directions take the block id as TRACED data: N
    distinct blocks reading out (and one writing back) must not grow
    the jit caches beyond one executable per pool pytree structure."""
    from deepspeed_tpu.inference import kv_cache as kvc
    cache = init_paged_cache(1, 1, 8, 16, 2, 2, 8, jnp.float32,
                             quantized=True)
    read0 = kvc._read_block_impl._cache_size()
    payloads = [paged_read_block(cache, b) for b in range(1, 6)]
    assert kvc._read_block_impl._cache_size() - read0 <= 1
    swap0 = kvc._swap_in_impl._cache_size()
    for b, p in enumerate(payloads, start=1):
        cache = paged_swap_in(cache, b, p)
    assert kvc._swap_in_impl._cache_size() - swap0 <= 1
