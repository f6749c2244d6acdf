"""Continuous batching + paged KV cache — the serving-layer contracts.

The acceptance oracle is one-shot ``generate()``: for the same prompts,
the ContinuousBatchingServer must be token-for-token identical (greedy),
while recycling slots (fewer decode-step·slot units than one-shot on a
staggered workload) and tracing the decode step at most once per
``(num_slots, block_size)`` configuration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)


def make_engine(seed=0, max_out_tokens=256, block_size=32, num_slots=4,
                max_queued_requests=128, **knobs):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    base.update(knobs)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens,
        block_size=block_size, num_slots=num_slots,
        max_queued_requests=max_queued_requests))


PROMPTS = [[1, 2, 3, 4], [7, 8], [5, 6, 7, 8, 9, 10], [11, 12, 13],
           [20, 21], [30], [40, 41, 42, 43, 44], [50, 51]]


def test_paged_decode_parity_with_oneshot_generate():
    """THE acceptance criterion: greedy server output == greedy
    generate(), token for token, with more requests than slots so
    recycling is exercised."""
    eng = make_engine()
    srv = ContinuousBatchingServer(eng)
    ids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS]
    out = srv.drain()
    ref = eng.generate(PROMPTS, max_new_tokens=6)
    assert [out[i] for i in ids] == ref
    # recycling happened (8 requests through 4 slots) on ONE trace
    st = srv.stats
    assert st["prefills"] == len(PROMPTS)
    assert st["decode_traces"] == 1


def test_parity_with_eos_early_exit():
    eng = make_engine(seed=3)
    ref = eng.generate([[1, 2, 3, 4]], max_new_tokens=8)
    eos = ref[0][5]                     # second generated token
    srv = ContinuousBatchingServer(eng)
    rid = srv.submit([1, 2, 3, 4], max_new_tokens=8, eos_token_id=eos)
    # an EOS on the very first (prefill) token also finishes cleanly
    t0 = ref[0][4]
    rid2 = srv.submit([1, 2, 3, 4], max_new_tokens=8, eos_token_id=t0)
    out = srv.drain()
    assert out[rid] == eng.generate([[1, 2, 3, 4]], max_new_tokens=8,
                                    eos_token_id=eos)[0]
    assert out[rid2] == [1, 2, 3, 4, t0]


@pytest.mark.parametrize("knobs", [
    dict(positional="rotary", norm_type="rmsnorm", gated_mlp=True,
         activation="silu", n_kv_head=2, tied_lm_head=False),   # llama/GQA
    dict(positional="alibi"),                                    # bloom
    dict(local_windows=(None, 4)),                               # gpt-neo
])
def test_paged_parity_across_architectures(knobs):
    """Rotary/GQA, ALiBi and windowed layers all route through the paged
    attention path (XLA fallback on CPU) and must match one-shot."""
    eng = make_engine(seed=1, **knobs)
    srv = ContinuousBatchingServer(eng)
    prompts = [[3, 17, 9, 44, 2], [60, 61, 62]]
    ids = [srv.submit(p, max_new_tokens=5) for p in prompts]
    out = srv.drain()
    assert [out[i] for i in ids] == eng.generate(prompts,
                                                 max_new_tokens=5)


def test_staggered_arrivals_fewer_slot_units_than_oneshot():
    """Head-of-line blocking, quantified: requests with mixed budgets
    arriving over time. One-shot batching pays num_slots × the slowest
    row per batch; continuous batching recycles early-EOS slots, so its
    decode-step·slot units must come in strictly lower."""
    eng = make_engine(num_slots=4)
    srv = ContinuousBatchingServer(eng)
    budgets = [4, 24, 4, 4, 24, 4, 4, 4]
    ids = [srv.submit(p, max_new_tokens=b)
           for p, b in zip(PROMPTS, budgets)]
    out = srv.drain()
    st = srv.stats
    # one-shot comparator: same requests in arrival order, batches of
    # num_slots, each batch spins until its slowest row finishes
    gen_lens = {}
    for rid, p in zip(ids, PROMPTS):
        gen_lens[rid] = len(out[rid]) - len(p)
    oneshot_units = 0
    for i in range(0, len(ids), srv.num_slots):
        batch = ids[i:i + srv.num_slots]
        # generate()'s while_loop runs max(gen)-1 decode steps for the
        # batch (token 0 comes from prefill), each over num_slots rows
        oneshot_units += srv.num_slots * (
            max(gen_lens[r] for r in batch) - 1)
    assert st["decode_step_slot_units"] < oneshot_units, \
        (st, oneshot_units)
    assert st["decode_traces"] == 1
    # outputs still exact vs the one-shot oracle, per-request
    for rid, p, b in zip(ids, PROMPTS, budgets):
        assert out[rid] == eng.generate([p], max_new_tokens=b)[0]


def test_decode_traced_once_across_request_mixes():
    """The decode step must not retrace as the request mix changes —
    one trace per (num_slots, block_size) config, full stop."""
    eng = make_engine()
    srv = ContinuousBatchingServer(eng)
    srv.submit([1, 2, 3], max_new_tokens=3)
    srv.drain()
    srv.submit(list(range(1, 100)), max_new_tokens=7)   # long prompt
    srv.submit([4], max_new_tokens=2)
    srv.drain()
    assert srv.stats["decode_traces"] == 1
    # prefill traces: one per prompt bucket (128-token bucket here)
    assert srv._prefill_jit._cache_size() == 1


def test_prompt_bucket_clamped_to_slot_span():
    """A prompt whose geometric bucket overshoots the slot's block span
    (250 tokens → 512 bucket > 256-token slot) must clamp to the span
    and still match one-shot generate."""
    eng = make_engine(max_out_tokens=256, block_size=32, num_slots=2)
    srv = ContinuousBatchingServer(eng)
    prompt = [1 + (i % 120) for i in range(250)]
    assert len(prompt) % 128 != 0            # genuinely mid-bucket
    rid = srv.submit(prompt, max_new_tokens=5)
    out = srv.drain()
    assert out[rid] == eng.generate([prompt], max_new_tokens=5)[0]


def test_admission_control():
    eng = make_engine(max_out_tokens=128, block_size=32, num_slots=2,
                      max_queued_requests=3)
    srv = ContinuousBatchingServer(eng)
    # per-slot budget 128 tokens = 4 blocks; a request spanning more
    # can NEVER run → loud at submit
    with pytest.raises(ValueError, match="spans"):
        srv.submit(list(range(1, 120)), max_new_tokens=64)
    for i in range(3):
        srv.submit([1, 2], max_new_tokens=4)
    with pytest.raises(RuntimeError, match="queue is full"):
        srv.submit([1, 2], max_new_tokens=4)
    srv.drain()
    # queue drained → admissible again
    srv.submit([1, 2], max_new_tokens=4)
    srv.drain()


def test_blocks_recycle_to_capacity():
    """After drain, every block is back on the free list."""
    eng = make_engine()
    srv = ContinuousBatchingServer(eng)
    total = srv.scheduler.allocator.free_blocks
    for p in PROMPTS:
        srv.submit(p, max_new_tokens=6)
    srv.drain()
    assert srv.scheduler.allocator.free_blocks == total
    assert srv.scheduler.idle


def test_server_config_validation():
    with pytest.raises(ValueError, match="block_size"):
        DeepSpeedInferenceConfig(block_size=48)
    with pytest.raises(ValueError, match="num_slots"):
        DeepSpeedInferenceConfig(num_slots=0)
    with pytest.raises(ValueError, match="max_queued_requests"):
        DeepSpeedInferenceConfig(max_queued_requests=-1)
    # per-slot budget below one block is loud at server build
    eng = make_engine(max_out_tokens=128, block_size=256)
    with pytest.raises(ValueError, match="below one block"):
        ContinuousBatchingServer(eng)
    with pytest.raises(ValueError, match="empty prompt"):
        ContinuousBatchingServer(make_engine()).submit([])


def test_duplicate_request_id_rejected():
    srv = ContinuousBatchingServer(make_engine())
    srv.submit([1, 2], max_new_tokens=2, request_id=7)
    with pytest.raises(ValueError, match="request_id 7"):
        srv.submit([3, 4], max_new_tokens=2, request_id=7)   # queued
    srv.drain()
    with pytest.raises(ValueError, match="request_id 7"):
        srv.submit([3, 4], max_new_tokens=2, request_id=7)   # finished
    assert srv.submit([3, 4], max_new_tokens=2) == 8         # auto id


def test_paged_kernel_interpret_matches_reference():
    """The Pallas paged kernel (interpret mode) against the gather
    oracle — block-table indirection, partial tail blocks, an idle
    slot, out-of-order block ids, and the second layer of a two-layer
    pool."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_attention_reference)
    S, H, KH, D, NB, BS, MB = 3, 8, 2, 16, 12, 32, 4
    q = jax.random.normal(jax.random.PRNGKey(0), (S, H, D), jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    vp = jax.random.normal(jax.random.PRNGKey(2), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    bt = jnp.asarray([[3, 5, 0, 0], [1, 2, 7, 9], [11, 0, 0, 0]],
                     jnp.int32)
    lens = jnp.asarray([40, 100, 17], jnp.int32)
    got = paged_decode_attention(q, kp, vp, bt, lens, interpret=True,
                                 layer=1)
    want = paged_decode_attention_reference(q, kp[1], vp[1], bt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # an idle slot (length 0) must produce zeros, not NaN
    got0 = paged_decode_attention(q, kp, vp, bt,
                                  jnp.asarray([0, 100, 17], jnp.int32),
                                  interpret=True, layer=1)
    assert not np.any(np.isnan(np.asarray(got0)))
    np.testing.assert_array_equal(np.asarray(got0[0]), 0.0)


def test_tensor_parallel_server_matches_single():
    """tp=2 over the virtual CPU mesh: paged serving must reproduce the
    unsharded server's tokens."""
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ref_eng = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=32, num_slots=2))
    tp_eng = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=32, num_slots=2,
        tensor_parallel={"tp_size": 2}))
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]]
    outs = []
    for eng in (ref_eng, tp_eng):
        srv = ContinuousBatchingServer(eng)
        ids = [srv.submit(p, max_new_tokens=5) for p in prompts]
        res = srv.drain()
        outs.append([res[i] for i in ids])
    assert outs[0] == outs[1]
