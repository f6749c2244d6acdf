"""Per-slot speculative decoding in the continuous-batching server.

Two oracles pin the tentpole:

1. **Exactness vs the one-shot speculative path**: the server's per-slot
   prompt-lookup speculation and ``engine.generate_speculative(draft=
   None)`` share the SAME proposal rule, acceptance rule, and verify
   math (the paged gather reproduces the dense cache bit-for-bit), so
   their outputs must be token-identical — not tie-tolerant, identical.
2. **Greedy parity**: speculation only changes how many target forwards
   run, never what they commit — server output with speculation ON
   matches plain greedy ``generate()`` up to oracle-verified argmax
   ties (the same standard the one-shot speculative suite pins).

Plus the trace-discipline contract (ONE verify executable per
``(speculation_tokens, num_slots, block_size)`` across varying per-slot
acceptance lengths), composition with chunked prefill + prefix caching
+ mid-speculation preemption, and the host/in-graph shared-helper
equivalence that keeps the two paths from drifting.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_speculative_decoding import _assert_equal_up_to_ties

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference.speculation import (LookupIndex,
                                                 greedy_accept,
                                                 greedy_accept_host,
                                                 lookup_proposals,
                                                 lookup_proposals_host)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, MetricRegistry,
                                     get_event_ring, set_event_ring,
                                     set_registry)
from deepspeed_tpu.telemetry import events as ev

K = 4


@pytest.fixture()
def fresh_telemetry():
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(512))
    try:
        yield
    finally:
        set_registry(prev_reg)
        set_event_ring(prev_ring)


def make_engine(seed=0, max_out_tokens=256, block_size=32, num_slots=4,
                model=None, **knobs):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    base.update(model or {})
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens,
        block_size=block_size, num_slots=num_slots, **knobs))


PROMPTS = [[1, 2, 3, 4], [7, 8], [5, 6, 7, 8, 9, 10], [11, 12, 13],
           [20, 21], [30]]


def _serve(srv, prompts, budget, **kw):
    ids = [srv.submit(p, max_new_tokens=budget, **kw) for p in prompts]
    out = srv.drain()
    return [out[i] for i in ids]


# ------------------------------------------------------------- oracles

def test_spec_server_matches_oneshot_speculative_exactly():
    """THE dedup oracle: server speculation == one-shot prompt-lookup
    speculation, token for token — same proposals, same acceptance,
    same verify math, so the extracted shared module provably serves
    both paths."""
    eng = make_engine()
    ref = eng.generate_speculative(PROMPTS, max_new_tokens=12,
                                   draft_tokens=K)
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    got = _serve(srv, PROMPTS, 12)
    assert got == ref
    st = srv.stats
    sp = st["speculation"]
    assert sp["k"] == K
    assert sp["verify_traces"] == 1
    assert sp["accepted"] > 0                  # speculation really fired
    assert sp["committed_tokens"] > sp["verify_steps"]
    assert st["retraces"] == 0


def test_spec_parity_with_plain_greedy():
    """Speculation changes throughput, never tokens: server output with
    speculation ON matches greedy generate() up to oracle-verified
    argmax ties (the one-shot suite's standard)."""
    eng = make_engine()
    want = eng.generate(PROMPTS, max_new_tokens=12)
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    got = _serve(srv, PROMPTS, 12)
    for b in range(len(PROMPTS)):
        _assert_equal_up_to_ties(eng, want[b], got[b])


@pytest.mark.parametrize("model", [
    dict(positional="rotary", norm_type="rmsnorm", gated_mlp=True,
         activation="silu", n_kv_head=2, tied_lm_head=False),  # llama/GQA
    dict(positional="alibi"),                                  # bloom
    dict(local_windows=(None, 4)),                             # gpt-neo
])
def test_spec_parity_across_architectures(model):
    """Rotary/GQA, ALiBi and windowed layers all route the paged verify
    (XLA fallback on CPU) and must reproduce the one-shot speculative
    path exactly."""
    eng = make_engine(seed=1, model=model)
    prompts = [[3, 17, 9, 44, 2], [60, 61, 62]]
    ref = eng.generate_speculative(prompts, max_new_tokens=8,
                                   draft_tokens=K)
    srv = ContinuousBatchingServer(
        make_engine(seed=1, model=model, speculation_tokens=K))
    assert _serve(srv, prompts, 8) == ref


def test_spec_parity_tp2():
    """tp=2 over the virtual CPU mesh: the batched verify shards like
    the decode step and must reproduce the unsharded output."""
    ref = make_engine().generate_speculative(
        [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], max_new_tokens=6,
        draft_tokens=K)
    srv = ContinuousBatchingServer(make_engine(
        speculation_tokens=K, num_slots=2,
        tensor_parallel={"tp_size": 2}))
    assert _serve(srv, [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], 6) == ref


def test_spec_eos_stops_inside_accepted_block():
    """An EOS landing mid-block (inside an accepted run of proposals)
    must stop the request exactly there — the tokens after it in the
    same verify chunk are never served."""
    eng = make_engine(seed=3)
    base = eng.generate([[1, 2, 3, 4]], max_new_tokens=12)[0]
    eos = base[4 + 5]                      # the 6th generated token
    ref = eng.generate_speculative([[1, 2, 3, 4]], max_new_tokens=12,
                                   draft_tokens=K, eos_token_id=eos)
    srv = ContinuousBatchingServer(make_engine(seed=3,
                                               speculation_tokens=K))
    got = _serve(srv, [[1, 2, 3, 4]], 12, eos_token_id=eos)
    assert got == ref
    assert got[0][-1] == eos
    assert srv.finish_reason(0) == "eos"


# ------------------------------------------------- composition layers

def test_spec_with_prefix_cache_and_chunked_prefill():
    """Speculation composes with PR-5: shared-prefix prompts admit warm
    (blocks reused), prefill in chunks interleaved with speculative
    decode steps for resident slots, and the output is still exactly
    the one-shot speculative stream."""
    eng = make_engine()
    prefix = list(range(1, 65))            # 2 full 32-token blocks
    prompts = [prefix + [100 + j, 101, 102 + j] for j in range(5)]
    ref = eng.generate_speculative(prompts, max_new_tokens=10,
                                   draft_tokens=K)
    srv = ContinuousBatchingServer(make_engine(
        speculation_tokens=K, enable_prefix_caching=True))
    got = _serve(srv, prompts, 10)
    assert got == ref
    st = srv.stats
    assert st["prefix_cache_hits"] > 0     # warm admissions happened
    assert st["prefill_chunks"] > len(prompts)   # chunked, interleaved
    assert st["speculation"]["accepted"] > 0
    assert st["retraces"] == 0


def test_spec_preemption_mid_speculation(fresh_telemetry):
    """A slot preempted MID-speculation folds only its committed tokens
    into the requeue prompt (never the speculative garbage beyond its
    live length), replays, and finishes token-identical to an
    uninterrupted run — the PR-7 lifecycle composes with the verify
    path."""
    srv = ContinuousBatchingServer(make_engine(num_slots=1,
                                               speculation_tokens=K))
    a = srv.submit([1, 2, 3], max_new_tokens=20, priority=0)
    for _ in range(2):
        srv.step()                 # a is mid-stream, tokens committed
    committed = len(srv.scheduler.slots[0].generated)
    assert committed >= 2
    b = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    eng = make_engine(num_slots=1)
    assert out[a] == eng.generate_speculative([[1, 2, 3]],
                                              max_new_tokens=20,
                                              draft_tokens=K)[0]
    assert len(out[a]) == 3 + 20           # full budget delivered
    assert out[b] == eng.generate_speculative([[4, 5, 6]],
                                              max_new_tokens=4,
                                              draft_tokens=K)[0]
    assert srv.finish_reason(a) in ("eos", "length")
    # the requeue folded a committed prefix (preempt ring event says so)
    pre = [e for e in get_event_ring().snapshot()
           if e["kind"] == ev.PREEMPT]
    assert pre and pre[0]["data"]["committed_tokens"] >= 2


def test_spec_blocks_recycle_to_capacity():
    """After a speculative drain every block — the speculation margin's
    extra tail included — is back on the free list."""
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    total = srv.scheduler.allocator.free_blocks
    _serve(srv, PROMPTS, 12)
    assert srv.scheduler.allocator.free_blocks == total
    assert srv.scheduler.idle


def test_spec_margin_accounted_in_admission():
    """The verify overshoot (K-1 positions) is reserved up front: a
    request whose prompt+budget exactly fills a slot's span no longer
    fits once the margin is added — rejected loudly at submit, never a
    corrupted accepted token at the span edge."""
    # span: 128 tokens = 4 blocks of 32 — exactly max_blocks_per_slot
    srv = ContinuousBatchingServer(make_engine(
        max_out_tokens=128, num_slots=2))
    srv.submit(list(range(1, 65)), max_new_tokens=64)       # fits
    srv.drain()
    spec = ContinuousBatchingServer(make_engine(
        max_out_tokens=128, num_slots=2, speculation_tokens=K))
    with pytest.raises(ValueError, match="speculation margin"):
        spec.submit(list(range(1, 65)), max_new_tokens=64)  # 128 + K-1
    # one block of headroom admits it again
    spec.submit(list(range(1, 65)), max_new_tokens=32)
    spec.drain()


# --------------------------------------------------- trace discipline

def test_spec_verify_traced_once_across_acceptance_lengths():
    """THE retrace pin: one verify executable per (K, num_slots,
    block_size), full stop. Two drains with wildly different acceptance
    behavior (repetitive prompts = long accepted runs, scattered
    prompts = constant rejection) and varying budgets must not add a
    single signature or retrace."""
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    _serve(srv, [[1, 2] * 8, [9, 9, 9, 9]], 16)       # lookup-friendly
    _serve(srv, [[5, 31, 7, 90], [44], [3, 1, 4, 1, 5, 9]], 5)
    _serve(srv, [list(range(1, 100))], 7)             # long prompt
    assert srv._verify_jit._cache_size() == 1
    assert len(getattr(srv._verify_jit, "retraces", ())) == 0
    assert srv.stats["retraces"] == 0
    # the plain decode program is never traced while speculation is on
    assert srv.stats["decode_traces"] == 0


def test_spec_efficiency_fewer_steps_than_plain_decode():
    """The raw-speed claim, CPU-verifiable form: on a lookup-friendly
    workload the speculative server finishes the same requests in
    strictly fewer device steps (each step commits >1 token per slot on
    average), with the stats to prove it."""
    prompts = [([3, 7, 11, 5] * 6)[: 12 + j] for j in range(4)]
    on = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    got_on = _serve(on, prompts, 24)
    off = ContinuousBatchingServer(make_engine())
    got_off = _serve(off, prompts, 24)
    assert got_on == got_off                # same tokens, fewer steps
    assert on.stats["decode_steps"] < off.stats["decode_steps"]
    sp = on.stats["speculation"]
    assert sp["tokens_per_forward"] > 1.0
    assert sp["acceptance_rate"] > 0.0
    # bookkeeping closes: proposals come K-1 per active slot-step
    assert sp["proposed"] == (K - 1) * on._spec_slot_steps
    assert sp["committed_tokens"] <= K * on._spec_slot_steps


def test_paged_verify_kernel_interpret_matches_reference():
    """The Pallas batched-verify kernel (interpret mode) against the
    gather oracle — block-table indirection, per-slot lengths, partial
    tail blocks, an idle slot, out-of-order block ids, GQA grouping, the
    second layer of a two-layer pool."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_verify_attention, paged_verify_attention_reference)
    S, Kq, H, KH, D, NB, BS = 3, 4, 8, 2, 16, 12, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (S, Kq, H, D),
                          jnp.float32)
    kp = jax.random.normal(jax.random.PRNGKey(1), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    vp = jax.random.normal(jax.random.PRNGKey(2), (2, NB, BS, KH * D),
                           jnp.float32)   # two layers, as stored
    bt = jnp.asarray([[3, 5, 0, 0], [1, 2, 7, 9], [11, 0, 0, 0]],
                     jnp.int32)
    lens = jnp.asarray([40, 100, 17], jnp.int32)
    got = paged_verify_attention(q, kp, vp, bt, lens, interpret=True,
                                 layer=1)
    want = paged_verify_attention_reference(q, kp[1], vp[1], bt, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # an idle slot (length 0) attends only its own chunk: finite, and
    # the first query (bound col <= 0) sees exactly position 0
    got0 = paged_verify_attention(q, kp, vp, bt,
                                  jnp.asarray([0, 100, 17], jnp.int32),
                                  interpret=True, layer=1)
    assert not np.any(np.isnan(np.asarray(got0)))


# ------------------------------------------- shared-helper equivalence

def test_host_proposals_match_ingraph_rule():
    """The server's host-side proposal/acceptance mirrors ARE the
    engine's in-graph rules — pinned on random histories so the shared
    module cannot drift apart."""
    rng = np.random.default_rng(0)
    for trial in range(50):
        n = int(rng.integers(1, 40))
        hist_list = rng.integers(0, 6, size=n).tolist()  # small vocab:
        S = n + int(rng.integers(0, 8))                  # rich repeats
        hist = np.zeros((1, S), np.int32)
        hist[0, :n] = hist_list
        got_jax = np.asarray(lookup_proposals(
            jnp.asarray(hist), jnp.asarray([n], jnp.int32),
            jnp.asarray([hist_list[-1]], jnp.int32), K))[0].tolist()
        got_host = lookup_proposals_host(hist_list, K - 1)
        assert got_host == got_jax, (trial, hist_list)


def test_lookup_index_matches_rescan_incrementally():
    """The server's O(1)-per-step LookupIndex is the SAME rule as the
    full rescan (and therefore the in-graph rule): pinned over random
    grow-by-chunks sequences, including the mid-stream rebuild a
    preemption/re-admission path takes."""
    rng = np.random.default_rng(2)
    for trial in range(30):
        hist = rng.integers(0, 5, size=int(rng.integers(1, 6))).tolist()
        idx = LookupIndex(hist)
        for _ in range(12):
            assert idx.proposals(K - 1) == \
                lookup_proposals_host(hist, K - 1), (trial, hist)
            chunk = rng.integers(0, 5,
                                 size=int(rng.integers(1, K))).tolist()
            hist.extend(chunk)
            idx.extend(chunk)
        # a cold rebuild of the grown history agrees with the
        # incrementally-maintained index
        assert LookupIndex(hist).proposals(K - 1) == \
            idx.proposals(K - 1)


def test_host_accept_matches_ingraph_rule():
    rng = np.random.default_rng(1)
    for trial in range(50):
        t_row = rng.integers(0, 4, size=K)
        props = rng.integers(0, 4, size=K - 1)
        m_jax, corr, committed = greedy_accept(
            jnp.asarray(t_row[None]), jnp.asarray(props[None]), K)
        m_host, committed_host = greedy_accept_host(t_row, props)
        assert m_host == int(m_jax[0])
        # the in-graph committed block carries padding past m; the
        # host returns exactly the m+1 tokens that commit
        assert committed_host == np.asarray(
            committed)[0][:m_host + 1].tolist()
        assert committed_host[-1] == int(corr[0, 0])


# ----------------------------------------------------- config + alarm

def test_spec_config_validation():
    with pytest.raises(ValueError, match="speculation_tokens"):
        DeepSpeedInferenceConfig(speculation_tokens=1)
    with pytest.raises(ValueError, match="block_size"):
        DeepSpeedInferenceConfig(speculation_tokens=64, block_size=32)
    DeepSpeedInferenceConfig(speculation_tokens=0)        # off is fine
    DeepSpeedInferenceConfig(speculation_tokens=32, block_size=32)


def test_spec_collapse_ring_event(fresh_telemetry):
    """Acceptance-rate collapse fires ONE ring event per episode and
    re-arms after recovery — sustained wasted verify width is visible,
    a healthy workload never alarms."""
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))

    def events():
        return [e for e in get_event_ring().snapshot()
                if e["kind"] == ev.SPEC_COLLAPSE]

    # below min volume: never fires however bad the rate
    srv._maybe_spec_collapse(proposed=8, accepted=0)
    assert events() == []
    # volume + near-zero acceptance: exactly one event, not one per step
    for _ in range(30):
        srv._maybe_spec_collapse(proposed=12, accepted=0)
    assert len(events()) == 1
    assert events()[0]["data"]["k"] == K
    # recovery re-arms; a second collapse fires a second event
    for _ in range(80):
        srv._maybe_spec_collapse(proposed=12, accepted=6)
    assert srv._spec_alarm is False
    for _ in range(80):
        srv._maybe_spec_collapse(proposed=12, accepted=0)
    assert len(events()) == 2
