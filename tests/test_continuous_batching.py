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


def test_bench_serve_continuous_smoke():
    """The bench phase's CPU smoke mode runs end-to-end and records the
    headline artifacts, including the continuous-vs-oneshot slot-unit
    win on the staggered trace."""
    import argparse
    import bench
    args = argparse.Namespace(iters=2, requests=10, arrival_rate=0.5,
                              smoke=True)
    rec = bench.phase_serve(args)
    assert rec["phase"] == "serve-continuous"
    assert rec["smoke"] is True
    assert rec["parity_exact"] is True
    assert rec["units_continuous"] < rec["units_oneshot"]
    assert rec["decode_traces"] == 1
    assert 0.0 < rec["slot_occupancy"] <= 1.0
    for k in ("tokens_per_s", "token_lat_p50_ms", "token_lat_p90_ms"):
        assert k in rec
    # telemetry snapshot embedded (docs/observability.md): histograms
    # populated, quantiles ordered, pool gauges present
    tm = rec["telemetry"]
    for k in ("ttft_p50_ms", "ttft_p90_ms", "queue_wait_p50_ms",
              "queue_wait_p90_ms", "decode_token_p50_ms",
              "slot_occupancy_last", "kv_free_blocks"):
        assert k in tm, k
    assert tm["ttft_count"] >= rec["requests"]     # every request + warmup
    assert tm["requests_finished"] >= rec["requests"]
    assert tm["ttft_p50_ms"] > 0
    assert tm["ttft_p50_ms"] <= tm["ttft_p90_ms"]
    assert tm["queue_wait_p50_ms"] <= tm["queue_wait_p90_ms"]
    assert tm["decode_token_p50_ms"] > 0
    # flight-recorder blob (docs/observability.md): one decode trace,
    # no retraces mid-replay, compiles timed
    fr = rec["flight_recorder"]
    assert fr["decode_traces"] == 1
    assert fr["retraces"] == 0
    assert fr["prefill_traces"] >= 1
    assert fr["compile_seconds_total"] > 0
    # request-tracing blob (docs/observability.md "Request tracing &
    # SLOs"): every replay request kept (sample rate 1.0), span trees
    # non-trivial
    tb = rec["tracing"]
    assert tb["sample_rate"] == 1.0
    assert tb["kept"] >= rec["requests"]      # every request + warmup
    assert tb["started"] >= tb["kept"] >= 1
    assert tb["spans_per_trace_p50"] >= 3     # root+queue+admission+...
    # SLO blob: generous objectives, so a healthy replay is compliant
    # and every configured objective was evaluated with a real value
    sb = rec["slo"]
    assert sb["compliance_ratio"] == 1.0
    assert sb["evaluations"] >= 1
    assert set(sb["objectives"]) == {"ttft_p90", "token_p50",
                                     "queue_wait_p90", "error_rate"}
    for obj in sb["objectives"].values():
        assert obj["violated"] is False
    # closed-loop mini-legs (docs/observability.md "SLOs, alerting &
    # incidents"): the undisturbed leg must not page — any false
    # positive is a semantics regression — while the seeded-kill leg
    # must walk the availability rule through firing -> resolved with
    # EXACTLY ONE incident bundle (episode rate limit) and still finish
    # every request via failover; the canary probes the same pool
    # throughout and must stay green on both legs
    assert sb["false_positive_alerts"] == 0
    assert sb["alerts_fired"] >= 1
    assert sb["alerts_resolved"] >= 1
    assert sb["bundle_captured"] == 1
    assert sb["chaos_finished"] == 4
    assert sb["canary_success_ratio"] == 1.0
    assert 0 < sb["canary_p50_ms"] <= sb["canary_p90_ms"]
    # shared-prefix replay (auto 8 requests in smoke mode): prefix
    # caching must actually hit, skip prefill compute vs the cold
    # baseline, and stay token-identical to caching-off
    pc = rec["prefix_cache"]
    assert pc["parity_exact"] is True
    assert pc["hit_rate"] >= 0.5
    assert pc["blocks_reused"] > 0
    assert pc["prefill_tokens_skipped"] > 0
    assert pc["prefill_token_units"] < pc["prefill_token_units_cold"]
    assert pc["chunk_traces"] == 1
    # overload A/B (auto in smoke mode): with the lifecycle layer on
    # (deadlines + priorities + SLO shedding), accepted-request p90
    # per-token latency AND goodput under the shared deadline are
    # strictly better than plain FIFO at the same overload arrival
    # rate — and the degradation ladder demonstrably fired
    # (The two legs' SECONDS are compared by bench.py, which records its
    # verdicts; this test, which shares its CPU with five other workers,
    # asserts what the same legs COUNT: a per-token latency in step()
    # calls, the ladder's rungs. ~0.15 s legs whose goodput differs by
    # a tenth flip under load, three attempts or not.)
    lc = rec["lifecycle"]
    on, off = lc["on"], lc["off"]
    assert isinstance(lc["p90_improved"], bool)
    assert isinstance(lc["goodput_improved"], bool)
    assert on["token_p90_ms"] > 0 and off["token_p90_ms"] > 0
    assert on["goodput_tokens_per_s"] > 0
    assert on["token_p90_steps"] < off["token_p90_steps"]
    assert on["shed"] + on["deadline_expired"] >= 1
    assert on["preempted"] >= 1
    assert on["accepted"] >= 1
    # the off-leg is the no-lifecycle baseline: nothing degraded
    assert (off["shed"], off["deadline_expired"], off["preempted"],
            off["cancelled"], off["failed"]) == (0, 0, 0, 0, 0)
    assert off["accepted"] == lc["on"]["requests"]
    # step observatory blob (docs/observability.md "Serving goodput &
    # KV-pool accounting"): phases decompose step wall BY CONSTRUCTION
    # (the 'other' residual stays ≤5%), the goodput fraction is a real
    # fraction, the dispatch-gap detector saw every decode boundary,
    # and the pool accounting is live
    spb = rec["step_profile"]
    assert spb["steps"] > 0
    assert 0.0 < spb["goodput_fraction"] <= 1.0
    assert abs(spb["goodput_fraction"] + spb["host_fraction"]
               - 1.0) < 1e-6
    assert 0.0 <= spb["residual_fraction"] <= 0.05
    for ph in ("admission", "propose", "dispatch", "sync_wait",
               "commit", "publish"):
        assert ph in spb["phases"], ph
    # phase totals reconcile with the step wall (identity up to float
    # rounding in the blob)
    assert abs(sum(p["total_s"] for p in spb["phases"].values())
               - spb["wall_s"]) <= 0.05 * spb["wall_s"] + 1e-5
    assert spb["dispatch_gap_count"] >= 1
    assert spb["dispatch_gap_p90_ms"] is not None
    assert spb["dispatch_gap_p90_ms"] >= 0.0
    assert 0.0 <= spb["pool"]["fragmentation_free_run_ratio"] <= 1.0
    assert spb["pool"]["block_lifetime_p50_ms"] is not None
    assert spb["pool"]["peak_blocks_p90"] >= 1
    # speculation A/B (auto K=4 in smoke mode, docs/serving.md
    # "Per-slot speculative decoding"): on the lookup-friendly
    # repetitive trace the verify forward must commit MORE than one
    # token per slot per forward, slot-step efficiency must be strictly
    # higher than the non-speculative leg (which is 1.0 by
    # construction), the outputs must be token-identical, and the
    # verify step must have compiled exactly ONE executable with zero
    # retraces across the replay's varying acceptance lengths
    sp = rec["speculation"]
    assert sp["k"] == 4
    assert sp["tokens_per_forward"] > 1.0
    assert sp["slot_step_efficiency_off"] == 1.0
    assert sp["slot_step_efficiency_on"] > sp["slot_step_efficiency_off"]
    assert sp["decode_steps_on"] < sp["decode_steps_off"]
    assert 0.0 < sp["acceptance_rate"] <= 1.0
    assert sp["parity_exact"] is True
    assert sp["verify_traces"] == 1
    assert sp["retraces_on"] == 0
    # async dispatch loop A/B (auto in smoke, docs/serving.md "Async
    # dispatch loop"): pipelined dispatch with lag-1 commit closes the
    # device-idle gap BY CONSTRUCTION for every dispatch that lands on
    # a busy device (counted: some ON, none OFF), greedy output
    # token-identical to the synchronous loop. The gap p90, the
    # host-tax share and tokens/s are this machine's seconds: bench.py
    # compares and records them, and a run on the chip reads them
    al = rec["async_loop"]
    assert al["parity_exact"] is True
    for verdict in ("gap_improved", "host_fraction_improved",
                    "tokens_per_s_no_worse"):
        assert isinstance(al[verdict], bool), verdict
    for leg in ("on", "off"):
        assert al[leg]["dispatch_gap_p90_ms"] is not None
        assert 0.0 < al[leg]["host_fraction"] < 1.0
        assert al[leg]["dispatch_boundaries"] >= 1
    assert al["on"]["pipelined_dispatches"] >= 1
    assert al["off"]["pipelined_dispatches"] == 0
    assert al["on"]["pipelined_steps"] >= 1
    assert al["on"]["retraces"] == 0
    assert al["on"]["decode_traces"] == 1     # zero new executables
    assert al["off"]["pipelined_steps"] == 0  # the off-leg never chains
    # the flake-class fix: the tokens/s basis is recorded
    # unconditionally so a reader always knows which evidence (single
    # attempt inside the symmetric floor, best-of-attempts, or the
    # structural skip) carried the no-worse verdict
    assert al["tokens_per_s_basis"] in (
        "single_attempt", "best_of_attempts", "noise_floor_skip")
    # lag-N dispatch-chain A/B (auto N=2 in smoke): deeper chains keep
    # exact parity through the SAME decode executable, the profiler's
    # depth histogram proves the chain deepened past lag-1, and the
    # chained dispatches land on a busy device (gap p90 no worse)
    cl = rec["commit_lag"]
    assert cl["max_commit_lag"] == 2
    assert cl["parity_exact"] is True
    assert isinstance(cl["gap_no_worse"], bool)      # seconds: recorded
    assert cl["gap_basis"] in ("single_attempt", "best_of_attempts")
    assert isinstance(cl["tokens_per_s_no_worse"], bool)
    # counted: the deeper chain lands more dispatches on a busy device
    assert cl["lagN"]["pipelined_dispatches"] >= 1
    assert cl["tokens_per_s_basis"] in (
        "single_attempt", "best_of_attempts", "noise_floor_skip")
    # the lag-2 chain demonstrably deepened past the lag-1 loop's
    # steady state (dispatch-over-one-outstanding records depth 2)
    assert cl["depth_max"] >= 3
    assert cl["lag1"]["commit_lag_depth_max"] <= 2
    assert cl["lagN"]["decode_traces"] == 1   # zero new executables
    assert cl["lagN"]["retraces"] == 0
    assert cl["dispatch_gap_p90_ms"] is not None
    # chained chunked-prefill leg (auto in smoke): chaining the
    # non-final chunks must cut the admission dispatch-gap tax —
    # structurally (fewer device-idle events per replay,
    # deterministic) and in total idle seconds (noise-disciplined) —
    # at byte-identical outputs and the same ONE chunk executable
    pfc = rec["prefill_chain"]
    assert pfc["parity_exact"] is True
    assert pfc["gap_samples_improved"] is True
    assert pfc["on"]["dispatch_gap_count"] < \
        pfc["off"]["dispatch_gap_count"]
    assert isinstance(pfc["gap_improved"], bool)     # idle SECONDS
    assert pfc["gap_basis"] in (
        "single_attempt", "best_of_attempts", "noise_floor_skip")
    assert pfc["dispatch_gap_p90_ms"] is not None
    assert pfc["on"]["prefill_chunks"] == pfc["off"]["prefill_chunks"]
    assert pfc["on"]["chunk_traces"] == 1
    assert pfc["on"]["retraces"] == 0
    # draft-model speculation A/B (auto in smoke): on the
    # non-repetitive trace the draft proposals must convert verify
    # width into committed tokens where lookup cannot, token-identical
    # outputs, through the SAME verify executable
    sd = rec["speculation_draft"]
    assert sd["parity_exact"] is True
    assert sd["draft_beats_lookup"] is True
    assert sd["tokens_per_forward"] > sd["tokens_per_forward_lookup"]
    assert sd["tokens_per_forward"] > 1.0
    assert sd["verify_traces"] == 1
    assert sd["retraces"] == 0
    # KV tiering A/B (auto int8+offload in smoke, docs/serving.md "KV
    # quantization & host tiering"): the int8 pool at 2x the slots
    # costs LESS device memory than the fp baseline (capacity ratio
    # >= 2 bytes/slot), actually sustains 2x the concurrent residents
    # at exact greedy parity with ONE decode executable — and the
    # offload replay demotes cold blocks to host RAM, swaps them back
    # on prefix hits (token-identical to a never-evicted pool, zero
    # evictions, zero preemptions) with host-tier bytes visible the
    # way /debug/memory reports them
    kt = rec["kv_tiering"]
    assert kt["kv_dtype"] == "int8"
    assert kt["capacity_ratio"] >= 2.0
    assert kt["pool_bytes_int8"] <= kt["pool_bytes_fp"]
    assert kt["max_resident_int8"] >= 2 * kt["max_resident_fp"]
    assert kt["parity_exact"] is True
    assert kt["decode_traces_int8"] == 1
    assert kt["retraces_int8"] == 0
    off = kt["offload"]
    assert off["parity_exact"] is True
    assert off["demotions"] > 0
    assert off["swap_ins"] > 0
    assert off["evictions"] == 0
    assert off["preempted"] == 0
    assert off["host_bytes_visible"] is True
    assert off["swap_outs_accounted"] == off["demotions"]
    # replicated-serving A/B (auto 2 replicas + seeded kill in smoke,
    # docs/serving.md "Replicated serving & failover"): with a replica
    # killed mid-decode, EVERY submitted request still finishes
    # eos/length (availability 1.0 — the replication.availability
    # regression gate's input) token-identical to the undisturbed leg,
    # failover demonstrably fired with bounded replay-token overhead,
    # and the per-replica stats rows name exactly one dead replica
    # disaggregated prefill/decode A/B (auto in smoke, docs/serving.md
    # "Disaggregated prefill/decode"): under the long-prompt +
    # resident-decoder interference mix, role-split decode per-token
    # p90 must not exceed colocated at equal total slots (the attempts/
    # best-of noise discipline rides in decode_p90_improved), outputs
    # token-identical, every handoff block consumed (none stranded),
    # handoff volume per request recorded, and the decode replica kept
    # ONE decode executable with zero retraces — the handoff reuses
    # the existing match_prefix -> paged_swap_in machinery
    dg = rec["disaggregation"]
    assert dg["roles"] == ["prefill", "decode"]
    assert dg["parity_exact"] is True
    assert isinstance(dg["decode_p90_improved"], bool)   # seconds
    assert dg["decode_p90_basis"] in ("single_attempt",
                                      "best_of_attempts")   # both sampled
    assert dg["decode_p90_ratio"] > 0
    assert dg["disaggregated"]["handoffs"] >= dg["interferers"]
    assert dg["disaggregated"]["handoff_blocks_published"] > 0
    assert dg["disaggregated"]["handoff_blocks_consumed"] == \
        dg["disaggregated"]["handoff_blocks_published"]
    assert dg["disaggregated"]["handoff_stranded_blocks"] == 0
    assert dg["disaggregated"]["handoff_bytes_per_request"] > 0
    assert dg["disaggregated"]["decode_swap_ins"] > 0
    assert dg["disaggregated"]["decode_traces"] == 1
    assert dg["disaggregated"]["retraces"] == 0
    assert dg["colocated"]["handoffs"] == 0    # the baseline never splits
    rp = rec["replication"]
    assert rp["replicas"] == 2
    assert rp["chaos_kill"] is True
    assert rp["availability"] == 1.0
    assert rp["availability_undisturbed"] == 1.0
    assert rp["parity_exact"] is True
    assert rp["failovers"] >= 1
    assert rp["dead_replicas"] == 1
    assert rp["replay_tokens"] >= 1
    assert 0.0 < rp["replay_token_overhead"] < 1.0
    assert rp["token_p90_ms"] is not None
    rows = rp["replicas_stats"]
    assert len(rows) == 2
    assert sum(1 for r in rows if r["health"] == "dead") == 1
    assert all(r["routed"] >= 1 for r in rows)
    # fleet observability leg (auto in smoke, docs/observability.md
    # "Fleet observability"): the role-split + seeded-kill run must
    # exercise every stitching path (submit, handoff AND failover hop
    # causes), every multi-leg request's kept trace must carry its hop
    # spans (coverage 1.0 — a lost hop is a blind leg), the federated
    # scrape's pool rollup must equal the per-replica sums even with
    # one replica dead (the staleness contract: last snapshot still
    # merges), replica label cardinality stays bounded by the pool
    # size, and the scrape p90 (the fleet_obs.scrape_p90_ms regression
    # gate's input) is a real measured wall
    fo = rec["fleet_obs"]
    assert fo["replicas"] == 2
    assert fo["finished_ok"] == fo["requests"]
    assert fo["scrapes"] >= 3
    assert fo["scrape_p90_ms"] is not None and fo["scrape_p90_ms"] > 0
    assert fo["hops_by_cause"]["submit"] >= 1
    assert fo["hops_by_cause"]["handoff"] >= 1
    assert fo["hops_by_cause"]["failover"] >= 1
    assert fo["hops_total"] == sum(fo["hops_by_cause"].values())
    assert fo["hops_total"] > fo["requests"]   # somebody crossed legs
    assert fo["multi_leg_requests"] >= 1
    assert fo["stitched_coverage"] == 1.0
    assert fo["merged_parity"] is True
    assert fo["dead_replicas"] == 1
    labels = set(fo["replica_label_values"])
    assert {"r0", "r1", "pool"} <= labels
    assert len(labels) <= 2 * fo["replicas"] + 1   # bounded cardinality
    # cost accounting blob (docs/observability.md "Cost accounting &
    # capacity"): every replay request billed (requests + warmup), the
    # closure residual within the wall-clock tolerance (fake-clock
    # exactness is pinned by tests/test_accounting.py — here the replay
    # runs on the monotonic clock), per-tenant device shares summing to
    # 1 across the three cycled tenants, unit cost positive (the
    # cost.device_seconds_per_1k_tokens regression gate's input), and
    # the capacity model evaluated with real post-replay rates
    co = rec["cost"]
    assert co["requests_billed"] == rec["requests"] + 1   # + warmup
    assert co["device_seconds_per_1k_tokens"] > 0
    assert co["device_seconds_total"] > 0
    assert co["closure_residual"] <= 0.05
    assert co["kv_block_seconds_total"] > 0
    assert set(co["tenant_device_share"]) == {"acme", "beta", "corp"}
    assert sum(co["tenant_device_share"].values()) == \
        pytest.approx(1.0, abs=0.01)
    cap = co["capacity"]
    assert cap["enabled"] is True
    assert cap["tokens_per_s"] > 0
    assert cap["sustainable_tokens_per_s"] > 0
    assert cap["admissible_requests_per_s"] > 0
    # the whole record (snapshot included) survives a JSON round-trip
    import json
    assert json.loads(json.dumps(rec))["telemetry"] == tm
