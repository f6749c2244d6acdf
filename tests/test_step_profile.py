"""Serving step observatory + KV-pool accounting — the PR-9 contracts.

The acceptance criteria (ISSUE 9): ``StepProfiler`` phases sum to the
step wall **by construction** (fake-clock exactness here, ≤5% residual
in the bench smoke); profiler OFF leaves the decode program and greedy
output byte-identical and registers none of the new metric families;
profiler ON adds zero retraces and exact greedy parity under chunked
prefill + speculation + injected preemption; the dispatch-gap detector
observes device idle between fetch and next dispatch; the allocator's
lifetime / age-at-eviction histograms match a hand-simulated
alloc/release trace on a fake clock; the fragmentation gauge is
correct on a crafted hole pattern; famine freezes ONE allocator-state
ring event per episode; ``GET /debug/goodput`` returns valid JSON over
HTTP; and ``dump_timeline`` gains a "server host" phase track whose
slices are monotonic and non-overlapping beside the request and device
tracks (double-recorded ring instants dedupe instead of overlapping).
"""
import json
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference.kv_cache import BlockAllocator
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, KVPoolAccountant,
                                     MetricRegistry, SpanLog, StepProfiler,
                                     get_event_ring, get_registry,
                                     get_span_log, set_event_ring,
                                     set_registry, set_span_log)
from deepspeed_tpu.telemetry.exporter import ROUTES
from deepspeed_tpu.telemetry.step_profile import NULL_STEP_HANDLE
from deepspeed_tpu.telemetry.tracing import (ring_timeline_events,
                                             span_timeline_events)


@pytest.fixture()
def fresh_telemetry():
    """Private process registry + event ring + span log for one test."""
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(256))
    prev_log = set_span_log(SpanLog())
    try:
        yield get_registry()
    finally:
        set_registry(prev_reg)
        set_event_ring(prev_ring)
        set_span_log(prev_log)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t


def make_engine(seed=0, max_out_tokens=256, block_size=32, num_slots=4,
                **knobs):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens,
        block_size=block_size, num_slots=num_slots, **knobs))


# ===================================================== StepProfiler unit


def test_phases_sum_to_wall_exactly(fresh_telemetry):
    """The by-construction identity: every interval between marks lands
    in exactly one phase, the finish tail in ``other`` — fake clock, so
    the sum is EXACT, not approximate."""
    fc = FakeClock()
    reg = MetricRegistry()
    prof = StepProfiler(registry=reg, clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.mark("admission")
    fc.t = 1.5
    sp.mark("prefill_chunk")
    fc.t = 2.0
    sp.mark("propose", dispatch=True)
    fc.t = 2.25
    sp.mark("dispatch")
    fc.t = 3.0
    sp.mark("sync_wait", fetch=True)
    fc.t = 3.5
    sp.mark("publish")
    fc.t = 3.75
    sp.mark("commit")
    fc.t = 4.0
    sp.finish()
    snap = prof.snapshot()
    assert snap["steps"] == 1
    assert snap["wall_s"] == 4.0
    phases = snap["phases_s"]
    assert phases == {
        "admission": 1.0, "prefill_chunk": 0.5, "propose": 0.5,
        "dispatch": 0.25, "sync_wait": 0.75, "publish": 0.5,
        "commit": 0.25, "other": 0.25}
    assert sum(phases.values()) == snap["wall_s"]   # the identity
    # device attribution: dispatch + sync_wait
    assert snap["device_s"] == 1.0
    assert snap["goodput_fraction"] == 0.25
    assert snap["host_fraction"] == 0.75
    # registry mirrors: one wall observation, one per phase
    rs = reg.snapshot()
    assert rs["serve_step_wall_seconds"]["series"][0]["count"] == 1
    labels = {s["labels"]["phase"]
              for s in rs["serve_step_phase_seconds"]["series"]}
    assert labels == set(phases)
    assert rs["serve_goodput_fraction"]["series"][0]["value"] == 0.25


def test_dispatch_gap_between_fetch_and_next_dispatch(fresh_telemetry):
    """Gap = device idle from step N's fetch to step N+1's dispatch —
    and exactly one gap per idle span."""
    fc = FakeClock()
    reg = MetricRegistry()
    prof = StepProfiler(registry=reg, clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.mark("propose", dispatch=True)    # no prior fetch: no gap
    fc.t = 2.0
    sp.mark("dispatch")
    fc.t = 3.0
    sp.mark("sync_wait", fetch=True)     # device idle starts at t=3
    fc.t = 3.5
    sp.finish()
    assert prof.snapshot()["dispatch_gap"]["count"] == 0
    sp = prof.begin()                    # t = 3.5
    fc.t = 5.0
    sp.mark("propose", dispatch=True)    # gap = 5.0 - 3.0 = 2.0
    fc.t = 5.5
    sp.mark("dispatch")
    fc.t = 6.0
    sp.mark("sync_wait", fetch=True)
    fc.t = 6.25
    sp.finish()
    gap = prof.snapshot()["dispatch_gap"]
    assert gap == {"count": 1, "total_s": 2.0, "max_s": 2.0,
                   "mean_s": 2.0}
    assert reg.snapshot()["serve_dispatch_gap_seconds"]["series"][0][
        "count"] == 1


def test_idle_finish_resets_dispatch_gap_baseline(fresh_telemetry):
    """A step that ends with no live work (drained server, traffic
    lull) resets the gap baseline — device idle for lack of WORK must
    never read as a multi-second host-tax gap."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.mark("sync_wait", fetch=True)
    fc.t = 1.5
    sp.finish(live=False)                # last resident retired
    # a 100 s lull, then a new request's first dispatch: NO gap
    fc.t = 101.5
    sp = prof.begin()
    fc.t = 102.0
    sp.mark("propose", dispatch=True)
    fc.t = 103.0
    sp.mark("sync_wait", fetch=True)
    fc.t = 103.5
    sp.finish(live=True)
    assert prof.snapshot()["dispatch_gap"]["count"] == 0
    # with work still resident the inter-step host time DOES count
    fc.t = 105.0
    sp = prof.begin()
    fc.t = 106.0
    sp.mark("propose", dispatch=True)    # gap = 106 - 103 = 3
    fc.t = 106.5
    sp.finish(live=True)
    gap = prof.snapshot()["dispatch_gap"]
    assert gap["count"] == 1 and gap["total_s"] == 3.0


def test_device_interval_attributes_and_advances_gap(fresh_telemetry):
    """A prefill program nested inside the admission phase counts
    toward the goodput fraction and moves the dispatch-gap boundary —
    the device was busy, not idle, across it."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.mark("sync_wait", fetch=True)     # decode fetch at t=1
    fc.t = 4.0
    sp.device_interval(2.0, 3.0)         # prefill: dispatch 2, fetch 3
    sp.mark("admission")
    fc.t = 5.0
    sp.mark("propose", dispatch=True)    # gap from PREFILL fetch: 2.0
    fc.t = 6.0
    sp.finish()
    snap = prof.snapshot()
    # sync_wait (1.0) + prefill interval (1.0)
    assert snap["device_s"] == 2.0
    gaps = snap["dispatch_gap"]
    # prefill dispatch at t=2 vs decode fetch t=1 (gap 1), decode
    # dispatch at t=5 vs prefill fetch t=3 (gap 2)
    assert gaps["count"] == 2
    assert gaps["total_s"] == 3.0
    assert gaps["max_s"] == 2.0


def test_step_spans_are_contiguous_and_tile_the_step(fresh_telemetry):
    """Every worked step leaves its ordered phase spans in the span
    log, parented under one serve:step; they are contiguous and sum to
    wall. A workless poll after it leaves one idle step, a second
    consecutive one nothing."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    sp = prof.begin()
    fc.t = 0.5
    sp.mark("admission")
    fc.t = 0.6
    sp.mark("propose", dispatch=True)
    fc.t = 0.75
    sp.mark("dispatch", program="serve_decode")
    fc.t = 1.0
    sp.finish()
    recs = get_span_log().snapshot()
    steps = [r for r in recs if r[0] == "serve:step"]
    assert len(steps) == 1
    name, t0, t1, parent, sid, key, attrs = steps[0]
    assert (t0, t1, parent, key) == (0.0, 1.0, 0, 1)
    assert attrs["profiler"] == prof.uid and attrs["pipelined"] is False
    kids = [r for r in recs if r[3] == sid]
    assert [r[0] for r in kids] == ["serve:admission", "serve:propose",
                                    "serve:dispatch", "serve:other"]
    assert sum(r[2] - r[1] for r in kids) == pytest.approx(1.0)
    for a, b in zip(kids, kids[1:]):
        assert a[2] == b[1]
    assert all(r[5] == 1 for r in kids)
    # the dispatch span carries what the gap detector saw at its start
    disp = kids[2][6]
    assert disp == {"gap_s": None, "depth": 1, "busy": False,
                    "program": "serve_decode"}
    for k in (2, 3):                  # two workless polls
        sp = prof.begin()
        fc.t += 1.0
        sp.mark("admission")
        sp.finish(live=False)
    steps = [r for r in get_span_log().snapshot() if r[0] == "serve:step"]
    assert [r[5] for r in steps] == [1, 2]
    assert steps[1][6]["idle"] is True


def test_null_handle_is_inert():
    assert NULL_STEP_HANDLE.mark("anything", dispatch=True) is None
    assert NULL_STEP_HANDLE.device_interval(0.0, 1.0) is None
    assert NULL_STEP_HANDLE.finish() is None


def test_span_log_capacity_validated():
    with pytest.raises(ValueError, match="capacity"):
        SpanLog(capacity=0)


# ============================================= KV-pool accountant (fake clock)


def test_block_lifetime_matches_hand_simulated_trace(fresh_telemetry):
    """Residency lifetimes against a hand-simulated alloc/release
    trace: histogram count and sum reconcile exactly."""
    fc = FakeClock()
    reg = MetricRegistry()
    acct = KVPoolAccountant(registry=reg, clock=fc)
    alloc = BlockAllocator(16, accountant=acct)
    a = alloc.allocate(3)          # t=0: blocks live
    fc.t = 2.0
    b = alloc.allocate(2)          # t=2
    fc.t = 5.0
    alloc.release(a)               # lifetimes 5, 5, 5
    fc.t = 11.0
    alloc.release(b)               # lifetimes 9, 9
    h = reg.snapshot()["serve_kv_block_lifetime_seconds"]["series"][0]
    assert h["count"] == 5
    assert h["sum"] == pytest.approx(3 * 5.0 + 2 * 9.0)
    # re-allocation starts a FRESH residency
    c = alloc.allocate(1)
    fc.t = 12.0
    alloc.release(c)
    h = reg.snapshot()["serve_kv_block_lifetime_seconds"]["series"][0]
    assert h["count"] == 6
    assert h["sum"] == pytest.approx(33.0 + 1.0)


def test_age_at_eviction_and_resurrection(fresh_telemetry):
    """A parked (prefix-registered, refcount-0) block observes its LRU
    age when evicted; a resurrected block observes NO eviction age and
    starts a new residency."""
    fc = FakeClock()
    reg = MetricRegistry()
    acct = KVPoolAccountant(registry=reg, clock=fc)
    alloc = BlockAllocator(4, enable_prefix_caching=True,
                           accountant=acct)       # 3 usable blocks
    blk = alloc.allocate(1)[0]
    assert alloc.register_prefix(blk, b"h1")
    fc.t = 2.0
    alloc.release([blk])            # parks in the LRU at t=2
    # resurrection: no eviction, fresh residency from t=3
    fc.t = 3.0
    assert alloc.match_prefix([b"h1"]) == [blk]
    fc.t = 4.0
    alloc.release([blk])            # parks again at t=4
    ev = reg.snapshot().get(
        "serve_kv_block_age_at_eviction_seconds")
    assert ev["series"][0]["count"] == 0
    # now exhaust the free list so the LRU evicts the parked block
    fc.t = 9.0
    out = alloc.allocate(3)         # 2 free + 1 evicted from the LRU
    assert blk in out
    ev = reg.snapshot()[
        "serve_kv_block_age_at_eviction_seconds"]["series"][0]
    assert ev["count"] == 1
    assert ev["sum"] == pytest.approx(9.0 - 4.0)
    # lifetime series saw both residencies (2.0 and 1.0)
    lt = reg.snapshot()["serve_kv_block_lifetime_seconds"]["series"][0]
    assert lt["count"] == 2
    assert lt["sum"] == pytest.approx(3.0)


def test_failed_admission_rollback_rewinds_accounting(fresh_telemetry):
    """A blocked queue head's prefix-hit rollback (match_prefix
    succeeded, tail allocation failed — retried every step) must NOT
    observe a ~0s residency nor re-stamp the block's LRU park time:
    the lifetime histogram and age-at-eviction stay clean."""
    fc = FakeClock()
    reg = MetricRegistry()
    acct = KVPoolAccountant(registry=reg, clock=fc)
    alloc = BlockAllocator(4, enable_prefix_caching=True,
                           accountant=acct)       # 3 usable
    blk = alloc.allocate(1)[0]
    assert alloc.register_prefix(blk, b"h1")
    fc.t = 2.0
    alloc.release([blk])            # parks at t=2; lifetime 2.0
    lt = reg.snapshot()["serve_kv_block_lifetime_seconds"]["series"][0]
    assert lt["count"] == 1
    # every-step retry churn: resurrect + rollback, twice
    for t in (3.0, 4.0):
        fc.t = t
        assert alloc.match_prefix([b"h1"]) == [blk]
        alloc.rollback_match([blk])
    lt = reg.snapshot()["serve_kv_block_lifetime_seconds"]["series"][0]
    assert lt["count"] == 1         # no phantom ~0s residencies
    assert alloc.free_blocks == 3   # pool state fully restored
    # eviction age measures from the ORIGINAL park (t=2), not the
    # last rollback (t=4)
    fc.t = 9.0
    out = alloc.allocate(3)
    assert blk in out
    ev = reg.snapshot()[
        "serve_kv_block_age_at_eviction_seconds"]["series"][0]
    assert ev["count"] == 1
    assert ev["sum"] == pytest.approx(7.0)
    # a shared (refcount>1) hit rolls back without touching refcount-1
    # residents' accounting
    alloc2 = BlockAllocator(4, enable_prefix_caching=True,
                            accountant=KVPoolAccountant(
                                registry=MetricRegistry(),
                                clock=fc))
    b2 = alloc2.allocate(1)[0]
    assert alloc2.register_prefix(b2, b"h2")
    assert alloc2.match_prefix([b"h2"]) == [b2]   # refcount 2
    alloc2.rollback_match([b2])                   # back to 1, live
    assert alloc2.live_blocks == 1


def test_idle_poll_steps_do_not_dilute_goodput(fresh_telemetry):
    """A workless step (no dispatch, no device interval — a traffic
    lull being polled) is counted apart: it must not drag the goodput
    fraction toward 0 or pollute the wall/phase histograms the
    regression gate reads."""
    fc = FakeClock()
    reg = MetricRegistry()
    prof = StepProfiler(registry=reg, clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.mark("propose", dispatch=True)
    fc.t = 3.0
    sp.mark("sync_wait", fetch=True)
    fc.t = 4.0
    sp.finish()                       # worked: wall 4, device 2
    for t in (14.0, 24.0):            # two 10s idle polls
        sp = prof.begin()
        fc.t = t
        sp.mark("admission")
        sp.finish(live=False)
    snap = prof.snapshot()
    assert snap["steps"] == 1
    assert snap["idle_steps"] == 2
    assert snap["idle_wall_s"] == pytest.approx(20.0)
    assert snap["wall_s"] == 4.0      # idle wall excluded
    assert snap["goodput_fraction"] == 0.5
    rs = reg.snapshot()
    assert rs["serve_step_wall_seconds"]["series"][0]["count"] == 1
    # a lull leaves ONE idle step in the span log, however long polled
    steps = [r for r in get_span_log().snapshot() if r[0] == "serve:step"]
    assert [bool(r[6].get("idle")) for r in steps] == [False, True]


def test_fragmentation_gauge_on_crafted_holes(fresh_telemetry):
    """Longest contiguous run / free count, on a crafted hole
    pattern."""
    reg = MetricRegistry()
    acct = KVPoolAccountant(registry=reg, clock=FakeClock())
    # {1,2,3} run of 3, singletons 5, 9, 10 -> longest 3 of 6
    ratio = acct.update_fragmentation([5, 1, 2, 3, 9, 10])
    assert ratio == pytest.approx(0.5)
    assert acct.last_longest_run == 3
    g = reg.snapshot()["serve_kv_free_longest_run_ratio"]["series"][0]
    assert g["value"] == pytest.approx(0.5)
    assert acct.update_fragmentation([]) == 1.0        # empty = trivial
    assert acct.update_fragmentation([7]) == 1.0
    assert acct.update_fragmentation([4, 2, 8, 6]) == 0.25  # all holes


def test_fragmentation_transition_path_is_rate_limited(
        fresh_telemetry):
    """The per-transition call recomputes only every FRAG_EVERY-th
    time — and skipped calls never even build the free-id list."""
    acct = KVPoolAccountant(registry=MetricRegistry(),
                            clock=FakeClock())
    calls = []

    def factory():
        calls.append(1)
        return [1, 2, 3, 9]

    assert acct.maybe_update_fragmentation(factory) == 0.75
    for _ in range(acct.FRAG_EVERY - 1):     # all skipped
        acct.maybe_update_fragmentation(factory)
    assert len(calls) == 1
    acct.maybe_update_fragmentation(factory)  # the Nth recomputes
    assert len(calls) == 2
    # the unconditional spelling stays unconditional (snapshot/famine)
    assert acct.update_fragmentation([4, 5]) == 1.0


def test_fragmentation_tracks_allocator_free_list(fresh_telemetry):
    """End to end through the allocator: carve holes by releasing
    alternating blocks and check the gauge input."""
    acct = KVPoolAccountant(registry=MetricRegistry(),
                            clock=FakeClock())
    alloc = BlockAllocator(10, accountant=acct)       # blocks 1..9
    blocks = alloc.allocate(9)
    alloc.release([b for b in blocks if b % 2 == 0])  # free 2,4,6,8
    ratio = acct.update_fragmentation(alloc.free_ids)
    assert ratio == pytest.approx(0.25)               # 4 singletons


def test_famine_freezes_one_ring_event_per_episode(fresh_telemetry):
    """Allocation failure freezes allocator state into the event ring
    ONCE; a success re-arms; reserved blocks appear in the snapshot."""
    acct = KVPoolAccountant(registry=MetricRegistry(),
                            clock=FakeClock())
    alloc = BlockAllocator(6, accountant=acct)        # 5 usable
    held = alloc.allocate(4)
    assert alloc.allocate(3) is None                  # famine
    assert alloc.allocate(2) is None                  # same episode
    evs = [e for e in get_event_ring().snapshot()
           if e["kind"] == "pool_famine"]
    assert len(evs) == 1
    d = evs[0]["data"]
    assert d["requested_blocks"] == 3
    assert d["free_list"] == 1 and d["live_blocks"] == 4
    assert d["usable_blocks"] == 5
    assert "fragmentation" in d
    assert alloc.allocate(1) is not None              # re-arms
    alloc.release(held)
    alloc.set_reserved(5)
    assert alloc.allocate(1) is None                  # new episode
    evs = [e for e in get_event_ring().snapshot()
           if e["kind"] == "pool_famine"]
    assert len(evs) == 2
    assert evs[1]["data"]["reserved_blocks"] == 5
    assert acct.snapshot()["famine_episodes"] == 2


# ======================================================= server contracts


def _run_scenario(telemetry_overrides=None, spec=0):
    """One deterministic serve scenario: prefix caching + chunked
    prefill, optional speculation, plus an injected strictly-higher-
    priority arrival that preempts a resident on a tight pool."""
    tel = {"trace_sample_rate": 0.0}
    tel.update(telemetry_overrides or {})
    knobs = dict(enable_prefix_caching=True, telemetry=tel,
                 max_out_tokens=128, num_slots=2)
    if spec:
        knobs["speculation_tokens"] = spec
    eng = make_engine(**knobs)
    srv = ContinuousBatchingServer(eng)
    prefix = [1 + (i % 90) for i in range(64)]
    # repetitive tails so prompt-lookup speculation has acceptance
    ids = [srv.submit(prefix + [3, 7, 11] * 4, max_new_tokens=20),
           srv.submit(prefix + [5, 9] * 6, max_new_tokens=16)]
    for _ in range(3):
        srv.step()
    while srv.scheduler.queue:    # the second waits for the shared
        srv.step()                # prefix the first is prefilling
    # strictly higher priority on a full pool -> preemption ladder
    ids.append(srv.submit([2, 4, 6, 8] * 8, max_new_tokens=24,
                          priority=5))
    res = srv.drain()
    stats = srv.stats
    srv.close()
    return [res[i] for i in ids], stats


def test_profiler_on_off_parity_retraces_and_metric_keys(
        fresh_telemetry):
    """ONE scenario, both gates: profiler ON under chunked prefill +
    injected preemption adds zero retraces, keeps one decode trace,
    sums phases to wall (exact, real clock), and covers every decode
    boundary; profiler OFF serves byte-identical tokens, reports None
    stats, and registers none of the new metric families."""
    out_on, st_on = _run_scenario()
    assert st_on["preempted"] >= 1          # the chaos actually ran
    assert st_on["decode_traces"] == 1
    assert st_on["retraces"] == 0
    spf = st_on["step_profile"]
    assert spf["steps"] > 0
    assert sum(spf["phases_s"].values()) == pytest.approx(
        spf["wall_s"], rel=1e-9, abs=1e-9)  # the identity, real clock
    assert spf["phases_s"].get("other", 0.0) <= 0.05 * spf["wall_s"]
    assert 0.0 < spf["goodput_fraction"] <= 1.0
    assert spf["dispatch_gap"]["count"] >= 1
    kv = st_on["kv_pool"]
    assert 0.0 <= kv["free_longest_run_ratio"] <= 1.0
    set_registry(MetricRegistry())          # isolate the OFF families
    out_off, st_off = _run_scenario({"step_profile": False})
    assert out_on == out_off                # byte-identical output
    assert st_off["step_profile"] is None
    assert st_off["kv_pool"] is None
    off_names = set(get_registry().snapshot())
    for name in ("serve_step_wall_seconds", "serve_step_phase_seconds",
                 "serve_goodput_fraction", "serve_dispatch_gap_seconds",
                 "serve_kv_block_lifetime_seconds",
                 "serve_kv_block_age_at_eviction_seconds",
                 "serve_kv_free_longest_run_ratio",
                 "serve_request_peak_blocks"):
        assert name not in off_names, name
    # the pre-existing serving families are untouched by the gate
    assert "serve_decode_step_seconds" in off_names


def test_profiler_on_speculation_parity_and_one_verify_trace(
        fresh_telemetry):
    """The verify path is instrumented too: speculation ON+profiler ON
    equals speculation ON+profiler OFF token for token, with one verify
    executable and zero retraces."""
    out_on, st_on = _run_scenario(spec=4)
    out_off, st_off = _run_scenario({"step_profile": False}, spec=4)
    assert out_on == out_off
    assert st_on["speculation"]["verify_steps"] > 0
    assert st_on["speculation"]["verify_traces"] == 1
    assert st_on["retraces"] == 0
    # verify rounds route through the same phase vocabulary
    for ph in ("propose", "dispatch", "sync_wait", "commit"):
        assert ph in st_on["step_profile"]["phases_s"], ph


def test_fake_clock_server_and_request_peak_blocks(fresh_telemetry):
    """One server, two contracts: the profiler shares the server's
    injectable clock (a fake-clock server still satisfies the sum
    identity — everything lands at zero width, wall included, without
    ever reading the real clock), and per-request peak blocks are
    observed at finish (prompt+budget block span per request, none for
    queue-only lifecycles)."""
    fc = FakeClock()
    reg = MetricRegistry()
    eng = make_engine()
    srv = ContinuousBatchingServer(eng, registry=reg, clock=fc)
    # 3+6 tokens -> ceil(9/32) = 1 block; 40+30 -> ceil(70/32) = 3
    srv.submit([1, 2, 3], max_new_tokens=6)
    srv.submit(list(range(1, 41)), max_new_tokens=30)
    srv.drain()
    spf = srv.stats["step_profile"]
    assert spf["steps"] > 0
    assert spf["wall_s"] == 0.0
    assert sum(spf["phases_s"].values()) == 0.0
    h = reg.snapshot()["serve_request_peak_blocks"]["series"][0]
    assert h["count"] == 2
    assert h["sum"] == pytest.approx(1.0 + 3.0)
    # a cancelled queued request never held blocks: not observed
    rid = srv.submit([5] * 200, max_new_tokens=40)    # 8-block span
    srv.cancel(rid)
    h = reg.snapshot()["serve_request_peak_blocks"]["series"][0]
    assert h["count"] == 2


# ===================================================== HTTP + timeline


def test_debug_goodput_without_profiler(fresh_telemetry):
    """An endpoint whose owner armed no profiler still answers with a
    valid, self-describing body."""
    eng = make_engine(telemetry={"http_port": 0, "step_profile": False})
    srv = ContinuousBatchingServer(eng)
    port = srv.http_server.port
    payload = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debug/goodput", timeout=10).read())
    assert payload["step_profile"]["enabled"] is False
    assert payload["kv_pool"]["enabled"] is False
    srv.close()


def _validate_trace_events(payload):
    """Per-track slices must be monotonic and nested-or-disjoint (the
    shared timeline invariant, same as tests/test_request_tracing.py)."""
    evs = payload["traceEvents"]
    tracks = {}
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e), e
        if e["ph"] == "X":
            assert e["dur"] >= 0, e
            tracks.setdefault((e["pid"], e["tid"]), []).append(
                (e["ts"], e["dur"], e["name"]))
    assert tracks, "no complete-event slices at all"
    eps = 0.5   # µs — float rounding in the writer
    for key, slices in tracks.items():
        slices.sort(key=lambda s: (s[0], -s[1]))
        stack = []
        for ts, dur, name in slices:
            while stack and ts >= stack[-1] - eps:
                stack.pop()
            if stack:
                assert ts + dur <= stack[-1] + eps, (key, name)
            stack.append(ts + dur)
    return tracks


def test_timeline_track_and_debug_goodput_over_http(fresh_telemetry,
                                                    tmp_path):
    """One served replay, both surfaces: dump_timeline renders sampled
    steps as phase slices on a "server host" track beside the request
    and device tracks (every track monotonic/non-overlapping), and
    GET /debug/goodput returns the live profiler + pool payloads as
    valid JSON over HTTP."""
    assert "/debug/goodput" in ROUTES
    eng = make_engine(telemetry={"trace_sample_rate": 1.0,
                                 "http_port": 0})
    srv = ContinuousBatchingServer(eng)
    for i in range(3):
        srv.submit([1 + i, 2, 3, 4 + i], max_new_tokens=5 + i)
    srv.drain()
    port = srv.http_server.port
    payload = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/debug/goodput", timeout=10).read())
    assert payload["step_profile"]["enabled"] is True
    assert payload["step_profile"]["steps"] >= 1
    assert set(payload["step_profile"]["phases_s"]) >= {
        "admission", "propose", "dispatch", "sync_wait"}
    assert payload["kv_pool"]["enabled"] is True
    # the help page lists the route
    help_body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/", timeout=10).read().decode()
    assert "/debug/goodput" in help_body
    path = tmp_path / "timeline.json"
    n = srv.dump_timeline(str(path))
    payload = json.loads(path.read_text())
    assert len(payload["traceEvents"]) == n
    tracks = _validate_trace_events(payload)
    # all three processes present: requests (1), device (2), host (3)
    assert any(k[0] == 1 for k in tracks)
    assert any(k[0] == 2 for k in tracks)
    host = [k for k in tracks if k[0] == 3]
    assert host, "no server-host phase track"
    phase_names = {nm for k in host for _, _, nm in tracks[k]}
    assert {"propose", "sync_wait"} <= phase_names
    metas = {e["args"]["name"] for e in payload["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"requests", "device", "server host"} <= metas
    srv.close()


def test_ring_slices_dedupe_same_track_and_ts(fresh_telemetry,
                                              monkeypatch):
    """Two ring events recorded at the SAME timestamp (fake clocks
    collapse time; a re-recorded step) must not emit overlapping
    duplicate slices — the shared ring→slice helper dedupes by
    (track, ts)."""
    from deepspeed_tpu.telemetry import events as ev_mod
    ring = EventRing(16)
    monkeypatch.setattr(ev_mod.time, "time", lambda: 100.0)
    ring.record("step_end", source="serve_decode", step=1, seconds=0.5)
    ring.record("step_end", source="serve_decode", step=1, seconds=0.5)
    ring.record("compile_end", fn="serve_decode", seconds=0.2)
    out = ring_timeline_events(ring)
    decode = [e for e in out if e["ph"] == "X" and e["pid"] == 2
              and e["tid"] == 1]
    assert len(decode) == 1                 # deduped, not overlapping
    # distinct tracks keep their own slice at the same instant
    compiles = [e for e in out if e["ph"] == "X" and e["tid"] == 2]
    assert len(compiles) == 1
    _validate_trace_events({"traceEvents": out})


def test_step_spans_render_as_contiguous_host_slices(fresh_telemetry):
    """The span log's phase spans of one profiler become contiguous
    slices on its track, on the timeline's clock; another profiler's
    steps stay off it."""
    fc = FakeClock(10.0)
    log = get_span_log()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    other = StepProfiler(registry=MetricRegistry(), clock=fc)
    for p in (prof, other):
        sp = p.begin()
        fc.t += 0.1
        sp.mark("admission")
        fc.t += 0.2
        sp.mark("propose", dispatch=True)
        fc.t += 0.3
        sp.mark("sync_wait", fetch=True)
        sp.finish()
    out = span_timeline_events(log, {prof.uid: 3}, offset=40.0)
    host = sorted([e for e in out if e["ph"] == "X"],
                  key=lambda e: e["ts"])
    assert {e["pid"] for e in host} == {3}
    assert [e["name"] for e in host] == ["admission", "propose",
                                         "sync_wait"]
    assert host[0]["ts"] == pytest.approx(50.0 * 1e6)
    for a, b in zip(host, host[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
    assert host[-1]["ts"] + host[-1]["dur"] == pytest.approx(50.6 * 1e6)
    assert host[0]["args"]["step"] == 1
    _validate_trace_events({"traceEvents": out})
