"""Request-lifecycle robustness — the chaos suite (ISSUE 7).

Deadlines, cancellation in every state, slot preemption with recompute
requeue, SLO-driven load shedding, and the famine degradation ladder
(prefix-LRU evict → preempt → shed), all driven deterministically
through seeded fault injection (telemetry/faultinject.py) and an
injectable server clock — ZERO real sleeps anywhere. The two oracles:

* with no lifecycle action triggered, greedy server output stays
  token-identical to one-shot ``generate()`` (the PR-1 parity bar);
* a preempted-then-requeued greedy request still matches one-shot
  ``generate()`` token for token (recompute preemption is exact).

Plus the hard termination guarantee: ``drain(timeout_s=...)`` provably
ends on a wedged slot, and a server busy degrading (reaping, shedding,
cancelling) is never reported hung by the watchdog.
"""
import json
import socket
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, FaultInjector,
                                     MetricRegistry, Watchdog,
                                     get_event_ring, get_registry,
                                     set_event_ring, set_registry,
                                     start_http_server)
from deepspeed_tpu.telemetry import events as ev


@pytest.fixture()
def fresh_telemetry():
    """Private process registry + event ring for one test — servers
    built inside see only their own metrics/events."""
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(512))
    try:
        yield get_registry()
    finally:
        set_registry(prev_reg)
        set_event_ring(prev_ring)


class FakeClock:
    """Injectable clock: advances only when the test says so (manual
    mode), or by a fixed amount per read (auto mode — enough for the
    drain-timeout proof, which only needs the clock to be strictly
    increasing)."""

    def __init__(self, t: float = 0.0, auto: float = 0.0):
        self.t = t
        self.auto = auto

    def __call__(self) -> float:
        v = self.t
        self.t += self.auto
        return v

    def advance(self, dt: float) -> None:
        self.t += dt


def make_engine(seed=0, max_out_tokens=256, block_size=32, num_slots=2,
                **knobs):
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens,
        block_size=block_size, num_slots=num_slots, **knobs))


def first_event_index(kind):
    for i, e in enumerate(get_event_ring().snapshot()):
        if e["kind"] == kind:
            return i
    return None


# --------------------------------------------------------------- oracle

def test_no_lifecycle_trigger_means_exact_parity(fresh_telemetry):
    """The PR-1 oracle survives the lifecycle layer: deadlines present
    but generous, priorities present but equal, shedding off — no
    action triggers, and every served output is token-identical to
    one-shot generate()."""
    eng = make_engine(num_slots=2)
    srv = ContinuousBatchingServer(eng)
    prompts = [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4], [10, 20, 30, 40]]
    ids = [srv.submit(p, max_new_tokens=6, deadline_s=1e6, priority=0)
           for p in prompts]
    out = srv.drain()
    st = srv.stats
    assert (st["cancelled"], st["deadline_expired"], st["preempted"],
            st["shed"], st["failed"]) == (0, 0, 0, 0, 0)
    for rid, p in zip(ids, prompts):
        ref = eng.generate([p], max_new_tokens=6)[0]
        assert out[rid] == ref[:len(out[rid])]
        assert srv.finish_reason(rid) in ("eos", "length")


# --------------------------------------------------- cancel, every state

def test_cancel_queued_request(fresh_telemetry):
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=4)
    b = srv.submit([4, 5, 6], max_new_tokens=4)     # queued behind a
    free0 = srv.scheduler.allocator.free_blocks
    assert srv.cancel(b) is True
    assert srv.finish_reason(b) == "cancelled"
    assert srv.result(b) == [4, 5, 6]               # prompt-only partial
    assert srv.scheduler.allocator.free_blocks == free0  # held no blocks
    out = srv.drain()
    assert srv.finish_reason(a) in ("eos", "length")
    assert len(out[a]) == 3 + 4
    # idempotent: a finished request cannot be cancelled again
    assert srv.cancel(b) is False
    assert srv.cancel(a) is False
    assert srv.cancel(12345) is False               # unknown id
    snap = fresh_telemetry.snapshot()
    assert snap["serve_cancelled_total"]["series"][0]["value"] == 1


def test_cancel_decoding_request_releases_blocks(fresh_telemetry):
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    usable = srv.scheduler.allocator.usable_blocks
    a = srv.submit([1, 2, 3], max_new_tokens=50)
    for _ in range(4):
        srv.step()                                  # prefill + decoding
    partial = list(srv.scheduler.slots[0].generated)
    assert len(partial) >= 2
    assert srv.cancel(a) is True
    assert srv.finish_reason(a) == "cancelled"
    assert srv.result(a) == [1, 2, 3] + partial     # partial output kept
    assert srv.scheduler.idle
    assert srv.scheduler.allocator.free_blocks == usable
    # the partial prefix matches the one-shot oracle (cancel never
    # corrupts what was already committed)
    ref = eng.generate([[1, 2, 3]], max_new_tokens=50)[0]
    assert srv.result(a) == ref[:3 + len(partial)]
    # the freed slot serves the next request normally
    b = srv.submit([7, 7], max_new_tokens=3)
    out = srv.drain()
    assert out[b] == eng.generate([[7, 7]], max_new_tokens=3)[0][:len(out[b])]


def test_cancel_mid_prefill_chunked(fresh_telemetry):
    """A multi-chunk prompt cancelled between chunks: the in-flight
    prefill job is dropped, the slot and every block come back."""
    eng = make_engine(num_slots=2, prefill_chunk_tokens=32)
    srv = ContinuousBatchingServer(eng)
    usable = srv.scheduler.allocator.usable_blocks
    a = srv.submit(list(range(1, 97)), max_new_tokens=4)   # 3 chunks
    srv.step()                                      # chunk 1 of 3
    assert srv._mid_prefill and srv._prefilling
    assert srv.cancel(a) is True
    assert srv.finish_reason(a) == "cancelled"
    assert not srv._mid_prefill and not srv._prefilling
    assert srv.scheduler.idle
    assert srv.scheduler.allocator.free_blocks == usable
    assert srv.result(a) == list(range(1, 97))      # no tokens yet


# ------------------------------------------------------------ deadlines

def test_deadline_reaps_queued_request_without_admission(fresh_telemetry):
    clock = FakeClock()
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng, clock=clock)
    a = srv.submit([1, 2, 3], max_new_tokens=40)          # occupies slot
    b = srv.submit([4, 5, 6], max_new_tokens=4, deadline_s=5.0)
    clock.advance(10.0)                              # b expires queued
    srv.step()
    assert srv.finish_reason(b) == "deadline"
    assert srv.result(b) == [4, 5, 6]                # never admitted
    out = srv.drain()
    assert srv.finish_reason(a) in ("eos", "length")
    assert len(out[a]) == 3 + 40
    snap = fresh_telemetry.snapshot()
    assert snap["serve_deadline_expired_total"]["series"][0]["value"] == 1
    assert first_event_index(ev.DEADLINE_EXPIRED) is not None


def test_deadline_expiry_mid_prefill(fresh_telemetry):
    """Deadline fires between two prefill chunks: the slot is retired
    with the prompt-only partial, the chunk queue is clean, and the
    next request is served normally."""
    clock = FakeClock()
    eng = make_engine(num_slots=1, prefill_chunk_tokens=32)
    srv = ContinuousBatchingServer(eng, clock=clock)
    a = srv.submit(list(range(1, 97)), max_new_tokens=4, deadline_s=2.0)
    srv.step()                                       # chunk 1 of 3
    assert srv._mid_prefill
    clock.advance(5.0)                               # expire mid-prefill
    srv.step()
    assert srv.finish_reason(a) == "deadline"
    assert not srv._mid_prefill and not srv._prefilling
    assert srv.scheduler.idle
    b = srv.submit([5, 5, 5], max_new_tokens=3)
    out = srv.drain()
    ref = eng.generate([[5, 5, 5]], max_new_tokens=3)[0]
    assert out[b] == ref[:len(out[b])]


def test_deadline_reaps_decoding_request_with_partial(fresh_telemetry):
    clock = FakeClock()
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng, clock=clock)
    a = srv.submit([1, 2, 3], max_new_tokens=50, deadline_s=10.0)
    for _ in range(4):
        srv.step()
    got = len(srv.scheduler.slots[0].generated)
    clock.advance(20.0)
    srv.step()                                       # reaped this round
    assert srv.finish_reason(a) == "deadline"
    ref = eng.generate([[1, 2, 3]], max_new_tokens=50)[0]
    assert srv.result(a) == ref[:3 + got]
    assert srv.scheduler.idle


# ---------------------------------------------- preemption + requeue

def test_preempt_requeue_greedy_parity(fresh_telemetry):
    """THE recompute-preemption oracle: a low-priority request preempted
    mid-decode by a high-priority arrival, requeued with its committed
    tokens folded into the prompt, resumes and finishes — its output
    token-for-token identical to an uninterrupted one-shot generate()."""
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=10, priority=0)
    for _ in range(4):
        srv.step()                     # a is resident, tokens committed
    committed_before = len(srv.scheduler.slots[0].generated)
    assert committed_before >= 3
    b = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    ref_a = eng.generate([[1, 2, 3]], max_new_tokens=10)[0]
    ref_b = eng.generate([[4, 5, 6]], max_new_tokens=4)[0]
    assert out[a] == ref_a[:len(out[a])]
    assert len(out[a]) == 3 + 10                  # full budget delivered
    assert out[b] == ref_b[:len(out[b])]
    assert srv.finish_reason(a) in ("eos", "length")
    assert first_event_index(ev.PREEMPT) is not None
    snap = fresh_telemetry.snapshot()
    assert snap["serve_preempted_total"]["series"][0]["value"] == 1


def test_preempt_requeue_replays_warm_with_prefix_cache(fresh_telemetry):
    """With prefix caching, the victim's full written blocks (prompt AND
    committed extension) demote into the LRU at preemption — the
    recompute prefill re-admits with cache hits instead of replaying
    cold, and the output is still exact."""
    eng = make_engine(num_slots=1, enable_prefix_caching=True,
                      max_out_tokens=256)
    srv = ContinuousBatchingServer(eng)
    prompt = [1 + (i % 100) for i in range(40)]       # 1 full 32-block
    a = srv.submit(prompt, max_new_tokens=40, priority=0)
    # decode until the extension crosses a block boundary (40 prompt +
    # 25 generated = 65 written tokens -> 2 full blocks)
    for _ in range(40):
        srv.step()
        if len(srv.scheduler.slots.get(0).generated) >= 26:
            break
    hits0 = srv.scheduler.prefix_hits
    b = srv.submit([9, 9, 9], max_new_tokens=4, priority=3)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    # the resumed admission hit cached blocks (prompt + extension)
    assert srv.scheduler.prefix_hits > hits0
    ref_a = eng.generate([prompt], max_new_tokens=40)[0]
    assert out[a] == ref_a[:len(out[a])]
    assert len(out[a]) == len(prompt) + 40


def test_equal_priority_never_preempts(fresh_telemetry):
    """Plain FIFO traffic on a tight pool queues — it must not thrash."""
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=6, priority=1)
    srv.step()
    b = srv.submit([4, 5, 6], max_new_tokens=4, priority=1)
    out = srv.drain()
    assert srv.stats["preempted"] == 0
    assert len(out[a]) == 3 + 6 and len(out[b]) == 3 + 4


def test_preemption_retries_bounded_then_failed(fresh_telemetry):
    """A request preempted past max_preemptions is failed loudly
    (finish reason 'failed', kept error trace) instead of livelocking
    through endless requeues."""
    eng = make_engine(num_slots=1, max_preemptions=1,
                      preemption_backoff_steps=0,
                      telemetry={"trace_sample_rate": 1.0})
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=30, priority=0)
    for _ in range(3):
        srv.step()
    b = srv.submit([4, 5], max_new_tokens=4, priority=1)   # preempt 1
    while b not in srv._results:
        srv.step()
    # a resumes once b finishes; preempt it again -> retries exhausted
    while srv.scheduler.find_slot(a) is None:
        srv.step()
    c = srv.submit([6, 6], max_new_tokens=4, priority=2)   # preempt 2
    out = srv.drain()
    assert srv.finish_reason(a) == "failed"
    assert srv.stats["failed"] == 1
    assert out[a][:3] == [1, 2, 3]                  # partial returned
    assert srv.finish_reason(c) in ("eos", "length")
    # the failure trace is always kept, with the cause on the root
    tr = [t for t in srv.tracer.traces() if t.trace_id == a][0]
    assert tr.status == "failed"
    assert "max_preemptions" in tr.root.attributes["error"]
    assert first_event_index(ev.REQUEST_FAILED) is not None


def test_backed_off_victim_waits_behind_high_priority(fresh_telemetry):
    """Priority-aware admission keeps preemption stable: a preempted
    low-priority request front-requeued past its backoff must NOT grab
    the free slot ahead of a queued higher-priority request — FIFO
    there would re-admit it, preempt it again immediately (one wasted
    prefill per episode), and burn max_preemptions into a spurious
    'failed' for a request that only had to wait its turn."""
    eng = make_engine(num_slots=1, max_preemptions=1,
                      preemption_backoff_steps=0)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=10, priority=0)
    for _ in range(3):
        srv.step()
    b = srv.submit([4, 5], max_new_tokens=4, priority=5)   # preempts a
    c = srv.submit([6, 7], max_new_tokens=4, priority=5)   # queued
    out = srv.drain()
    # a was preempted exactly once (by b); c was admitted ahead of the
    # requeued a instead of preempting it a second time
    assert srv.stats["preempted"] == 1
    assert srv.stats["failed"] == 0
    assert srv.finish_reason(a) in ("eos", "length")
    assert len(out[a]) == 3 + 10
    ref_a = eng.generate([[1, 2, 3]], max_new_tokens=10)[0]
    assert out[a] == ref_a                          # recompute exact
    for r in (b, c):
        assert srv.finish_reason(r) in ("eos", "length")


def test_seeded_prefill_fault_reaches_warm_prefix_requests(
        fresh_telemetry):
    """The seeded prefill-failure coin flips at ADMISSION, once per
    request — a warm-prefix request (whose first chunk starts at
    cached_len, not 0) must be just as mortal as a cold one."""
    eng = make_engine(num_slots=1, enable_prefix_caching=True)
    fi = FaultInjector(seed=0)
    srv = ContinuousBatchingServer(eng, fault_injector=fi)
    prompt = [1 + (i % 90) for i in range(40)]
    a = srv.submit(prompt, max_new_tokens=4)        # cold: warms cache
    srv.drain()
    assert srv.finish_reason(a) in ("eos", "length")
    fi.prefill_failure_rate = 1.0                   # certain death now
    b = srv.submit(prompt + [3, 3], max_new_tokens=4)
    srv.drain()
    assert srv.scheduler.prefix_hits > 0            # b admitted warm
    assert srv.finish_reason(b) == "failed"
    assert fi.injected.get("prefill_failure") == 1


def test_ttft_observed_when_preempted_before_first_token(fresh_telemetry):
    """A request preempted MID-PREFILL (no token ever emitted) must
    still observe its true TTFT at re-admission — keying the skip on
    'was preempted' instead of 'already emitted a token' would hide
    exactly the slowest first tokens from the TTFT histogram and the
    SLO gate reading it."""
    eng = make_engine(num_slots=1, prefill_chunk_tokens=32)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit(list(range(1, 97)), max_new_tokens=4,
                   priority=0)                     # 3 chunks
    srv.step()                                     # chunk 1 of 3 only
    assert srv._mid_prefill                        # no token yet
    b = srv.submit([5, 6], max_new_tokens=2, priority=3)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    assert srv.finish_reason(a) in ("eos", "length")
    assert len(out[a]) == 96 + 4                   # full budget, exact
    # BOTH requests delivered a first token exactly once
    assert fresh_telemetry.histogram("serve_ttft_seconds").count == 2
    # the resumed re-admission did not double-observe queue wait
    assert fresh_telemetry.histogram(
        "serve_queue_wait_seconds").count == 2


# ----------------------------------------------------- shed + SLO breach

SHED_TELEM = {"slo": {"enabled": True, "queue_wait_p90_s": 0.01,
                      "eval_interval_s": 0.0, "window_s": 600.0}}


def test_shed_on_queue_wait_breach(fresh_telemetry):
    """Queue-wait p90 breaches (fake clock, injected waits) -> each
    step sheds lowest-priority newest queued work down to the
    num_slots floor, with fast-fail results and 'shed' reasons."""
    clock = FakeClock()
    eng = make_engine(num_slots=1, enable_load_shedding=True,
                      telemetry=SHED_TELEM)
    srv = ContinuousBatchingServer(eng, clock=clock)
    a = srv.submit([1, 2, 3], max_new_tokens=3)
    srv.step()                      # a resident; prefill ran
    waiters = [srv.submit([4, 4 + i], max_new_tokens=4, priority=0)
               for i in range(4)]
    keeper = srv.submit([9, 9], max_new_tokens=4, priority=7)
    clock.advance(1.0)              # everything queued has waited 1s
    out = srv.drain()
    st = srv.stats
    assert st["shed"] >= 1
    shed = [r for r in waiters if srv.finish_reason(r) == "shed"]
    assert shed, "no waiter was shed"
    # shed requests fast-fail with the prompt as the partial result
    for r in shed:
        assert len(out[r]) == 2
    # the high-priority request is never the shedding victim
    assert srv.finish_reason(keeper) in ("eos", "length")
    assert first_event_index(ev.SHED) is not None
    snap = fresh_telemetry.snapshot()
    assert snap["serve_shed_total"]["series"][0]["value"] == st["shed"]


def test_shedding_without_slo_objective_is_config_error():
    eng = make_engine(enable_load_shedding=True)
    with pytest.raises(ValueError, match="queue_wait_p90"):
        ContinuousBatchingServer(eng)


def test_held_violation_verdict_does_not_shed_fresh_burst(
        fresh_telemetry):
    """The SLO monitor deliberately HOLDS a violation verdict across a
    no-traffic window (no auto-clear, PR 6) — but shedding must act
    only on live in-window evidence: a fresh burst arriving hours after
    an old breach has ~0 queue wait and must not be fast-failed on the
    stale verdict."""
    clock = FakeClock()
    eng = make_engine(num_slots=1, enable_load_shedding=True,
                      telemetry=SHED_TELEM)
    srv = ContinuousBatchingServer(eng, clock=clock)
    # phase 1: a genuine breach — queued work waits 1s vs a 10ms target
    srv.submit([1, 2, 3], max_new_tokens=3)
    srv.step()
    old = [srv.submit([4, 4 + i], max_new_tokens=3) for i in range(3)]
    clock.advance(1.0)
    srv.drain()
    shed_before = srv.stats["shed"]
    assert shed_before >= 1
    assert any(srv.finish_reason(r) == "shed" for r in old)
    # phase 2: idle far past the window, then a fresh burst — the held
    # (no_data) verdict keeps the SLO red but must not shed anything
    clock.advance(1000.0)
    fresh = [srv.submit([7, 7 + i], max_new_tokens=3) for i in range(4)]
    out = srv.drain()
    assert srv.stats["shed"] == shed_before
    for r in fresh:
        assert srv.finish_reason(r) in ("eos", "length")
        assert len(out[r]) == 2 + 3


# ------------------------------------------------- famine ladder order

def test_overload_lifecycle_on_bounds_the_tail_fifo_lets_grow(
        fresh_telemetry):
    """Two arrivals a step against two slots for eight steps, one fake
    second a step, the same trace twice: plain FIFO serves every request
    and the last ones wait out the whole backlog; with deadlines and
    load shedding on, what is served finishes inside the deadline and
    the rest is shed or reaped, in steps, on no machine's clock."""
    def leg(on):
        clock = FakeClock()
        knobs = dict(enable_load_shedding=True,
                     telemetry=SHED_TELEM) if on else {}
        per_request = {"deadline_s": 6.0} if on else {}
        srv = ContinuousBatchingServer(make_engine(num_slots=2, **knobs),
                                       clock=clock)
        born, took, step = {}, [], 0
        while step < 8 or not srv.scheduler.idle:
            if step < 8:
                for j in range(2):
                    born[srv.submit([1 + step, 2 + j], max_new_tokens=4,
                                    **per_request)] = step
            for rid in srv.step():
                if srv.finish_reason(rid) in ("eos", "length"):
                    took.append(step + 1 - born[rid])
            clock.advance(1.0)
            step += 1
        return sorted(took), srv.stats

    fifo, st_off = leg(False)
    served, st_on = leg(True)
    assert len(fifo) == 16
    assert (st_off["shed"], st_off["deadline_expired"],
            st_off["preempted"], st_off["cancelled"],
            st_off["failed"]) == (0, 0, 0, 0, 0)
    assert served and max(served) <= 7 < max(fifo)
    assert served[int(len(served) * 0.9)] < fifo[int(len(fifo) * 0.9)]
    assert st_on["shed"] >= 1 and st_on["deadline_expired"] >= 1
    assert len(served) + st_on["shed"] + st_on["deadline_expired"] == 16


def test_famine_ladder_evict_then_preempt_then_shed(fresh_telemetry):
    """The degradation ladder under block famine fires its rungs in
    order — prefix-LRU eviction, then preemption, then shedding — and
    each rung leaves its event-ring entry."""
    clock = FakeClock()
    eng = make_engine(num_slots=2, max_out_tokens=128,
                      enable_prefix_caching=True,
                      enable_load_shedding=True, telemetry=SHED_TELEM)
    srv = ContinuousBatchingServer(eng, clock=clock)
    # pool: 2 slots x 4 blocks. rA spans 4 blocks, its 2 full prompt
    # blocks are cached -> park in the LRU at finish
    pa = [1 + (i % 90) for i in range(65)]
    ra = srv.submit(pa, max_new_tokens=59)
    srv.drain()
    assert srv.scheduler.allocator.cached_blocks >= 2
    # rB + rC (cold, 4 blocks each) fill the pool; rC's allocation must
    # evict the parked LRU blocks — rung 1
    rb = srv.submit([100 + i % 20 for i in range(65)], max_new_tokens=59)
    srv.step()
    rc = srv.submit([50 + i % 13 for i in range(65)], max_new_tokens=59)
    while srv.scheduler.find_slot(rc) is None:   # admitted once rB's
        srv.step()                               # prefill is through
    assert first_event_index(ev.PREFIX_EVICT) is not None
    assert srv.scheduler.find_slot(rb) is not None
    assert srv.scheduler.find_slot(rc) is not None
    # rD (higher priority) finds no slot and no blocks: preempts the
    # newest equal-lowest resident (rC) — rung 2
    rd = srv.submit([7, 7, 7], max_new_tokens=4, priority=2)
    srv.step()
    assert srv.stats["preempted"] >= 1
    # rE..rH overfill the queue, then their waits breach the SLO once
    # a slot frees and one of them is admitted — rung 3
    for i in range(4):
        srv.submit([30 + i, 31], max_new_tokens=4, priority=0)
    clock.advance(1.0)
    srv.drain()
    assert srv.stats["shed"] >= 1
    i_evict = first_event_index(ev.PREFIX_EVICT)
    i_preempt = first_event_index(ev.PREEMPT)
    i_shed = first_event_index(ev.SHED)
    assert i_evict < i_preempt < i_shed, (i_evict, i_preempt, i_shed)


def test_injected_famine_blocks_admission_until_cleared(fresh_telemetry):
    """famine_blocks withholds pool blocks: admission stalls (no crash,
    request queued), and clearing the famine lets it proceed."""
    eng = make_engine(num_slots=1)
    fi = FaultInjector(famine_blocks=7)          # pool has 8 usable
    srv = ContinuousBatchingServer(eng, fault_injector=fi)
    a = srv.submit([1, 2, 3], max_new_tokens=40)  # needs 2 blocks
    srv.step()
    assert srv.scheduler.allocator.reserved_blocks == 7
    assert srv.scheduler.find_slot(a) is None     # famine blocks it
    assert srv.scheduler.pending_requests == 1
    fi.famine_blocks = 0                          # chaos over
    out = srv.drain()
    ref = eng.generate([[1, 2, 3]], max_new_tokens=40)[0]
    assert out[a] == ref[:len(out[a])]
    assert fi.injected.get("famine") == 1
    snap = fresh_telemetry.snapshot()
    fam = snap["fault_injections_total"]["series"]
    assert any(s["labels"].get("kind") == "famine" for s in fam)


# -------------------------------------------------- fault injection

def test_injected_prefill_failure_fails_request_not_server(
        fresh_telemetry):
    eng = make_engine(num_slots=2,
                      telemetry={"trace_sample_rate": 1.0})
    fi = FaultInjector()
    srv = ContinuousBatchingServer(eng, fault_injector=fi)
    usable = srv.scheduler.allocator.usable_blocks
    a = srv.submit([1, 2, 3], max_new_tokens=4)
    fi.fail_prefill_for(a)
    b = srv.submit([4, 5, 6], max_new_tokens=4)
    out = srv.drain()
    assert srv.finish_reason(a) == "failed"
    assert out[a] == [1, 2, 3]
    assert srv.finish_reason(b) in ("eos", "length")   # loop survived
    assert srv.scheduler.allocator.free_blocks == usable
    tr = [t for t in srv.tracer.traces() if t.trace_id == a][0]
    assert tr.status == "failed"
    assert "injected prefill failure" in tr.root.attributes["error"]


def test_seeded_prefill_failures_are_deterministic(fresh_telemetry):
    """Same seed -> byte-identical fault schedule across two runs."""
    def run(seed):
        eng = make_engine(num_slots=2)
        fi = FaultInjector(seed=seed, prefill_failure_rate=0.5)
        srv = ContinuousBatchingServer(eng, fault_injector=fi)
        ids = [srv.submit([1 + i, 2, 3], max_new_tokens=3)
               for i in range(12)]
        srv.drain()
        return [srv.finish_reason(r) for r in ids]

    r1, r2 = run(7), run(7)
    assert r1 == r2
    assert "failed" in r1 and "length" in r1


def test_config_armed_injector_wedges_every_nth(fresh_telemetry):
    """The config path: telemetry.fault_injection builds the injector,
    wedge_nth_request wedges request #N, and a bounded drain reaps it."""
    eng = make_engine(num_slots=2, telemetry={
        "fault_injection": {"enabled": True, "wedge_nth_request": 2}})
    srv = ContinuousBatchingServer(eng, clock=FakeClock(auto=0.01))
    assert srv._fi is not None
    a = srv.submit([1, 2, 3], max_new_tokens=3)
    b = srv.submit([4, 5, 6], max_new_tokens=3)      # wedged (2nd)
    out = srv.drain(timeout_s=5.0)
    assert srv.finish_reason(a) in ("eos", "length")
    assert srv.finish_reason(b) == "cancelled"
    assert len(out[b]) > 3 + 3          # decoded past its budget: wedged
    assert srv.stats["fault_injection"]["injected"]["wedged_slot"] == 1


# ------------------------------------------------ bounded drain + wedge

def test_drain_timeout_terminates_wedged_slot(fresh_telemetry):
    """THE termination proof: a wedged slot never finishes, the old
    unbounded drain would spin forever — drain(timeout_s=...) cancels
    the straggler and returns partial results. The auto-advancing fake
    clock makes termination a certainty, not a race: every step reads
    the clock, the clock only goes up."""
    clock = FakeClock(auto=0.05)
    eng = make_engine(num_slots=2)
    fi = FaultInjector()
    srv = ContinuousBatchingServer(eng, clock=clock,
                                   fault_injector=fi)
    a = srv.submit([1, 2, 3], max_new_tokens=3)
    w = srv.submit([9, 9], max_new_tokens=3)
    fi.wedge(w)
    out = srv.drain(timeout_s=10.0)
    assert srv.scheduler.idle                       # provably terminated
    assert srv.finish_reason(a) in ("eos", "length")
    assert srv.finish_reason(w) == "cancelled"
    assert out[w][:2] == [9, 9]
    assert len(out[w]) > 2 + 3                      # wedged past budget
    with pytest.raises(ValueError, match="timeout_s"):
        srv.drain(timeout_s=-1.0)


def test_deadline_reaps_wedged_slot_and_watchdog_stays_green(
        fresh_telemetry):
    """The watchdog-clears scenario: a wedged request is reaped by its
    deadline, and a server whose only 'progress' is lifecycle work
    (cancel/reap) is never reported hung — degradation feeds the
    heartbeat."""
    wd_clock = FakeClock()
    srv_clock = FakeClock()
    eng = make_engine(num_slots=1)
    fi = FaultInjector()
    srv = ContinuousBatchingServer(eng, clock=srv_clock,
                                   fault_injector=fi)
    srv.watchdog = Watchdog(deadline_s=5.0, clock=wd_clock,
                            name="test_serve")
    w = srv.submit([9, 9], max_new_tokens=2, deadline_s=3.0)
    fi.wedge(w)
    for _ in range(6):
        srv.step()                    # wedged decode IS progress
        wd_clock.advance(1.0)
        assert srv.watchdog.check() is False
    srv_clock.advance(10.0)           # deadline passes
    srv.step()                        # reap = progress too
    assert srv.finish_reason(w) == "deadline"
    wd_clock.advance(4.0)             # still inside the re-armed window
    assert srv.watchdog.check() is False
    assert srv.watchdog.stalls == 0
    # the pure-lifecycle heartbeat: no steps at all, only a cancel
    q = srv.submit([1, 1], max_new_tokens=2, deadline_s=100.0)
    wd_clock.advance(4.0)             # near the 5s deadline again
    srv.cancel(q)                     # lifecycle action -> heartbeat
    wd_clock.advance(4.0)             # past old deadline, inside new
    assert srv.watchdog.check() is False
    assert srv.watchdog.stalls == 0


# -------------------------------------------------- exporter robustness

def test_stalled_scrape_client_does_not_pin_endpoint(fresh_telemetry):
    """One client connects and goes silent (socket open, no request):
    the handler has a read timeout, so live scrapes keep working and
    close() joins cleanly (returns True)."""
    http = start_http_server(0, registry=fresh_telemetry,
                             handler_timeout_s=0.2)
    try:
        stalled = socket.create_connection(("127.0.0.1", http.port))
        # a live scrape succeeds while the stalled connection is open
        with urllib.request.urlopen(
                f"http://127.0.0.1:{http.port}/metrics.json",
                timeout=5) as resp:
            assert resp.status == 200
            json.loads(resp.read())
        stalled.close()
    finally:
        assert http.close() is True   # serve thread joined, reported
    with pytest.raises(ValueError, match="handler_timeout_s"):
        start_http_server(0, registry=fresh_telemetry,
                          handler_timeout_s=0.0)
