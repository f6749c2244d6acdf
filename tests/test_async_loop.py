"""Async serving loop: pipelined dispatch with lag-1 host commit.

The ISSUE-11 contracts:

* **Parity oracles intact under pipelining**: greedy async output is
  token-identical to one-shot ``generate()`` (and byte-identical to the
  sync-fallback server); speculation under async is token-identical to
  ``generate_speculative(draft=None)`` — under prefix caching + chunked
  prefill + preemption.
* **Zero new executables**: the chained dispatch feeds step N's device
  outputs straight into step N+1 — same abstract signature, same ONE
  decode/verify executable, zero retraces (``_cache_size()`` pinned).
* **Lag-1 reconciliation edges**: EOS/budget landing on the last slot
  mid-pipeline discards the chained garbage step; cancel / deadline /
  preemption force a bounded flush at the committed boundary (the
  victim's in-flight token is discarded, nobody else loses one);
  ``drain(timeout_s=...)`` still provably terminates with a wedged
  in-flight step; an injected prefill failure under async fails the
  request, not the server. All fake-clock, zero real sleeps.
* **Worker-thread publishing**: metric publishing rides a worker
  drained at every flush / ``drain()`` / ``stats`` read — registry
  counts agree with host mirrors at every surface a test can touch.
* **StepProfiler commit lag**: phases still sum to wall exactly when
  fetch(N) happens inside step N+1, and dispatch gaps pair against the
  fetch that actually drained the device (pipelined dispatches observe
  zero gaps).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.inference.async_loop import PublishWorker
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, FaultInjector,
                                     MetricRegistry, StepProfiler,
                                     set_event_ring, set_registry)


@pytest.fixture()
def fresh_telemetry():
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(512))
    try:
        yield
    finally:
        set_registry(prev_reg)
        set_event_ring(prev_ring)


class FakeClock:
    def __init__(self, t=0.0, auto=0.0):
        self.t = float(t)
        self.auto = float(auto)

    def __call__(self):
        v = self.t
        self.t += self.auto
        return v

    def advance(self, dt):
        self.t += dt


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
           [20, 21], [30], [40, 41, 42, 43, 44], [50, 51]]


def _serve(srv, prompts, budget, **kw):
    ids = [srv.submit(p, max_new_tokens=budget, **kw) for p in prompts]
    out = srv.drain()
    return [out[i] for i in ids]


# --------------------------------------------------------------- oracles

def test_async_default_on_and_sync_fallback():
    assert DeepSpeedInferenceConfig().async_loop is True
    srv = ContinuousBatchingServer(make_engine(async_loop=False))
    assert srv.stats["async_loop"]["enabled"] is False
    got = _serve(srv, PROMPTS[:3], 6)
    # the sync fallback never pipelines
    st = srv.stats["async_loop"]
    assert st["pipeline_starts"] == 0 and st["pipelined_steps"] == 0
    assert got == make_engine().generate(PROMPTS[:3], max_new_tokens=6)


def test_async_greedy_parity_and_pipeline_engaged():
    """THE oracle under pipelining: greedy output token-identical to
    one-shot generate(), with the pipeline demonstrably active (lag-1
    commits happened) and still ONE decode executable."""
    eng = make_engine()
    srv = ContinuousBatchingServer(eng)
    got = _serve(srv, PROMPTS, 6)
    assert got == eng.generate(PROMPTS, max_new_tokens=6)
    st = srv.stats
    assert st["async_loop"]["enabled"] is True
    assert st["async_loop"]["pipeline_starts"] >= 1
    assert st["async_loop"]["pipelined_steps"] >= 1
    assert st["decode_traces"] == 1
    assert st["retraces"] == 0
    # a drained server has nothing in flight and an empty worker queue
    assert st["async_loop"]["commit_lag"] == 0
    assert st["async_loop"]["worker"]["queue_depth"] == 0


def test_async_output_identical_to_sync_fallback():
    """The async loop changes WHEN commits happen, never WHAT commits:
    both loops serve byte-identical tokens for the same requests."""
    a = _serve(ContinuousBatchingServer(make_engine()), PROMPTS, 6)
    b = _serve(ContinuousBatchingServer(make_engine(async_loop=False)),
               PROMPTS, 6)
    assert a == b


@pytest.mark.parametrize("model", [
    dict(positional="rotary", norm_type="rmsnorm", gated_mlp=True,
         activation="silu", n_kv_head=2, tied_lm_head=False),  # llama/GQA
    dict(positional="alibi"),                                  # bloom
    dict(local_windows=(None, 4)),                             # gpt-neo
])
def test_async_parity_across_architectures(model):
    eng = make_engine(seed=1, model=model)
    srv = ContinuousBatchingServer(eng)
    prompts = [[3, 17, 9, 44, 2], [60, 61, 62]]
    assert _serve(srv, prompts, 5) == eng.generate(prompts,
                                                   max_new_tokens=5)
    assert srv.stats["async_loop"]["pipelined_steps"] >= 1


def test_async_parity_tp2():
    """tp=2 over the virtual CPU mesh: the chained (committed) device
    tokens re-enter the same compiled decode — parity AND one trace."""
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tp_eng = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=32, num_slots=2,
        tensor_parallel={"tp_size": 2}))
    srv = ContinuousBatchingServer(tp_eng)
    got = _serve(srv, [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], 5)
    ref = _serve(ContinuousBatchingServer(make_engine(
        num_slots=2, async_loop=False)),
        [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], 5)
    assert got == ref
    assert srv.stats["decode_traces"] == 1
    assert srv.stats["retraces"] == 0


def test_async_spec_parity_with_oneshot_speculative():
    """Speculation under async: commit-then-dispatch keeps proposals
    fresh — output token-identical to generate_speculative(draft=None),
    one verify executable, zero retraces."""
    K = 4
    eng = make_engine()
    ref = eng.generate_speculative(PROMPTS[:6], max_new_tokens=12,
                                   draft_tokens=K)
    srv = ContinuousBatchingServer(make_engine(speculation_tokens=K))
    got = _serve(srv, PROMPTS[:6], 12)
    assert got == ref
    st = srv.stats
    assert st["async_loop"]["pipelined_steps"] >= 1
    assert st["speculation"]["verify_traces"] == 1
    assert st["retraces"] == 0
    # bookkeeping closes under lag: proposals counted per committed
    # slot-round, K-1 each
    assert st["speculation"]["proposed"] == \
        (K - 1) * srv._spec_slot_steps


def test_async_with_prefix_cache_chunked_prefill_and_preemption(
        fresh_telemetry):
    """The composition bar: prefix caching + chunked prefill + an
    injected higher-priority preemption, async ON vs sync OFF —
    identical outputs (chunk scheduling and the preemption ladder
    force flushes; steady decode still pipelines)."""
    def run(async_on):
        srv = ContinuousBatchingServer(make_engine(
            num_slots=2, enable_prefix_caching=True,
            max_out_tokens=128, async_loop=async_on))
        prefix = [1 + (i % 90) for i in range(64)]
        ids = [srv.submit(prefix + [3, 7, 11] * 4, max_new_tokens=20),
               srv.submit(prefix + [5, 9] * 6, max_new_tokens=16)]
        for _ in range(6):
            srv.step()
        ids.append(srv.submit([2, 4, 6, 8] * 8, max_new_tokens=24,
                              priority=5))
        res = srv.drain()
        return [res[i] for i in ids], srv.stats

    out_on, st_on = run(True)
    out_off, st_off = run(False)
    assert out_on == out_off
    assert st_on["preempted"] >= 1
    assert st_on["retraces"] == 0
    # host actions really did force flushes
    assert sum(st_on["async_loop"]["flushes"].values()) >= 1


# -------------------------------------------- lag-1 reconciliation edges

def test_eos_on_last_slot_mid_pipeline(fresh_telemetry):
    """The canonical reconciliation edge: the ONLY resident finishes at
    step N while the chained step N+1 is already in flight — N+1's
    garbage token is discarded, the output ends exactly at the budget,
    and every block returns to the pool."""
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    total = srv.scheduler.allocator.free_blocks
    ref = eng.generate([[1, 2, 3]], max_new_tokens=5)[0]
    rid = srv.submit([1, 2, 3], max_new_tokens=5)
    steps = 0
    while rid not in srv._results:
        srv.step()
        steps += 1
        assert steps < 50
    assert srv.result(rid) == ref          # no extra token ever leaks
    assert srv.finish_reason(rid) in ("eos", "length")
    st = srv.stats["async_loop"]
    assert st["pipelined_steps"] >= 1      # the pipeline was live
    assert st["commit_lag"] == 1           # the garbage step is in flight
    srv.step()                             # idle poll flushes the remnant
    st = srv.stats["async_loop"]
    assert st["commit_lag"] == 0
    assert st["garbage_steps"] >= 1
    assert st["flushes"].get("drain_tail", 0) >= 1
    assert srv.scheduler.allocator.free_blocks == total
    assert srv.scheduler.idle


def test_cancel_mid_pipeline_discards_inflight_token(fresh_telemetry):
    """cancel() takes effect at the COMMITTED boundary: the partial
    output equals exactly what the caller could observe before the
    cancel — the in-flight lag-1 token is discarded, and the committed
    prefix still matches the one-shot oracle."""
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=50)
    for _ in range(4):
        srv.step()
    assert srv.stats["async_loop"]["commit_lag"] == 1
    partial = list(srv.scheduler.slots[0].generated)
    assert len(partial) >= 2
    assert srv.cancel(a) is True
    assert srv.result(a) == [1, 2, 3] + partial
    ref = eng.generate([[1, 2, 3]], max_new_tokens=50)[0]
    assert srv.result(a) == ref[:3 + len(partial)]
    assert srv.stats["async_loop"]["discarded_tokens"] >= 1
    assert srv.stats["async_loop"]["flushes"].get("cancel", 0) == 1
    assert srv.scheduler.idle


def test_deadline_reap_mid_pipeline_fake_clock(fresh_telemetry):
    """A deadline expiring while a step is in flight flushes with the
    victim's token discarded — the partial equals the committed view,
    matching the oracle prefix. Fake clock, zero sleeps."""
    clock = FakeClock()
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng, clock=clock)
    a = srv.submit([1, 2, 3], max_new_tokens=50, deadline_s=10.0)
    for _ in range(5):
        srv.step()
    got = len(srv.scheduler.slots[0].generated)
    clock.advance(20.0)
    srv.step()                             # reaped this round
    assert srv.finish_reason(a) == "deadline"
    ref = eng.generate([[1, 2, 3]], max_new_tokens=50)[0]
    assert srv.result(a) == ref[:3 + got]
    assert srv.scheduler.idle
    assert srv.stats["async_loop"]["discarded_tokens"] >= 1


def test_preemption_mid_pipeline_flushes_then_preempts(fresh_telemetry):
    """A strictly-higher-priority arrival lands while the pipeline is
    live: the flush commits the victim's in-flight token FIRST (no
    token is lost to the preemption), then recompute-requeue proceeds —
    and the resumed output is token-identical to an uninterrupted
    one-shot run."""
    eng = make_engine(num_slots=1)
    srv = ContinuousBatchingServer(eng)
    a = srv.submit([1, 2, 3], max_new_tokens=10, priority=0)
    for _ in range(4):
        srv.step()
    assert srv.stats["async_loop"]["commit_lag"] == 1
    b = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    out = srv.drain()
    assert srv.stats["preempted"] == 1
    assert srv.stats["async_loop"]["flushes"].get("host_action", 0) >= 1
    assert out[a] == eng.generate([[1, 2, 3]], max_new_tokens=10)[0]
    assert len(out[a]) == 3 + 10
    assert out[b] == eng.generate([[4, 5, 6]], max_new_tokens=4)[0]


# what the loop before ISSUE 32 (its pipelined and synchronous bodies
# separate functions) counted on the script below: the one loop with a
# per-step lag must count the same
_MIDCHAIN_PARENT = {
    "decode-lag1": dict(
        pipeline_starts=2, pipelined_steps=20, discarded_tokens=2,
        garbage_steps=1, flushes={"host_action": 1, "drain": 1},
        flush_depths={"host_action": {"1": 1}, "drain": {"1": 1}}),
    "decode-lag2": dict(
        pipeline_starts=2, pipelined_steps=21, discarded_tokens=4,
        garbage_steps=2, flushes={"host_action": 1, "drain": 1},
        flush_depths={"host_action": {"2": 1}, "drain": {"2": 1}}),
    "verify-lag1": dict(
        pipeline_starts=2, pipelined_steps=9, discarded_tokens=0,
        garbage_steps=0, flushes={"host_action": 1},
        flush_depths={"host_action": {"1": 1}}),
}


@pytest.mark.parametrize("case", sorted(_MIDCHAIN_PARENT))
def test_queue_arriving_mid_chain_runs_one_lag0_round(fresh_telemetry,
                                                      case):
    """A request queued while a chain is in flight: the next step
    flushes the chain (``host_action``), admits, and runs ONE round at
    lag 0 — it returns with nothing in flight and is not a pipelined
    step — and the step after it starts a new chain. Fake clock; the
    counters are the ones the two-loop server produced."""
    kind, lag = case.split("-lag")
    knobs = {"max_commit_lag": int(lag)}
    if kind == "verify":
        knobs["speculation_tokens"] = 4
    eng = make_engine(num_slots=2, **knobs)
    srv = ContinuousBatchingServer(eng, clock=FakeClock(auto=0.001))
    a = srv.submit([1, 2, 3, 1, 2, 3], max_new_tokens=24)
    for _ in range(4):          # admission at lag 0, then the chain
        srv.step()
    depth = srv.stats["async_loop"]["commit_lag"]
    assert depth == (1 if kind == "verify" else min(int(lag), 3))
    b = srv.submit([4, 5, 6], max_new_tokens=3)
    before = srv.stats
    srv.step()                  # the lag-0 step
    st = srv.stats
    assert st["async_loop"]["flushes"] == {"host_action": 1}
    assert st["async_loop"]["flush_depths"] == {
        "host_action": {str(depth): 1}}
    assert st["async_loop"]["commit_lag"] == 0
    assert st["async_loop"]["pipeline_starts"] \
        == before["async_loop"]["pipeline_starts"] == 1
    assert st["async_loop"]["pipelined_steps"] \
        == before["async_loop"]["pipelined_steps"]
    assert st["step_profile"]["commit_lag"]["pipelined_steps"] \
        == before["step_profile"]["commit_lag"]["pipelined_steps"]
    assert st["step_profile"]["steps"] \
        == before["step_profile"]["steps"] + 1
    # the flush committed the chain and the round its own program
    assert st["decode_steps"] == before["decode_steps"] + depth + 1
    assert len(srv.scheduler.slots) == 2
    srv.step()                  # queue empty again: a new chain
    st = srv.stats["async_loop"]
    assert st["commit_lag"] == 1 and st["pipeline_starts"] == 2
    out = srv.drain()
    ref = make_engine(num_slots=2, async_loop=False, **knobs)
    got = ContinuousBatchingServer(ref)
    ra = got.submit([1, 2, 3, 1, 2, 3], max_new_tokens=24)
    rb = got.submit([4, 5, 6], max_new_tokens=3)
    want = got.drain()
    assert (out[a], out[b]) == (want[ra], want[rb])
    st = srv.stats["async_loop"]
    counters = {k: st[k] for k in (
        "pipeline_starts", "pipelined_steps", "flushes", "flush_depths",
        "discarded_tokens", "garbage_steps")}
    assert counters == _MIDCHAIN_PARENT[case]


# ----------------------------------- a backlog behind full slots (ISSUE 33)

def make_latent_engine(num_slots=2, max_out_tokens=64, **knobs):
    """The latent-cache family (LongCat-Flash) at a tiny size: the
    other pool the one step loop serves."""
    from deepspeed_tpu.model_implementations.longcat_flash import (
        LongcatFlashConfig, init_params as init_latent)
    cfg = LongcatFlashConfig(
        dtype=jnp.float32, vocab_size=128, hidden_size=64, num_layers=2,
        num_attention_heads=4, ffn_hidden_size=128,
        expert_ffn_hidden_size=32, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=8, zero_expert_num=4, moe_topk=3,
        max_position_embeddings=1024, experts_held=(2, 6))
    params = init_latent(jax.random.PRNGKey(3), cfg)
    return InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=max_out_tokens, block_size=16,
        num_slots=num_slots, **knobs))


_SHARED = [1 + (i % 90) for i in range(64)]
_SHORT = [[1, 2, 3, 1, 2, 3], [4, 5, 6], [7, 8, 9, 7], [3, 2, 1], [9, 9, 8]]
# name -> (engine builder, prompts): five requests through two slots,
# budgets apart so that retirements come one at a time
_BACKLOG_FAMILIES = {
    "gpt2": (lambda **k: make_engine(num_slots=2, **k), _SHORT),
    "latent": (make_latent_engine, _SHORT),
    # prefix caching implies one-block chunked prefill: a shared
    # two-block prefix, distinct tails
    "prefix-chunked": (
        lambda **k: make_engine(num_slots=2, max_out_tokens=128,
                                enable_prefix_caching=True, **k),
        [_SHARED + [3, 7, 11], _SHARED + [5, 9], _SHARED + [2, 4, 6, 8],
         _SHARED[:40] + [13], _SHARED + [17, 19]]),
}
_BACKLOG_BUDGETS = [9, 14, 6, 11, 5]
_BACKLOG_CASES = [
    "decode-lag1-gpt2", "decode-lag3-gpt2", "verify-lag1-gpt2",
    "verify-lag3-gpt2", "decode-lag1-latent", "decode-lag3-latent",
    "decode-lag1-prefix-chunked", "decode-lag3-prefix-chunked",
    "verify-lag1-prefix-chunked"]


def _backlog_server(case, clock=None, **extra):
    kind, lag, family = case.split("-", 2)
    knobs = {"max_commit_lag": int(lag[3:]), **extra}
    if kind == "verify":
        knobs["speculation_tokens"] = 4
    build, prompts = _BACKLOG_FAMILIES[family]
    kw = {} if clock is None else {"clock": clock}
    return ContinuousBatchingServer(build(**knobs), **kw), prompts


@pytest.mark.parametrize("case", _BACKLOG_CASES)
def test_backlog_behind_full_slots_pipelines_until_a_retirement(
        fresh_telemetry, case):
    """A queue deeper than the slots, every slot resident: nothing the
    host could do needs committed state, so the steps run lagged (no
    ``host_action`` flush, the chain stays in flight) exactly like steps
    with an empty queue. A retirement is found at a lagged commit; the
    NEXT step sees the free slot, flushes the chain, admits into it and
    is not pipelined; the one after (slots full again) starts a new
    chain. (Chunked: the step whose chunk FINISHES the refill's prefill,
    slots full again, starts that chain itself.) The served tokens equal
    ``async_loop: false`` token for token. Fake clock."""
    srv, prompts = _backlog_server(case, clock=FakeClock(auto=0.001))
    sched = srv.scheduler
    ids = [srv.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, _BACKLOG_BUDGETS)]

    def loop():
        a = srv._async_stats
        return (a["pipeline_starts"] + a["pipelined_steps"],
                a["flushes"].get("host_action", 0), len(srv._inflight))

    seen = {"lagged_with_backlog": 0, "flush_then_admit": 0,
            "restart": 0, "refill_started_chain": 0}
    was_lag0_refill = False
    guard = 0
    while not sched.idle:
        guard += 1
        assert guard < 400
        full = not sched._free_slots
        backlog = bool(sched.queue)
        chunking = bool(srv._prefilling)
        resident0 = {s.request.request_id for s in sched.slots.values()}
        lagged0, flushes0, depth0 = loop()
        prefills0 = srv._prefills
        srv.step()
        lagged1, flushes1, depth1 = loop()
        if backlog and full and not chunking:
            # the host had nothing it could change: a lagged step
            assert flushes1 == flushes0
            assert lagged1 == lagged0 + 1
            # (a verify round whose commit retired everyone dispatches
            # nothing)
            assert depth1 >= 1 or not sched.slots
            seen["lagged_with_backlog"] += 1
            if was_lag0_refill:
                assert depth0 == 0 and depth1 == 1      # a new chain
                seen["restart"] += 1
            was_lag0_refill = False
        elif backlog and not full:
            # a free slot and a waiter: lag 0, flush first, then admit
            # its round is committed before it returns, unless its
            # chunk finished the prefill and left the next step nothing
            # to act on: then its decode starts the next chain
            starts = int(bool(srv.chunk_tokens)
                         and srv._prefills > prefills0
                         and bool(sched.slots)
                         and not srv._host_can_act())
            assert lagged1 == lagged0 + starts
            assert flushes1 == flushes0 + (1 if depth0 else 0)
            assert depth1 == starts
            seen["restart"] += starts
            seen["refill_started_chain"] += starts
            # somebody moved in (chunked: nobody does while a prefill
            # is in flight)
            assert {s.request.request_id for s in sched.slots.values()
                    } - resident0 or chunking
            seen["flush_then_admit"] += 1 if depth0 else 0
            was_lag0_refill = (not starts and not sched._free_slots
                               and bool(sched.queue))
        else:
            was_lag0_refill = False
    out = srv.drain()
    assert seen["lagged_with_backlog"] >= 4
    # (a verify round commits up to K tokens a slot: fewer rounds)
    assert seen["flush_then_admit"] >= (2 if case.startswith("decode")
                                        else 1)
    assert seen["restart"] >= 1
    # only a refill through chunks leaves its decode in flight
    assert (seen["refill_started_chain"] >= 1) == bool(srv.chunk_tokens)
    assert srv.stats["retraces"] == 0
    ref, _ = _backlog_server(case, async_loop=False)
    rids = [ref.submit(p, max_new_tokens=b)
            for p, b in zip(prompts, _BACKLOG_BUDGETS)]
    want = ref.drain()
    assert ref.stats["async_loop"]["pipelined_steps"] == 0
    assert [out[i] for i in ids] == [want[i] for i in rids]


@pytest.mark.parametrize("case", ["decode-lag1-gpt2", "decode-lag3-gpt2",
                                  "verify-lag1-gpt2", "verify-lag3-gpt2"])
def test_queued_head_that_outranks_a_resident_keeps_lag0_and_preempts(
        fresh_telemetry, case):
    """Full slots and a backlog of equals: lagged steps. A waiter that
    outranks a resident is something the host CAN act on: the next step
    runs at lag 0, flushes the chain and preempts, as it always did.
    With preemption off (``max_preemptions: 0``) the same waiter cannot
    get in, and the steps stay lagged."""
    srv, prompts = _backlog_server(case, clock=FakeClock(auto=0.001))
    ids = [srv.submit(p, max_new_tokens=30) for p in prompts[:4]]
    for _ in range(6):
        srv.step()
    st = srv.stats
    assert st["async_loop"]["commit_lag"] >= 1
    assert st["async_loop"]["flushes"].get("host_action", 0) == 0
    assert st["preempted"] == 0 and len(srv.scheduler.queue) == 2
    vip = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    srv.step()
    st = srv.stats
    assert st["async_loop"]["flushes"]["host_action"] == 1
    assert st["async_loop"]["commit_lag"] == 0
    assert st["preempted"] == 1
    assert srv.scheduler.find_slot(vip) is not None
    out = srv.drain()
    ref, _ = _backlog_server(case, async_loop=False)
    rids = [ref.submit(p, max_new_tokens=30) for p in prompts[:4]]
    want = ref.drain()
    assert [out[i] for i in ids] == [want[i] for i in rids]
    assert out[vip][:3] == [4, 5, 6] and len(out[vip]) == 7

    off, _ = _backlog_server(case, clock=FakeClock(auto=0.001),
                             max_preemptions=0)
    for p in prompts[:4]:
        off.submit(p, max_new_tokens=30)
    for _ in range(6):
        off.step()
    off.submit([4, 5, 6], max_new_tokens=4, priority=5)
    before = off.stats["async_loop"]
    off.step()
    after = off.stats["async_loop"]
    assert after["flushes"].get("host_action", 0) == 0
    assert after["commit_lag"] >= 1
    assert (after["pipelined_steps"] + after["pipeline_starts"]
            == before["pipelined_steps"] + before["pipeline_starts"] + 1)
    off.drain()
    assert off.stats["preempted"] == 0


def _allocator_state(sched):
    a = sched.allocator
    return (list(a._free), set(a._free_set), dict(a._refcount),
            dict(a._hash_to_block), dict(a._block_hash), list(a._lru),
            a.evictions, a.demotions, a.swap_ins, sched.prefix_hits,
            sched.prefix_misses, sched._c_hits.value,
            sched._c_misses.value, list(sched._free_slots),
            [r.request_id for r in sched.queue], sorted(sched.slots))


@pytest.mark.parametrize("prefix_caching", [False, True],
                         ids=["plain", "prefix-cache"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_may_act_is_side_effect_free_and_never_a_false_cannot(
        fresh_telemetry, seed, prefix_caching):
    """``Scheduler.may_act`` over a random walk of scheduler states
    (submits at mixed priorities, admissions, releases, preemptions
    with back-off, deadlines, injected famine, a pool too small for the
    queue): it leaves the allocator's refcounts, free lists, prefix
    index and the hit / miss counters as they were, and whenever it
    answers "cannot", ``admit_next`` admits nothing and no resident
    ranks below the waiter — a false "cannot" would let a chain run
    past a state change the host could have made."""
    from deepspeed_tpu.inference.scheduler import Request, Scheduler
    rng = np.random.default_rng(seed)
    BS = 4
    sched = Scheduler(num_slots=3, num_blocks=14, block_size=BS,
                      max_blocks_per_slot=6, max_queued_requests=64,
                      registry=MetricRegistry(),
                      enable_prefix_caching=prefix_caching)
    shared = list(range(1, 9))
    rid = clock = 0
    answers = {True: 0, False: 0}
    for _ in range(400):
        clock += 1
        now = float(clock)
        op = rng.integers(0, 10)
        if op < 4 and len(sched.queue) < 8:
            plen = int(rng.integers(1, 12))
            prompt = (shared[:plen] if rng.random() < 0.5 else
                      rng.integers(1, 50, size=plen).tolist())
            rid += 1
            sched.submit(Request(
                rid, prompt, max_new_tokens=int(rng.integers(1, 10)),
                priority=int(rng.integers(0, 3)),
                deadline_ts=(now + float(rng.integers(1, 6))
                             if rng.random() < 0.2 else None)))
        elif op < 6 and sched.slots:
            slot = int(rng.choice(sorted(sched.slots)))
            state = sched.slots[slot]
            if prefix_caching:
                sched.commit_prefix(state)
            sched.release(slot)
        elif op < 7 and sched.slots:
            slot = int(rng.choice(sorted(sched.slots)))
            sched.slots[slot].generated.extend([5, 6])
            sched.preempt(slot, clock, backoff_steps=int(rng.integers(0, 4)))
        elif op < 8:
            sched.allocator.set_reserved(int(rng.integers(0, 6)))
        before = _allocator_state(sched)
        can = sched.may_act(clock, now)
        can_in = sched.may_act(clock, now, preemption=False)
        assert _allocator_state(sched) == before
        answers[can] += 1
        head = sched.next_ready(clock, now)
        victim = sched.pick_preemption_victim()
        outranks = (head is not None and victim is not None
                    and victim[1].request.priority < head.priority)
        free_slot = bool(sched._free_slots)
        # exact where it can be: an eligible head with a free slot, or
        # with a resident it outranks, is "the host may act" (whatever
        # the pool holds); nothing else is
        assert can == (head is not None and (free_slot or outranks))
        assert can_in == (head is not None and free_slot)
        adm = sched.admit_next(clock, now)
        if adm is not None:
            assert can and can_in
    assert answers[True] > 20 and answers[False] > 20


def test_drain_timeout_terminates_wedged_inflight_step(fresh_telemetry):
    """The PR-7 termination proof survives pipelining: a wedged slot
    decodes forever through CHAINED steps; the bounded drain cancels it
    with one step in flight, the flush discards its token, and the
    server ends idle. Auto-advancing fake clock, zero sleeps."""
    clock = FakeClock(auto=0.05)
    eng = make_engine(num_slots=2)
    fi = FaultInjector()
    srv = ContinuousBatchingServer(eng, clock=clock, fault_injector=fi)
    a = srv.submit([1, 2, 3], max_new_tokens=3)
    w = srv.submit([9, 9], max_new_tokens=3)
    fi.wedge(w)
    out = srv.drain(timeout_s=10.0)
    assert srv.scheduler.idle
    assert srv.finish_reason(a) in ("eos", "length")
    assert srv.finish_reason(w) == "cancelled"
    assert out[w][:2] == [9, 9]
    assert len(out[w]) > 2 + 3            # wedged decoded past budget
    st = srv.stats["async_loop"]
    assert st["pipelined_steps"] >= 1     # the wedge ran pipelined
    assert st["commit_lag"] == 0          # nothing left in flight


def test_injected_prefill_failure_under_async(fresh_telemetry):
    """Prefill fault injection composes with the async loop: the target
    request fails (always-kept reason), other requests pipeline to
    completion, every block returns."""
    eng = make_engine(num_slots=2)
    fi = FaultInjector()
    srv = ContinuousBatchingServer(eng, fault_injector=fi)
    usable = srv.scheduler.allocator.usable_blocks
    a = srv.submit([1, 2, 3], max_new_tokens=6)
    fi.fail_prefill_for(a)
    b = srv.submit([4, 5, 6], max_new_tokens=6)
    out = srv.drain()
    assert srv.finish_reason(a) == "failed"
    assert out[a] == [1, 2, 3]
    assert srv.finish_reason(b) in ("eos", "length")
    assert out[b] == eng.generate([[4, 5, 6]], max_new_tokens=6)[0]
    assert srv.scheduler.allocator.free_blocks == usable


# ----------------------------------------------- worker-thread publishing

def test_worker_drained_metrics_agree_with_host_mirrors():
    """After drain() every worker-published instrument agrees with the
    owner-thread mirrors — no test or scraper can observe a half-
    published step after a flush point."""
    reg = MetricRegistry()
    eng = make_engine()
    srv = ContinuousBatchingServer(eng, registry=reg)
    ids = [srv.submit(p, max_new_tokens=6) for p in PROMPTS]
    out = srv.drain()
    st = srv.stats
    steps = st["decode_steps"]
    assert reg.counter("serve_decode_steps_total").value == steps
    assert reg.histogram("serve_decode_step_seconds").count == steps
    assert reg.histogram("serve_token_seconds").count == steps
    assert reg.counter("serve_tokens_total").value == \
        sum(len(out[i]) - len(p) for i, p in zip(ids, PROMPTS))
    wk = st["async_loop"]["worker"]
    assert wk["queue_depth"] == 0
    assert wk["errors"] == 0
    # publishes batch (one worker job per up-to-16 step records), so
    # jobs >= 1 whenever any step committed through the async path
    assert wk["published"] >= 1


def test_publish_worker_unit():
    """PublishWorker semantics: drain blocks until empty, close is
    idempotent and later submits run inline, a raising job is counted
    and never kills the thread."""
    w = PublishWorker(name="t")
    hits = []
    for i in range(10):
        w.submit(lambda i=i: hits.append(i))
    w.submit(lambda: 1 / 0)               # must not kill the thread
    w.submit(lambda: hits.append(99))
    w.drain()
    assert hits[:10] == list(range(10)) and hits[-1] == 99
    assert w.errors == 1 and w.published == 11
    assert w.depth == 0 and w.max_depth >= 1
    w.close()
    w.close()                             # idempotent
    w.submit(lambda: hits.append(7))      # inline after close
    assert hits[-1] == 7


# ------------------------------------------------- StepProfiler commit lag

def test_profiler_pipelined_dispatch_zero_gap_and_pairing():
    """Commit-lag gap pairing: a dispatch issued while another program
    is outstanding observes a ZERO gap; the next real gap is measured
    against the fetch that actually drained the device."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    # step 1: pipeline start — dispatch, no fetch
    sp = prof.begin()
    fc.t = 1.0
    sp.pipelined(since=1.0)
    sp.mark("propose", dispatch=True)
    fc.t = 2.0
    sp.finish()
    snap = prof.snapshot()
    assert snap["commit_lag"]["outstanding"] == 1
    assert snap["dispatch_gap"]["count"] == 0
    assert snap["commit_lag"]["pipelined_steps"] == 1
    # step 2: chained — dispatch N+1 (device busy -> gap 0), THEN fetch N
    sp = prof.begin()
    fc.t = 3.0
    sp.pipelined()
    sp.mark("propose", dispatch=True)       # outstanding: 0-gap
    fc.t = 3.5
    sp.mark("sync_wait", fetch=True)        # fetch N: still 1 outstanding
    fc.t = 4.0
    sp.finish()
    snap = prof.snapshot()
    assert snap["commit_lag"]["outstanding"] == 1
    assert snap["commit_lag"]["pipelined_dispatches"] == 1
    gap = snap["dispatch_gap"]
    assert gap["count"] == 1 and gap["total_s"] == 0.0
    # flush: the fetch that drains the device opens the idle span
    prof.note_fetch(5.0)
    assert prof.snapshot()["commit_lag"]["outstanding"] == 0
    sp = prof.begin()
    fc.t = 7.0
    sp.mark("propose", dispatch=True)       # real gap vs t=5 fetch
    fc.t = 7.5
    sp.mark("sync_wait", fetch=True)
    fc.t = 8.0
    sp.finish()
    gap = prof.snapshot()["dispatch_gap"]
    assert gap["count"] == 2
    assert gap["total_s"] == 2.0 and gap["max_s"] == 2.0


def test_profiler_pipelined_phases_sum_and_device_credit():
    """Phases still sum to wall EXACTLY when fetch(N) happens inside
    step N+1, and a pipelined step's device credit is the full wall
    (the device verifiably had work the whole step) — never more."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    sp = prof.begin()                       # t=0; step N in flight
    fc.t = 0.5
    sp.mark("admission")
    fc.t = 0.6
    sp.mark("prefill_chunk")
    fc.t = 1.0
    sp.pipelined()
    sp.mark("propose", dispatch=True)       # dispatch N+1
    fc.t = 1.2
    sp.mark("dispatch")
    fc.t = 2.0
    sp.mark("sync_wait", fetch=True)        # fetch N, lag-1
    fc.t = 2.5
    sp.mark("commit")
    fc.t = 2.75
    sp.mark("publish")
    fc.t = 3.0
    sp.finish()
    snap = prof.snapshot()
    phases = snap["phases_s"]
    assert sum(phases.values()) == snap["wall_s"] == 3.0  # the identity
    assert phases["sync_wait"] == 0.8
    assert snap["device_s"] == 3.0          # busy the whole step
    assert snap["goodput_fraction"] == 1.0


def test_profiler_deferred_chunk_span_clamped_and_paired():
    """The no-sync chunk path: dispatch noted at dispatch time (real
    gap accounting), the device span realized at a later fetch with
    note_dispatch=False — outstanding pairing stays balanced and the
    credit clamps to the current step's window."""
    fc = FakeClock()
    prof = StepProfiler(registry=MetricRegistry(), clock=fc)
    sp = prof.begin()
    fc.t = 1.0
    sp.note_dispatch(1.0)                   # chunk leaves the host
    fc.t = 2.0
    sp.mark("prefill_chunk")
    fc.t = 3.0
    # realized at the decode's dispatch boundary (server pattern): the
    # chunk span ends where the decode slivers take over — adjacent,
    # never double-counted
    sp.device_interval(1.0, 3.0, note_dispatch=False)
    sp.mark("propose", dispatch=True)       # gap 0: chunk kept it busy
    fc.t = 3.25
    sp.mark("sync_wait", fetch=True)
    fc.t = 3.5
    sp.finish()
    snap = prof.snapshot()
    assert snap["commit_lag"]["outstanding"] == 0       # paired
    assert snap["device_s"] == pytest.approx(2.25)      # [1,3] + [3,3.25]
    gap = snap["dispatch_gap"]
    assert gap["count"] == 1 and gap["total_s"] == 0.0
    # a span whose dispatch predates the step clamps to the step window
    sp = prof.begin()                       # t=3.5
    fc.t = 4.0
    sp.device_interval(1.0, 4.0, note_dispatch=False)
    fc.t = 4.5
    sp.finish()
    assert prof.snapshot()["device_s"] == pytest.approx(2.75)


def test_cancel_mid_prefill_clears_pending_chunk_marker(fresh_telemetry):
    """Regression: tearing down a mid-prefill slot whose chunk dispatch
    was deferred (no fetch yet) must clear the pending marker AND
    rebalance the profiler's outstanding pairing — otherwise every
    later dispatch reads a forced 0-gap and the next realize credits
    idle wall as device time."""
    srv = ContinuousBatchingServer(make_engine(
        num_slots=1, prefill_chunk_tokens=32))
    a = srv.submit(list(range(1, 97)), max_new_tokens=4)    # 3 chunks
    srv.step()               # chunk 1 dispatched, fetch deferred
    assert srv._chunk_pending_t0 is not None
    assert srv._profiler.outstanding == 1
    assert srv.cancel(a) is True
    assert srv._chunk_pending_t0 is None
    assert srv._profiler.outstanding == 0
    # the next request's telemetry is healthy
    b = srv.submit([5, 6, 7], max_new_tokens=3)
    srv.drain()
    assert srv.finish_reason(b) in ("eos", "length")
    assert srv._profiler.outstanding == 0


def test_close_without_drain_commits_inflight_step(fresh_telemetry):
    """close() on a pipelined server must flush the in-flight step —
    its committed token, finishes, and metrics land instead of being
    silently dropped with the worker."""
    reg = MetricRegistry()
    srv = ContinuousBatchingServer(make_engine(num_slots=1),
                                   registry=reg)
    srv.submit([1, 2, 3], max_new_tokens=6)
    steps = 0
    while srv.stats["async_loop"]["commit_lag"] == 0:
        srv.step()
        steps += 1
        assert steps < 10
    gen_before = len(srv.scheduler.slots[0].generated)
    srv.close()
    st = srv.stats
    assert st["async_loop"]["commit_lag"] == 0
    assert st["async_loop"]["flushes"].get("close", 0) == 1
    assert len(srv.scheduler.slots[0].generated) == gen_before + 1
    assert reg.counter("serve_tokens_total").value == gen_before + 1


def test_multi_chunk_prefill_does_not_leak_outstanding(fresh_telemetry):
    """Regression: each non-final chunk used to note a dispatch while
    the whole chain realizes through ONE fetch — on a server whose only
    resident is mid-prefill (no decoder runs between chunks) the
    profiler's outstanding counter leaked, permanently zeroing every
    future dispatch gap. One note per pending chain keeps it balanced."""
    srv = ContinuousBatchingServer(make_engine(
        num_slots=1, prefill_chunk_tokens=32))
    a = srv.submit(list(range(1, 130)), max_new_tokens=3)   # 5 chunks
    srv.drain()
    assert srv.finish_reason(a) in ("eos", "length")
    assert srv.stats["prefill_chunks"] >= 5
    assert srv._profiler.outstanding == 0       # paired, not leaked
    # gaps still measurable afterwards: a fresh request's sync decode
    # records real (non-pipelined-only) boundaries
    srv.submit([5, 6, 7], max_new_tokens=3)
    srv.drain()
    assert srv._profiler.outstanding == 0
    snap = srv._profiler.snapshot()
    assert snap["dispatch_gap"]["count"] >= 1
    # the off-by-more leak symptom was gap_total frozen at 0 forever
    # with every dispatch misread as pipelined; a balanced counter
    # keeps pipelined_dispatches plausible (bounded by gap count)
    assert snap["commit_lag"]["pipelined_dispatches"] <= \
        snap["dispatch_gap"]["count"]


# ---------------------------------------------------------- stats surface

def test_async_stats_blob_shape():
    srv = ContinuousBatchingServer(make_engine())
    _serve(srv, PROMPTS[:4], 5)
    blob = srv.stats["async_loop"]
    for k in ("enabled", "commit_lag", "pipeline_starts",
              "pipelined_steps", "flushes", "discarded_tokens",
              "garbage_steps", "worker"):
        assert k in blob, k
    for k in ("published", "errors", "queue_depth", "max_depth"):
        assert k in blob["worker"], k
    import json
    assert json.loads(json.dumps(blob)) == blob


# ------------------------- an admitted prompt rides the step's program
# (ISSUE 51; docs/serving.md "Async dispatch loop", the rider round)

_RIDER_PROMPTS = [[1, 2, 3, 1, 2, 3], [4, 5, 6], [7, 8, 9, 7], [3, 2, 1],
                  [9, 9, 8], list(range(1, 20)), [11, 12]]
_RIDER_BUDGETS = [9, 14, 6, 11, 5, 7, 8]


def _calls(srv, monkeypatch):
    """Every serving program of ``srv`` counted by name as it is
    dispatched."""
    seen = []
    for attr in ("_prefill_jit", "_decode_jit", "_admit_jit", "_chunk_jit",
                 "_verify_jit"):
        fn = getattr(srv, attr)
        if fn is None:
            continue

        def counted(*a, _fn=fn, **k):
            seen.append(_fn.name)
            return _fn(*a, **k)
        counted.name = fn.name
        counted._cache_size = fn._cache_size
        monkeypatch.setattr(srv, attr, counted)
    return seen


def _step_spans(srv):
    """``srv``'s worked ``serve:step`` spans (the log is the
    process's)."""
    return [r for r in srv._profiler.span_log.snapshot(prefix="serve:")
            if r[0] == "serve:step" and not r[6].get("idle")
            and r[6]["profiler"] == srv._profiler.uid]


@pytest.mark.parametrize("lag", [1, 3])
def test_rider_serves_the_tokens_of_the_two_program_round(
        fresh_telemetry, monkeypatch, lag):
    """A LongCat-family server serves the same tokens with the family's
    ``paged_decode_admit`` and with it hidden (the round every other
    family runs: a prefill program, then the decode program). With it,
    no ``serve_prefill`` is ever traced: an empty server's admissions
    run the one program alone, every refill of a backlog rides a step
    that dispatches that ONE program, and ``serve_admissions_total``
    says which was which."""
    from deepspeed_tpu.model_implementations import longcat_flash as lf
    from deepspeed_tpu.telemetry import get_registry

    def serve():
        srv = ContinuousBatchingServer(make_latent_engine(
            max_commit_lag=lag), clock=FakeClock(auto=0.001))
        seen = _calls(srv, monkeypatch)
        ids = [srv.submit(p, max_new_tokens=b)
               for p, b in zip(_RIDER_PROMPTS, _RIDER_BUDGETS)]
        per_step = []
        while not srv.scheduler.idle:
            n = len(seen)
            srv.step()
            per_step.append(seen[n:])
        out = srv.drain()
        return srv, [out[i] for i in ids], per_step

    srv, got, per_step = serve()
    st = srv.stats
    assert srv._admit_jit is not None
    assert st["prefill_traces"] == 0 and st["decode_admit_traces"] == 1
    assert st["admissions"] == {"rider": 5, "alone": 2, "chunk": 0}
    assert st["prefills"] == 7 and st["retraces"] == 0
    # the fill of the empty server: two admissions alone, then the
    # step's decode program; every later admitting step is ONE program
    assert per_step[0] == ["serve_decode_admit"] * 2 + ["serve_decode"]
    riders = [calls for calls in per_step[1:]
              if "serve_decode_admit" in calls]
    assert riders == [["serve_decode_admit"]] * 5
    snap = get_registry().snapshot()["serve_admissions_total"]["series"]
    assert {s["labels"]["path"]: s["value"] for s in snap} == {
        "rider": 5.0, "alone": 2.0, "chunk": 0.0}
    spans = _step_spans(srv)
    rode = [r for r in spans if r[6]["rider"]]
    assert len(rode) == 5
    assert all(r[6]["admitted"] == 1 and not r[6]["pipelined"]
               for r in rode)
    assert sum(r[6]["admitted"] for r in spans) == 7
    # the routing counters of the program have a row of their own
    routed = {s["labels"]["program"]: s["value"] for s in
              get_registry().snapshot()["serve_moe_tokens_routed_total"]
              ["series"]}
    assert routed["decode_admit"] > 0 and routed.get("prefill", 0) == 0

    monkeypatch.delattr(lf, "paged_decode_admit")
    hidden, want, per_step = serve()
    st = hidden.stats
    assert hidden._admit_jit is None
    assert st["decode_admit_traces"] == 0 and st["prefill_traces"] == 1
    assert st["admissions"] == {"rider": 0, "alone": 7, "chunk": 0}
    assert not any(r[6]["rider"] for r in _step_spans(hidden))
    assert got == want


def test_k_waiters_and_k_free_slots_move_in_over_k_steps_in_queue_order(
        fresh_telemetry, monkeypatch):
    """With a slot decoding, three eligible requests and three free
    slots are admitted over three steps, one rider each, in queue order
    (every one of those steps is lag 0 and dispatches one program); the
    same three into an EMPTY server move in within one step, each
    through the program alone."""
    srv = ContinuousBatchingServer(make_latent_engine(num_slots=4),
                                   clock=FakeClock(auto=0.001))
    seen = _calls(srv, monkeypatch)
    first = srv.submit([5, 6, 7], max_new_tokens=40)
    for _ in range(3):
        srv.step()
    assert len(srv._inflight) == 1          # decoding, pipelined
    waiters = [srv.submit(p, max_new_tokens=4)
               for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
    order = []
    for k in range(3):
        n = len(seen)
        srv.step()
        assert seen[n:] == ["serve_decode_admit"]
        assert not srv._inflight            # lag 0: committed
        order.append({s.request.request_id
                      for s in srv.scheduler.slots.values()})
    assert order == [{first, *waiters[:k + 1]} for k in range(3)]
    assert srv.stats["admissions"] == {"rider": 3, "alone": 1, "chunk": 0}
    out = srv.drain()

    empty = ContinuousBatchingServer(make_latent_engine(num_slots=4),
                                     clock=FakeClock(auto=0.001))
    seen = _calls(empty, monkeypatch)
    again = [empty.submit(p, max_new_tokens=4)
             for p in ([1, 2, 3], [4, 5], [6, 7, 8, 9])]
    empty.step()
    assert seen == ["serve_decode_admit"] * 3 + ["serve_decode"]
    assert len(empty.scheduler.slots) == 3
    assert empty.stats["admissions"] == {"rider": 0, "alone": 3, "chunk": 0}
    alone = empty.drain()
    assert [out[i] for i in waiters] == [alone[i] for i in again]


@pytest.mark.parametrize("waiters", [2, 3])
def test_the_ladder_preempts_for_one_rider_a_step(fresh_telemetry,
                                                  monkeypatch, waiters):
    """Full slots of low priority and several waiters that outrank them:
    a step preempts ONE resident, for the head that then rides it, and
    the next head waits for the next step (the ladder never runs behind
    a rider: a resident evicted for a head that cannot move in this step
    would lose its work for nothing). Each request is preempted at most
    once, and the tokens are those of the round with the entry point
    hidden, which preempts for every head in one step."""
    from deepspeed_tpu.model_implementations import longcat_flash as lf

    def serve(rider):
        srv = ContinuousBatchingServer(make_latent_engine(num_slots=3),
                                       clock=FakeClock(auto=0.001))
        seen = _calls(srv, monkeypatch)
        low = [srv.submit(p, max_new_tokens=30)
               for p in _RIDER_PROMPTS[:3]]
        for _ in range(4):
            srv.step()
        assert len(srv.scheduler.slots) == 3 and srv._inflight
        vips = [srv.submit(p, max_new_tokens=5, priority=5)
                for p in _RIDER_PROMPTS[3:3 + waiters]]
        per_step = []
        for _ in range(waiters):
            n, before = len(seen), srv.stats["preempted"]
            srv.step()
            per_step.append((srv.stats["preempted"] - before, seen[n:],
                             sum(srv.scheduler.find_slot(v) is not None
                                 for v in vips)))
        if rider:
            # one victim, one program, one more waiter resident: a step
            assert per_step == [(1, ["serve_decode_admit"], k + 1)
                                for k in range(waiters)]
            assert srv._rider is None
        else:
            assert per_step[0] == (
                waiters, ["serve_prefill"] * waiters + ["serve_decode"],
                waiters)
        out = srv.drain()
        st = srv.stats
        assert st["preempted"] == waiters and st["failed"] == 0
        assert all(srv.finish_reason(i) == "length" for i in low + vips)
        return st, [out[i] for i in low + vips]

    st, got = serve(rider=True)
    # the preempted residents ride back in as slots come free
    assert st["admissions"]["rider"] == 2 * waiters
    monkeypatch.delattr(lf, "paged_decode_admit")
    st, want = serve(rider=False)
    assert st["admissions"] == {"rider": 0, "alone": 3 + 2 * waiters,
                                "chunk": 0}
    assert got == want


def test_empty_server_traces_each_bucket_of_the_program_once(
        fresh_telemetry):
    """Prompts of two buckets into an empty server: both are admitted in
    the first step, the program is traced once a bucket, and riders of
    either bucket then reuse those traces (what the benchmark's warm-up
    counts on: nothing compiles inside the window)."""
    long_a = [1 + (i % 90) for i in range(130)]
    long_b = [2 + (i % 70) for i in range(150)]
    srv = ContinuousBatchingServer(make_latent_engine(
        num_slots=2, max_out_tokens=512), clock=FakeClock(auto=0.001))
    a = srv.submit([1, 2, 3], max_new_tokens=5)
    b = srv.submit(long_a, max_new_tokens=9)
    c = srv.submit([4, 5, 6, 7], max_new_tokens=6)
    d = srv.submit(long_b, max_new_tokens=4)
    srv.step()
    assert len(srv.scheduler.slots) == 2
    assert srv.stats["decode_admit_traces"] == 2
    out = srv.drain()
    st = srv.stats
    assert st["decode_admit_traces"] == 2 and st["prefill_traces"] == 0
    assert st["admissions"] == {"rider": 2, "alone": 2, "chunk": 0}
    hidden = ContinuousBatchingServer(make_latent_engine(
        num_slots=2, max_out_tokens=512, async_loop=False))
    want = _serve(hidden, [[1, 2, 3], long_a, [4, 5, 6, 7], long_b], 9)
    for rid, full, budget in zip((a, b, c, d), want, (5, 9, 6, 4)):
        assert out[rid] == full[:len(out[rid])]
        assert len(out[rid]) == len(full) - 9 + budget


@pytest.mark.parametrize("mode", ["speculation", "chunking", "neither"])
def test_the_rider_needs_the_entry_point_and_plain_monolithic_serving(
        fresh_telemetry, monkeypatch, mode):
    """Which round runs is read off the family's module and the modes
    already configured, never a switch: a family with the entry point
    served with speculation or chunked prefill keeps today's programs
    (here the dense decoder behind a stand-in module, since the latent
    family refuses both), and serves one-shot ``generate()``'s tokens;
    without either mode the same stand-in is taken up."""
    import types

    from deepspeed_tpu.inference import server as server_mod

    def never(*a, **k):
        raise AssertionError("the entry point must not run")
    monkeypatch.setattr(server_mod, "model_family",
                        lambda cfg: types.SimpleNamespace(
                            paged_decode_admit=never))
    knobs = {"speculation": {"speculation_tokens": 4},
             "chunking": {"prefill_chunk_tokens": 32}, "neither": {}}[mode]
    eng = make_engine(num_slots=2, **knobs)
    srv = ContinuousBatchingServer(eng)
    if mode == "neither":
        assert srv._admit_jit is not None
        return
    assert srv._admit_jit is None
    assert _serve(srv, PROMPTS[:5], 6) == eng.generate(PROMPTS[:5],
                                                       max_new_tokens=6)
    st = srv.stats
    assert st["decode_admit_traces"] == 0
    assert st["admissions"] == {
        "rider": 0, "alone": 0 if mode == "chunking" else 5,
        "chunk": 5 if mode == "chunking" else 0}


def test_dense_family_keeps_its_two_program_round(fresh_telemetry,
                                                  monkeypatch):
    """The dense decoder has no ``paged_decode_admit``: every admission
    is ``serve_prefill`` alone and then the step's ``serve_decode``, as
    before, and no step carries a rider."""
    srv = ContinuousBatchingServer(make_engine(num_slots=2),
                                   clock=FakeClock(auto=0.001))
    assert srv._admit_jit is None
    seen = _calls(srv, monkeypatch)
    got = _serve(srv, _SHORT, 6)
    assert got == make_engine().generate(_SHORT, max_new_tokens=6)
    assert set(seen) == {"serve_prefill", "serve_decode"}
    assert seen.count("serve_prefill") == 5
    st = srv.stats
    assert st["admissions"] == {"rider": 0, "alone": 5, "chunk": 0}
    assert st["decode_admit_traces"] == 0 and st["prefill_traces"] == 1
    assert not any(r[6]["rider"] for r in _step_spans(srv))
