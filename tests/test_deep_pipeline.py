"""Deep pipeline serving: lag-N dispatch chains, chained chunked
prefill, and draft-model speculation on the paged path.

The ISSUE-20 contracts:

* **Byte-identity at the defaults**: ``max_commit_lag=1`` with no
  ``speculation_draft`` IS the PR-10 lag-1 loop — the existing async
  suite pins that; here the default knob values themselves are pinned.
* **Lag-N greedy parity**: any chain depth serves token-identical
  output to one-shot ``generate()``, through ONE decode executable,
  zero retraces — the chain only moves WHEN commits happen.
* **Lag-N chaos matrix**: EOS / cancel / deadline / preemption /
  bounded drain landing at every chain position still equal the
  one-shot oracle (prefix), with zero stranded blocks — fake clock,
  no sleeps.
* **Chained chunked prefill**: ``prefill_chain`` dispatches all
  non-final chunks of the head prompt device-side in one step —
  byte-identical outputs at every batch size around num_slots.
* **Draft-model speculation**: per-slot proposals from a real draft
  engine feed the SAME paged verify executable (zero new target
  executables) and keep the output exactly greedy — token-identical
  to one-shot ``generate_speculative(draft=...)`` AND to ``generate``.
"""
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.inference import (ContinuousBatchingServer,
                                     DeepSpeedInferenceConfig,
                                     InferenceEngine)
from deepspeed_tpu.model_implementations.transformer import (
    InferenceTransformerConfig, init_params)
from deepspeed_tpu.telemetry import (EventRing, MetricRegistry,
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


def make_draft(seed=7):
    """A genuinely smaller draft over the same vocab (interchangeable
    token ids — the only compatibility the paged path needs)."""
    cfg = InferenceTransformerConfig(vocab_size=128, n_positions=256,
                                     n_embd=16, n_layer=1, n_head=2,
                                     dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    return InferenceEngine((cfg, params),
                           DeepSpeedInferenceConfig(dtype="float32"))


PROMPTS = [[1, 2, 3, 4], [7, 8], [5, 6, 7, 8, 9, 10], [11, 12, 13],
           [20, 21], [30], [40, 41, 42, 43, 44], [50, 51]]


def _serve(srv, prompts, budget, **kw):
    ids = [srv.submit(p, max_new_tokens=budget, **kw) for p in prompts]
    out = srv.drain()
    return [out[i] for i in ids]


# ------------------------------------------------------------- defaults

def test_default_knobs_pin_lag1_and_no_draft():
    cfg = DeepSpeedInferenceConfig()
    assert cfg.max_commit_lag == 1       # byte-identical to the PR-10 loop
    assert cfg.prefill_chain is False
    assert cfg.speculation_draft is None


def test_knob_validation():
    with pytest.raises(ValueError, match="max_commit_lag"):
        DeepSpeedInferenceConfig(max_commit_lag=0)
    with pytest.raises(ValueError, match="prefill_chain"):
        DeepSpeedInferenceConfig(prefill_chain=True)
    # prefill_chain needs A chunked mode, either knob arms one
    DeepSpeedInferenceConfig(prefill_chain=True,
                             prefill_chunk_tokens=128)
    DeepSpeedInferenceConfig(prefill_chain=True,
                             enable_prefix_caching=True)
    with pytest.raises(ValueError, match="speculation_draft"):
        DeepSpeedInferenceConfig(speculation_draft=object(),
                                 speculation_tokens=0)
    with pytest.raises(ValueError, match="speculation_tokens"):
        ContinuousBatchingServer(make_engine(speculation_tokens=0),
                                 draft_engine=make_draft())


def test_config_fingerprint_skips_draft_engine_object():
    """speculation_draft holds a live engine — serialization surfaces
    (config_fingerprint, model_dump_json) must never choke on it."""
    cfg = DeepSpeedInferenceConfig(speculation_tokens=4,
                                   speculation_draft=make_draft())
    from deepspeed_tpu.telemetry.incident import config_fingerprint
    fp = config_fingerprint(cfg)
    assert isinstance(fp, str) and fp
    assert "speculation_draft" not in cfg.model_dump_json()


# --------------------------------------------------------- lag-N parity

def test_lag3_greedy_parity_single_executable(fresh_telemetry):
    """THE tentpole oracle: a depth-3 dispatch chain serves token-
    identical greedy output through the same ONE decode executable,
    and the chain demonstrably deepened past lag-1."""
    eng = make_engine(max_commit_lag=3)
    srv = ContinuousBatchingServer(eng)
    got = _serve(srv, PROMPTS, 6)
    assert got == eng.generate(PROMPTS, max_new_tokens=6)
    st = srv.stats
    assert st["async_loop"]["max_commit_lag"] == 3
    assert st["async_loop"]["commit_lag"] == 0        # drained
    assert st["decode_traces"] == 1
    assert st["retraces"] == 0
    # the profiler's depth histogram saw the chain deepen
    snap = srv._profiler.snapshot()["commit_lag"]
    assert snap["depth_max"] >= 2
    assert sum(snap["depth_hist"].values()) >= 1
    # deep-chain gaps ride depth 1 only (deeper dispatches land on a
    # provably busy device)
    assert set(snap["gap_s_by_depth"]) <= {"1"}


@pytest.mark.parametrize("lag", [2, 4])
def test_lag_matrix_outputs_identical_to_lag1(lag):
    """Commit lag changes WHEN tokens commit, never WHAT commits."""
    got = _serve(ContinuousBatchingServer(
        make_engine(max_commit_lag=lag)), PROMPTS[:5], 6)
    ref = _serve(ContinuousBatchingServer(make_engine()), PROMPTS[:5], 6)
    assert got == ref


def test_lag3_finishes_surface_late_and_garbage_discarded(
        fresh_telemetry):
    """A slot finishing mid-chain runs <= N-1 garbage rows; the idle
    flush discards them all, blocks return, and the flush-depth
    forensics record how deep the drained chain was."""
    eng = make_engine(num_slots=1, max_commit_lag=3)
    srv = ContinuousBatchingServer(eng)
    total = srv.scheduler.allocator.free_blocks
    ref = eng.generate([[1, 2, 3]], max_new_tokens=5)[0]
    rid = srv.submit([1, 2, 3], max_new_tokens=5)
    steps = 0
    while rid not in srv._results:
        srv.step()
        steps += 1
        assert steps < 50
    assert srv.result(rid) == ref          # no garbage token ever leaks
    srv.step()                             # idle poll flushes the chain
    st = srv.stats["async_loop"]
    assert st["commit_lag"] == 0
    assert st["garbage_steps"] >= 1
    assert st["flushes"].get("drain_tail", 0) >= 1
    depths = st["flush_depths"].get("drain_tail", {})
    assert depths and all(isinstance(k, str) for k in depths)
    assert srv.scheduler.allocator.free_blocks == total
    assert srv.scheduler.idle


# ---------------------------------------------------- lag-N chaos matrix

def _chaos_case(event, steps_before):
    """One chaos cell: a lag-3 server, fake clock, ``event`` landing
    after ``steps_before`` pipelined steps — the observable output must
    equal the one-shot oracle (prefix), with zero stranded blocks."""
    clock = FakeClock()
    eng = make_engine(num_slots=1, max_commit_lag=3)
    srv = ContinuousBatchingServer(eng, clock=clock)
    total = srv.scheduler.allocator.free_blocks
    ref = eng.generate([[1, 2, 3]], max_new_tokens=40)[0]
    a = srv.submit([1, 2, 3], max_new_tokens=40, deadline_s=(
        100.0 if event == "deadline" else None))
    for _ in range(steps_before):
        srv.step()
    if event == "cancel":
        committed = list(srv.scheduler.slots[0].generated)
        assert srv.cancel(a) is True
        assert srv.result(a) == ref[:3 + len(committed)]
        assert srv.finish_reason(a) == "cancelled"
    elif event == "deadline":
        committed = list(srv.scheduler.slots[0].generated)
        clock.advance(200.0)
        srv.step()                         # reaped at the boundary
        assert srv.finish_reason(a) == "deadline"
        # the reap flushes the chain first: the victim keeps its
        # committed prefix (possibly grown by the flush), still an
        # exact oracle prefix
        got = srv.result(a)
        assert got == ref[:len(got)]
        assert len(got) >= 3 + len(committed)
    elif event == "preempt":
        b = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
        out = srv.drain()
        assert out[a] == ref               # resumed, token-identical
        assert out[b] == eng.generate([[4, 5, 6]],
                                      max_new_tokens=4)[0]
        assert srv.stats["preempted"] >= 1
    else:                                  # bounded drain, immediate
        committed = list(srv.scheduler.slots[0].generated)
        out = srv.drain(timeout_s=0.0)
        assert srv.finish_reason(a) == "cancelled"
        got = out[a]
        assert got == ref[:len(got)]
        assert len(got) >= 3 + len(committed)
    srv.drain()
    assert srv.scheduler.idle
    assert srv.scheduler.allocator.free_blocks == total


@pytest.mark.parametrize("event", ["cancel", "deadline", "preempt",
                                   "drain"])
def test_lag3_chaos_reps(event, fresh_telemetry):
    """Fast-lane representative: each event at a mid-chain position
    (the chain is provably deep at step 3 with max_commit_lag=3)."""
    _chaos_case(event, steps_before=3)


@pytest.mark.parametrize("event", ["cancel", "deadline", "preempt",
                                   "drain"])
@pytest.mark.parametrize("steps_before", [1, 2, 4, 6])
def test_lag3_chaos_full_matrix(event, steps_before, fresh_telemetry):
    """The full chain-position sweep (slow lane): every event at every
    depth the chain passes through while filling and while full."""
    _chaos_case(event, steps_before)


# ------------------------------------------------- chained chunked prefill

def _prefill_chain_parity_case(n_prompts):
    prompts = [[(3 + 7 * i + j) % 120 + 1 for j in range(70 + 9 * i)]
               for i in range(n_prompts)]

    def run(chain):
        srv = ContinuousBatchingServer(make_engine(
            num_slots=2, prefill_chunk_tokens=32, prefill_chain=chain))
        got = _serve(srv, prompts, 6)
        return got, srv.stats

    got_on, st_on = run(True)
    got_off, st_off = run(False)
    assert got_on == got_off
    assert got_on == make_engine().generate(prompts, max_new_tokens=6)
    # same chunk programs ran — only their step scheduling changed
    assert st_on["prefill_chunks"] == st_off["prefill_chunks"]
    assert st_on["chunk_traces"] == 1
    assert st_on["retraces"] == 0
    assert st_on["async_loop"]["prefill_chain"] is True
    # ... and the chained chunks leave the device idle fewer times
    assert st_on["step_profile"]["dispatch_gap"]["count"] < \
        st_off["step_profile"]["dispatch_gap"]["count"]


def test_prefill_chain_parity_at_batch_size(fresh_telemetry):
    """Fast-lane representative of the BS sweep: parity exactly at the
    batch size (n_prompts == num_slots == 2)."""
    _prefill_chain_parity_case(2)


@pytest.mark.parametrize("n_prompts", [1, 3, 4])
def test_prefill_chain_parity_around_batch_size(n_prompts,
                                                fresh_telemetry):
    """BS-1 / BS+1 / 2*BS (num_slots=2; slow lane — BS itself is the
    fast representative above): chaining the non-final chunks changes
    dispatch granularity only — outputs byte-identical to the one-
    chunk-per-step server and to one-shot generate()."""
    _prefill_chain_parity_case(n_prompts)


def test_prefill_chain_dispatches_whole_chain_in_one_step(
        fresh_telemetry):
    """The mechanism pin: one step() advances the head job through ALL
    its non-final chunks (5-chunk prompt -> start lands on the final
    chunk), where the unchained server advances exactly one."""
    long_prompt = list(range(1, 130))      # 129 tokens = 5 chunks of 32
    srv = ContinuousBatchingServer(make_engine(
        num_slots=1, prefill_chunk_tokens=32, prefill_chain=True))
    srv.submit(long_prompt, max_new_tokens=3)
    srv.step()
    assert srv._prefilling[0]["start"] == 128   # 4 non-final chunks ran
    assert srv.stats["prefill_chunks"] == 4
    ref = ContinuousBatchingServer(make_engine(
        num_slots=1, prefill_chunk_tokens=32))
    ref.submit(long_prompt, max_new_tokens=3)
    ref.step()
    assert ref._prefilling[0]["start"] == 32    # one chunk per step
    # the whole chain realizes through ONE profiler dispatch note
    assert srv._profiler.outstanding == 1
    srv.drain()
    assert srv._profiler.outstanding == 0


def test_prefill_chain_composes_with_lag_and_prefix_cache(
        fresh_telemetry):
    """Composition bar: chained prefill + lag-2 chain + prefix caching
    vs the all-defaults server — byte-identical outputs."""
    prefix = [1 + (i % 90) for i in range(64)]
    prompts = [prefix + [3, 7, 11] * 4, prefix + [5, 9] * 6,
               [2, 4, 6, 8] * 8]

    def run(**kw):
        srv = ContinuousBatchingServer(make_engine(
            num_slots=2, enable_prefix_caching=True,
            prefill_chunk_tokens=32, max_out_tokens=128, **kw))
        return _serve(srv, prompts, 12)

    assert run(prefill_chain=True, max_commit_lag=2) == run()


# ------------------------------------------------- draft-model speculation

@pytest.mark.parametrize("tied", [False, True],
                         ids=["small-draft", "tied-draft"])
def test_draft_spec_greedy_parity_and_zero_new_target_executables(
        tied, fresh_telemetry):
    """Draft proposals feed the SAME paged verify: output token-
    identical to one-shot generate_speculative(draft=...) (and so to
    greedy generate), with the target pinned at one verify executable
    and zero retraces at any acceptance pattern: the small draft's
    proposals nearly all miss, those of a draft with the target's own
    weights all land, so the draft's mirrored pool and batched forwards
    carried the target's own next tokens into the verify."""
    K = 4
    eng = make_engine(speculation_tokens=K)
    draft = make_engine() if tied else make_draft()
    ref = make_engine().generate_speculative(
        PROMPTS[:6], draft=draft, max_new_tokens=12, draft_tokens=K)
    assert ref == make_engine().generate(PROMPTS[:6], max_new_tokens=12)
    srv = ContinuousBatchingServer(eng, draft_engine=draft)
    got = _serve(srv, PROMPTS[:6], 12)
    assert got == ref
    st = srv.stats
    sp = st["speculation"]
    assert sp["draft"] == "model"
    assert sp["verify_traces"] == 1        # zero NEW target executables
    assert st["retraces"] == 0
    assert sp["draft_decode_traces"] == 1  # one draft decode program
    assert sp["proposed"] == (K - 1) * srv._spec_slot_steps
    if tied:
        assert sp["accepted"] == sp["proposed"]
        assert sp["tokens_per_forward"] > 1.0
    else:
        assert sp["tokens_per_forward"] is not None


def test_draft_via_config_field_wires_server(fresh_telemetry):
    """The speculation_draft config knob wires the same object the
    draft_engine constructor arg would (cheap: no serving)."""
    draft = make_draft()
    eng = make_engine(speculation_tokens=3, speculation_draft=draft)
    srv = ContinuousBatchingServer(eng)
    assert srv.draft is draft


def test_draft_via_config_field_serves_parity(fresh_telemetry):
    """Serving through the config-field wiring matches greedy
    generate() (slow lane; the constructor-arg path is the fast
    parity representative)."""
    draft = make_draft()
    eng = make_engine(speculation_tokens=3, speculation_draft=draft)
    srv = ContinuousBatchingServer(eng)
    got = _serve(srv, PROMPTS[:3], 8)
    assert got == make_engine().generate(PROMPTS[:3], max_new_tokens=8)


def test_draft_spec_async_identical_to_sync(fresh_telemetry):
    """The async loop changes WHEN verify rounds commit, never WHAT —
    draft mode included."""
    draft = make_draft()

    def run(async_on):
        srv = ContinuousBatchingServer(
            make_engine(speculation_tokens=4, async_loop=async_on),
            draft_engine=draft)
        return _serve(srv, PROMPTS[:5], 10)

    assert run(True) == run(False)


def test_draft_spec_chaos_cancel_and_preempt(fresh_telemetry):
    """Lifecycle chaos through the draft path: cancel mid-speculation
    keeps an exact oracle prefix; preemption re-admission rebuilds the
    draft pool (full re-prefill) and stays token-identical."""
    draft = make_draft()
    eng = make_engine(num_slots=1, speculation_tokens=4)
    srv = ContinuousBatchingServer(eng, draft_engine=draft)
    total = srv.scheduler.allocator.free_blocks
    ref = make_engine().generate([[1, 2, 3]], max_new_tokens=30)[0]
    a = srv.submit([1, 2, 3], max_new_tokens=30)
    for _ in range(3):
        srv.step()
    committed = list(srv.scheduler.slots[0].generated)
    assert srv.cancel(a) is True
    assert srv.result(a) == ref[:3 + len(committed)]
    # preemption leg: low-pri victim resumed after a high-pri arrival
    b = srv.submit([1, 2, 3], max_new_tokens=10, priority=0)
    for _ in range(2):
        srv.step()
    c = srv.submit([4, 5, 6], max_new_tokens=4, priority=5)
    out = srv.drain()
    assert out[b] == ref[:3 + 10]
    assert out[c] == make_engine().generate([[4, 5, 6]],
                                            max_new_tokens=4)[0]
    assert srv.scheduler.allocator.free_blocks == total
    # every drained draft row is zeroed — nothing stranded device-side
    import numpy as np
    assert int(np.asarray(srv._draft_cache.lengths).sum()) == 0


def test_draft_spec_with_chunked_prefill_and_prefix_cache(
        fresh_telemetry):
    """Draft admission hooks BOTH prefill-completion sites: monolithic
    and final-chunk. Chunked + prefix-cached serving with a draft stays
    exactly greedy."""
    draft = make_draft()
    prefix = [1 + (i % 90) for i in range(64)]
    prompts = [prefix + [3, 7, 11] * 4, prefix + [5, 9] * 6]
    srv = ContinuousBatchingServer(make_engine(
        num_slots=2, speculation_tokens=3, enable_prefix_caching=True,
        prefill_chunk_tokens=32, max_out_tokens=128),
        draft_engine=draft)
    got = _serve(srv, prompts, 10)
    assert got == make_engine().generate(prompts, max_new_tokens=10)
    assert srv.stats["retraces"] == 0


# ----------------------------------------------------------- TP variants

def test_lag2_tp2_parity_single_trace():
    """tp=2 over the virtual CPU mesh at lag-2: chained device tokens
    re-enter the same compiled decode — parity AND one trace."""
    base = dict(vocab_size=128, n_positions=256, n_embd=32, n_layer=2,
                n_head=4, dtype=jnp.float32)
    cfg = InferenceTransformerConfig(**base)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tp_eng = InferenceEngine((cfg, params), DeepSpeedInferenceConfig(
        dtype="float32", max_out_tokens=256, block_size=32, num_slots=2,
        tensor_parallel={"tp_size": 2}, max_commit_lag=2))
    srv = ContinuousBatchingServer(tp_eng)
    got = _serve(srv, [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], 5)
    ref = _serve(ContinuousBatchingServer(make_engine(
        num_slots=2, async_loop=False)),
        [[1, 2, 3], [9, 8, 7, 6, 5], [4, 4]], 5)
    assert got == ref
    assert srv.stats["decode_traces"] == 1
    assert srv.stats["retraces"] == 0


# --------------------------------------------------------- stats surface

def test_deep_pipeline_stats_blob_shape(fresh_telemetry):
    """New stats keys are JSON-clean (str-keyed depth dicts) and the
    goodput debug payload carries the chain forensics."""
    srv = ContinuousBatchingServer(make_engine(max_commit_lag=2))
    a = srv.submit([1, 2, 3], max_new_tokens=20)
    for _ in range(3):
        srv.step()
    srv.cancel(a)
    blob = srv.stats["async_loop"]
    for k in ("max_commit_lag", "prefill_chain", "flush_depths"):
        assert k in blob, k
    import json
    assert json.loads(json.dumps(blob)) == blob
    assert blob["flushes"].get("cancel", 0) == 1
    assert blob["flush_depths"]["cancel"]            # depth recorded
    dbg = srv._goodput_snapshot()
    assert dbg["async_loop"]["max_commit_lag"] == 2
    assert json.loads(json.dumps(dbg["async_loop"])) == \
        dbg["async_loop"]
    sp = srv.stats["speculation"]
    assert sp["draft"] == "prompt-lookup"  # no draft engine wired
