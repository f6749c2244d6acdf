"""``serve:program``: one span record for every device program the
server launches, written when the fetch that proves it finished returns
(``StepProfiler.program_launched`` / ``program_fetched``;
docs/observability.md "Spans").

* every launched program leaves exactly one record, in launch order;
* records never overlap and none starts before its program's dispatch;
* the bookkeeping sits BESIDE the step profile: ``outstanding`` drains,
  the goodput gauge stays a fraction, and ``tests/test_step_profile.py``
  / ``tests/test_async_loop.py`` pass unedited;
* with ``telemetry.step_profile`` off nothing is recorded;
* a fault inside the bookkeeping raises nothing, is counted in
  ``serve_program_span_errors_total`` and serves the clean run's tokens.

Host-pure cases on the fake clock, and the tiny servers
``tests/test_async_loop.py`` / ``tests/test_deep_pipeline.py`` build.
"""
import collections

import pytest

import test_async_loop as tal
import test_deep_pipeline as tdp
from deepspeed_tpu.inference import ContinuousBatchingServer
from deepspeed_tpu.telemetry import MetricRegistry, StepProfiler
from deepspeed_tpu.telemetry import step_profile as sprof
from deepspeed_tpu.telemetry.spans import (ATTRS, END, KEY, NAME, START,
                                           SpanLog, set_span_log)

ATTRIBUTES = {"program", "bucket", "rows", "prompt_tokens", "dispatched_in",
              "fetched_in", "depth", "waited"}
JITS = ("_prefill_jit", "_decode_jit", "_admit_jit", "_chunk_jit",
        "_verify_jit", "_draft_prefill_jit", "_draft_decode_jit")


@pytest.fixture()
def fresh():
    """A span log and a registry of this test's own."""
    from deepspeed_tpu.telemetry import (EventRing, set_event_ring,
                                         set_registry)
    log = SpanLog()
    prev_log = set_span_log(log)
    prev_reg = set_registry(MetricRegistry())
    prev_ring = set_event_ring(EventRing(512))
    try:
        yield log
    finally:
        set_span_log(prev_log)
        set_registry(prev_reg)
        set_event_ring(prev_ring)


def programs(log):
    return [r for r in log.snapshot() if r[NAME] == sprof.PROGRAM_SPAN]


def watch(srv, monkeypatch):
    """Every device program ``srv`` dispatches, by name and in order,
    and every launch its profiler was told of, with its clock reading."""
    calls, launches = [], []
    for attr in JITS:
        fn = getattr(srv, attr)
        if fn is None:
            continue

        def counted(*a, _fn=fn, **k):
            calls.append(_fn.name)
            return _fn(*a, **k)
        counted.name = fn.name
        counted._cache_size = fn._cache_size
        monkeypatch.setattr(srv, attr, counted)
    prof = srv._profiler
    if prof is not None:
        told = prof.program_launched

        def launched(program, now, *a, **k):
            launches.append((program, now))
            return told(program, now, *a, **k)
        monkeypatch.setattr(prof, "program_launched", launched)
    return calls, launches


def check(srv, log, calls, launches):
    """(a) one record a launched program, in launch order; (b) ordered,
    never overlapping, none before its dispatch; the step profile's own
    pairing drained."""
    recs = programs(log)
    left = list(srv._profiler._launched)
    assert [r[ATTRS]["program"] for r in recs] \
        + [e[1] for e in left] == calls
    # only what no fetch names on its own can be left behind
    assert all(e[1] in ("serve_prefill_chunk", "serve_draft_prefill",
                        "serve_draft_decode") for e in left)
    assert [p for p, _ in launches] == calls
    end = float("-inf")
    for r, (_, t) in zip(recs, launches):
        a = r[ATTRS]
        assert set(a) == ATTRIBUTES
        assert r[START] >= end and r[END] >= r[START]
        assert t is None or r[START] >= t
        assert a["waited"] is None or 0.0 <= a["waited"]
        assert r[KEY] == a["dispatched_in"] <= a["fetched_in"]
        assert a["depth"] >= 1
        end = r[END]
    assert srv._profiler.outstanding == 0
    assert srv._profiler._c_span_errors["launch"].value == 0
    assert srv._profiler._c_span_errors["fetch"].value == 0
    return recs


# name -> builder(**engine knobs) of a server, its prompts, one budget
def _gpt2(**knobs):
    return lambda **extra: (ContinuousBatchingServer(
        tal.make_engine(**knobs, **extra), clock=tal.FakeClock(auto=0.001)),
        tal.PROMPTS, 6)


def _family(case):
    def build(**extra):
        srv, prompts = tal._backlog_server(
            case, clock=tal.FakeClock(auto=0.001), **extra)
        return srv, prompts, None      # tal._BACKLOG_BUDGETS
    return build


def _chain(**extra):
    srv = ContinuousBatchingServer(tal.make_engine(
        num_slots=2, prefill_chunk_tokens=32, prefill_chain=True,
        max_commit_lag=2, **extra), clock=tal.FakeClock(auto=0.001))
    return srv, [list(range(1, 130)), [5, 6, 7], list(range(3, 100))], 5


def _draft(**extra):
    srv = ContinuousBatchingServer(
        tdp.make_engine(speculation_tokens=4, **extra),
        draft_engine=tdp.make_draft(), clock=tal.FakeClock(auto=0.001))
    return srv, tdp.PROMPTS[:6], 8


SERVERS = {
    "lag0": _gpt2(async_loop=False),
    "lag1": _gpt2(max_commit_lag=1),
    "lag3": _gpt2(max_commit_lag=3),
    "rider-lag1": _family("decode-lag1-latent"),
    "rider-lag3": _family("decode-lag3-latent"),
    "chunked-lag1": _family("decode-lag1-prefix-chunked"),
    "chunked-lag3": _family("decode-lag3-prefix-chunked"),
    "chunk-chain": _chain,
    "verify-lag0": _gpt2(async_loop=False, speculation_tokens=4),
    "verify-lag1": _family("verify-lag1-gpt2"),
    "verify-chunked": _family("verify-lag1-prefix-chunked"),
    "verify-draft": _draft,
}


def serve(srv, prompts, budget):
    budgets = tal._BACKLOG_BUDGETS if budget is None \
        else [budget] * len(prompts)
    ids = [srv.submit(p, max_new_tokens=b)
           for p, b in zip(prompts, budgets)]
    out = srv.drain()
    return [out[i] for i in ids]


@pytest.mark.parametrize("case", sorted(SERVERS))
def test_every_launched_program_leaves_one_ordered_record(
        fresh, monkeypatch, case):
    srv, prompts, budget = SERVERS[case]()
    calls, launches = watch(srv, monkeypatch)
    serve(srv, prompts, budget)
    recs = check(srv, fresh, calls, launches)
    by = collections.Counter(r[ATTRS]["program"] for r in recs)
    assert by["serve_decode"] + by["serve_spec_verify"] > 0
    for r in recs:
        a = r[ATTRS]
        prefills = a["program"] in (
            "serve_prefill", "serve_prefill_chunk", "serve_decode_admit",
            "serve_draft_prefill")
        assert (a["prompt_tokens"] > 0) == prefills, a
        assert (a["bucket"] is not None) == (
            prefills or a["program"] == "serve_spec_verify"), a
    if case.startswith("rider"):
        assert by["serve_decode_admit"] and not by["serve_prefill"]
        assert any(r[ATTRS]["rows"] and r[ATTRS]["prompt_tokens"]
                   for r in recs)        # a prompt rode decoding rows
    if case.startswith("chunk"):
        # a chunk no fetch waits for closes where the next one opens;
        # a prompt's last chunk is fetched where it was launched
        chunks = [r[ATTRS] for r in recs
                  if r[ATTRS]["program"] == "serve_prefill_chunk"]
        assert any(a["waited"] is None for a in chunks)
        assert sum(a["waited"] is not None for a in chunks) == len(prompts)
    if case == "verify-draft":
        assert by["serve_draft_decode"] == 4 * by["serve_spec_verify"]
        assert by["serve_draft_prefill"] == len(prompts)
    if case in ("lag1", "lag3"):
        lag = int(case[3:])
        assert max(r[ATTRS]["depth"] for r in recs) == lag + 1
        assert max(r[ATTRS]["fetched_in"] - r[ATTRS]["dispatched_in"]
                   for r in recs) == lag
    if case.endswith("lag0"):
        assert {r[ATTRS]["fetched_in"] - r[ATTRS]["dispatched_in"]
                for r in recs} == {0}
    # the goodput the step profile credits is still a fraction of wall
    snap = srv._profiler.snapshot()
    assert 0.0 < snap["goodput_fraction"] <= 1.0


def _cancel_mid_pipeline(srv):
    a = srv.submit([1, 2, 3], max_new_tokens=50)
    for _ in range(4):
        srv.step()
    assert srv.stats["async_loop"]["commit_lag"] >= 1
    assert srv.cancel(a)            # a flush BETWEEN steps
    b = srv.submit([5, 6, 7], max_new_tokens=3)
    return [srv.result(a), srv.drain()[b]]


def _cancel_mid_chain(srv):
    a = srv.submit(list(range(1, 97)), max_new_tokens=4)    # 3 chunks
    srv.step()                      # chunk 1 out, its fetch deferred
    assert srv._chunk_pending_t0 is not None
    assert srv.cancel(a)            # the slot is dropped mid-chain
    b = srv.submit([5, 6, 7], max_new_tokens=3)
    return [srv.drain()[b]]


def _preempt_mid_prefill(srv):
    prefix = [1 + (i % 90) for i in range(64)]
    ids = [srv.submit(prefix + [3, 7, 11] * 4, max_new_tokens=20),
           srv.submit(prefix + [5, 9] * 6, max_new_tokens=16)]
    srv.step()                      # both resident, the head mid-prefill
    assert srv._prefilling
    ids.append(srv.submit([2, 4, 6, 8] * 8, max_new_tokens=24, priority=5))
    out = srv.drain()
    assert srv.stats["preempted"] >= 1
    return [out[i] for i in ids]


def _close_undrained(srv):
    srv.submit([1, 2, 3], max_new_tokens=6)
    for _ in range(3):
        srv.step()
    srv.close()
    return [list(srv.scheduler.slots[0].generated)]


EDGES = {
    "flush-between-steps": (dict(num_slots=1), _cancel_mid_pipeline),
    "slot-dropped-mid-chain": (
        dict(num_slots=1, prefill_chunk_tokens=32), _cancel_mid_chain),
    "preemption-mid-prefill": (
        dict(num_slots=2, enable_prefix_caching=True, max_out_tokens=128),
        _preempt_mid_prefill),
    "close-undrained": (dict(num_slots=1), _close_undrained),
}


@pytest.mark.parametrize("edge", sorted(EDGES))
def test_edges_are_handled_by_rule(fresh, monkeypatch, edge):
    """A flush between steps, a slot dropped with chunks outstanding, a
    preemption mid-prefill, a close with a program in flight: each
    launched program still leaves its one record (a dropped slot's
    chunks are proven by the next fetch), and nothing is counted as a
    fault."""
    knobs, script = EDGES[edge]
    srv = ContinuousBatchingServer(tal.make_engine(**knobs),
                                   clock=tal.FakeClock(auto=0.001))
    calls, launches = watch(srv, monkeypatch)
    script(srv)
    recs = check(srv, fresh, calls, launches)
    if edge == "flush-between-steps":
        # the fetch between steps has no mark before it: its wait is
        # not known, its end is
        assert any(r[ATTRS]["waited"] is None
                   and r[ATTRS]["program"] == "serve_decode" for r in recs)
    if edge == "slot-dropped-mid-chain":
        first = recs[0][ATTRS]
        assert first["program"] == "serve_prefill_chunk"
        assert first["waited"] is None
        assert first["fetched_in"] > first["dispatched_in"]


@pytest.mark.parametrize("case", ["lag1", "rider-lag1", "chunk-chain",
                                  "verify-draft"])
def test_profile_off_records_nothing(fresh, case):
    on = serve(*SERVERS[case]())
    assert programs(fresh)
    fresh.clear()
    srv, prompts, budget = SERVERS[case](telemetry={"step_profile": False})
    assert srv._profiler is None
    assert serve(srv, prompts, budget) == on
    assert programs(fresh) == []


class _Faulty(collections.deque):
    """A FIFO whose every third append raises."""
    n = 0

    def append(self, item):
        type(self).n += 1
        if type(self).n % 3 == 0:
            raise RuntimeError("injected")
        super().append(item)


@pytest.mark.parametrize("site", ["launch", "fetch"])
@pytest.mark.parametrize("case", ["lag1", "rider-lag1", "chunk-chain",
                                  "verify-lag1"])
def test_a_fault_in_the_bookkeeping_ends_nothing(fresh, monkeypatch, case,
                                                 site):
    def run(broken):
        srv, prompts, budget = SERVERS[case]()
        prof = srv._profiler
        if broken and site == "launch":
            _Faulty.n = 0
            prof._launched = _Faulty()
        elif broken:
            real, n = prof.span_log.record, [0]

            def record(name, *a, **k):
                n[0] += name == sprof.PROGRAM_SPAN
                if name == sprof.PROGRAM_SPAN and n[0] % 2:
                    raise ValueError("injected")
                return real(name, *a, **k)
            monkeypatch.setattr(prof.span_log, "record", record)
        return serve(srv, prompts, budget), prof

    clean, _ = run(False)
    got, prof = run(True)
    assert got == clean
    assert prof._c_span_errors[site].value > 0
    other = "fetch" if site == "launch" else "launch"
    assert prof._c_span_errors[other].value == 0
    assert prof.outstanding == 0        # the step profile's own pairing


# ------------------------------------------------- the FIFO on its own

class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _profiler():
    log = SpanLog()
    return StepProfiler(registry=MetricRegistry(), clock=Clock(),
                        span_log=log), log


def test_start_is_the_later_of_dispatch_and_the_record_before():
    prof, log = _profiler()
    a = prof.program_launched("serve_decode", 0.0, rows=4)
    b = prof.program_launched("serve_decode", 1.0, rows=4)
    prof.program_fetched(a, 10.0, waited=9.0)
    c = prof.program_launched("serve_decode", 11.0, rows=4)
    prof.program_fetched(b, 20.0, waited=8.5)
    prof.program_fetched(c, 30.0, waited=0.0)      # a late host
    spans = [(r[START], r[END], r[ATTRS]["depth"], r[ATTRS]["waited"])
             for r in programs(log)]
    assert spans == [(0.0, 10.0, 1, 9.0), (10.0, 20.0, 2, 8.5),
                     (20.0, 30.0, 2, 0.0)]


def test_an_unwaited_program_closes_where_the_next_opens():
    prof, log = _profiler()
    prof.program_launched("serve_prefill_chunk", 1.0, 32, 0, 32)
    prof.program_launched("serve_prefill_chunk", 2.0, 32, 0, 32)
    d = prof.program_launched("serve_decode", 5.0, rows=2)
    late = prof.program_launched("serve_draft_prefill", None, 64, 0, 40)
    prof.program_fetched(d, 9.0, waited=3.0)
    assert [(r[START], r[END], r[ATTRS]["waited"])
            for r in programs(log)] == [
        (1.0, 2.0, None), (2.0, 5.0, None), (5.0, 9.0, 3.0)]
    e = prof.program_launched("serve_decode", 12.0, rows=2)
    prof.program_fetched(e, 15.0, waited=2.0)
    # no clock read of its own: it opens where the one before it closed
    assert [(r[START], r[END]) for r in programs(log)][3:] == [
        (9.0, 12.0), (12.0, 15.0)]
    assert late == d + 1


def test_fifo_rules_need_no_counter():
    """A fetch with nothing launched, a ticket proven already, ticket 0,
    a launch no fetch ever named (proven by a later one's)."""
    prof, log = _profiler()
    prof.program_fetched(0, 1.0)
    prof.program_fetched(7, 1.0)
    a = prof.program_launched("serve_prefill", 2.0, 64, 0, 50)
    b = prof.program_launched("serve_decode", 3.0, rows=1)
    prof.program_fetched(b, 6.0, waited=1.0)    # a's fetch never came
    prof.program_fetched(a, 7.0, waited=1.0)    # ... or came late
    prof.program_fetched(b, 8.0, waited=1.0)
    assert [(r[ATTRS]["program"], r[START], r[END], r[ATTRS]["waited"])
            for r in programs(log)] == [
        ("serve_prefill", 2.0, 3.0, None), ("serve_decode", 3.0, 6.0, 1.0)]
    assert not prof._launched
    assert all(c.value == 0 for c in prof._c_span_errors.values())


def test_a_fifo_nothing_proves_is_cleared_and_counted(monkeypatch):
    prof, log = _profiler()
    monkeypatch.setattr(sprof, "MAX_LAUNCHED", 4)
    tickets = [prof.program_launched("serve_decode", float(i))
               for i in range(6)]
    assert tickets[:4] == [1, 2, 3, 4] and tickets[4] == 0
    assert prof._c_span_errors["launch"].value == 1
    prof.program_fetched(tickets[5], 9.0, waited=0.5)
    assert len(programs(log)) == 1 and not prof._launched


def test_null_handle_takes_the_calls():
    assert sprof.NULL_STEP_HANDLE.program_fetched(3, 1.0, since=0.5) is None
