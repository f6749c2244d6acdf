"""CPU tests of the readers of the server's ``serve:program`` records
(``benchmark/lib/program_queue.py`` and the seven metric files over it)
on synthetic span logs: an admitting step with a hole, a late host, a
chain proven at a later step's fetch, a window edge that cuts a record,
and the populations that are empty (no log, a log without the records, a
window without an admission, a reader that raises). Counts and
identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import os
import sys
import types

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import harness, program_queue as pq  # noqa: E402
from deepspeed_tpu.telemetry.spans import SpanLog, set_span_log  # noqa: E402

BACKLOG = ("serve_refill_share_pct", "serve_prefill_program_ms_per_ktok",
           "serve_admission_idle_ms", "serve_program_span_skew_pct")
CHAT = ("chat_prefill_program_ms_per_ktok", "chat_admission_idle_ms",
        "chat_program_span_skew_pct")
NEW = BACKLOG + CHAT
LAYER = "server host loop (inference/server.py, scheduler.py)"
RUN = {"kind": "serve", "t0": 0.0, "t1": 100.0}


def reader(name):
    return harness.load_reader(name)


@pytest.fixture()
def log():
    fresh = SpanLog()
    prev = set_span_log(fresh)
    try:
        yield fresh
    finally:
        set_span_log(prev)


def step(log, key, a, b, **attrs):
    log.record("serve:step", a, b, key=key, attrs=attrs or None)


def program(log, name, a, b, key=0, bucket=None, rows=0, prompt_tokens=0,
            depth=1, waited=0.0, fetched_in=None):
    log.record("serve:program", a, b, key=key, attrs={
        "program": name, "bucket": bucket, "rows": rows,
        "prompt_tokens": prompt_tokens, "dispatched_in": key,
        "fetched_in": key if fetched_in is None else fetched_in,
        "depth": depth, "waited": waited})


def trace_of(executions, lo=0.0, hi=None, busy=None):
    """What a reader asks of a reduced trace: chip 0's program
    executions, the window and the busy seconds."""
    hi = max(e for _, _, e in executions) if hi is None else hi
    dev = types.SimpleNamespace(modules=list(executions))
    return types.SimpleNamespace(
        devices=[dev], lo=lo, hi=hi, window_s=hi - lo,
        busy_s=sum(e - s for _, s, e in executions)
        if busy is None else busy)


def test_the_contract_names_each_new_reader_its_file_and_its_cells():
    contract = harness.load_contract()
    by = {m["name"]: m for m in contract["per_layer"]}
    cells = {w["name"]: w for w in contract["workloads"]}
    e2e = {m["name"]: m for m in contract["end_to_end"]}
    assert set(NEW) <= set(by)
    for name in NEW:
        m = by[name]
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
        assert m["unit"] and m["better"] == "lower"
        assert m["source"] == "program_span" and m["layer"] == LAYER
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        # each listed cell reports the end-to-end metric this one moves
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]]["workloads"]
            assert cell.startswith("serve-")
    serving = {c for c in cells if c.startswith("serve-")}
    assert set(by["serve_program_span_skew_pct"]["workloads"]) \
        | set(by["chat_program_span_skew_pct"]["workloads"]) == serving
    assert not any("longcat" in c for c in
                   by["serve_prefill_program_ms_per_ktok"]["workloads"])


# ------------------------------------------------- the empty populations

@pytest.mark.parametrize("name", NEW)
def test_empty_populations_read_none(log, name, monkeypatch):
    """No log, an empty log, a log with no ``serve:program`` record, a
    window with no worked step or no admission, a training run, a
    traced run without a trace window: None, and nothing raises."""
    read = reader(name)
    trace = trace_of([("jit_serve_decode(1)", 0.0, 1.0)])
    traced = dict(RUN, trace_t0=0.0, trace_t1=50.0)
    for run in (RUN, traced, {"kind": "train"}, dict(RUN, trace_t0=None)):
        assert read(run, None) is None
        assert read(run, trace) is None                  # an empty log
    for k in range(4):                # the parent's log: steps, no record
        step(log, k, 10.0 * k, 10.0 * k + 9.0, admitted=k % 2)
        log.record("serve:sync_wait", 10.0 * k, 10.0 * k + 8.0, key=k)
    assert read(traced, trace) is None
    # records, but none a prompt's, none waited for, no admission
    log.clear()
    for k in range(4):
        step(log, k, 10.0 * k, 10.0 * k + 9.0, admitted=0)
        program(log, "serve_decode", 10.0 * k, 10.0 * k + 8.0, key=k,
                rows=4, waited=None)
    if name == "serve_refill_share_pct":
        assert read(traced, trace) == 0.0    # a window that refilled nothing
    else:
        assert read(traced, trace) is None
    # a window that holds no step at all
    assert read(dict(traced, t0=500.0, t1=600.0, trace_t0=500.0,
                     trace_t1=600.0), trace) is None
    # no span log to read
    from benchmark.lib import program_spans as ps
    monkeypatch.setattr(ps, "span_records", lambda prefix=None: None)
    assert read(traced, trace) is None


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_raises_reads_none(log, name, monkeypatch, capsys):
    step(log, 0, 0.0, 9.0, admitted=1)
    program(log, "serve_prefill", 1.0, 2.0, prompt_tokens=10, bucket=16)

    def boom(*a, **k):
        raise ZeroDivisionError("injected")
    for fn in ("refill_share_pct", "prefill_program_ms_per_ktok",
               "admission_idle_ms", "span_skew_pct"):
        monkeypatch.setattr(pq, fn, boom)
    assert reader(name)(RUN, None) is None
    assert "reader_failed" in capsys.readouterr().out


# --------------------------------------------------------- the readers

def test_refill_share_counts_the_steps_a_prompt_reaches_into(log):
    # five worked steps of 1, 1, 3, 1, 2 s and an idle poll
    for k, (a, b) in enumerate([(0, 1), (1, 2), (2, 5), (5, 6), (6, 8)]):
        step(log, k, float(a), float(b), admitted=int(k == 2))
    step(log, 9, 8.0, 20.0, idle=True)
    program(log, "serve_decode", 0.1, 0.9, key=0, rows=4)
    program(log, "serve_decode", 1.1, 2.0, key=1, rows=4, depth=2)
    # step 2 flushed, prefilled (a hole before and after), decoded
    program(log, "serve_prefill", 2.5, 4.0, key=2, bucket=64,
            prompt_tokens=50, waited=1.5)
    program(log, "serve_decode", 4.2, 4.9, key=2, rows=4)
    program(log, "serve_decode", 5.1, 5.9, key=3, rows=4)
    program(log, "serve_decode", 6.1, 7.9, key=4, rows=4)
    read = reader("serve_refill_share_pct")
    assert read(RUN, None) == pytest.approx(100.0 * 3 / 8)
    # a rider: the decode program carried a prompt in step 4
    program(log, "serve_decode_admit", 7.9, 8.0, key=4, bucket=64, rows=4,
            prompt_tokens=20)
    assert read(RUN, None) == pytest.approx(100.0 * 5 / 8)
    # a window edge that cuts the record: only steps ending in the
    # window count, and the record still reaches the one it overlaps
    assert read(dict(RUN, t0=4.5, t1=6.5), None) == pytest.approx(
        100.0 * 3 / 4)
    assert read(dict(RUN, t0=5.5, t1=6.5), None) == 0.0


def test_a_chain_proven_at_a_later_steps_fetch_reaches_both_steps(log):
    """Non-final chunks launched in step 0 and proven by step 1's fetch
    (a slot dropped mid-chain, a lagged round): the record straddles the
    boundary and both steps ran a prompt."""
    for k in range(4):
        step(log, k, float(k), k + 1.0)
    program(log, "serve_prefill_chunk", 0.2, 0.4, key=0, bucket=32,
            prompt_tokens=32, waited=None)
    program(log, "serve_prefill_chunk", 0.4, 1.5, key=0, bucket=32,
            prompt_tokens=32, waited=None, fetched_in=1)
    program(log, "serve_decode", 1.5, 1.9, key=1, rows=2)
    program(log, "serve_decode", 2.1, 2.9, key=2, rows=2)
    # an empty record (closed where the next opened) inside step 3
    program(log, "serve_prefill_chunk", 3.5, 3.5, key=3, bucket=32,
            prompt_tokens=7, waited=None)
    assert reader("serve_refill_share_pct")(RUN, None) == \
        pytest.approx(75.0)
    # none of them was waited for at depth 1: no prefill cost to read
    assert reader("serve_prefill_program_ms_per_ktok")(RUN, None) is None


@pytest.mark.parametrize("name", ["serve_prefill_program_ms_per_ktok",
                                  "chat_prefill_program_ms_per_ktok"])
def test_prefill_cost_is_seconds_over_prompt_tokens(log, name, capsys):
    step(log, 0, 0.0, 50.0)
    program(log, "serve_prefill", 1.0, 1.5, bucket=1024, prompt_tokens=1000)
    program(log, "serve_prefill", 2.0, 2.3, bucket=512, prompt_tokens=500)
    program(log, "serve_prefill_chunk", 3.0, 3.2, bucket=512,
            prompt_tokens=500)
    # not counted: behind another program, not waited for, a rider, a
    # decode, one that ended after the window closed
    program(log, "serve_prefill", 4.0, 9.0, bucket=512, prompt_tokens=1,
            depth=2)
    program(log, "serve_prefill_chunk", 10.0, 19.0, bucket=512,
            prompt_tokens=1, waited=None)
    program(log, "serve_decode_admit", 20.0, 29.0, bucket=512, rows=3,
            prompt_tokens=1)
    program(log, "serve_decode", 30.0, 39.0, rows=3)
    program(log, "serve_prefill", 99.0, 101.0, bucket=512, prompt_tokens=1)
    assert reader(name)(RUN, None) == pytest.approx(1e3 * 1.0 / 2.0)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if "prefill_programs" in ln][-1]
    assert '"512": {"programs": 2, "prompt_tokens": 1000' in line
    assert '"programs": 3' in line


@pytest.mark.parametrize("name", ["serve_admission_idle_ms",
                                  "chat_admission_idle_ms"])
def test_admission_idle_is_what_no_record_covers(log, name):
    # step 0 admits 2: a hole of 0.5 s before the prefill, 0.25 after
    step(log, 0, 0.0, 4.0, admitted=2)
    program(log, "serve_prefill", 0.5, 2.0, bucket=64, prompt_tokens=60)
    program(log, "serve_decode", 2.25, 4.0, rows=4, waited=0.0)  # late host
    # step 1 admits nothing: its hole is not an admission's
    step(log, 1, 4.0, 6.0, admitted=0)
    program(log, "serve_decode", 5.0, 6.0, key=1, rows=4)
    # step 2 admits 1 under a record launched in step 1 (cut at the edge)
    step(log, 2, 6.0, 8.0, admitted=1)
    program(log, "serve_decode", 6.0, 7.5, key=1, rows=4, depth=2,
            fetched_in=2)
    assert reader(name)(RUN, None) == pytest.approx(
        1e3 * (0.5 + 0.25 + 0.5) / 3)


@pytest.mark.parametrize("name", ["serve_program_span_skew_pct",
                                  "chat_program_span_skew_pct"])
def test_skew_is_the_records_median_against_the_executions(log, name,
                                                           capsys):
    execs = []
    for k in range(10):
        step(log, k, 100.0 + k, 101.0 + k)
        # a waited record 1 % longer than its execution
        program(log, "serve_decode", 100.0 + k, 100.0 + k + 0.909, key=k,
                rows=4, depth=2)
        execs.append(("jit_serve_decode(7)", k + 0.0, k + 0.9))
    # a prompt's program fetched where it was launched: twice as long
    program(log, "serve_prefill", 110.0, 110.4, key=10, bucket=64,
            prompt_tokens=60)
    execs.append(("jit_serve_prefill(9)", 10.1, 10.3))
    # not waited for: counted, its length is not an execution's
    program(log, "serve_decode", 110.4, 110.5, key=10, waited=None)
    # cut by the window's edge: left out
    program(log, "serve_decode", 110.9, 111.2, key=11)
    run = dict(RUN, trace_t0=100.0, trace_t1=111.0)
    got = reader(name)(run, trace_of(execs, hi=11.0))
    assert got == pytest.approx(100.0 * (0.909 - 0.9) / 0.9)
    out = capsys.readouterr().out
    line = [ln for ln in out.splitlines() if "program_span_skew" in ln][-1]
    assert '"records": 11, "executions": 10' in line
    assert '"serve_prefill": {"records": 1, "executions": 1' in line
    assert "serve_prefill_chunk" not in line
    # no trace, or no execution of the decode program in it: None
    assert reader(name)(run, None) is None
    assert reader(name)(run, trace_of(
        [("jit_serve_prefill(9)", 10.1, 10.3)], hi=11.0)) is None


def test_helpers_on_edges():
    steps = [("serve:step", 0.0, 1.0, 0, 1, 0, None),
             ("serve:step", 1.0, 2.0, 0, 2, 1, None)]
    rec = ("serve:program", 1.0, 1.0, 0, 3, 0, {"prompt_tokens": 1})
    # an empty record on a shared edge belongs to the step it opens in
    assert pq.reached(steps, [rec]) == {1}
    assert pq.reached(steps, []) == set()
    assert pq.covered_seconds(steps[0], [], []) == 0.0
    assert pq.program_records([]) == []
    assert pq.attr(("x", 0, 1, 0, 0, 0, None), "waited") is None
