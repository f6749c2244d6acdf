"""CPU tests of the readers of the program's own spans and names
(``benchmark/lib/program_spans.py`` and the eleven metric files over it):
against a synthetic span log, a hand-made trace, and a short trace
recorded on a TPU v5 lite from this PR's build by
``benchmark/tools/record_named_trace.py`` (named programs and kernels;
its span log, scope tables and the values the readers read ON THE CHIP
are in ``testdata/tiny_named_trace.json``). Counts and identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import harness, program_spans as ps  # noqa: E402
from benchmark.lib import trace_reduce as tr  # noqa: E402
from deepspeed_tpu.telemetry.spans import SpanLog, set_span_log  # noqa: E402

NEW = ("decode_kv_read_ms", "decode_kernel_ms", "decode_dispatch_gap_ms",
       "serve_goodput_pct", "admission_phase_p90_ms",
       "server_queue_wait_p90_ms", "request_prefill_p90_ms",
       "train_fwd_bwd_ms", "train_optimizer_ms", "trace_lower_s",
       "compile_cache_misses")


def reader(name):
    return harness.load_reader(name)


class Req:
    def __init__(self, rid):
        self.rid = rid


@pytest.fixture()
def log():
    fresh = SpanLog()
    prev = set_span_log(fresh)
    try:
        yield fresh
    finally:
        set_span_log(prev)


def test_the_contract_names_each_new_reader_and_its_file():
    contract = harness.load_contract()
    by = {m["name"]: m for m in contract["per_layer"]}
    assert set(NEW) <= set(by)
    # over whatever the contract lists now, not over its last entries
    layers = {m["layer"] for m in contract["per_layer"]
              if m["name"] not in NEW}
    cells = {w["name"] for w in contract["workloads"]}
    for name in by:
        assert os.path.isfile(os.path.join(BENCH, "metrics", name + ".py"))
        if name in NEW:
            assert by[name]["layer"] in layers      # a layer PERF.md has
        assert set(by[name].get("workloads", ())) <= cells
        assert by[name]["moves"] in {m["name"]
                                     for m in contract["end_to_end"]}


# ------------------------------------------------------- a synthetic log

def _step(log, key, t0, phases, **attrs):
    """One ``serve:step`` whose phase spans tile it."""
    sid = log.next_id()
    t = t0
    for name, dur in phases:
        log.record("serve:" + name, t, t + dur, parent=sid, key=key)
        t += dur
    log.record("serve:step", t0, t, key=key, span_id=sid, attrs=attrs)
    return t


def _request(log, rid, submit, queue, prefill, decode, requeue=None):
    sid = log.next_id()
    t = submit
    rounds = [(queue, prefill, decode)] + ([requeue] if requeue else [])
    for q, p, d in rounds:
        for name, dur in (("queue_wait", q), ("prefill", p), ("decode", d)):
            log.record("serve:" + name, t, t + dur, parent=sid, key=rid)
            t += dur
    log.record("serve:request", submit, t, key=rid, span_id=sid)


def test_span_readers_on_a_synthetic_log(log):
    t = 100.0
    for k in range(10):                 # every other step admits
        t = _step(log, k, t, [("admission", 0.010 * (k + 1) if k % 2
                               else 0.001), ("dispatch", 0.002),
                              ("sync_wait", 0.040), ("other", 0.001)],
                  admitted=k % 2, device_s=0.042, pipelined=False)
    _step(log, 99, t, [("admission", 0.5)], idle=True)     # a lull's poll
    _step(log, 100, 300.0, [("admission", 9.0)], admitted=1,
          device_s=0.0)                                    # outside
    for rid in range(5):
        _request(log, rid, 100.0 + rid, 0.010 * (rid + 1), 0.050, 1.0)
    _request(log, 5, 101.0, 0.010, 0.020, 0.5, requeue=(0.030, 0.025, 0.5))
    _request(log, 77, 100.0, 7.0, 7.0, 7.0)                # not counted
    run = {"kind": "serve", "t0": 99.0, "t1": 200.0,
           "counted": [Req(r) for r in range(6)]}
    # goodput: ten worked steps of the window, 42 ms device each
    wall = sum(0.043 + (0.010 * (k + 1) if k % 2 else 0.001)
               for k in range(10))
    assert reader("serve_goodput_pct")(run, None) == pytest.approx(
        100 * 0.42 / wall)
    # admission: the five admitting steps (20, 40 .. 100 ms)
    assert reader("admission_phase_p90_ms")(run, None) == pytest.approx(
        ps.p90([20.0, 40.0, 60.0, 80.0, 100.0]))
    # queue wait and prefill: per counted request, requeues added up
    assert reader("server_queue_wait_p90_ms")(run, None) == pytest.approx(
        ps.p90([10.0, 20.0, 30.0, 40.0, 50.0, 40.0]))
    assert reader("request_prefill_p90_ms")(run, None) == pytest.approx(
        ps.p90([50.0] * 5 + [45.0]))
    # a training run reads none of them
    for name in NEW[:7]:
        assert reader(name)({"kind": "train"}, None) is None


def test_readers_return_none_when_the_spans_are_absent(log, monkeypatch):
    run = {"kind": "serve", "t0": 0.0, "t1": 1.0, "counted": [Req(1)],
           "trace_t0": 0.0, "trace_steps": 2}
    for name in NEW[:7]:                # an empty log
        assert reader(name)(run, None) is None
    # a program with no span log, phase totals or tables at all (the
    # parent commit): nothing raises, nothing is reported
    monkeypatch.setattr(ps, "span_records", lambda prefix=None: None)
    monkeypatch.setattr(ps, "phase_totals", lambda: None)
    monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
    trace = tr.Reduced({0: {"ops": [("%x.1 = f32[] add()", 0.0, 1e-3)],
                            "modules": [("jit__unknown(1)", 0.0, 1e-3)]}},
                       [], window=(0.0, 1.0))
    for name in NEW:
        for kind in ("serve", "train"):
            assert reader(name)(dict(run, kind=kind), trace) is None


def test_compile_readers_read_the_phase_totals(monkeypatch):
    monkeypatch.setattr(ps, "phase_totals", lambda: {
        "serve_decode": {"trace_s": 3.0, "lower_s": 2.0, "compile_s": 5.0,
                         "cache_read_s": 0.0, "traces": 1, "compiles": 1,
                         "cache_hits": 0, "cache_misses": 1},
        "add": {"trace_s": 0.25, "lower_s": 0.0, "compile_s": 0.0,
                "cache_read_s": 0.5, "traces": 40, "compiles": 2,
                "cache_hits": 2, "cache_misses": 0}})
    assert reader("trace_lower_s")({}, None) == 5.25
    assert reader("compile_cache_misses")({}, None) == 1


# ---------------------------------------------------- a hand-made trace

def test_innermost_seconds_partition_the_busy_time():
    """Every busy instant goes to exactly one event, even where a child
    overruns its container or two events merely touch."""
    ops = [("while", 0.0, 10.0), ("a", 1.0, 4.0), ("b", 4.0, 10.5),
           ("c", 5.0, 6.0), ("d", 20.0, 21.0)]
    own = ps.innermost_seconds(ops)
    assert own == [1.0, 3.0, 5.5, 1.0, 1.0]
    assert sum(own) == tr.total(tr.merge((s, e) for _, s, e in ops))



def test_train_scopes_on_a_hand_made_trace(monkeypatch):
    """Two steps on two chips; the while's body runs under fwd_bwd, the
    copies under optimizer; self times, worst chip, per traced step."""
    table = {"while.1": "fwd_bwd", "fusion.2": "fwd_bwd/mlp",
             "flash_attention_fwd.3": "fwd_bwd/attn_kernel",
             "copy-done.4": "optimizer", "fusion.5": "optimizer",
             "add.6": None}
    monkeypatch.setattr(ps, "tables", lambda program: (
        (table, {"flash_attention_fwd.3": "flash_attention_fwd"})
        if program == "train_step" else ({}, {})))

    def chip(scale):
        ops, mods = [], []
        for k in range(2):
            t = 10.0 * k
            mods.append(("jit_train_step(9)", t, t + 6.0))
            ops += [("%while.1 = () while()", t, t + 3.0),
                    ("%fusion.2 = f32[] fusion()", t + 0.5, t + 1.5),
                    ("%flash_attention_fwd.3 = f32[] custom-call(), "
                     "custom_call_target=\"tpu_custom_call\"",
                     t + 1.5, t + 2.5),
                    ("%copy-done.4 = f32[] copy-done()", t + 3.0,
                     t + 3.0 + 2.0 * scale),
                    ("%fusion.5 = f32[] fusion()", t + 5.0, t + 5.5),
                    ("%add.6 = f32[] add()", t + 5.5, t + 6.0)]
        return {"ops": ops, "modules": mods}
    trace = tr.Reduced({0: chip(0.5), 1: chip(1.0)}, [],
                       window=(-1.0, 20.0))
    run = {"kind": "train", "trace_steps": 2}
    # the while's own second is fwd_bwd's too: 1 + 1 + 1
    assert reader("train_fwd_bwd_ms")(run, trace) == pytest.approx(3000.0)
    assert reader("train_optimizer_ms")(run, trace) == pytest.approx(2500.0)
    busy = trace.devices[1].busy_s / 2
    assert 3.0 + 2.5 <= busy + 1e-9     # attributed once, never twice
    assert reader("train_fwd_bwd_ms")({"kind": "serve"}, trace) is None


# ------------------------------------- the trace recorded on the chip

@pytest.fixture()
def recorded(monkeypatch):
    """The recorded trace with its span log, tables and run record."""
    with open(os.path.join(BENCH, "testdata",
                           "tiny_named_trace.json")) as fh:
        data = json.load(fh)
    fresh = SpanLog()
    fresh.extend([tuple(r) for r in data["spans"]])
    prev = set_span_log(fresh)
    monkeypatch.setattr(ps, "tables", lambda program: (
        data["tables"].get(program, {}).get("scopes", {}),
        data["tables"].get(program, {}).get("kernels", {})))
    trace = tr.read(os.path.join(BENCH, "testdata",
                                 "tiny_named_trace.xplane.pb"))
    run = dict(data["run"], counted=[Req(r) for r in data["run"]["rids"]])
    try:
        yield data, run, trace
    finally:
        set_span_log(prev)


def test_recorded_trace_carries_the_names(recorded):
    data, run, trace = recorded
    summary = trace.summary()
    assert {ps.program_of(p[0]) for p in summary["programs"]} == {
        "serve_decode"}
    assert [k[0] for k in summary["kernels"]] == ["paged_decode_attention"]
    assert "jit__unknown" not in json.dumps(summary)
    names = {n for n, _ in trace.device_ops(top=50)}
    assert "paged_decode_attention" in names and "_unknown_" not in names
    # the benchmark's own spans are there too, for the anchor
    assert {n for n, _, _ in trace.host_spans} >= {"bench:step"}


def test_recorded_trace_reads_what_the_chip_read(recorded):
    data, run, trace = recorded
    on_chip = data["read_on_the_chip"]
    for name in ("decode_kv_read_ms", "decode_kernel_ms",
                 "decode_dispatch_gap_ms", "serve_goodput_pct",
                 "server_queue_wait_p90_ms", "request_prefill_p90_ms"):
        assert reader(name)(run, trace) == pytest.approx(on_chip[name]), name
    assert on_chip["admission_phase_p90_ms"] is None   # nothing admitted
    assert reader("admission_phase_p90_ms")(run, trace) is None
    # the scope table attributes, it does not double count
    scopes = ps.decode_scopes(trace)
    runs = ps.executions(trace, "serve_decode")
    assert scopes["executions"] == len(runs) == 11
    program_ms = 1e3 * sorted(e - s for s, e in runs)[len(runs) // 2]
    assert (scopes["ms_by_scope"]["kv_read"]
            + ps.kernel_ms(trace, "serve_decode", "paged_decode_attention")
            <= program_ms)
    assert sum(scopes["ms_by_scope"].values()) <= program_ms * 1.05
    assert scopes["unknown_share_pct"] < 10.0
    # two layers, one kernel call each, in every execution
    per = ps.ops_by_execution(trace, "serve_decode")
    assert all(sum(1 for t, *_ in ops if ps.instruction(t).startswith(
        "paged_decode_attention")) == 2 for ops in per)


def test_the_anchor_maps_the_span_log_onto_the_trace(recorded):
    data, run, trace = recorded
    # bench:window opened at trace.lo; trace_t0 was read just after
    assert ps.to_trace(run, trace, run["trace_t0"]) == trace.lo
    assert ps.to_trace(run, trace, run["trace_t1"]) == pytest.approx(
        trace.hi, abs=2e-3)
    # every bench:step span holds one serve:step, mapped through it
    steps = [(ps.to_trace(run, trace, r[ps.START]),
              ps.to_trace(run, trace, r[ps.END]))
             for r in ps.span_records("serve:step")]
    outer = sorted((s, e) for n, s, e in trace.host_spans
                   if n == "bench:step")
    inside = [st for st in steps
              if any(a - 5e-4 <= st[0] and st[1] <= b + 5e-4
                     for a, b in outer)]
    assert len(outer) == 12 and len(inside) == 12
    gaps = ps.dispatch_gaps(run, trace)
    assert gaps["gaps"] == 10
    by = gaps["seconds_by_span"]
    assert set(by) <= {"serve:" + p for p in (
        "step", "admission", "prefill_chunk", "propose", "dispatch",
        "sync_wait", "commit", "publish", "other", "flush")} | {
        "_no_serve_span_"}
    runs = ps.executions(trace, "serve_decode")
    assert sum(by.values()) == pytest.approx(
        sum(b[0] - a[1] for a, b in zip(runs, runs[1:])))
    # the host sees a result after the device wrote it
    assert 0.0 <= gaps["program_end_to_sync_wait_end_ms"] < 50.0
