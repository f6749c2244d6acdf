"""CPU tests of the movement readers (``lib/movement_readers.py``) on
hand-made timelines: the two identities with the accepted readers,
``offload_duplex_pct`` 0 and 100, pairing of the k-th start with the k-th
done inside one execution, and what a program without a movement table
reads (nothing). Counts and identities only; not tier-1.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (harness, movement_readers as mr,  # noqa: E402
                           program_spans as ps, trace_reduce as tr)

H2D, D2H = "host_to_device", "device_to_host"


def row(kind, role, pair=None, nbytes=1e9, pass_=None, scopes=None):
    return {"kind": kind, "role": role, "bytes": nbytes,
            "wire_bytes": nbytes, "group": None, "calls": 1,
            "per_iteration": False, "pair": pair, "pass": pass_,
            "scopes": scopes}


def text(name, opcode):
    return f"%{name} = f32[8]{{0}} {opcode}(%x)"


def reduced(chips, window):
    """``chips``: per chip ``(executions [(start, end)], ops [(name,
    opcode, start, end)])`` -> a ``trace_reduce.Reduced``."""
    events = {k: {"modules": [(f"jit_train_step({k})", s, e)
                              for s, e in runs],
                  "ops": [(text(n, o), s, e) for n, o, s, e in ops]}
              for k, (runs, ops) in enumerate(chips)}
    return tr.Reduced(events, [(tr.WINDOW_SPAN, *window)])


@pytest.fixture()
def table(monkeypatch):
    """Installs a movement table (and no scope table) for the readers."""
    held = {}
    monkeypatch.setattr(mr, "movement_table", lambda program=mr.PROGRAM: held)
    monkeypatch.setattr(mr, "pass_table", lambda program=mr.PROGRAM: {})
    monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
    return held


def read(name, run, trace):
    return harness.load_reader(name)(run, trace)


OFFLOAD_TABLE = {
    "copy-start.1": row(H2D, "start", "copy-done.1", pass_="optimizer",
                        scopes="optimizer"),
    "copy-done.1": row(H2D, "done", "copy-start.1", pass_="optimizer",
                       scopes="optimizer"),
    "copy-start.2": row(D2H, "start", "copy-done.2", pass_="optimizer",
                        scopes="optimizer"),
    "copy-done.2": row(D2H, "done", "copy-start.2", pass_="optimizer",
                       scopes="optimizer")}


def offload_step(t, together):
    """One execution from ``t``: a fetch and a store of 1 GB each, a copy
    inside the device, some compute. ``together``: both transfers in
    flight over the same 0.1 s; else one after the other."""
    if together:
        # hand-made: the two starts (and the two dones) share an interval
        ops = [("copy-start.1", "copy-start", t, t + .01),
               ("copy-start.2", "copy-start", t, t + .01),
               ("fusion.1", "fusion", t + .01, t + .09),
               ("copy-done.1", "copy-done", t + .09, t + .10),
               ("copy-done.2", "copy-done", t + .09, t + .10)]
        end = t + .10
    else:
        ops = [("copy-start.1", "copy-start", t, t + .01),
               ("copy-done.1", "copy-done", t + .01, t + .10),
               ("copy-start.2", "copy-start", t + .10, t + .12),
               ("copy-done.2", "copy-done", t + .12, t + .20)]
        end = t + .20
    ops += [("copy-start.9", "copy-start", end, end + .005),
            ("copy-done.9", "copy-done", end + .005, end + .03),
            ("fusion.2", "fusion", end + .03, end + .05)]
    return (t, end + .05), ops


@pytest.mark.parametrize("together,duplex", [(False, 0.0), (True, 100.0)])
def test_offload_metrics_and_copy_identity(table, together, duplex, capsys):
    table.update(OFFLOAD_TABLE)
    runs, ops = [], []
    for k in range(2):                     # the same names, twice
        span, more = offload_step(1.0 + k * 0.5, together)
        runs.append(span)
        ops += more
    trace = reduced([(runs, ops)], (1.0, 2.0))
    run = {"kind": "train", "trace_steps": 2, "chips": 1}
    fetch = read("offload_fetch_wait_ms", run, trace)
    store = read("offload_store_wait_ms", run, trace)
    assert read("offload_duplex_pct", run, trace) == pytest.approx(duplex)
    if together:
        assert fetch + store == pytest.approx(20.0)
        in_flight = 0.10                    # seconds a step, either way
    else:
        assert (fetch, store) == (pytest.approx(100.0), pytest.approx(100.0))
        in_flight = 0.20
    assert read("offload_link_gb_per_s", run, trace) == \
        pytest.approx(2.0 / in_flight)
    # fetch + store + the copies inside the device = what copy_wait_pct
    # reads, per step
    line = [json.loads(ln)["movement"] for ln in
            capsys.readouterr().out.splitlines() if '"movement"' in ln]
    assert len(line) == 1                   # logged once, by the first reader
    ident = line[0]["identity"]
    assert line[0]["same_memory_ms"] == pytest.approx(30.0)
    assert ident["copies_ms"] == pytest.approx(fetch + store + 30.0)
    assert ident["copies_ms"] == pytest.approx(ident["copy_wait_ms"])
    assert ident["copy_wait_ms"] == pytest.approx(
        read("copy_wait_pct", run, trace) * trace.window_s * 10 / 2)
    by = {(r["kind"], r["pass"], r["scope"], r["as"]): r
          for r in line[0]["by_kind_pass_scope"]}
    got = by[(H2D, "optimizer", "optimizer", "async")]
    assert got["calls_per_step"] == 1 and got["bytes_per_step"] == 1e9
    assert got["in_flight_ms_per_step"] == pytest.approx(100.0)
    dirs = line[0]["link"]["by_direction"]
    assert dirs[H2D]["gb_per_s_in_flight"] == pytest.approx(10.0)
    # the x4 cell's metrics have nothing to read here
    assert read("gather_exposed_ms", run, trace) is None


X4_TABLE = {
    "all-gather.1": row("all-gather", "sync", pass_="fwd",
                        scopes="fwd_bwd/mlp"),
    "async-collective-start.3": row("all-gather", "start",
                                    "async-collective-done.3", pass_="bwd",
                                    scopes="fwd_bwd/mlp"),
    "async-collective-done.3": row("all-gather", "done",
                                   "async-collective-start.3", pass_="bwd",
                                   scopes="fwd_bwd/mlp"),
    "fusion.414": row("all-gather", "carrier", pass_="bwd",
                      scopes="fwd_bwd"),
    "all-reduce.2": row("all-reduce", "sync", pass_="bwd",
                        scopes="fwd_bwd/lm_head"),
    "all-to-all.4": row("all-to-all", "sync", pass_="fwd",
                        scopes="fwd_bwd"),
    "fusion.5": row("reduce-scatter", "fused", pass_="fwd",
                    scopes="fwd_bwd/mlp")}


def x4_step(t, slow):
    """One execution: ``slow`` x as long in its collectives."""
    f = slow
    ops = [("all-gather.1", "all-gather", t, t + .010 * f),
           ("async-collective-start.3", "fusion", t + .02, t + .021),
           ("fusion.414", "fusion", t + .021, t + .05),
           ("async-collective-done.3", "fusion", t + .05, t + .05 + .004 * f),
           ("all-reduce.2", "all-reduce", t + .06, t + .06 + .006 * f),
           ("all-to-all.4", "all-to-all", t + .075, t + .075 + .003 * f),
           # a collective the table does not know, and a fusion it does
           ("collective-permute-done.7", "collective-permute-done",
            t + .084, t + .086),
           ("fusion.5", "fusion", t + .09, t + .098)]
    return (t, t + .1), ops


def test_x4_metrics_split_what_collective_exposed_pct_reads(table, capsys):
    table.update(X4_TABLE)
    chips = []
    for slow in (1, 2, 1, 1):               # chip 1 waits longest
        runs, ops = zip(*(x4_step(1.0 + k * 0.2, slow) for k in range(3)))
        chips.append((list(runs), [o for more in ops for o in more]))
    trace = reduced(chips, (1.0, 1.6))
    run = {"kind": "train", "trace_steps": 3, "chips": 4}
    gather = read("gather_exposed_ms", run, trace)
    reduce = read("reduce_exposed_ms", run, trace)
    other = read("exchange_other_exposed_ms", run, trace)
    assert gather == pytest.approx(20.0 + 1.0 + 8.0)   # sync + both halves
    assert reduce == pytest.approx(12.0)      # the fused one is not in it
    assert other == pytest.approx(6.0 + 2.0)  # all-to-all + the unnamed one
    exposed = read("collective_exposed_pct", run, trace)
    assert gather + reduce + other == pytest.approx(
        exposed * trace.window_s * 10 / 3)
    line = [json.loads(ln)["movement"] for ln in
            capsys.readouterr().out.splitlines() if '"movement"' in ln][0]
    assert line["chip"] == 1 and line["unnamed_ms"] == pytest.approx(2.0)
    assert line["identity"]["collectives_ms"] == pytest.approx(
        line["identity"]["collective_exposed_ms"])
    by = {(r["kind"], r["as"]): r for r in line["by_kind_pass_scope"]}
    # what the accepted reader's names cannot see is logged, not counted
    assert by[("reduce-scatter", "fused")]["exposed_ms_per_step"] == \
        pytest.approx(8.0)
    assert by[("all-gather", "carrier")]["exposed_ms_per_step"] == \
        pytest.approx(29.0)
    # an async gather is in flight from its start to its done's end
    assert by[("all-gather", "async")]["in_flight_ms_per_step"] == \
        pytest.approx(38.0)
    assert by[("collective-permute-done", "unnamed")][
        "exposed_ms_per_step"] == pytest.approx(2.0)
    assert read("offload_fetch_wait_ms", run, trace) is None
    assert read("offload_duplex_pct", run, trace) is None


def test_a_program_without_a_movement_table_reads_nothing(table):
    (span, ops) = offload_step(1.0, True)
    trace = reduced([([span], ops)], (1.0, 2.0))
    run = {"kind": "train", "trace_steps": 1, "chips": 1}
    for name in ("offload_fetch_wait_ms", "offload_store_wait_ms",
                 "offload_duplex_pct", "offload_link_gb_per_s",
                 "gather_exposed_ms", "reduce_exposed_ms",
                 "exchange_other_exposed_ms"):
        assert read(name, run, trace) is None
        assert read(name, run, None) is None
        assert read(name, {"kind": "serve"}, trace) is None


def test_intersect_and_pairing_inside_one_execution():
    assert mr.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert mr.intersect([(0, 1)], [(1, 2)]) == []
    tbl = dict(OFFLOAD_TABLE)
    Op = ps.Op
    ops = [Op("", 0.0, 0.1, 0.1, "copy-start.1", None, ""),
           Op("", 0.2, 0.3, 0.1, "copy-start.1", None, ""),   # a loop body
           Op("", 0.4, 0.5, 0.1, "copy-done.1", None, ""),
           Op("", 0.6, 0.7, 0.1, "copy-done.1", None, "")]
    assert mr.in_flight(ops, tbl) == {
        "copy-start.1": [(0.0, 0.5), (0.2, 0.7)]}
