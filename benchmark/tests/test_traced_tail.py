"""CPU tests of what keeps a traced run inside its time at any speed of
the server: the shared reduction (every by-name reader reads through it
what the code before it read, to the digit: that code lives on here, as
``old_*``), the cap on the executions the readers walk, the rule that a
known scope no instruction carries reads 0.0, and the ceilings the
serving loops keep by themselves. Counts and identities only.

    python -m pytest benchmark/tests -q -p no:cacheprovider
"""
import bisect
import collections
import json
import os
import sys
import time
from statistics import median

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH))

from benchmark.lib import (harness, longcat_readers as lr,  # noqa: E402
                           program_spans as ps, serve_cell,
                           trace_reduce as tr, trace_select)
from deepspeed_tpu.telemetry.spans import SpanLog, set_span_log  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "tiny_named_trace.xplane.pb")


def reader(name):
    return harness.load_reader(name)


class Req:
    def __init__(self, rid):
        self.rid = rid


@pytest.fixture()
def recorded(monkeypatch):
    """The trace recorded on the chip with its span log, tables and run
    record (``testdata/tiny_named_trace.*``)."""
    with open(TRACE.replace(".xplane.pb", ".json")) as fh:
        data = json.load(fh)
    fresh = SpanLog()
    fresh.extend([tuple(r) for r in data["spans"]])
    prev = set_span_log(fresh)
    monkeypatch.setattr(ps, "tables", lambda program: (
        data["tables"].get(program, {}).get("scopes", {}),
        data["tables"].get(program, {}).get("kernels", {})))
    run = dict(data["run"], counted=[Req(r) for r in data["run"]["rids"]])
    # what the roofline readers ask of a run: the host's step records
    # (t_start, t_end, live slots, live positions), shapes and peaks
    t0, t1 = run["trace_t0"], run["trace_t1"]
    run.update(
        steps=[(t0 + (t1 - t0) * k / 12, t0 + (t1 - t0) * (k + 1) / 12,
                2, 100 + 2 * k) for k in range(12)],
        shapes={"kv_heads": 4, "head_dim": 16, "itemsize": 2},
        peaks={"hbm_bytes_per_s": 819e9})
    try:
        yield data, run
    finally:
        set_span_log(prev)


# --------------------------------------------- the code path before PR 30

def old_ops_by_execution(trace, program, device=0):
    dev = trace.devices[device]
    runs = ps.executions(trace, program, device)
    starts = [s for s, _ in runs]
    out = [[] for _ in runs]
    for op in dev.ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= runs[i][1] + 1e-9:
            out[i].append(op)
    for k, ops in enumerate(out):
        out[k] = [(t, s, e, own) for (t, s, e, _), own
                  in zip(ops, ps.innermost_seconds(ops))]
    return out


def old_scope_seconds(ops, table):
    acc = collections.defaultdict(float)
    for text, _, _, own in ops:
        sc = table.get(ps.instruction(text))
        acc[sc.rsplit("/", 1)[-1] if sc else ps.UNKNOWN] += own
    return dict(acc)


def old_in_scope(ops, table, scope):
    total = 0.0
    for text, _, _, own in ops:
        sc = table.get(ps.instruction(text))
        if sc and scope in sc.split("/"):
            total += own
    return total


def old_is_kernel(program, kernel):
    _, kernels = ps.tables(program)
    return lambda text: kernels.get(ps.instruction(text),
                                    tr.op_name(text)) == kernel


def old_kernel_calls(trace, program, kernel):
    is_it = old_is_kernel(program, kernel)
    return [(s, e) for ops in old_ops_by_execution(trace, program)
            for t, s, e, _ in ops if is_it(t)]


def old_read(name, run, trace):
    """What ``metrics/<name>.py`` read before the shared reduction."""
    table, named = ps.tables("serve_decode")
    runs = old_ops_by_execution(trace, "serve_decode")
    if name == "decode_kv_read_ms":
        per = [old_scope_seconds(ops, table) for ops in runs]
        return 1e3 * median(p.get("kv_read", 0.0) for p in per)
    if name == "decode_kernel_ms":
        is_it = old_is_kernel("serve_decode", "paged_decode_attention")
        return 1e3 * median(sum(e - s for t, s, e, _ in ops if is_it(t))
                            for ops in runs)
    if name in ("decode_program_ms", "chat_decode_program_ms",
                "longcat_decode_program_ms"):
        return median((e - s) * 1e3
                      for s, e in ps.executions(trace, "serve_decode"))
    if name == "paged_decode_roofline":
        from benchmark.lib import flops
        s = run["shapes"]
        calls = old_kernel_calls(trace, "serve_decode",
                                 "paged_decode_attention")
        steps = [st for st in trace_select.traced_steps(run) if st[2] > 0]
        live = sum(st[3] for st in steps) / len(steps)
        need = len(calls) * flops.paged_decode_bytes(
            live, s["kv_heads"], s["head_dim"], s["itemsize"]
        ) / run["peaks"]["hbm_bytes_per_s"]
        return 100.0 * need / sum(e - b for b, e in calls)
    if name.startswith("group:"):
        scopes = name[len("group:"):].split("+")
        return 1e3 * median(sum(old_in_scope(ops, table, s) for s in scopes)
                            for ops in runs)
    raise KeyError(name)


@pytest.mark.parametrize("name", [
    "decode_kv_read_ms", "decode_kernel_ms", "decode_program_ms",
    "chat_decode_program_ms", "longcat_decode_program_ms",
    "paged_decode_roofline", "group:attn_qkv+mlp", "group:kv_read",
    "group:attn_kernel+attn_out+lm_head"])
def test_every_by_name_reader_reads_the_same_through_the_shared_reduction(
        recorded, name):
    data, run = recorded
    trace = tr.read(TRACE, cap_programs=("serve_decode",))
    if name.startswith("group:"):
        new = lr.scope_group_ms(trace, name[len("group:"):].split("+"))
    else:
        new = reader(name)(run, trace)
    old = old_read(name, run, tr.read(TRACE))
    assert new is not None and new == old      # to every digit
    if name in data["read_on_the_chip"]:
        assert new == pytest.approx(data["read_on_the_chip"][name])


def test_the_reduction_is_built_once_for_every_reader(recorded, monkeypatch):
    data, run = recorded
    trace = tr.read(TRACE)
    calls = []
    inner = ps.innermost_seconds
    monkeypatch.setattr(ps, "innermost_seconds",
                        lambda ops: calls.append(1) or inner(ops))
    for name in ("decode_kv_read_ms", "decode_kernel_ms",
                 "paged_decode_roofline", "decode_program_ms"):
        assert reader(name)(run, trace) is not None
    lr.scope_group_ms(trace, ("mlp",))
    # one pass per execution of the program, however many readers
    assert len(calls) == len(ps.executions(trace, "serve_decode")) == 11
    ops = ps.ops_by_execution(trace, "serve_decode")
    assert ops is ps.ops_by_execution(trace, "serve_decode")
    assert all(op.name == ps.instruction(op.text) for op in ops[0])
    assert {op.kernel for op in ops[0]} >= {"paged_decode_attention"}
    assert {op.scope for op in ops[0]} >= {"kv_read", None}


# ------------------------------------------------------------------- the cap

def _synthetic(executions, program="serve_decode", other=0):
    """``executions`` runs of ``jit_<program>`` of 10 ms every 12 ms, three
    instructions each, and ``other`` runs of a one-op program between."""
    ops, mods = [], []
    for k in range(executions):
        t = 0.012 * k
        mods.append((f"jit_{program}(7)", t, t + 0.010))
        ops += [(f"%slice.{k} = f32[] slice()", t, t + 0.004),
                (f"%fusion.{k} = f32[] fusion()", t + 0.004, t + 0.009),
                (f"%add.{k} = f32[] add()", t + 0.009, t + 0.010)]
    for k in range(other):
        t = 0.012 * k + 0.0105
        mods.append(("jit_convert_element_type(3)", t, t + 0.001))
        ops.append((f"%convert.{k} = f32[] convert()", t, t + 0.001))
    return {0: {"ops": ops, "modules": mods}}


N = tr.MAX_EXECUTIONS


@pytest.mark.parametrize("case", ["recorded", "n", "4n", "unnamed", "uncapped"])
def test_the_execution_cap(case, recorded):
    data, run = recorded
    if case == "recorded":
        # fewer executions than the cap: untouched, to the digit
        plain, capped = tr.read(TRACE), tr.read(TRACE, ("serve_decode",))
        assert capped.cut_s == 0 and (capped.lo, capped.hi) == (plain.lo,
                                                                plain.hi)
        assert capped.busy_s == plain.busy_s
        assert capped.device_ops() == plain.device_ops()
        for name in ("decode_kv_read_ms", "decode_kernel_ms"):
            assert reader(name)(run, capped) == reader(name)(run, plain)
        return
    window = (-0.001, 0.012 * 4 * N + 1.0)
    if case == "n":
        trace = tr.Reduced(_synthetic(N), [], window, ("serve_decode",))
        assert trace.cut_s > 0         # the window ends with the N-th...
        assert len(ps.executions(trace, "serve_decode")) == N   # ...whole
        return
    if case == "uncapped":
        trace = tr.Reduced(_synthetic(4 * N), [], window)
        assert trace.cut_s == 0
        assert len(ps.executions(trace, "serve_decode")) == 4 * N
        return
    if case == "unnamed":
        # a one-op program nobody reads runs 4 N times: no cut
        trace = tr.Reduced(_synthetic(N - 1, other=4 * N), [], window,
                           ("serve_decode", "serve_prefill"))
        assert trace.cut_s == 0
        assert len(ps.executions(trace, "convert_element_type")) == 4 * N
        return
    trace = tr.Reduced(_synthetic(4 * N, other=4 * N), [], window,
                       ("serve_prefill", "serve_decode"))
    runs = ps.executions(trace, "serve_decode")
    assert len(runs) == N and runs[0][0] == 0.0
    assert runs[-1] == (pytest.approx(0.012 * (N - 1)),
                        pytest.approx(0.012 * (N - 1) + 0.010))
    per = ps.ops_by_execution(trace, "serve_decode")
    assert [len(ops) for ops in per] == [3] * N
    assert per[-1][0].name == f"slice.{N - 1}"
    # everything follows the cut: the window, the busy time, the others
    assert trace.window_s == pytest.approx(0.012 * (N - 1) + 0.011)
    assert trace.cut_s == pytest.approx(window[1] - trace.hi)
    assert len(ps.executions(trace, "convert_element_type")) == N - 1
    assert trace.busy_s == pytest.approx(N * 0.010 + (N - 1) * 0.001)
    assert trace.traced_executions()["jit_serve_decode"] == [4 * N, N]


# --------------------------------- gone is 0.0, missing is nothing

SCOPED = {"slice": "kv_read", "fusion": "mlp", "add": None}


def _tables(with_cut=True, kernel=False):
    def tables(program):
        if program not in ("serve_decode", "train_step"):
            return {}, {}
        table = {}
        for k in range(8):
            table[f"slice.{k}"] = "kv_read" if with_cut else "attn_kernel"
            table[f"fusion.{k}"] = "mlp" if program == "serve_decode" \
                else "fwd_bwd/mlp"
            table[f"add.{k}"] = None
        named = {f"fusion.{k}": "paged_decode_attention"
                 for k in range(8)} if kernel else {}
        return table, named
    return tables


@pytest.mark.parametrize("case", [
    "cut", "no_cut", "never_ran", "no_table", "no_trace", "unknown_scope",
    "kernel_missing", "train_gone", "train_never_ran", "group_gone"])
def test_a_known_scope_no_instruction_carries_reads_zero(case, monkeypatch):
    run = {"kind": "serve", "trace_steps": 4}
    trace = tr.Reduced(_synthetic(4), [], (-0.001, 1.0))
    kv = reader("decode_kv_read_ms")
    if case == "cut":                 # today's program: the cut is timed
        monkeypatch.setattr(ps, "tables", _tables(with_cut=True))
        assert kv(run, trace) == pytest.approx(4.0)
    elif case == "no_cut":            # decode attends the pool in place
        monkeypatch.setattr(ps, "tables", _tables(with_cut=False))
        assert kv(run, trace) == 0.0
        assert ps.decode_scopes(trace)["ms_by_scope"]["attn_kernel"] == \
            pytest.approx(4.0)
    elif case == "never_ran":         # the program is not in the window
        monkeypatch.setattr(ps, "tables", _tables(with_cut=False))
        other = tr.Reduced(_synthetic(4, "serve_prefill"), [], (-0.001, 1.0))
        assert kv(run, other) is None
    elif case == "no_table":          # a program without a compile watch
        monkeypatch.setattr(ps, "tables", lambda program: ({}, {}))
        assert kv(run, trace) is None
    elif case == "no_trace":
        assert kv(run, None) is None
    elif case == "unknown_scope":     # a name the compile watch never had
        monkeypatch.setattr(ps, "tables", _tables(with_cut=False))
        assert "kv_gather" not in ps.known_scopes()
        assert "kv_gather" not in ps.decode_scopes(trace)["ms_by_scope"]
        assert lr.scope_group_ms(trace, ("kv_gather",)) is None
    elif case == "kernel_missing":    # a loss, not a gain: nothing printed
        monkeypatch.setattr(ps, "tables", _tables(kernel=False))
        assert reader("decode_kernel_ms")(run, trace) is None
        assert ps.kernel_ms(trace, "serve_decode",
                            "paged_decode_attention") is None
        monkeypatch.setattr(ps, "tables", _tables(kernel=True))
        again = tr.Reduced(_synthetic(4), [], (-0.001, 1.0))
        assert reader("decode_kernel_ms")(run, again) == pytest.approx(5.0)
    elif case == "train_gone":
        monkeypatch.setattr(ps, "tables", _tables())
        steps = tr.Reduced(_synthetic(4, "train_step"), [], (-0.001, 1.0))
        run = {"kind": "train", "trace_steps": 4}
        assert ps.train_scope_ms(run, steps, "fwd_bwd") == pytest.approx(5.0)
        assert ps.train_scope_ms(run, steps, "optimizer") == 0.0
        assert ps.train_scope_ms(run, steps, "not_a_scope") is None
    elif case == "train_never_ran":
        monkeypatch.setattr(ps, "tables", _tables())
        run = {"kind": "train", "trace_steps": 4}
        assert ps.train_scope_ms(run, trace, "optimizer") is None
    elif case == "group_gone":
        monkeypatch.setattr(ps, "tables", _tables())
        assert lr.scope_group_ms(trace, ("dense_ffn",)) == 0.0
        assert lr.scope_group_ms(trace, ("mlp", "dense_ffn")) == \
            pytest.approx(5.0)
        other = tr.Reduced(_synthetic(4, "serve_prefill"), [], (-0.001, 1.0))
        assert lr.scope_group_ms(other, ("dense_ffn",)) is None


def test_the_readers_docstring_says_a_program_with_no_cut_reads_zero():
    path = os.path.join(BENCH, "metrics", "decode_kv_read_ms.py")
    with open(path) as fh:
        assert "0.0" in fh.read().split('"""')[1]


# ---------------------------------------------------------------- ceilings

class _Slot:
    def __init__(self, rid):
        self.request = type("R", (), {"request_id": rid})()
        self.generated = []


class StuckServer:
    """Always busy, never finishes a request. ``moves``: every step adds
    a token to every resident request (so nothing ever stalls, and
    nothing ever ends)."""

    def __init__(self, moves=False):
        self.moves = moves
        self.steps = 0
        self.scheduler = type("S", (), {"slots": {}, "idle": False,
                                        "pending_requests": 0})()

    def submit(self, prompt, max_new_tokens, eos_token_id, request_id):
        self.scheduler.slots[len(self.scheduler.slots)] = _Slot(request_id)

    def step(self):
        self.steps += 1
        time.sleep(0.001)
        if self.moves:
            for st in self.scheduler.slots.values():
                st.generated.append(1)
        return []

    def close(self):
        pass


def _requests(n=3):
    return [serve_cell.Tracked(i, [1, 2, 3], 10 ** 6, 0.0, True)
            for i in range(n)]


@pytest.mark.parametrize("loop,moves,error", [
    ("drain", False, serve_cell.ServerStalled),
    ("open_loop", False, serve_cell.ServerStalled),
    ("open_loop", True, serve_cell.DrainCeiling),
    ("backlog_fill", False, serve_cell.ServerStalled)])
def test_a_server_that_never_finishes_ends_the_run_with_a_named_error(
        loop, moves, error):
    server = StuckServer(moves)
    sess = serve_cell.Session(server)
    t0 = time.perf_counter()
    try:
        with pytest.raises(error) as e:
            if loop == "drain":
                for r in _requests():
                    sess.submit(r)
                sess.drain(stall_s=0.05)
            elif loop == "open_loop":
                serve_cell.run_open_loop(
                    sess, _requests(), 0.02, 0.0, harness.Tracer(False, ""),
                    0.0, stall_s=0.05, drain_s=0.1)
            else:
                server.num_slots = 8       # three resident, never eight
                server.scheduler.pending_requests = 3
                serve_cell.run_backlog(sess, _requests(), iter(()), 1.0,
                                       harness.Tracer(False, ""), 0.0,
                                       stall_s=0.05)
    finally:
        sess.close()
    assert isinstance(e.value, harness.RunCeiling)
    assert time.perf_counter() - t0 < 2.0          # well before any limit
    assert server.steps > 1
    # the ceilings themselves sit well inside the driver's 360 s
    assert serve_cell.STALL_S + serve_cell.DRAIN_S < 300


def test_a_server_that_finishes_passes_no_ceiling():
    class Quick(StuckServer):
        def step(self):
            done = [st.request.request_id
                    for st in self.scheduler.slots.values()]
            self.scheduler.slots.clear()
            self.scheduler.idle = True
            return done

        def result(self, rid):
            return [1, 2, 3] + [1] * 4

        def finish_reason(self, rid):
            return "length"

        def forget(self, rid):
            pass

        def submit(self, *a, **k):
            super().submit(*a, **k)
            self.scheduler.idle = False
    sess = serve_cell.Session(Quick())
    reqs = [serve_cell.Tracked(i, [1, 2, 3], 4, 0.0, True) for i in range(3)]
    for r in reqs:
        sess.submit(r)
    sess.drain(stall_s=0.0001)     # finishing is moving
    assert all(len(r.token_times) == 4 and not r.failed for r in reqs)
    sess.close()


# ---------------------------------------------------------------- the marks

def test_tail_marks_are_seconds_since_the_start_and_seconds_inside():
    tail = harness.TailMarks()
    tail.begin(time.time() - 100.0)
    tail.mark("window_closed", ago=2.0)
    tail.mark("drained")
    with tail.timed("profiler_stopped"):
        pass
    with tail.timed("metrics_read", "decode_kv_read_ms"):
        pass
    tail.counts["traced_executions"] = {"jit_serve_decode": [190, 64]}
    line = tail.line()
    (a, ta), (b, tb) = line["tail_marks"]
    assert (a, b) == ("window_closed", "drained")
    assert 97.9 < ta < 98.5 and 99.9 < tb < 100.5
    assert line["tail_seconds"]["profiler_stopped"] >= 0.0
    assert "decode_kv_read_ms" in line["tail_seconds"]["metrics_read"]
    assert line["traced_executions"]["jit_serve_decode"] == [190, 64]
    json.dumps(line)


# ------------------------------------------- what the profiler is given

class FakeTracer:
    """Records when the loop started, opened and stopped it."""
    enabled = True

    def __init__(self, sess):
        self.sess, self.active, self.t1 = sess, False, None
        self.events = []

    def start(self, window=True):
        self.active = True
        self.events.append(("start", self.sess.clock(), len(self.sess.steps)))

    def open_window(self):
        self.events.append(("open", self.sess.clock(), len(self.sess.steps)))

    def stop(self):
        if self.active:
            self.active, self.t1 = False, self.sess.clock()
            self.events.append(("stop", self.t1, len(self.sess.steps)))


@pytest.mark.parametrize("trace_seconds,by", [(30.0, "steps"),
                                              (0.05, "seconds")])
def test_an_open_loops_profile_is_bounded_in_seconds_and_in_steps(
        trace_seconds, by, monkeypatch):
    monkeypatch.setattr(serve_cell, "PROFILE_LEAD_S", 0.05)
    server = StuckServer(moves=True)      # a step a millisecond, for ever
    sess = serve_cell.Session(server)
    tracer = FakeTracer(sess)
    begin = sess.clock()
    win = serve_cell.run_open_loop(sess, _requests(), trace_seconds, 0.2,
                                   tracer, trace_seconds,
                                   leave_when_traced=True)
    sess.close()
    (a, t_start, _), (b, t_open, n_open), (c, t_stop, n_stop) = tracer.events
    assert (a, b, c) == ("start", "open", "stop")
    # not under the profiler: the lead-in, but for its last stretch
    assert t_start - begin >= 0.2 - 0.05 - 1e-3
    assert win["t0"] - 0.06 <= t_start <= t_open and t_open >= win["t0"]
    if by == "steps":
        assert n_stop - n_open == tr.MAX_EXECUTIONS
        assert t_stop - t_open < trace_seconds
    else:
        assert n_stop - n_open < tr.MAX_EXECUTIONS
        assert t_stop - win["t0"] >= trace_seconds
    # the loop ended where its trace did, requests in flight and all
    assert len(sess.steps) - n_stop <= 1


def test_a_backlogs_profile_is_bounded_in_steps():
    server = StuckServer(moves=True)
    server.num_slots = 3
    server.scheduler.pending_requests = 3
    sess = serve_cell.Session(server)
    tracer = FakeTracer(sess)
    tracer.open_window = lambda: None
    win = serve_cell.run_backlog(sess, _requests(), iter(()), 0.4, tracer,
                                 30.0)
    sess.close()
    (_, _, n_start), (_, t_stop, n_stop) = tracer.events
    assert n_stop - n_start == tr.MAX_EXECUTIONS
    assert win["t1"] - win["t0"] >= 0.4 > t_stop - win["t0"]


# ------------------------------------------------------------- idle gaps

def old_idle_gaps(trace, top=10):
    acc = collections.defaultdict(float)
    for s, e in tr.gaps(trace.devices[0].busy, trace.lo, trace.hi):
        mid = (s + e) / 2
        cover = [(se - ss, n) for n, ss, se in trace.host_spans
                 if ss <= mid <= se]
        acc[min(cover)[1] if cover else "_no_host_span_"] += e - s
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:top]]


@pytest.mark.parametrize("case", ["recorded", "older_recording", "nested",
                                  "large"])
def test_idle_gaps_in_one_sweep_name_what_the_old_pass_named(case):
    if case == "recorded":
        trace = tr.read(TRACE)
    elif case == "older_recording":
        trace = tr.read(TRACE.replace("named", "tpu"))
    else:
        n = 40 if case == "nested" else 4 * N
        spans = []
        for k in range(n):
            t = 0.012 * k
            spans += [("bench:step", t - 0.001, t + 0.0115),
                      ("serve:step", t - 0.0005, t + 0.011),
                      ("serve:dispatch", t - 0.0004, t + 0.0001),
                      ("serve:sync_wait", t + 0.0001, t + 0.0105)]
        spans.append(("serve:request", -0.5, 0.012 * n))     # another thread
        trace = tr.Reduced(_synthetic(n, other=n), spans,
                           (-0.0008, 0.012 * n))
    t0 = time.perf_counter()
    new = trace.idle_gaps()
    spent = time.perf_counter() - t0
    if case != "large":
        assert new == old_idle_gaps(trace) and new
    else:
        # 2 gaps an execution x 4 spans a step, four times today's window
        assert spent < 1.0
        assert {k for k, _ in new} <= {n for n, _, _ in trace.host_spans}
