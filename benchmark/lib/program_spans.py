"""What the program says about itself, for the per-layer metrics that
read it: the span log (``deepspeed_tpu/telemetry/spans.py``:
``serve:step`` and its phases, ``serve:request`` and its lifecycle,
``train:step``, ``compile:<program>``), the compile watch's phase totals
and its scope and kernel tables (``telemetry/compile_watch.py``), and the
joins of those with a reduced device trace.

Two clocks meet here. Spans carry ``time.perf_counter`` readings, which
is also the clock of ``run["t0"]``, ``run["t1"]``, ``run["trace_t0"]``
(``serve_cell.Session.clock``, ``harness.Tracer``). The trace has its
own: ``trace.lo`` is where ``bench:window`` opened on it, and
``run["trace_t0"]`` was read on the host just after that, so a host time
``t`` sits at ``trace.lo + (t - run["trace_t0"])`` (:func:`to_trace`).

Device instructions are told apart by NAME: an ``XLA Ops`` event starts
with the instruction's name (``%fusion.12 = ...``), an ``XLA Modules``
event is ``jit_<program>(<id>)``, and ``scope_table(<program>)`` maps
the one to the scopes the program opened around it. A program that has
none of this (an older checkout) makes every function here return None
or an empty result; nothing raises.
"""
from __future__ import annotations

import bisect
import collections
import functools
import re
from statistics import median, quantiles
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.lib.trace_reduce import op_name

NAME, START, END, PARENT, ID, KEY, ATTRS = range(7)
_MODULE = re.compile(r"^jit_(.+?)(?:\(\d+\))?$")
_INSTR = re.compile(r"^%?([^\s=]+)")
STEP = "serve:step"
OVERLAYS = ("serve:step", "serve:flush", "serve:request",
            "serve:queue_wait", "serve:prefill", "serve:decode")
UNKNOWN = "_no_scope_"


# ------------------------------------------------------------- the program

def span_records(prefix: Optional[str] = None) -> Optional[list]:
    """The process span log's records, or None where the program keeps
    none."""
    try:
        from deepspeed_tpu.telemetry.spans import get_span_log
        return get_span_log().snapshot(prefix=prefix)
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None


def span_log_stats() -> Optional[dict]:
    try:
        from deepspeed_tpu.telemetry.spans import get_span_log
        return get_span_log().stats()
    except Exception:  # noqa: BLE001
        return None


def phase_totals() -> Optional[Dict[str, dict]]:
    try:
        from deepspeed_tpu.telemetry.compile_watch import phase_totals
        return phase_totals()
    except Exception:  # noqa: BLE001
        return None


def tables(program: str) -> Tuple[dict, dict]:
    """``(scope table, kernel table)`` of a watched program; empty where
    the program has no such tables."""
    try:
        from deepspeed_tpu.telemetry.compile_watch import (kernel_table,
                                                           scope_table)
        return scope_table(program), kernel_table(program)
    except Exception:  # noqa: BLE001
        return {}, {}


def watched_programs() -> List[str]:
    """Names of the programs the compile watch can make a table for."""
    try:
        from deepspeed_tpu.telemetry.compile_watch import watched_programs
        return watched_programs()
    except Exception:  # noqa: BLE001
        return []


def slow_steps() -> list:
    """``slow_step`` events of the flight-recorder ring."""
    try:
        from deepspeed_tpu.telemetry.events import get_event_ring
        return [e for e in get_event_ring().snapshot()
                if e["kind"] == "slow_step"]
    except Exception:  # noqa: BLE001
        return []


# ------------------------------------------------------------------- spans

def ending_in(records: Iterable[tuple], lo: float, hi: float,
              name: Optional[str] = None) -> List[tuple]:
    return [r for r in records if lo <= r[END] <= hi
            and (name is None or r[NAME] == name)]


def window_steps(records: Sequence[tuple], lo: float, hi: float
                 ) -> List[tuple]:
    """Worked ``serve:step`` spans that ended inside ``[lo, hi]``."""
    return [r for r in ending_in(records, lo, hi, STEP)
            if not (r[ATTRS] or {}).get("idle")]


def by_parent(records: Iterable[tuple]) -> Dict[int, List[tuple]]:
    out: Dict[int, List[tuple]] = collections.defaultdict(list)
    for r in records:
        out[r[PARENT]].append(r)
    return out


def seconds_by_key(records: Iterable[tuple], name: str, keys) -> Dict:
    """Total seconds of the spans named ``name`` for each key in
    ``keys`` (a request's requeues add up)."""
    keys = set(keys)
    out: Dict = collections.defaultdict(float)
    for r in records:
        if r[NAME] == name and r[KEY] in keys:
            out[r[KEY]] += r[END] - r[START]
    return dict(out)


def p90(values: Sequence[float]) -> Optional[float]:
    if len(values) < 2:
        return None
    return quantiles(values, n=10, method="inclusive")[8]


def goodput_pct(records: Sequence[tuple], lo: float, hi: float
                ) -> Optional[float]:
    """The program's own device-attributed share of its worked steps'
    wall (``device_s`` on each ``serve:step`` span: the figure behind
    ``serve_goodput_fraction``), over the steps of the window."""
    steps = [r for r in window_steps(records, lo, hi)
             if (r[ATTRS] or {}).get("device_s") is not None]
    wall = sum(r[END] - r[START] for r in steps)
    if not steps or wall <= 0:
        return None
    return 100.0 * sum(r[ATTRS]["device_s"] for r in steps) / wall


def admission_ms(records: Sequence[tuple], lo: float, hi: float
                 ) -> List[float]:
    """Milliseconds in ``serve:admission`` of each window step that
    admitted a request."""
    kids = by_parent(r for r in records if r[NAME] == "serve:admission")
    return [1e3 * sum(k[END] - k[START] for k in kids.get(s[ID], ()))
            for s in window_steps(records, lo, hi)
            if (s[ATTRS] or {}).get("admitted")]


# ------------------------------------------------------ the trace's clock

def to_trace(run: dict, trace, t: float) -> float:
    """A host (``perf_counter``) time on the trace's clock, through the
    ``bench:window`` anchor."""
    return trace.lo + (t - run["trace_t0"])


# ------------------------------------------------------- names on the device

def program_of(module: str) -> Optional[str]:
    """``jit_serve_decode(123)`` -> ``serve_decode``."""
    m = _MODULE.match(module)
    return m.group(1) if m else None


@functools.lru_cache(maxsize=1 << 16)
def instruction(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _INSTR.match(text)
    return m.group(1) if m else text


def executions(trace, program: str, device: int = 0
               ) -> List[Tuple[float, float]]:
    """Start and end of each execution of ``jit_<program>`` on one chip,
    oldest first."""
    return sorted((s, e) for n, s, e in trace.devices[device].modules
                  if program_of(n) == program)


def innermost_seconds(ops: Sequence[tuple]) -> List[float]:
    """For ``(text, start, end, ...)`` events of one line, possibly
    nested, the seconds each was the innermost one running. Every
    instant of the union of their intervals goes to exactly one event,
    so the result sums to the busy time whatever the rounding of the
    nested events' edges (``trace_reduce.self_times`` clamps a container
    that its children overrun, which adds a little)."""
    own = [0.0] * len(ops)
    stack: List[int] = []
    t = float("-inf")
    for i in sorted(range(len(ops)), key=lambda k: (ops[k][1], -ops[k][2])):
        s = ops[i][1]
        while stack and ops[stack[-1]][2] <= s:
            j = stack.pop()
            if ops[j][2] > t:
                own[j] += ops[j][2] - t
                t = ops[j][2]
        if stack and s > t:
            own[stack[-1]] += s - t
        t = max(t, s)
        stack.append(i)
    while stack:
        j = stack.pop()
        if ops[j][2] > t:
            own[j] += ops[j][2] - t
            t = ops[j][2]
    return own


Op = collections.namedtuple(
    "Op", "text start end own name scope kernel")
Op.__doc__ = """One instruction of one execution, with everything a
reader asks of it resolved once: ``own`` the seconds it was the
innermost one running, ``name`` its instruction name (``fusion.12``),
``scope`` its path in the program's scope table (``fwd_bwd/mlp`` or
None), ``kernel`` its name in the kernel table, else its instruction
name without the numbers."""


def _grouped(trace, device: int) -> list:
    """ONE pass over a chip's instructions: ``[(program, start, end,
    [raw ops])]`` for every execution of every program inside the
    window, oldest first; an instruction belongs to the execution that
    holds it. Kept on the trace."""
    memo = trace.__dict__.setdefault("_grouped", {})
    if device not in memo:
        dev = trace.devices[device]
        mods = sorted((s, e, n) for n, s, e in dev.modules)
        starts = [m[0] for m in mods]
        held: List[list] = [[] for _ in mods]
        for op in dev.ops:
            i = bisect.bisect_right(starts, op[1]) - 1
            if i >= 0 and op[2] <= mods[i][1] + 1e-9:
                held[i].append(op)
        memo[device] = [(program_of(n), s, e, ops)
                        for (s, e, n), ops in zip(mods, held)]
    return memo[device]


def ops_by_execution(trace, program: str, device: int = 0
                     ) -> List[List[Op]]:
    """For each execution of the program on one chip, the :class:`Op`
    of every instruction that ran inside it. Built once per trace,
    program and chip, and kept on the trace: every reader of a program
    shares it."""
    memo = trace.__dict__.setdefault("_ops", {})
    key = (program, device)
    if key not in memo:
        table, named = tables(program)
        out = []
        for prog, _, _, ops in _grouped(trace, device):
            if prog != program:
                continue
            row = []
            for (text, s, e, _), own in zip(ops, innermost_seconds(ops)):
                name = instruction(text)
                row.append(Op(text, s, e, own, name, table.get(name),
                              named.get(name) or op_name(text)))
            out.append(row)
        memo[key] = out
    return memo[key]


def known_scopes() -> frozenset:
    """The scopes the program's compile watch knows (a scope a program
    could open), or none for a program without a watch."""
    try:
        from deepspeed_tpu.telemetry.compile_watch import SCOPES
        return frozenset(SCOPES)
    except Exception:  # noqa: BLE001
        return frozenset()


def scope_seconds(ops: Iterable[Op]) -> Dict[str, float]:
    """Self seconds of a set of instructions by innermost scope;
    instructions the table gives no scope are under ``UNKNOWN``."""
    acc: Dict[str, float] = collections.defaultdict(float)
    for op in ops:
        acc[op.scope.rsplit("/", 1)[-1] if op.scope else UNKNOWN] += op.own
    return dict(acc)


def in_scope(ops: Iterable[Op], scope: str) -> float:
    """Self seconds of the instructions with ``scope`` anywhere on their
    path (``fwd_bwd/mlp`` is in ``fwd_bwd`` and in ``mlp``)."""
    return sum(op.own for op in ops
               if op.scope and scope in op.scope.split("/"))


def decode_scopes(trace, program: str = "serve_decode") -> Optional[dict]:
    """Per execution of the decode program on chip 0: median ms in each
    innermost scope, and the share of its device time with no known
    scope. A scope the compile watch knows and that no instruction of
    the program carries reads 0.0: a program that no longer does the
    work a scope names spends no time in it. None stays for no trace,
    no scope table, or a program that never ran in the window."""
    if trace is None:
        return None
    table, _ = tables(program)
    runs = ops_by_execution(trace, program)
    if not table or not runs:
        return None
    per = [scope_seconds(ops) for ops in runs]
    names = sorted({k for p in per for k in p} | known_scopes())
    busy = sum(sum(p.values()) for p in per)
    return {"executions": len(runs),
            "ms_by_scope": {k: 1e3 * median(p.get(k, 0.0) for p in per)
                            for k in names},
            "unknown_share_pct": 100.0 * sum(
                p.get(UNKNOWN, 0.0) for p in per) / busy if busy else None}


def kernel_calls(trace, program: str, kernel: str
                 ) -> List[Tuple[float, float]]:
    """Start and end of every call of the kernel NAMED ``kernel`` (by
    the program's kernel table, else by the instruction's own name)
    inside the executions of the program on chip 0."""
    return [(op.start, op.end) for ops in ops_by_execution(trace, program)
            for op in ops if op.kernel == kernel]


def kernel_ms(trace, program: str, kernel: str) -> Optional[float]:
    """Median over executions of the program on chip 0 of the device
    time of the kernel calls NAMED ``kernel``. A kernel that is not
    there reads None, never 0: a missing kernel is a loss, not a gain."""
    if trace is None:
        return None
    runs = ops_by_execution(trace, program)
    if not runs:
        return None
    per = [sum(op.end - op.start for op in ops if op.kernel == kernel)
           for ops in runs]
    return 1e3 * median(per) if any(per) else None


def train_scope_ms(run: dict, trace, scope: str,
                   program: str = "train_step") -> Optional[float]:
    """Self time of the instructions in ``scope`` per traced step, on
    the chip where it is largest; 0.0 for a scope the compile watch
    knows and no instruction of the executed program carries."""
    if trace is None or run.get("kind") != "train":
        return None
    table, _ = tables(program)
    if not table or not run.get("trace_steps"):
        return None
    worst, ran = 0.0, False
    for k in range(len(trace.devices)):
        runs = ops_by_execution(trace, program, k)
        ran = ran or bool(runs)
        worst = max(worst, in_scope((op for grp in runs for op in grp),
                                    scope))
    if worst <= 0 and not (ran and scope in known_scopes()):
        return None
    return 1e3 * worst / run["trace_steps"]


def dispatch_gaps(run: dict, trace, program: str = "serve_decode"
                  ) -> Optional[dict]:
    """Idle gaps of chip 0 between consecutive executions of the decode
    program (no other program between them), with what the host was
    doing in them: each gap's seconds shared among the ``serve:`` phase
    spans it overlaps, through the window anchor; and where the two
    clocks meet: the offset from each ``serve:dispatch`` span's end to
    the start of the next execution, and from an execution's end to the
    end of the ``serve:sync_wait`` that fetched it."""
    if trace is None:
        return None
    mods = sorted((s, e, program_of(n))
                  for n, s, e in trace.devices[0].modules)
    gaps = [(a[1], b[0]) for a, b in zip(mods, mods[1:])
            if a[2] == b[2] == program and b[0] > a[1]]
    if not gaps:
        return None
    out = {"gaps": len(gaps),
           "median_ms": 1e3 * median(e - s for s, e in gaps),
           "max_ms": 1e3 * max(e - s for s, e in gaps)}
    records = span_records("serve:")
    if records and run.get("trace_t0") is not None:
        # what the serving thread was doing: the phase spans tile the
        # steps and no two overlap (the step and flush spans lie over
        # them, a request's lifecycle spans over everything)
        spans = sorted(
            (to_trace(run, trace, r[START]), to_trace(run, trace, r[END]),
             r[NAME]) for r in records if r[NAME] not in OVERLAYS)
        spans = [x for x in spans if x[1] >= trace.lo and x[0] <= trace.hi]
        first = [x[0] for x in spans]
        by: Dict[str, float] = collections.defaultdict(float)
        for s, e in gaps:
            # a gap is about as long as the anchor is exact (~1 ms), so
            # it is shared out by overlap, not put down whole to the
            # span over its middle
            left = e - s
            for a, b, name in spans[max(bisect.bisect_right(first, s) - 1,
                                        0):bisect.bisect_left(first, e)]:
                part = min(b, e) - max(a, s)
                if part > 0:
                    by[name] += part
                    left -= part
            if left > 1e-9:
                by["_no_serve_span_"] += left
        out["seconds_by_span"] = dict(sorted(by.items(),
                                             key=lambda kv: -kv[1]))
        spans = [(n, a, b) for a, b, n in spans]
        starts = [s for s, _, p in mods if p == program]
        skew = []
        for n, s, e in spans:
            if n != "serve:dispatch":
                continue
            i = bisect.bisect_left(starts, s)
            if i < len(starts):
                skew.append(starts[i] - e)
        if skew:
            out["dispatch_end_to_program_start_ms"] = 1e3 * median(skew)
        # under the pipelined loop a program starts when the one before
        # it ends, long after its dispatch returned: the fetch is the
        # tighter meeting of the two clocks (the host sees a result a
        # transfer after the device wrote it)
        ends = sorted(e for _, e, p in mods if p == program)
        lag = []
        for n, s, e in spans:
            if n == "serve:sync_wait" and e > s:
                i = bisect.bisect_right(ends, e) - 1
                if i >= 0 and e - ends[i] < 0.1:
                    lag.append(e - ends[i])
        if lag:
            out["program_end_to_sync_wait_end_ms"] = 1e3 * median(lag)
    return out
