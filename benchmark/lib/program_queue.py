"""What the server's ``serve:program`` records say: one span-log record
for every device program it launched, written when the fetch that proved
the program finished returned (``deepspeed_tpu/telemetry/step_profile.py``
``StepProfiler.program_fetched``). ``start`` is the later of the
program's dispatch and the end of the record before it, ``end`` the host
clock right after the fetch, so the records never overlap and a time no
record covers is a time the host had nothing queued on the device.

A record's attributes: ``program``, ``bucket``, ``rows``,
``prompt_tokens``, ``dispatched_in`` / ``fetched_in`` (step numbers),
``depth`` (programs outstanding at its dispatch, itself included) and
``waited`` (seconds the host blocked in the fetch; None where no fetch
waited for the program on its own, and then ``end`` is only where the
next record opens).

Every function here returns None where it has nothing to read: a
program without the records (an older checkout), a run with the step
profile off, a window with no worked step, no admission, no prefill
program or no waited record. :func:`guarded` makes a reader's ``read``
hold to that whatever happens inside it.
"""
from __future__ import annotations

import bisect
import functools
from statistics import median
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmark.lib import harness, program_spans as ps

PROGRAM = "serve:program"
DECODE = "serve_decode"
PREFILLS = ("serve_prefill", "serve_prefill_chunk")


def guarded(read: Callable) -> Callable:
    """``read(run, trace)`` that returns None where it would raise (and
    says so on an earlier line): a reader of tracing never ends a run."""
    @functools.wraps(read)
    def safe(run, trace):
        try:
            return read(run, trace)
        except Exception as e:  # noqa: BLE001
            harness.log({"reader_failed": read.__module__,
                         "error": f"{type(e).__name__}: {e}"})
            return None
    return safe


def attr(record: tuple, key: str, default=None):
    return (record[ps.ATTRS] or {}).get(key, default)


def seconds(record: tuple) -> float:
    return record[ps.END] - record[ps.START]


def program_records(records: Optional[Sequence[tuple]] = None
                    ) -> List[tuple]:
    """The ``serve:program`` records of ``records`` (default: the
    process span log), oldest first; empty where there are none."""
    if records is None:
        records = ps.span_records("serve:")
    return sorted((r for r in records or () if r[ps.NAME] == PROGRAM),
                  key=lambda r: (r[ps.START], r[ps.END]))


def serve_window(run: dict) -> Optional[Tuple[list, list]]:
    """``(worked steps, program records)`` of a serving run's measured
    window, the steps oldest first; None where either is empty."""
    if run.get("kind") != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    steps = sorted(ps.window_steps(records, run["t0"], run["t1"]),
                   key=lambda s: s[ps.START])
    programs = program_records(records)
    return (steps, programs) if steps and programs else None


def reached(steps: Sequence[tuple], programs: Sequence[tuple]) -> set:
    """Indices of the ``steps`` (sorted by start, not overlapping) that a
    record of ``programs`` reaches into: the two share more than an
    instant, or the record is empty and lies inside the step."""
    starts = [s[ps.START] for s in steps]
    hit = set()
    for r in programs:
        a, b = r[ps.START], r[ps.END]
        i = max(bisect.bisect_right(starts, a) - 1, 0)
        while i < len(steps) and steps[i][ps.START] <= b:
            s, e = steps[i][ps.START], steps[i][ps.END]
            if (a < e and b > s) or (a == b and s <= a <= e):
                hit.add(i)
            i += 1
    return hit


def covered_seconds(step: tuple, programs: Sequence[tuple],
                    starts: Sequence[float]) -> float:
    """Seconds of ``step`` inside some record of ``programs`` (sorted by
    start, never overlapping; ``starts`` their starts)."""
    s, e = step[ps.START], step[ps.END]
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    got = 0.0
    while i < len(programs) and programs[i][ps.START] < e:
        got += max(min(programs[i][ps.END], e)
                   - max(programs[i][ps.START], s), 0.0)
        i += 1
    return got


def span_errors() -> Optional[float]:
    """``serve_program_span_errors_total`` over its sites; None for a
    program without the family."""
    try:
        from deepspeed_tpu.telemetry import get_registry
        series = get_registry().snapshot().get(
            "serve_program_span_errors_total", {}).get("series")
    except Exception:  # noqa: BLE001 — an older program: nothing to read
        return None
    return sum(s["value"] for s in series) if series else None


# ------------------------------------------------------------ the readers

def refill_share_pct(run: dict) -> Optional[float]:
    """Share of the window's worked ``serve:step`` wall in steps that a
    record with ``prompt_tokens`` above 0 reaches into: a step that ran
    a prompt (a prefill program, a chunk, a rider)."""
    got = serve_window(run)
    if got is None:
        return None
    steps, programs = got
    wall = sum(seconds(s) for s in steps)
    if wall <= 0:
        return None
    hit = reached(steps, [r for r in programs
                          if attr(r, "prompt_tokens", 0) > 0])
    refill = sum(seconds(steps[i]) for i in hit)
    starts = [r[ps.START] for r in programs]
    covered = sum(covered_seconds(s, programs, starts) for s in steps)
    harness.log({"refill_share": {
        "steps": len(steps), "refill_steps": len(hit), "wall_s": wall,
        "refill_wall_s": refill,
        "queue_empty_pct_of_wall": 100.0 * (wall - covered) / wall,
        "program_records": len(programs),
        "program_span_errors": span_errors()}})
    return 100.0 * refill / wall


def prefill_program_ms_per_ktok(run: dict) -> Optional[float]:
    """Milliseconds a 1000 prompt tokens over the window's records of a
    prefill program (``serve_prefill``, ``serve_prefill_chunk``) that
    were waited for and launched with nothing else outstanding; the
    earlier line gives it by ``bucket``. A record fetched where it was
    launched holds the launch and the fetch besides the execution."""
    got = serve_window(run)
    if got is None:
        return None
    by: Dict[object, List[float]] = {}
    for r in got[1]:
        if (attr(r, "program") in PREFILLS and attr(r, "depth") == 1
                and attr(r, "waited") is not None
                and attr(r, "prompt_tokens", 0) > 0
                and run["t0"] <= r[ps.END] <= run["t1"]):
            row = by.setdefault(attr(r, "bucket"), [0, 0, 0.0])
            row[0] += 1
            row[1] += attr(r, "prompt_tokens")
            row[2] += seconds(r)
    tokens = sum(row[1] for row in by.values())
    if not tokens:
        return None
    total = sum(row[2] for row in by.values())
    harness.log({"prefill_programs": {
        "programs": sum(row[0] for row in by.values()),
        "prompt_tokens": tokens, "seconds": total,
        "by_bucket": {str(k): {"programs": n, "prompt_tokens": t,
                               "seconds": sec,
                               "ms_per_ktok": 1e6 * sec / t}
                      for k, (n, t, sec) in sorted(
                          by.items(), key=lambda kv: str(kv[0]))}}})
    return 1e6 * total / tokens


def admission_idle_ms(run: dict) -> Optional[float]:
    """Over the window's worked steps that admitted a request, the
    seconds of each no record covers, summed, over the requests they
    admitted: how long the host left the device's queue EMPTY for an
    admitted request, in milliseconds."""
    got = serve_window(run)
    if got is None:
        return None
    steps = [s for s in got[0] if attr(s, "admitted")]
    admitted = sum(attr(s, "admitted") for s in steps)
    if not admitted:
        return None
    programs = got[1]
    starts = [r[ps.START] for r in programs]
    wall = sum(seconds(s) for s in steps)
    idle = wall - sum(covered_seconds(s, programs, starts) for s in steps)
    harness.log({"admission_idle": {"steps": len(steps),
                                    "admitted": admitted, "wall_s": wall,
                                    "idle_s": idle}})
    return 1e3 * idle / admitted


def _pair(records: Sequence[tuple], runs: Sequence[Tuple[float, float]]
          ) -> dict:
    """Counts and medians (ms) of some waited records beside the
    device's executions of their program."""
    waited = [seconds(r) for r in records if attr(r, "waited") is not None]
    return {"records": len(records), "executions": len(runs),
            "not_waited_pct": 100.0 * (len(records) - len(waited))
            / len(records) if records else None,
            "record_ms": 1e3 * median(waited) if waited else None,
            "execution_ms": 1e3 * median(e - s for s, e in runs)
            if runs else None}


def span_skew_pct(run: dict, trace) -> Optional[float]:
    """Over the traced window: the median length of the waited
    ``serve_decode`` records less the median device time of a
    ``jit_serve_decode`` execution, over the latter, in percent: how far
    a record's edges (a dispatch, a fetch) lie from the execution's. The
    earlier line has the two counts (equal, to one at each edge), the
    same pair for the prefill programs the window holds, and the shares
    of the window that the records, the trace's executions and its
    instructions cover."""
    if (trace is None or run.get("kind") != "serve"
            or run.get("trace_t0") is None or run.get("trace_t1") is None):
        return None
    lo, hi = run["trace_t0"], run["trace_t1"]
    inside = [r for r in program_records()
              if r[ps.START] >= lo and r[ps.END] <= hi]
    if not inside:
        return None

    def named(names):
        return [r for r in inside if attr(r, "program") in names]
    decode = _pair(named((DECODE,)), ps.executions(trace, DECODE))
    line = {"decode": decode, "window_s": hi - lo}
    prefill = {p: _pair([r for r in named((p,)) if attr(r, "depth") == 1],
                        ps.executions(trace, p)) for p in PREFILLS}
    line["prefill"] = {p: v for p, v in prefill.items()
                       if v["records"] or v["executions"]}
    if hi > lo:
        mods = trace.devices[0].modules
        line.update(
            records_cover_pct=100.0 * sum(map(seconds, inside)) / (hi - lo),
            executions_cover_pct=100.0 * sum(
                e - s for _, s, e in mods) / trace.window_s,
            instructions_cover_pct=100.0 * trace.busy_s / trace.window_s)
    harness.log({"program_span_skew": line})
    if not decode["record_ms"] or not decode["execution_ms"]:
        return None
    return 100.0 * (decode["record_ms"] - decode["execution_ms"]) \
        / decode["execution_ms"]
