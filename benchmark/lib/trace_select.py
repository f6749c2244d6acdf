"""What the serving metrics share: the executions of a named program in
a trace, the kernel calls of a given name inside them, and the run's
host-side records cut to the traced window.

A reader names a program (``jit_<program>`` on the ``XLA Modules``
line), a kernel or a scope; none matches an operand's shape, so a
change to how the KV pool is laid out or handed to a kernel moves no
reader off its program.
"""
from __future__ import annotations

from statistics import median

from benchmark.lib import program_spans as ps

DECODE = ("serve_decode",)
PREFILL = ("serve_prefill", "serve_prefill_chunk")


def traced_steps(run: dict) -> list:
    """The host's step records ``(t_start, t_end, live, live_tokens)``
    that ended inside the traced window."""
    lo, hi = run["trace_t0"], run["trace_t1"]
    return [st for st in run["steps"] if lo <= st[1] <= hi]


def traced_admissions(run: dict, programs: int) -> list:
    """Prompt lengths of the requests admitted in the traced window,
    oldest first, at most ``programs`` of them (one prefill program runs
    per admission; the edges of the two clocks can differ by one)."""
    lo, hi = run["trace_t0"], run["trace_t1"]
    got = [p for t, p in run["admissions"] if lo <= t <= hi]
    return got[:programs] if programs else []


def program_runs(trace, programs) -> list:
    """``(start, end)`` of every execution on chip 0 of the programs
    named in ``programs``, wholly inside the traced window."""
    return sorted(x for p in programs for x in ps.executions(trace, p))


def kernel_calls(trace, programs, kernel: str) -> list:
    """``(start, end)`` of every call of the kernel NAMED ``kernel``
    inside those executions."""
    return [x for p in programs for x in ps.kernel_calls(trace, p, kernel)]


def decode_program_ms(run: dict, trace):
    """Median device time of one execution of the decode program."""
    if trace is None or run["kind"] != "serve":
        return None
    runs = program_runs(trace, DECODE)
    return median((e - s) * 1e3 for s, e in runs) if runs else None
