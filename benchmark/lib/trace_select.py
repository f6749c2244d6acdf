"""What the serving metrics share: telling the paged-decode kernel from
the flash kernel in a trace, and cutting the run's host-side records to
the traced window.

The program gives its kernels no names (a Pallas call's instruction is
named after whatever Python function encloses it), so a kernel call is
told by its operands: the paged kernels read the KV pool, a
``[num_blocks, block_size, heads * head_dim]`` operand that nothing else
has. Stable kernel names are listed in PERF.md for the tracing PR.
"""
from __future__ import annotations

from statistics import median


def is_paged(text: str, run: dict) -> bool:
    """A kernel call that reads the paged KV pool."""
    s = run["shapes"]
    pool = (f"[{run['num_blocks']},{run['block_size']},"
            f"{s['kv_heads'] * s['head_dim']}]")
    alt = (f"[{run['num_blocks']},{run['block_size']},{s['kv_heads']},"
           f"{s['head_dim']}]")
    return pool in text or alt in text


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


def decode_program_ms(run: dict, trace):
    """Median device time of one execution of the program that holds the
    paged-decode kernel."""
    if trace is None or run["kind"] != "serve":
        return None
    progs = trace.modules_with(lambda t: is_paged(t, run))
    return median((e - s) * 1e3 for _, s, e, _ in progs) if progs else None
