"""Device time of the prefill programs (``jit_serve_prefill``, and
``jit_serve_prefill_chunk`` where a cell runs it) in the traced window,
per thousand prompt tokens they prefilled."""

from benchmark.lib.trace_select import (PREFILL, program_runs,
                                        traced_admissions)


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    progs = program_runs(trace, PREFILL)
    tokens = sum(traced_admissions(run, len(progs)))
    if not progs or not tokens:
        return None
    return sum(e - s for s, e in progs) * 1e3 / (tokens / 1000.0)
