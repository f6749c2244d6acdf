"""Device time of the prefill programs (the compiled programs that hold
the flash kernel and no paged kernel) in the traced window, per thousand
prompt tokens they prefilled."""

from benchmark.lib.trace_select import is_paged, traced_admissions


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    progs = trace.modules_with(lambda t: not is_paged(t, run))
    tokens = sum(traced_admissions(run, len(progs)))
    if not progs or not tokens:
        return None
    return sum(e - s for _, s, e, _ in progs) * 1e3 / (tokens / 1000.0)
