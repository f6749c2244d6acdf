"""Share of the traced window in which the core sat in a copy-start or
copy-done instruction (the optimizer state's stream between pinned host
memory and HBM): the core runs one instruction at a time, so no compute
ran then. Device trace, worst chip."""

from benchmark.lib.trace_reduce import COPY_OPS


def read(run, trace):
    if trace is None:
        return None
    return trace.exposed_pct(COPY_OPS)
