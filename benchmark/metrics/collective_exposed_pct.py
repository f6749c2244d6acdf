"""Share of the traced window in which the core sat in a collective
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute:
synchronous ones, and the start and done halves of asynchronous ones),
so no compute ran on that chip. Device trace, worst chip."""

from benchmark.lib.trace_reduce import COLLECTIVES


def read(run, trace):
    if trace is None or run["chips"] < 2:
        return None
    return trace.exposed_pct(COLLECTIVES)
