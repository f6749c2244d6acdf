"""How far a ``serve:program`` record's edges lie from the execution it
stands for: over the traced window, the median length of the waited
``serve_decode`` records less the median device time of a
``jit_serve_decode`` execution, over the latter, in percent (the
program's span log against the device trace). The error bar of every
number read off the records; the earlier line has the two counts, the
prefill programs' pair and what the records cover of the window."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.span_skew_pct(run, trace)
