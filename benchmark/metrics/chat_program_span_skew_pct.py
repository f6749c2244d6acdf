"""``serve_program_span_skew_pct`` in the chat cell (a metric moves one
end-to-end metric; there the decode records bound the time between two
tokens): the same reader, over the traced second schedule, which also
holds prefill programs fetched where they were launched."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.span_skew_pct(run, trace)
