"""``serve_admission_idle_ms`` in the chat cell (a metric moves one
end-to-end metric; there it is the time to the first token): the same
reader."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.admission_idle_ms(run)
