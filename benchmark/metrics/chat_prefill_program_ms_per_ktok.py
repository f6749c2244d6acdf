"""``serve_prefill_program_ms_per_ktok`` in the chat cell (a metric moves
one end-to-end metric; there it is the time to the first token): the
same reader. Beside the trace's ``prefill_ms_per_ktok`` it says what the
launch and the fetch add to a prompt's program."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.prefill_program_ms_per_ktok(run)
