"""What a prefill program costs the measured window, in milliseconds a
1000 prompt tokens: over the ``serve:program`` records of
``serve_prefill`` / ``serve_prefill_chunk`` that were waited for and
launched with nothing else outstanding, their seconds over their
``prompt_tokens`` (the program's span log; by ``bucket`` on the earlier
line). Host-side: a record fetched where it was launched holds the
launch and the fetch besides the execution."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.prefill_program_ms_per_ktok(run)
