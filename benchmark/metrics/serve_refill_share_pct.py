"""Share of the measured window's worked ``serve:step`` wall spent in
steps that a ``serve:program`` record with ``prompt_tokens`` above 0
reaches into (the program's span log): a step that ran a prompt, as a
prefill program, a chunk or the rider of a decode program, while its
slots do not decode or decode slower. One definition for every family;
the earlier line has the steps, and the share of their wall with nothing
queued on the device."""

from benchmark.lib import program_queue as pq


@pq.guarded
def read(run, trace):
    return pq.refill_share_pct(run)
