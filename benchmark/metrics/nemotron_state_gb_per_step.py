"""State bytes a decode step moved, in GB (1e9): the program's own
counter (``serve_hybrid_state_bytes_total{program="decode"}``: live slots
x the ``M`` layers x one slot-layer's state and convolution tail, read
and written; an expert layer and an attention layer add nothing) over
its decode steps, whole process."""

from benchmark.lib import granite_readers as gr


def read(run, trace):
    got = gr.counters("decode")
    if not got:
        return None
    return got["state_bytes"] / got["steps"] / 1e9
