"""State bytes a decode step moved, in GB (1e9): the program's own
counter (``serve_hybrid_state_bytes_total{program="decode"}``: live slots
x state layers x one slot-layer's state and convolution tail, read and
written) over its decode steps, whole process. The earlier line has
every counter."""

from benchmark.lib import granite_readers as gr, harness


def read(run, trace):
    got = gr.counters("decode")
    if not got:
        return None
    harness.log({"hybrid_counters": {"decode": got,
                                     "prefill": gr.counters("prefill")}})
    return got["state_bytes"] / got["steps"] / 1e9
