"""State bytes a decode step moved, in GB (1e9): the program's own
counter (``serve_retention_state_bytes_total{program="decode"}``: live
slots x layers x one slot-layer's stored S and z, read and written) over
its decode steps, whole process. The earlier line has every counter."""

from benchmark.lib import brumby_readers as br, harness


def read(run, trace):
    got = br.counters("decode")
    if not got:
        return None
    harness.log({"retention_counters": {"decode": got,
                                        "prefill": br.counters("prefill")}})
    return got["state_bytes"] / got["steps"] / 1e9
