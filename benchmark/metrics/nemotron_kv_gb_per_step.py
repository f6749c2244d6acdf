"""K/V bytes a decode step had to read, in GB (1e9): the program's own
counter (``serve_kv_rows_read_total{program="decode", kind="full"}``:
every live slot's whole context a ``*`` layer) times a row's K and V (2
heads of 128), over its decode steps, whole process."""

from benchmark.lib import flops_laguna, granite_readers as gr


def read(run, trace):
    got = gr.counters("decode")
    s = run.get("shapes") or {}
    if not got or "kv_heads" not in s:
        return None
    row = flops_laguna.row_bytes(s["kv_heads"], s["head_dim"], s["itemsize"])
    return got["kv_rows"] * row / got["steps"] / 1e9
