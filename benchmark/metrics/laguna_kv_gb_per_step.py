"""Cache bytes a decode step had to read, in GB (1e9): the program's own
counters (``serve_kv_rows_read_total{program="decode"}``: every live
slot's whole context a full layer, ``min(context, window)`` a window
layer) times a row's K and V, over its decode steps, whole process. The
earlier line has every counter and the split by layer kind."""

from benchmark.lib import flops_laguna, harness, laguna_readers as lg


def read(run, trace):
    got = lg.counters()
    s = run.get("shapes") or {}
    if not got or "kv_heads" not in s:
        return None
    row = flops_laguna.row_bytes(s["kv_heads"], s["head_dim"], s["itemsize"])
    harness.log({"laguna_cache_counters": dict(
        got, full_gb_per_step=got["full_rows"] * row / got["steps"] / 1e9,
        window_gb_per_step=got["window_rows"] * row / got["steps"] / 1e9)})
    return (got["full_rows"] + got["window_rows"]) * row / got["steps"] / 1e9
