"""Cache bytes a decode step of MiMo-V2 had to read, in GB (1e9): the
program's own counters (``serve_kv_rows_read_total{program="decode"}``:
every live slot's whole context a full layer, ``min(context, window)`` a
window layer) times the REAL bytes of a row of each kind (a full layer's
4 heads x (192 + 128) lanes, a window layer's 8 heads), over its decode
steps, whole process. The earlier line has every counter and the split
by layer kind."""

from benchmark.lib import harness, mimo_readers as mm


def read(run, trace):
    s = mm.sizes(run)
    got = mm.counters()
    if not got or not s:
        return None
    by_kind = {kind: got[kind + "_rows"] * mm.row_bytes(s, kind)
               / got["steps"] / 1e9 for kind in ("full", "window")}
    harness.log({"mimo_cache_counters": dict(
        got, full_gb_per_step=by_kind["full"],
        window_gb_per_step=by_kind["window"])})
    return by_kind["full"] + by_kind["window"]
