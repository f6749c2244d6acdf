"""90th percentile of ``serve:queue_wait`` (``submit()`` to a slot, on
the server's own stamps) over the requests due in the window: the
outside ``queue_wait_p90_ms`` less the generator's lateness and the
rounding to a step."""

from statistics import median

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:queue_wait")
    if not records:
        return None
    waits = ps.seconds_by_key(records, "serve:queue_wait",
                              [r.rid for r in run["counted"]])
    ms = [1e3 * v for v in waits.values()]
    if len(ms) < 2:
        return None
    harness.log({"server_queue_wait_ms": {
        "requests": len(ms), "p50": median(ms), "max": max(ms)}})
    return ps.p90(ms)
