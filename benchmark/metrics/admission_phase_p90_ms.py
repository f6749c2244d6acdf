"""90th percentile of the ``serve:admission`` phase over the steps of the
measured window that admitted a request (a monolithic prefill runs
inside it): how long an admission holds every resident decoder."""

from statistics import median

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:")
    if not records:
        return None
    ms = ps.admission_ms(records, run["t0"], run["t1"])
    if len(ms) < 2:
        return None
    harness.log({"admission_phase_ms": {
        "steps": len(ms), "p50": median(ms), "max": max(ms)}})
    return ps.p90(ms)
