"""90th percentile of ``serve:prefill`` (a slot to the first token
committed) over the requests due in the window."""

from statistics import median

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    records = ps.span_records("serve:prefill")
    if not records:
        return None
    got = ps.seconds_by_key(records, "serve:prefill",
                            [r.rid for r in run["counted"]])
    ms = [1e3 * v for v in got.values()]
    if len(ms) < 2:
        return None
    # by the prompt's length (the server's prefill buckets are 128 * 2**k)
    by = {}
    for r in run["counted"]:
        if r.rid in got:
            b = 128
            while b < len(getattr(r, "prompt", ())):
                b *= 2
            by.setdefault(b, []).append(1e3 * got[r.rid])
    harness.log({"request_prefill_ms": {
        "requests": len(ms), "p50": median(ms), "min": min(ms),
        "max": max(ms), "by_prompt_bucket": {
            b: [len(v), median(v)] for b, v in sorted(by.items())}}})
    return ps.p90(ms)
