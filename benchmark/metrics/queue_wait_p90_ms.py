"""From when a request was due to the start of the step that admitted it
(the benchmark's own stamps): 90th percentile over the requests due in
the window."""

from statistics import quantiles


def read(run, trace):
    if run["kind"] != "serve":
        return None
    waits = [(r.admitted - r.due) * 1e3 for r in run["counted"]
             if r.admitted is not None]
    if len(waits) < 2:
        return None
    return quantiles(waits, n=10, method="inclusive")[8]
