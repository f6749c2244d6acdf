"""How late the load generator ran: from when a request was due to when
``submit()`` had returned, 99th percentile. One thread submits and
steps, so this is the wait for the running step to return."""

from statistics import quantiles


def read(run, trace):
    if run["kind"] != "serve":
        return None
    late = [(r.submitted - r.due) * 1e3 for r in run["counted"]]
    if len(late) < 2:
        return None
    return quantiles(late, n=100, method="inclusive")[98]
