"""Gap between consecutive output tokens of one request, pooled over all
tokens of all requests due in the window: 90th percentile. Tokens are
stamped at step boundaries."""
from statistics import quantiles

from benchmark.lib.serve_cell import itl_gaps_ms


def read(run, trace):
    if run["kind"] != "serve":
        return None
    gaps = itl_gaps_ms(run["counted"])
    if len(gaps) < 2:
        return None
    return quantiles(gaps, n=10, method="inclusive")[8]
