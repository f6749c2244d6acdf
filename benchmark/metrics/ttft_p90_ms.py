"""Time to the first token, from when the request was DUE (not from when
the generator got to it) to the step boundary at which its first token
was visible: 90th percentile over every request due in the window. A
failed or refused request has no first token and counts as the worst."""
from statistics import quantiles

from benchmark.lib.serve_cell import ttft_ms


def read(run, trace):
    if run["kind"] != "serve" or len(run["counted"]) < 2:
        return None
    return quantiles(ttft_ms(run["counted"]), n=10, method="inclusive")[8]
