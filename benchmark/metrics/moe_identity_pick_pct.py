"""Share of the top-k picks that fell on zero-compute (identity) experts
(the program's routing counters, every program, whole process): 256 of
768 outputs = 33 % at uniform routing. These cost no matmul."""

from benchmark.lib import longcat_readers as lr


def read(run, trace):
    got = lr.routing()
    if not got or not got["tokens_routed"]:
        return None
    return 100.0 * got["identity_picks"] / (
        got["tokens_routed"] * run["shapes"]["top_k"])
