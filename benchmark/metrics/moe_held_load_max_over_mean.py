"""Picks on the busiest held expert over the mean of the held experts
(the program's routing counters, every program, whole process): 1 is
even load; the busiest expert sets a grouped matmul's longest group."""

from benchmark.lib import longcat_readers as lr


def read(run, trace):
    got = lr.routing()
    if not got or not sum(got["held"]):
        return None
    return max(got["held"]) * len(got["held"]) / sum(got["held"])
