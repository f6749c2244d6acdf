"""Median host time of one whole optimizer step, dispatch to loss on the
host."""

from statistics import median


def read(run, trace):
    if run["kind"] != "train":
        return None
    return median(run["step_seconds"]) * 1e3
