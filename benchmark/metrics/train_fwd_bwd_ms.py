"""Device time of the instructions in the train step's ``fwd_bwd`` scope
per traced step (self times, so nothing counts twice), on the chip where
it is largest."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    return ps.train_scope_ms(run, trace, "fwd_bwd")
