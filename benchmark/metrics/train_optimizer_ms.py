"""Device time of the instructions in the train step's ``optimizer``
scope per traced step, the waits inside the offload stream's
``copy-start`` / ``copy-done`` included, on the chip where it is
largest."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    return ps.train_scope_ms(run, trace, "optimizer")
