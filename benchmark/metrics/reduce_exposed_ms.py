"""Core time inside collectives of kind ``reduce-scatter`` or
``all-reduce`` (synchronous, or either half of an asynchronous pair, by
the train step's movement table), per traced step, on the chip whose
core waited longest in collectives."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "reduce_exposed_ms")
