"""Core time inside collectives of kind ``all-gather`` (synchronous, or
either half of an asynchronous pair; what an ``async-collective-*``
wraps is named by the train step's movement table), per traced step, on
the chip whose core waited longest in collectives."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "gather_exposed_ms")
