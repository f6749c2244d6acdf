"""Core time inside every other collective (``all-to-all``,
``collective-permute``, ...) and any collective instruction the train
step's movement table could not name, per traced step, on the chip
whose core waited longest in collectives."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "exchange_other_exposed_ms")
