"""Core time inside the ``copy-start`` / ``copy-done`` instructions that
bring the optimizer state from pinned host memory to the device (rows of
kind ``host_to_device`` in the train step's movement table), per traced
step, on the chip whose core waited longest: no compute ran then."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "offload_fetch_wait_ms")
