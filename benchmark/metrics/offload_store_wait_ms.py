"""Core time inside the ``copy-start`` / ``copy-done`` instructions that
take the updated optimizer state back to pinned host memory (rows of
kind ``device_to_host`` in the train step's movement table), per traced
step, on the chip whose core waited longest."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "offload_store_wait_ms")
