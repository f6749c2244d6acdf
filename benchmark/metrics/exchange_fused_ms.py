"""Core time inside the movement table's ``fused`` rows (a
reduce-scatter the TPU writes as all-reduce + slice inside
``fusion.N``: no other metric counts it), per traced step, on the chip
whose core waited longest in collectives."""

from benchmark.lib import exchange_readers


def read(run, trace):
    return exchange_readers.fused_ms(run, trace)
