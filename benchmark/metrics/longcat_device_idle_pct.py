"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals / window); the LongCat-Flash
decode-batch cell."""


def read(run, trace):
    return None if trace is None else trace.idle_pct()
