"""Share of the traced window in which no operation ran on the device
(1 - union of the device's op intervals / window), mean over chips; the open-loop cell."""



def read(run, trace):
    return None if trace is None else trace.idle_pct()
