"""``peak_bytes_in_use`` of ``memory_stats()`` after the window, in GB
(1e9 bytes); the LongCat-Flash decode-batch cell."""


def read(run, trace):
    return run["memory_peak_bytes"] / 1e9
