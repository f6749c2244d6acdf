"""``peak_bytes_in_use`` of ``memory_stats()`` after the window on the
fullest chip, in GB (1e9 bytes); training cells."""



def read(run, trace):
    return run["memory_peak_bytes"] / 1e9
