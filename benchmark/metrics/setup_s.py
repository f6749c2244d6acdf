"""Process start to the opening of the measured window: imports, weights,
tracing, lowering, compiling or reading the compile cache, warm-up, the
correctness check, and the filling of slots or the lead-in that the
cell's traffic needs. Host clock."""



def read(run, trace):
    return run["setup_s"]
