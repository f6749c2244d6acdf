"""Seconds the program's compile watch recorded for its programs in this
process (tracing and lowering are not in it; with a warm persistent
cache this is the time to read the executables back)."""



def read(run, trace):
    return run["compile_s"]
