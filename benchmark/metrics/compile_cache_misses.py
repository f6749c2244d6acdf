"""Backend compiles in this process that the persistent compile cache did
not serve (every function jax reported): 0 on a warm run."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    totals = ps.phase_totals()
    if not totals:
        return None
    return sum(v["cache_misses"] for v in totals.values())
