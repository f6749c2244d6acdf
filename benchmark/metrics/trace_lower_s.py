"""Seconds this process spent tracing functions and lowering them to MLIR
(every function jax reported, watched or not): the part of set-up that
no compile cache holds. The earlier line has the slowest programs."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    totals = ps.phase_totals()
    if not totals:
        return None
    rows = sorted(totals.items(),
                  key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]))
    harness.log({"compile_phases": {k: v for k, v in rows[:8]}})
    return sum(v["trace_s"] + v["lower_s"] for v in totals.values())
