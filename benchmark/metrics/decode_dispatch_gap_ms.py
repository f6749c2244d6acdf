"""Median idle gap of chip 0 between consecutive executions of the decode
program in the traced window. The earlier line has the same gaps by the
innermost ``serve:`` span over each gap's middle (the program's span log
on the trace's clock), where the host and device clocks meet, and the
``slow_step`` events of the measured window."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    got = ps.dispatch_gaps(run, trace)
    if got is None:
        return None
    slow = [e["data"] for e in ps.slow_steps()
            if run["t0"] <= e["data"].get("start", 0.0) <= run["t1"]]
    harness.log({"decode_dispatch_gaps": got, "slow_steps_in_window": slow})
    return got["median_ms"]
