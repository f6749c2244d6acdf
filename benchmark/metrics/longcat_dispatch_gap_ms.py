"""Median idle gap of chip 0 between consecutive executions of the decode
program in the traced window; the LongCat-Flash decode-batch cell. The
earlier line has the gaps by ``serve:`` span."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    got = ps.dispatch_gaps(run, trace)
    if got is None:
        return None
    harness.log({"longcat_dispatch_gaps": got})
    return got["median_ms"]
