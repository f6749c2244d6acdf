"""Device time one execution of the decode program (``jit_serve_decode``)
spends cutting each layer's K and V out of the pool: self time of the
instructions whose scope is ``kv_read`` (the compile watch's scope
table), median over the executions of the traced window. A decode
program that cuts nothing (no instruction carries ``kv_read``: decode
attends the pool in place) reads 0.0, which is what it spends; a run
with no trace, no scope table or no execution of the program in the
window reads nothing. The earlier line has every scope and the share
with none."""

from benchmark.lib import harness, program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    got = ps.decode_scopes(trace)
    if got is None:
        return None
    harness.log({"decode_scopes": got})
    return got["ms_by_scope"].get("kv_read")
