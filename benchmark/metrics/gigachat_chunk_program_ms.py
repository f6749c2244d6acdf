"""Device time of one execution of the chunk program
(``jit_serve_prefill_chunk``: 1024 prompt rows of one slot against its
cached context), median over the traced window. In this cell an
execution is a prefix-cache hit's whole turn, at a context of ~33k rows;
no slot decodes while it runs. The earlier line has the count and the
chunk program's time by scope group."""

from statistics import median

from benchmark.lib import gigachat_readers as gr, harness
from benchmark.lib import longcat_readers as lr
from benchmark.lib.trace_select import program_runs


def read(run, trace):
    if trace is None or run["kind"] != "serve":
        return None
    runs = program_runs(trace, (gr.CHUNK,))
    if not runs:
        return None
    harness.log({"gigachat_chunk_program": {
        "executions": len(runs),
        "mla_ms": lr.scope_group_ms(trace, gr.MLA, program=gr.CHUNK),
        "moe_ms": lr.scope_group_ms(trace, gr.MOE, lr.EXPERT_KERNELS,
                                    program=gr.CHUNK),
        "dense_ffn_ms": lr.scope_group_ms(trace, lr.DENSE,
                                          program=gr.CHUNK)}})
    return median((e - s) * 1e3 for s, e in runs)
