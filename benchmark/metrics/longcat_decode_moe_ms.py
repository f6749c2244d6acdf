"""Device time one execution of the decode program spends in the expert
layers: self time of the instructions inside ``moe_router``,
``moe_dispatch``, ``moe_experts`` (with the compiler's grouped-matmul
kernels, which carry no scope) and ``moe_combine``, median over the
executions of the traced window. The earlier line has the four apart."""

from benchmark.lib import harness, longcat_readers as lr


def read(run, trace):
    parts = {s: lr.scope_group_ms(trace, (s,)) for s in lr.MOE}
    if any(v is None for v in parts.values()):
        return None
    parts["moe_experts"] = lr.experts_ms(trace)
    harness.log({"longcat_decode_moe_ms_by_scope": parts})
    return lr.scope_group_ms(trace, lr.MOE, lr.EXPERT_KERNELS)
