"""Device time one execution of the decode program spends in the five
expert layers: self time of the instructions inside ``moe_router``,
``moe_dispatch``, ``moe_experts`` (with the compiler's grouped-matmul
kernels, which carry no scope), ``moe_combine`` and ``moe_shared``,
median over the executions of the traced window. The earlier line has
the five apart."""

from benchmark.lib import gigachat_readers as gr, harness
from benchmark.lib import longcat_readers as lr


def read(run, trace):
    parts = {s: lr.scope_group_ms(trace, (s,)) for s in gr.MOE}
    if any(v is None for v in parts.values()):
        return None
    parts["moe_experts"] = lr.experts_ms(trace)
    harness.log({"gigachat_decode_moe_ms_by_scope": parts})
    return lr.scope_group_ms(trace, gr.MOE, lr.EXPERT_KERNELS)
