"""Device time one execution of the decode program spends in MiMo-V2's
expert layers: router, dispatch, the held experts' grouped matmuls (the
scope ``moe_experts`` and the compiler's ``ragged-dot-*`` kernels) and
combine (the model has no shared expert), all sparse layers, median over
the executions of the traced window."""

from benchmark.lib import longcat_readers as lr, mimo_readers as mm


def read(run, trace):
    if mm.sizes(run) is None:
        return None
    return lr.scope_group_ms(trace, lr.MOE, lr.EXPERT_KERNELS)
