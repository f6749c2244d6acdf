"""Device time one execution of the decode program spends in the ``E``
layers: router, dispatch, the held experts' grouped matmuls (the scope
``moe_experts`` and the compiler's ``ragged-dot-*`` kernels), combine
and the shared expert (``moe_shared``), all expert layers, median over
the executions of the traced window."""

from benchmark.lib import granite_readers as gr, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, gr.MOE, lr.EXPERT_KERNELS)
