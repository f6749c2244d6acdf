"""Device time one execution of the decode program spends in the gated
MLPs (scope ``mlp``), all layers, median over the executions of the
traced window."""

from benchmark.lib import brumby_readers as br, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, br.MLP)
