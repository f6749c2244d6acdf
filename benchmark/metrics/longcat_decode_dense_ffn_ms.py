"""Device time one execution of the decode program spends in the dense
SwiGLU FFNs (scope ``dense_ffn``, two a layer), median over the
executions of the traced window."""

from benchmark.lib import longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, lr.DENSE)
