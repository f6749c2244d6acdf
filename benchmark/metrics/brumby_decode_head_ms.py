"""Device time one execution of the decode program spends in the final
norm and the head (scope ``lm_head``: 1.56 GB of weights a step, a
larger share on a stage of 6 layers than in the deployment), median over
the executions of the traced window."""

from benchmark.lib import brumby_readers as br, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, br.HEAD)
