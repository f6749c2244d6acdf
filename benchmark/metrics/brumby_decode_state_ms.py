"""Device time one execution of the decode program spends in the
retention mixer: self time of the instructions inside the scopes
``ret_qkvg`` (projections, head norms, rotary, gate), ``ret_state`` (the
state-update kernel) and ``ret_out``, all layers, median over the
executions of the traced window."""

from benchmark.lib import brumby_readers as br, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, br.STATE)
