"""Device time one execution of the decode program spends in the WINDOW
attention layers: self time of the instructions inside the scope
``attn_window`` (projections, rotary, gate, the append into the ring and
the paged kernel over the ring), all window layers, median over the
executions of the traced window."""

from benchmark.lib import laguna_readers as lg, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, lg.WINDOW)
