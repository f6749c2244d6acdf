"""Device time one execution of the decode program spends in the ``*``
layers (the scope ``attn_full``: projections, the append through the
block tables, the paged kernel over 2 key/value heads, ``W_o``), median
over the executions of the traced window."""

from benchmark.lib import granite_readers as gr, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, gr.ATTN)
