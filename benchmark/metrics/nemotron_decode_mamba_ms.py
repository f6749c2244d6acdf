"""Device time one execution of the decode program spends in the ``M``
layers' mixers: the scopes ``mamba_in`` (the three input projections),
``mamba_conv`` (the convolution and its tail), ``mamba_state`` (the
state update and query over 8 B/C groups, or the kernel named for it)
and ``mamba_out`` (the gated norm a group and the output projection),
all state layers, median over the executions of the traced window."""

from benchmark.lib import granite_readers as gr, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, gr.MAMBA, (gr.STATE_KERNEL,))
