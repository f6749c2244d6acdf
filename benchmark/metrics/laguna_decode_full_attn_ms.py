"""Device time one execution of the decode program spends in the FULL
attention layers: self time of the instructions inside the scope
``attn_full`` (projections, partial YaRN rotary, gate, the append through
the block tables and the paged kernel over the live blocks), all full
layers, median over the executions of the traced window."""

from benchmark.lib import laguna_readers as lg, longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, lg.FULL)
