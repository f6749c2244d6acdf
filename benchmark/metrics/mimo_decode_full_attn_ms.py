"""Device time one execution of the decode program spends in MiMo-V2's
FULL attention layers: self time of the instructions inside the scope
``attn_full`` (projections to 64 query heads of 192 and 4 key/value
heads, partial rotary, the value scale, the append through the block
tables and the paged kernel over the live blocks), all full layers,
median over the executions of the traced window."""

from benchmark.lib import longcat_readers as lr, mimo_readers as mm


def read(run, trace):
    if mm.sizes(run) is None:
        return None
    return lr.scope_group_ms(trace, mm.FULL)
