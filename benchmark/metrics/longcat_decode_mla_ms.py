"""Device time one execution of the decode program spends in latent
attention: self time of the instructions inside the scopes ``mla_qkv``
(projections, latent norms, rotary), ``latent_write`` (the pool append),
``mla_attn`` (absorption and the latent-decode kernel) and ``attn_out``,
all eight attentions, median over the executions of the traced window."""

from benchmark.lib import longcat_readers as lr


def read(run, trace):
    return lr.scope_group_ms(trace, lr.MLA)
