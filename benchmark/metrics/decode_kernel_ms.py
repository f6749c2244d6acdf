"""Device time of the kernel NAMED ``paged_decode_attention`` in one
execution of the decode program (``jit_serve_decode``), median over the
executions of the traced window: the name-keyed twin of what
``paged_decode_roofline`` finds by the pool's shape."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    return ps.kernel_ms(trace, "serve_decode", "paged_decode_attention")
