"""Device time of the kernel NAMED ``paged_decode_attention`` in one
execution of the decode program (``jit_serve_decode``), median over the
executions of the traced window: per execution, the calls whose total
``paged_decode_roofline`` holds against the bytes they had to read."""

from benchmark.lib import program_spans as ps


def read(run, trace):
    if run["kind"] != "serve":
        return None
    return ps.kernel_ms(trace, "serve_decode", "paged_decode_attention")
