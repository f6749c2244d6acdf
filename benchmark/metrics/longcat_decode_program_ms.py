"""Device time of one execution of the decode program (``jit_serve_decode``),
median over the traced window; the LongCat-Flash decode-batch cell."""

from benchmark.lib.trace_select import decode_program_ms as read  # noqa: F401
