"""Device time of one execution of the decode program (the compiled
program that holds the paged-decode kernel), median over the traced
window; the backlog cell."""

from benchmark.lib.trace_select import decode_program_ms as read  # noqa: F401
