"""As ``decode_program_ms``, in the open-loop cell (a metric moves one
end-to-end metric, and that cell reports another)."""

from benchmark.lib.trace_select import decode_program_ms as read  # noqa: F401
