"""Of the time at least one host-link transfer was in flight (a
``-start`` begins to its ``-done`` ends, union over the movement
table's host rows), the share in which both directions were: near 0
the stream's two directions take turns, near 100 they run together."""

from benchmark.lib import movement_readers


def read(run, trace):
    return movement_readers.metric(run, trace, "offload_duplex_pct")
